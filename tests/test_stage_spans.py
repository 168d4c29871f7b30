"""Stage-ledger stages as profiler spans: one clock for the serving thread
and the device trace (utils/metrics.py ``StageLedger.stage``), the closed
account of a storm tick (every serving-thread ns between two harvests in
one stage of the later record), the sharded tick's ledger, the WAL
writer's spans and counters, and what a span costs.

Oracles: (1) each storm stage's ledger ns equals the total of its
``ff.storm.stage.*`` spans in a CPU profile; (2) a client pause shows as
``frame_wait`` on the tick that follows it, and no record's
serving-thread stages sum past its wall clock; (3) nested stages count
each instant once; (4) one fixed-shape sharded record per tick, with
``admit`` only where the tick had submits."""

import gc
import glob
import os
import socket
import struct
import threading
import time

import jax
import numpy as np
import pytest

from fluidframework_tpu.server.kernel_host import KernelSequencerHost
from fluidframework_tpu.server.merge_host import KernelMergeHost
from fluidframework_tpu.server.routerlicious import RouterliciousService
from fluidframework_tpu.server.storm import StormController
from fluidframework_tpu.utils.metrics import (
    GC_CLOCK,
    STORM_STAGES,
    MetricsRegistry,
    StageLedger,
)


def make_storm(num_docs=4, **kwargs):
    seq_host = KernelSequencerHost(num_slots=2, initial_capacity=num_docs)
    merge_host = KernelMergeHost(flush_threshold=10**9)
    service = RouterliciousService(merge_host=merge_host,
                                   batched_deli_host=seq_host,
                                   auto_pump=False)
    storm = StormController(service, seq_host, merge_host, **kwargs)
    return service, storm


def words(rng, k):
    kinds = rng.choice([0, 0, 0, 1], size=k).astype(np.uint32)
    slots = rng.integers(0, 16, k).astype(np.uint32)
    vals = rng.integers(0, 1 << 20, k).astype(np.uint32)
    return (kinds | (slots << 2) | (vals << 12)).astype(np.uint32)


def serve(storm, clients, docs, ticks, k=8, cseq0=1, seed=0):
    rng = np.random.default_rng(seed)
    for t in range(ticks):
        hdr = {"op": "storm", "rid": t,
               "docs": [[d, clients[d], cseq0 + t * k, 1, k]
                        for d in docs]}
        body = b"".join(words(rng, k).tobytes() for _ in docs)
        storm.submit_frame(lambda p: None, hdr, memoryview(body))
        storm.flush()


SERVING = [s for s in STORM_STAGES if s != "wal_commit_wait"]


def test_ledger_stages_equal_their_profiler_spans(tmp_path):
    """One clock, the same edges: every storm stage's ledger ns equals
    the total of its ``ff.storm.stage.<stage>`` spans within 1 % or
    50 us."""
    from jax.profiler import ProfileData

    docs = ["a", "b", "c"]
    service, storm = make_storm()
    clients = {d: service.connect(d, lambda m: None).client_id
               for d in docs}
    service.pump()
    serve(storm, clients, docs, ticks=1)  # compile outside the trace
    storm.ledger.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        serve(storm, clients, docs, ticks=4, cseq0=9)
    finally:
        jax.profiler.stop_trace()
    assert len(storm.ledger) == 4
    # One more cut takes what was timed after the last harvest (the
    # acks it sent).
    storm.ledger.record(-1, 0, 0, 0)
    ledger_ns = {s: sum(r[s] for r in storm.ledger.records())
                 for s in SERVING}
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    span_ns = dict.fromkeys(SERVING, 0)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stage = e.name.removeprefix("ff.storm.stage.")
                if stage in span_ns:
                    span_ns[stage] += e.duration_ns
    for stage in ("ingress_decode", "admission", "scatter",
                  "device_dispatch", "readback", "ack_pack", "ack_send"):
        assert ledger_ns[stage] > 0, stage
    for stage in SERVING:
        diff = abs(ledger_ns[stage] - span_ns[stage])
        assert diff <= max(0.01 * ledger_ns[stage], 50_000), \
            (stage, ledger_ns[stage], span_ns[stage])


def _read_frame(sock):
    length = struct.unpack(">I", sock.recv(4, socket.MSG_WAITALL))[0]
    return sock.recv(length, socket.MSG_WAITALL)


def test_client_pause_reads_as_frame_wait_and_the_account_closes():
    """Over the bridge: a client that waits ~100 ms before its next frame
    shows >= 80 ms of frame_wait on the tick that follows, and on every
    record the serving-thread stages sum to no more than its wall."""
    from fluidframework_tpu.native import bridge
    from fluidframework_tpu.protocol.codec import (
        decode_storm_push, encode_storm_frame, is_storm_body)
    from fluidframework_tpu.server.bridge_host import BridgeFrontDoor

    if bridge._load_library() is None:
        pytest.skip("native bridge did not build")
    docs = ["p-a", "p-b"]
    service, storm = make_storm(flush_threshold_docs=len(docs))
    front = BridgeFrontDoor(service, 0)
    try:
        clients = {d: service.connect(d, lambda m: None).client_id
                   for d in docs}
        service.pump()
        rng = np.random.default_rng(3)
        sock = socket.create_connection(("127.0.0.1", front.port))
        sock.settimeout(60)
        k = 8
        for t in range(3):
            if t == 2:
                time.sleep(0.1)
            sock.sendall(encode_storm_frame(
                {"op": "storm", "rid": t,
                 "docs": [[d, clients[d], 1 + t * k, 1, k] for d in docs]},
                b"".join(words(rng, k).tobytes() for _ in docs)))
            body = _read_frame(sock)
            while not (is_storm_body(body)
                       and decode_storm_push(body).get("rid") == t):
                body = _read_frame(sock)
        sock.close()
    finally:
        front.close()
    recs = storm.ledger.records()
    assert len(recs) == 3
    assert recs[2]["frame_wait"] >= 80e6, recs[2]
    for rec in recs:
        assert sum(rec[s] for s in SERVING) <= rec["wall"] + 1e6, rec


def test_nested_stages_count_each_instant_once():
    led = StageLedger(registry=MetricsRegistry(), prefix="t.stage")
    with led.stage("idle_service"):
        time.sleep(0.01)
        with led.stage("ack_send"):
            time.sleep(0.02)
        time.sleep(0.01)
    rec = led.record(0, 0, 0, 0, wall_ns=None)
    assert 18e6 <= rec["ack_send"] < 30e6
    assert 18e6 <= rec["idle_service"] < 30e6
    assert rec["ack_send"] + rec["idle_service"] <= rec["wall"]
    # The cut is exact: a stage open across record() splits at the cut.
    with led.stage("scatter"):
        time.sleep(0.01)
        first = led.record(1, 0, 0, 0, wall_ns=None)
        time.sleep(0.01)
    second = led.record(2, 0, 0, 0, wall_ns=None)
    for r in (first, second):
        assert 8e6 <= r["scatter"] <= r["wall"]


def test_stage_open_across_clear_charges_only_after_it():
    """A stage another thread holds open across clear() (the bench
    clears the ledger from its main thread while the pump is in a
    stage) counts only its time after the clear."""
    led = StageLedger()
    with led.stage("frame_wait"):
        time.sleep(0.02)
        led.clear()
        time.sleep(0.005)
    rec = led.record(0, 0, 0, 0, wall_ns=None)
    assert 4e6 <= rec["frame_wait"] <= rec["wall"] < 15e6


def test_stage_closed_out_of_order_is_charged_once():
    """A suspended stage closed under the stage that suspended it was
    charged up to its suspension and takes nothing more."""
    led = StageLedger()
    outer = led.stage("ingress_decode").__enter__()
    time.sleep(0.01)
    inner = led.stage("admission").__enter__()
    time.sleep(0.01)
    outer.__exit__(None, None, None)
    time.sleep(0.01)
    inner.__exit__(None, None, None)
    rec = led.record(0, 0, 0, 0, wall_ns=None)
    assert led.open_stage() is None
    assert 8e6 <= rec["ingress_decode"] < 18e6
    assert rec["admission"] >= 18e6
    assert rec["ingress_decode"] + rec["admission"] <= rec["wall"]


def test_clear_on_another_thread_never_lets_stages_pass_the_wall():
    """The bench clears the ledger from its main thread while the serving
    thread times stages and cuts records: no record's stages sum past
    its wall."""
    led = StageLedger()
    stop = threading.Event()
    recs = []

    def serve():
        while not stop.is_set():
            with led.stage("frame_wait"):
                time.sleep(0.0005)
            with led.stage("scatter"):
                pass
            recs.append(led.record(0, 0, 0, 0, wall_ns=None))

    thread = threading.Thread(target=serve)
    thread.start()
    try:
        for _ in range(300):
            led.clear()
            time.sleep(0.0003)
    finally:
        stop.set()
        thread.join()
    assert recs
    for rec in recs:
        assert rec["frame_wait"] + rec["scatter"] <= rec["wall"], rec


def test_frames_no_tick_serves_leave_no_ledger_time():
    """A frame refused at validation or shed at admission charges neither
    ingress_decode nor admission: no tick serves it."""
    service, storm = make_storm()
    client = service.connect("a", lambda m: None).client_id
    service.pump()
    body = memoryview(words(np.random.default_rng(5), 8).tobytes())
    with pytest.raises(ValueError):
        storm.submit_frame(lambda p: None, {
            "op": "storm", "docs": [["a", client, 1, 1, 0]]}, body)

    class Throttle:
        def admit_write(self, tenant_id, client_id, weight):
            return 0.5

    hdr = {"op": "storm", "rid": 1, "docs": [["a", client, 1, 1, 8]]}
    nacks = []
    storm.admission = Throttle()
    storm.submit_frame(nacks.append, hdr, body)
    assert len(nacks) == 1
    assert storm._staged_ns == {"ingress_decode": 0, "admission": 0}
    storm.admission = None
    storm.submit_frame(lambda p: None, hdr, body)
    assert storm._staged_ns["ingress_decode"] > 0
    assert storm._staged_ns["admission"] > 0


def test_stage_held_open_across_calls_and_receive_stamp():
    led = StageLedger()
    span = led.stage("ingress_decode").__enter__()
    assert led.open_stage() is span
    time.sleep(0.005)
    span.__exit__(None, None, None)
    span.__exit__(None, None, None)  # idempotent
    assert led.open_stage() is None
    assert span.t1 - span.t0 >= 5e6
    time.sleep(0.005)
    t_rx = time.monotonic_ns() - 3_000_000
    with led.stage("admission", since_ns=t_rx):
        pass
    # A receive stamp inside time already charged counts from where the
    # charged time ends.
    with led.stage("cohort", since_ns=t_rx):
        pass
    rec = led.record(0, 0, 0, 0)
    assert rec["ingress_decode"] >= 5e6 and rec["admission"] >= 3e6
    assert rec["cohort"] < 1e6
    with pytest.raises(ValueError, match="unknown ledger stage"):
        led.stage("not_a_stage")


def test_gc_field_counts_collections_since_the_last_record():
    led = StageLedger()
    led.record(0, 0, 0, 0)
    before = GC_CLOCK.total_ns
    junk = [[i] for i in range(200_000)]
    gc.collect()
    del junk
    rec = led.record(1, 0, 0, 0)
    assert GC_CLOCK.total_ns > before
    assert rec["gc"] >= GC_CLOCK.total_ns - before > 0
    assert "gc" not in led.attribution()  # a field, not a stage


def test_stage_costs_microseconds_with_the_profiler_off():
    """Loose bound for CI noise: a stage enter + exit with no profile
    being collected stays under 5 us (about 2 us on a quiet host)."""
    led = StageLedger()
    n = 1_000
    best = float("inf")
    for _ in range(30):  # best of short rounds: a busy host's noise drops out
        t0 = time.perf_counter()
        for _ in range(n):
            with led.stage("scatter"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best <= 5e-6, best


def test_group_wal_counts_fsyncs_into_storm_stats(tmp_path):
    docs = ["w-a", "w-b"]
    service, storm = make_storm(spill_dir=str(tmp_path),
                                durability="group")
    clients = {d: service.connect(d, lambda m: None).client_id
               for d in docs}
    service.pump()
    serve(storm, clients, docs, ticks=3)
    wal = storm._group_wal
    try:
        assert wal.batches >= 1 and wal.fsync_ns > 0
        assert storm.stats["wal_batches"] == wal.batches
        assert storm.stats["wal_fsync_ns"] == wal.fsync_ns
    finally:
        wal.close()


def test_sharded_ledger_one_record_per_tick_admit_only_with_submits():
    from fluidframework_tpu.parallel.mesh import make_mesh
    from fluidframework_tpu.parallel.serving import (
        SHARD_STAGES, ShardedServing)

    serving = ShardedServing(make_mesh(jax.devices()[:2]), num_docs=8, k=4,
                             num_hosts=2)
    serving.join_all()
    rng = np.random.default_rng(1)
    for row in range(8):
        serving.submit(row, words(rng, 4), 1)
    serving.tick()
    serving.tick()  # no submits
    for row in range(0, 8, 2):
        serving.submit(row, words(rng, 4), 5)
    serving.tick()
    recs = serving.ledger.records()
    assert [r["tick"] for r in recs] == [0, 1, 2]
    assert [r["batch_docs"] for r in recs] == [8, 0, 4]
    assert [r["batch_ops"] for r in recs] == [32, 0, 16]
    for rec in recs:
        assert set(SHARD_STAGES) <= set(rec) and "gc" in rec
        assert sum(rec[s] for s in SHARD_STAGES) <= rec["wall"]
    assert recs[0]["admit"] > 0 and recs[2]["admit"] > 0
    assert recs[1]["admit"] == 0
    assert all(r["dispatch"] > 0 and r["readback"] > 0 for r in recs)
