"""Round-10 observability plane (server/storm.py + utils/metrics.py +
utils/telemetry.py): the per-tick stage ledger, the sampled per-op trace
joins, the device-side kstats counters riding the tick readback, and the
tracing overhead guard.

Oracles: (1) every serving tick commits exactly one fixed-shape ledger
record whose stage splits are non-negative and whose per-stage
histograms surface in the shared registry (alfred's get_metrics view);
(2) a frame stamped with a trace id gets a joined span whose hop marks
are monotonic in pipeline order, and its ack carries the marks back;
(3) the device stats plane agrees with the host-side sequenced/dup
accounting; (4) tracing at the default sample rate does not visibly tax
tick throughput."""

import time

import numpy as np
import pytest

from fluidframework_tpu.protocol.codec import stamp_trace
from fluidframework_tpu.server.kernel_host import KernelSequencerHost
from fluidframework_tpu.server.merge_host import KernelMergeHost
from fluidframework_tpu.server.routerlicious import RouterliciousService
from fluidframework_tpu.server.storm import StormController
from fluidframework_tpu.utils.metrics import STORM_STAGES


def make_service(num_docs=8, **storm_kwargs):
    seq_host = KernelSequencerHost(num_slots=2, initial_capacity=num_docs)
    merge_host = KernelMergeHost(flush_threshold=10**9)
    service = RouterliciousService(merge_host=merge_host,
                                   batched_deli_host=seq_host,
                                   auto_pump=False)
    storm = StormController(service, seq_host, merge_host,
                            flush_threshold_docs=10**9, **storm_kwargs)
    return service, storm, merge_host


def join_docs(service, docs):
    clients = {d: service.connect(d, lambda m: None).client_id
               for d in docs}
    service.pump()
    return clients


def make_words(rng, k, num_slots=16):
    kinds = rng.choice([0, 0, 0, 1, 2], size=k).astype(np.uint32)
    slots = rng.integers(0, num_slots, k).astype(np.uint32)
    vals = rng.integers(0, 1 << 20, k).astype(np.uint32)
    return (kinds | (slots << 2) | (vals << 12)).astype(np.uint32)


def run_ticks(storm, clients, docs, k=16, ticks=3, tc_from=None,
              push=None, cseq0=1):
    rng = np.random.default_rng(0)
    cseq = {d: cseq0 for d in docs}
    for t in range(ticks):
        hdr = {"op": "storm", "rid": t,
               "docs": [[d, clients[d], cseq[d], 1, k] for d in docs]}
        if tc_from is not None:
            stamp_trace(hdr, tc_from + t)
        body = b"".join(make_words(rng, k).tobytes() for _ in docs)
        storm.submit_frame(push, hdr, memoryview(body))
        storm.flush()
        for d in docs:
            cseq[d] += k
    return cseq


class TestStageLedger:
    def test_one_fixed_shape_record_per_tick(self):
        service, storm, merge_host = make_service()
        docs = ["a", "b", "c"]
        clients = join_docs(service, docs)
        run_ticks(storm, clients, docs, k=16, ticks=4)
        recs = storm.ledger.records()
        assert len(recs) == storm.stats["ticks"] == 4
        for rec in recs:
            # Fixed shape: every stage key present on every record.
            assert all(s in rec for s in STORM_STAGES)
            assert all(rec[s] >= 0 for s in STORM_STAGES)
            assert rec["batch_docs"] == 3
            assert rec["batch_ops"] == 3 * 16
        # The attributable splits cover real work: scatter + dispatch +
        # readback are never all zero on a tick that ran the device.
        assert all(rec["scatter"] + rec["device_dispatch"]
                   + rec["readback"] > 0 for rec in recs)

    def test_stage_histograms_reach_shared_registry(self):
        service, storm, merge_host = make_service()
        clients = join_docs(service, ["a"])
        run_ticks(storm, clients, ["a"], ticks=2)
        snap = merge_host.metrics.snapshot()
        for stage in ("scatter", "device_dispatch", "readback", "ack_pack"):
            assert snap[f"storm.stage.{stage}.count"] >= 2
            assert snap[f"storm.stage.{stage}.p99"] >= 0
        # merge_host.metrics IS the service registry when assembled by
        # RouterliciousService — the alfred get_metrics surface.
        assert service.metrics is merge_host.metrics

    def test_attribution_shares_sum_to_one(self):
        service, storm, _mh = make_service()
        clients = join_docs(service, ["a", "b"])
        run_ticks(storm, clients, ["a", "b"], ticks=3)
        att = storm.ledger.attribution()
        shares = [v["share"] for s, v in att.items() if s != "_window"]
        assert abs(sum(shares) - 1.0) < 0.01
        assert att["_window"]["ticks"] == 3
        assert att["_window"]["mean_batch_docs"] == 2.0

    def test_group_wal_commit_wait_backfilled(self, tmp_path):
        service, storm, _mh = make_service(
            spill_dir=str(tmp_path), durability="group")
        clients = join_docs(service, ["a"])
        run_ticks(storm, clients, ["a"], ticks=2)
        # Forced flush drains acks behind the fsync watermark, so the
        # records' commit-wait has been amended by now.
        for rec in storm.ledger.records():
            assert rec["wal_commit_wait"] > 0
        storm._group_wal.close()

    def test_replay_ticks_do_not_pollute_the_ledger(self, tmp_path):
        from fluidframework_tpu.server.durable_store import GitSnapshotStore
        from fluidframework_tpu.server.historian import Historian
        snapshots = Historian(GitSnapshotStore(str(tmp_path / "git")))
        service, storm, _mh = make_service(
            spill_dir=str(tmp_path / "wal"), durability="group",
            snapshots=snapshots)
        clients = join_docs(service, ["a"])
        run_ticks(storm, clients, ["a"], ticks=1)
        storm.checkpoint()
        run_ticks(storm, clients, ["a"], ticks=2, cseq0=17)
        n_before = len(storm.ledger)
        storm._group_wal.close()

        # Fresh controller stack over the same spill dir: recover()
        # replays 2 WAL ticks through the serving path — none of them
        # may append ledger records (they are reconstruction).
        service2, storm2, _mh2 = make_service(
            spill_dir=str(tmp_path / "wal"), durability="group",
            snapshots=snapshots)
        storm2.recover()
        assert storm2.stats["ticks"] == 2  # replayed
        assert len(storm2.ledger) == 0
        storm2._group_wal.close()
        assert n_before == 3


class TestPerOpTracing:
    def test_span_joined_and_ack_carries_hops(self):
        service, storm, _mh = make_service()
        clients = join_docs(service, ["a", "b"])
        acked = []
        run_ticks(storm, clients, ["a", "b"], ticks=2, tc_from=100,
                  push=acked.append)
        assert len(acked) == 2
        for t, ack in enumerate(acked):
            assert ack["tc"] == 100 + t
            hops = ack["hops"]
            order = ["ingress", "admit", "dispatch", "sequenced", "ack_tx"]
            assert list(hops) == order
            ts = [hops[h] for h in order]
            assert ts == sorted(ts)  # pipeline order, monotonic ns
        spans = list(storm.tracer.spans)
        assert len(spans) == 2
        assert spans[0]["total_ms"] >= 0
        assert set(spans[0]["deltas_ms"]) == {
            "ingress_to_admit", "admit_to_dispatch",
            "dispatch_to_sequenced", "sequenced_to_ack_tx"}
        # Hop histograms surface in the registry for get_metrics.
        snap = _mh.metrics.snapshot()
        assert snap["storm.hop.admit_to_dispatch.count"] == 2

    def test_durable_hop_present_under_group_wal(self, tmp_path):
        service, storm, _mh = make_service(
            spill_dir=str(tmp_path), durability="group")
        clients = join_docs(service, ["a"])
        acked = []
        run_ticks(storm, clients, ["a"], ticks=1, tc_from=7,
                  push=acked.append)
        hops = acked[0]["hops"]
        assert "durable" in hops
        assert hops["sequenced"] <= hops["durable"] <= hops["ack_tx"]
        storm._group_wal.close()

    def test_same_trace_id_from_two_sessions_never_collides(self):
        """Clients pick trace ids independently (every StormStream
        counts from 1), so two sessions sampling the SAME small integer
        in one tick must produce two clean spans — the server scopes
        its tracer key per session, never on the raw client id."""
        service, storm, _mh = make_service()
        clients = join_docs(service, ["a", "b"])
        rng = np.random.default_rng(5)
        acks_a, acks_b = [], []
        for doc, sink in (("a", acks_a.append), ("b", acks_b.append)):
            hdr = stamp_trace(
                {"op": "storm",
                 "docs": [[doc, clients[doc], 1, 1, 8]]}, 1)  # same tc!
            storm.submit_frame(sink, hdr,
                               memoryview(make_words(rng, 8).tobytes()))
        storm.flush()  # ONE tick sequences both frames
        assert storm.stats["ticks"] == 1
        for acked in (acks_a, acks_b):
            assert len(acked) == 1
            assert acked[0]["tc"] == 1  # the client's raw id, unscoped
            hops = acked[0]["hops"]
            assert list(hops) == ["ingress", "admit", "dispatch",
                                  "sequenced", "ack_tx"]
            ts = list(hops.values())
            assert ts == sorted(ts)
        assert len(storm.tracer.spans) == 2

    def test_server_caps_client_controlled_sampling(self):
        """One connection stamping EVERY frame must not commandeer the
        tracer: past max_traces_per_tick the extra ids are ignored (the
        frames still serve and ack normally, just untraced)."""
        service, storm, _mh = make_service()
        docs = ["a", "b", "c"]
        clients = join_docs(service, docs)
        storm.max_traces_per_tick = 2
        rng = np.random.default_rng(8)
        acked = []
        for i, doc in enumerate(docs):
            hdr = stamp_trace(
                {"op": "storm",
                 "docs": [[doc, clients[doc], 1, 1, 8]]}, 100 + i)
            storm.submit_frame(acked.append, hdr,
                               memoryview(make_words(rng, 8).tobytes()))
        storm.flush()
        assert [a.get("tc") for a in acked] == [100, 101, None]
        assert len(storm.tracer.spans) == 2
        assert storm.stats["sequenced_ops"] == 3 * 8  # all served
        # The cap is per tick round: the next round traces again.
        hdr = stamp_trace(
            {"op": "storm", "docs": [["a", clients["a"], 9, 1, 8]]}, 200)
        storm.submit_frame(acked.append, hdr,
                           memoryview(make_words(rng, 8).tobytes()))
        storm.flush()
        assert acked[-1]["tc"] == 200

    def test_shed_traced_frames_do_not_consume_cap_slots(self):
        """Traced frames refused at admission must not eat the per-tick
        trace budget — tracing has to keep working DURING the overload
        it exists to diagnose."""
        service, storm, _mh = make_service()
        clients = join_docs(service, ["a", "b", "c"])
        storm.max_traces_per_tick = 1
        storm.max_pending_docs = 2
        rng = np.random.default_rng(13)
        acked = []

        def submit(docs, tc=None):
            hdr = {"op": "storm",
                   "docs": [[d, clients[d], 1, 1, 8] for d in docs]}
            if tc is not None:
                stamp_trace(hdr, tc)
            storm.submit_frame(
                acked.append, hdr,
                memoryview(b"".join(make_words(rng, 8).tobytes()
                                    for _ in docs)))

        submit(["a"])               # untraced, buffered (pending=1)
        submit(["b", "c"], tc=2)    # traced, SHED at the queue bound
        assert acked[-1]["error"] == "busy"
        submit(["b"], tc=3)         # traced, admitted — the shed frame
        storm.flush()               # must not have burned its cap slot
        traced = [a for a in acked if a.get("tc") is not None]
        assert [a["tc"] for a in traced] == [3]
        assert len(storm.tracer.spans) == 1

    def test_quarantine_shed_refunds_staged_ns_and_trace_slot(self):
        """A buffered frame shed at quarantine must refund the ledger ns
        and sampling-cap slot it staged — the next tick never served it."""
        service, storm, _mh = make_service()
        clients = join_docs(service, ["a", "b"])
        rng = np.random.default_rng(17)
        for doc, tc in (("a", 1), ("b", 2)):
            hdr = stamp_trace(
                {"op": "storm",
                 "docs": [[doc, clients[doc], 1, 1, 8]]}, tc)
            storm.submit_frame(lambda p: None, hdr,
                               memoryview(make_words(rng, 8).tobytes()))
        staged_both = dict(storm._staged_ns)
        assert storm._traced_pending == 2
        storm._quarantine_doc("a", "test", 0)
        assert storm._traced_pending == 1
        assert 0 <= storm._staged_ns["ingress_decode"] \
            < staged_both["ingress_decode"]
        assert 0 <= storm._staged_ns["admission"] \
            < staged_both["admission"]
        # The surviving frame still serves and traces.
        storm.flush()
        assert storm.stats["sequenced_ops"] == 8
        assert [s["trace_id"][0] for s in storm.tracer.spans] == [2]

    def test_unhashable_trace_id_is_ignored_not_nacked(self):
        """The "tc" field is client-opaque JSON — a list/dict id cannot
        key the tracer, but the frame itself is valid and must serve."""
        service, storm, _mh = make_service()
        clients = join_docs(service, ["a"])
        rng = np.random.default_rng(21)
        acked = []
        hdr = stamp_trace(
            {"op": "storm", "docs": [["a", clients["a"], 1, 1, 8]]},
            [3, "x"])
        storm.submit_frame(acked.append, hdr,
                           memoryview(make_words(rng, 8).tobytes()))
        storm.flush()
        assert storm.stats["sequenced_ops"] == 8
        assert len(acked) == 1 and "error" not in acked[0]
        assert "tc" not in acked[0] and len(storm.tracer.spans) == 0

    def test_admission_keys_on_session_identity_not_frame_header(self):
        """The docstring's contract, pinned: the per-client admission
        identity is the submit_frame ARGUMENT (service-assigned), never
        the client-controlled writer ids inside the frame's doc entries
        (a self-stamped id would mint a fresh token bucket per frame)."""
        service, storm, _mh = make_service()
        clients = join_docs(service, ["a"])
        seen = []

        class Admission:
            def add_pressure_probe(self, probe):
                pass

            def admit_write(self, tenant_id, client_id, weight):
                seen.append((tenant_id, client_id))
                return None

        storm.admission = Admission()
        rng = np.random.default_rng(22)
        hdr = {"op": "storm",
               "docs": [["a", "forged-client-id", 1, 1, 8]]}
        storm.submit_frame(None, hdr,
                           memoryview(make_words(rng, 8).tobytes()),
                           tenant_id="t1", client_id="session-client")
        assert seen == [("t1", "session-client")]

    def test_untraced_frames_cost_no_span(self):
        service, storm, _mh = make_service()
        clients = join_docs(service, ["a"])
        acked = []
        run_ticks(storm, clients, ["a"], ticks=2, push=acked.append)
        assert all("tc" not in a for a in acked)
        assert len(storm.tracer.spans) == 0

    def test_e2e_stormstream_over_alfred_socket(self, tmp_path):
        """The full client join: StormStream samples a frame, the alfred
        asyncio front door stamps ingress, and the client's span spans
        client_send → server hops → client_rx in one clock domain."""
        import asyncio
        import threading

        from fluidframework_tpu.drivers.network_driver import (
            NetworkDocumentService, StormStream)
        from fluidframework_tpu.server.alfred import AlfredServer

        service, storm, _mh = make_service()
        server = AlfredServer(service)
        loop = asyncio.new_event_loop()
        started = threading.Event()

        async def run():
            await server.start()
            started.set()
            await server.serve_forever()

        thread = threading.Thread(target=loop.run_until_complete,
                                  args=(run(),), daemon=True)
        thread.start()
        assert started.wait(10)

        svc = NetworkDocumentService("127.0.0.1", server.port, "doc-x")
        try:
            conn = svc.connect(lambda msgs: None)
            service.pump()
            stream = StormStream(svc, sample_every=1)
            rng = np.random.default_rng(1)
            words = make_words(rng, 8)
            tc = stream.submit([["doc-x", conn.client_id, 1, 1, 8]],
                               words.tobytes())
            assert tc is not None
            deadline = time.monotonic() + 30
            while not stream.acked and time.monotonic() < deadline:
                # The tick must run on the server's loop thread (acks
                # push into the session outbox) — the wire op does that.
                svc._request({"op": "storm_flush"})
                time.sleep(0.02)
            assert stream.acked == 1
            deadline = time.monotonic() + 10
            while not stream.tracer.spans and time.monotonic() < deadline:
                time.sleep(0.01)
            span = stream.tracer.spans[0]
            hops = span["hops"]
            assert list(hops)[0] == "client_send"
            assert list(hops)[-1] == "client_rx"
            assert "sequenced" in hops and "ack_tx" in hops
            ts = list(hops.values())
            assert ts == sorted(ts)
            assert span["total_ms"] > 0
        finally:
            svc.close()
            loop.call_soon_threadsafe(lambda: None)


class TestDeviceKstats:
    def test_device_counters_match_host_accounting(self):
        service, storm, merge_host = make_service()
        clients = join_docs(service, ["a", "b"])
        run_ticks(storm, clients, ["a", "b"], k=16, ticks=2)
        snap = merge_host.metrics.snapshot()
        assert snap["storm.device.sequenced_ops"] == \
            storm.stats["sequenced_ops"] == 64
        assert snap["storm.device.dup_ops"] == 0
        assert snap["storm.device.sentinel_docs"] == 0

    def test_dup_resend_counted_on_device(self):
        service, storm, merge_host = make_service()
        clients = join_docs(service, ["a"])
        rng = np.random.default_rng(3)
        words = make_words(rng, 8)
        hdr = {"op": "storm",
               "docs": [["a", clients["a"], 1, 1, 8]]}
        storm.submit_frame(None, dict(hdr), memoryview(words.tobytes()))
        storm.flush()
        # Verbatim resend: kernel cseq dedup drops all 8 as duplicates —
        # the device-side dup counter must see them.
        storm.submit_frame(None, dict(hdr), memoryview(words.tobytes()))
        storm.flush()
        snap = merge_host.metrics.snapshot()
        assert snap["storm.device.dup_ops"] == 8
        assert snap["storm.device.sequenced_ops"] == 8


@pytest.mark.parametrize("shape", [(16, 16, 24)])
def test_tracing_overhead_guard(shape):
    """Overhead guard (satellite): tracing every frame must not visibly
    tax tick throughput — the per-frame cost is a couple of dict writes
    and ns reads. The <2% bar at the DEFAULT 1-in-64 sample on the full
    socket path is not measured; this smoke bounds the in-process worst
    case (sample EVERY frame) loosely enough to stay deterministic under
    CI noise."""
    num_docs, k, ticks = shape

    def timed_run(tc_from):
        service, storm, _mh = make_service(num_docs=num_docs)
        docs = [f"d{i}" for i in range(num_docs)]
        clients = join_docs(service, docs)
        run_ticks(storm, clients, docs, k=k, ticks=2, tc_from=None)  # warm
        t0 = time.perf_counter()
        run_ticks(storm, clients, docs, k=k, ticks=ticks,
                  tc_from=tc_from, cseq0=2 * k + 1)
        return (time.perf_counter() - t0) / ticks

    base = min(timed_run(None) for _ in range(2))
    traced = min(timed_run(10_000) for _ in range(2))
    # Loose CI bound, not the <2% acceptance figure.
    assert traced <= base * 1.5, (traced, base)
