"""KernelMergeHost: the merge/map kernels serving behind the server.

The north-star wiring (BASELINE.json): converged server-side state for
SharedString + SharedMap documents is produced by the batched device
kernels, fed from the live sequenced stream, and must match the client
replicas byte-for-byte — including under capacity pressure (compaction,
slot growth) and client-slot overflow (scalar rerouting).
"""

import random

import pytest

from fluidframework_tpu.dds.map import SharedMap
from fluidframework_tpu.dds.matrix import SharedMatrix
from fluidframework_tpu.dds.sequence import SharedString
from fluidframework_tpu.drivers.local_driver import LocalDocumentService
from fluidframework_tpu.ops import mergetree_kernel as mtk
from fluidframework_tpu.protocol.messages import (
    MessageType,
    SequencedDocumentMessage,
)
from fluidframework_tpu.runtime.container import Container
from fluidframework_tpu.server.merge_host import KernelMergeHost
from fluidframework_tpu.server.local_server import LocalCollabServer
from fluidframework_tpu.server.routerlicious import RouterliciousService
from tests.test_matrix import get_matrix, grid_of
from tests.test_mergetree import random_edit


def make_doc(server, doc_id):
    service = LocalDocumentService(server, doc_id)
    container = Container.create_detached(service)
    datastore = container.runtime.create_datastore("default")
    datastore.create_channel("text", SharedString.channel_type)
    datastore.create_channel("root", SharedMap.channel_type)
    container.attach()
    return container


def get_parts(container):
    datastore = container.runtime.get_datastore("default")
    return datastore.get_channel("text"), datastore.get_channel("root")


def run_farm(server, host, rng, n_docs=2, n_clients=3, rounds=4):
    docs = []
    for d in range(n_docs):
        c1 = make_doc(server, f"doc{d}")
        others = [Container.load(LocalDocumentService(server, f"doc{d}"))
                  for _ in range(n_clients - 1)]
        docs.append([c1] + others)

    for _round in range(rounds):
        for containers in docs:
            paused = [c for c in containers if rng.random() < 0.3]
            for c in paused:
                c.inbound.pause()
            for _ in range(rng.randrange(3, 8)):
                c = containers[rng.randrange(len(containers))]
                text, root = get_parts(c)
                if rng.random() < 0.6:
                    random_edit(rng, text)
                else:
                    r = rng.random()
                    if r < 0.6:
                        root.set(f"k{rng.randrange(6)}", rng.randrange(100))
                    elif r < 0.85:
                        root.delete(f"k{rng.randrange(6)}")
                    else:
                        root.clear()
            for c in paused:
                c.inbound.resume()

    # Replicas converged (the oracle) — then the device replica must match.
    for d, containers in enumerate(docs):
        texts = [get_parts(c)[0].get_text() for c in containers]
        maps = [dict(get_parts(c)[1].data.items()) for c in containers]
        assert all(t == texts[0] for t in texts)
        assert all(m == maps[0] for m in maps)
        assert host.text(f"doc{d}", "default", "text") == texts[0], d
        assert host.map_entries(f"doc{d}", "default", "root") == maps[0], d


@pytest.mark.parametrize("seed", range(3))
def test_local_server_device_replica_matches_clients(seed):
    host = KernelMergeHost(flush_threshold=16)
    server = LocalCollabServer(merge_host=host)
    run_farm(server, host, random.Random(seed))
    assert host.stats["device_ops"] > 0


def test_routerlicious_merger_lambda_matches_clients():
    host = KernelMergeHost(flush_threshold=10_000)  # ticks via checkpoints
    server = RouterliciousService(merge_host=host)
    run_farm(server, host, random.Random(7))
    # The merger lambda's checkpoint cadence flushed the host (flush
    # threshold was never crossed).
    assert host.stats["device_ops"] > 0


def test_routerlicious_restart_rebuilds_fresh_host_from_op_log():
    """The host is memory-only; a restarted service with a fresh host must
    rebuild the device replica from the scriptorium durable log (the merger
    lambda replays it on creation)."""
    host1 = KernelMergeHost(flush_threshold=16)
    server1 = RouterliciousService(merge_host=host1)
    run_farm(server1, host1, random.Random(11), n_docs=2)
    expected = {d: host1.text(f"doc{d}", "default", "text")
                for d in range(2)}
    maps = {d: host1.map_entries(f"doc{d}", "default", "root")
            for d in range(2)}

    host2 = KernelMergeHost(flush_threshold=16)
    server2 = RouterliciousService(bus=server1.bus, store=server1.store,
                                   merge_host=host2)
    # Documents load lazily: touching each doc (a reconnecting client)
    # instantiates its merger lambda, which replays the durable log.
    for d in range(2):
        server2.connect(f"doc{d}", lambda msgs: None)
    for d in range(2):
        assert host2.text(f"doc{d}", "default", "text") == expected[d]
        assert host2.map_entries(f"doc{d}", "default", "root") == maps[d]


@pytest.mark.soak  # ~80s: growth/compaction pressure sweep
@pytest.mark.slow
def test_capacity_pressure_compacts_and_grows():
    host = KernelMergeHost(merge_slots=8, map_slots=4, num_props=1,
                           flush_threshold=4)
    server = LocalCollabServer(merge_host=host)
    rng = random.Random(3)
    c1 = make_doc(server, "doc")
    c2 = Container.load(LocalDocumentService(server, "doc"))
    for _ in range(120):
        c = c1 if rng.random() < 0.5 else c2
        text, root = get_parts(c)
        random_edit(rng, text)
        root.set(f"key{rng.randrange(12)}", rng.randrange(10))
    t1, m1 = get_parts(c1)
    t2, m2 = get_parts(c2)
    assert t1.get_text() == t2.get_text()
    assert host.text("doc", "default", "text") == t1.get_text()
    assert host.map_entries("doc", "default", "root") == dict(m1.data.items())
    assert (host.stats["compactions"] > 0
            or host.stats["migrations"] > 0
            or any(p.slots > 8 for p in host._merge_pools.values()))
    assert host._map_slots > 4  # 12 keys forced map slot growth


@pytest.mark.soak  # ~65s: cross-bucket migration sweep
@pytest.mark.slow
def test_bucketed_pools_isolate_large_documents():
    """Ragged batching: one hot channel migrating to a bigger bucket must
    not widen the small channels' segment table (SURVEY §5.7)."""
    host = KernelMergeHost(merge_slots=8, num_props=1, flush_threshold=8)
    server = LocalCollabServer(merge_host=host)
    big = make_doc(server, "big")
    small = make_doc(server, "small")
    big_text, _ = get_parts(big)
    small_text, _ = get_parts(small)
    small_text.insert_text(0, "tiny")
    # Interleave positions so zamboni can't fully pack the big doc; msn
    # pinned low by a second (idle) client would also work, but distinct
    # inserts at position 0 keep every segment live anyway.
    for i in range(80):
        big_text.insert_text(i % max(len(big_text.get_text()), 1), "xy")
    host.flush()
    assert host.text("big", "default", "text") == big_text.get_text()
    assert host.text("small", "default", "text") == "tiny"
    big_row = host._merge_rows[("big", "default", "text")]
    small_row = host._merge_rows[("small", "default", "text")]
    assert big_row.pool is not small_row.pool
    assert small_row.pool.slots == 8  # small docs still pay the small bill
    assert big_row.pool.slots > 8
    assert host.stats["migrations"] > 0
    # And the migrated row keeps converging.
    big_text.insert_text(0, "Z")
    host.flush()
    assert host.text("big", "default", "text") == big_text.get_text()


@pytest.mark.parametrize("seed", range(3))
def test_matrix_channels_served_by_device_kernel(seed):
    """SharedMatrix docs behind the service: device grid == every replica
    (matrix.ts:547 hosted — the remaining north-star processCore path)."""
    from tests.test_matrix_kernel import random_matrix_edit

    host = KernelMergeHost(flush_threshold=16)
    server = LocalCollabServer(merge_host=host)
    rng = random.Random(seed)
    c1 = Container.create_detached(LocalDocumentService(server, "doc"))
    c1.runtime.create_datastore("default").create_channel(
        "grid", SharedMatrix.channel_type)
    c1.attach()
    c2 = Container.load(LocalDocumentService(server, "doc"))
    m1 = get_matrix(c1)
    m2 = get_matrix(c2)
    m1.insert_rows(0, 2)
    m1.insert_cols(0, 2)
    for _ in range(60):
        random_matrix_edit(rng, m1 if rng.random() < 0.5 else m2)
    assert grid_of(m1) == grid_of(m2)
    assert host.matrix_grid("doc", "default", "grid") == grid_of(m1)
    summary = host.summarize("doc")
    assert summary["datastores"]["default"]["grid"]["kind"] == "matrix"
    assert summary["datastores"]["default"]["grid"]["grid"] == grid_of(m1)


def test_matrix_client_overflow_routes_to_scalar():
    host = KernelMergeHost(flush_threshold=4, max_client_slots=32)
    server = LocalCollabServer(merge_host=host)
    c1 = Container.create_detached(LocalDocumentService(server, "doc"))
    c1.runtime.create_datastore("default").create_channel(
        "grid", SharedMatrix.channel_type)
    c1.attach()
    m1 = get_matrix(c1)
    m1.insert_rows(0, 1)
    m1.insert_cols(0, 1)
    # More clients than the configured ceiling → scalar rerouting.
    replicas = [Container.load(LocalDocumentService(server, "doc"))
                for _ in range(host.max_client_slots + 1)]
    for i, c in enumerate(replicas):
        get_matrix(c).set_cell(0, 0, i)
    assert host.stats["overflow_routed"] > 0
    assert grid_of(m1) == grid_of(get_matrix(replicas[-1]))
    assert host.matrix_grid("doc", "default", "grid") == grid_of(m1)
    # The scalar-served channel keeps tracking later edits.
    m1.insert_cols(1, 1)
    m1.set_cell(0, 1, "post")
    assert host.matrix_grid("doc", "default", "grid") == grid_of(m1)


def _op_message(seq, ref_seq, client_id, channel_op, msn=0):
    return SequencedDocumentMessage(
        client_id=client_id,
        sequence_number=seq,
        minimum_sequence_number=msn,
        client_sequence_number=seq,
        reference_sequence_number=ref_seq,
        type=MessageType.OPERATION,
        contents={"address": "default",
                  "contents": {"address": "text", "contents": channel_op}},
        timestamp=seq,
        data=None,
    )


def test_client_slot_overflow_routes_to_scalar():
    """More distinct writers than the configured ceiling → scalar
    rerouting, with the full history replayed and later ops served."""
    host = KernelMergeHost(merge_slots=256, flush_threshold=8,
                           max_client_slots=32)
    n_clients = host.max_client_slots + 5
    seq = 0
    for i in range(n_clients):
        seq += 1
        host.ingest("doc", _op_message(
            seq, seq - 1, f"c{i}",
            {"type": "insert", "pos": 0, "text": f"<{i}>"}))
    expected = "".join(f"<{i}>" for i in reversed(range(n_clients)))
    assert host.text("doc", "default", "text") == expected
    assert host.stats["overflow_routed"] == 1
    assert host.stats["scalar_ops"] > 0
    # Ops after the reroute apply through the scalar engine.
    seq += 1
    host.ingest("doc", _op_message(seq, seq - 1, "c0",
                                   {"type": "remove", "start": 0, "end": 4}))
    assert host.text("doc", "default", "text") == expected[4:]


@pytest.mark.soak  # ~70s: 6000-op memory-bound soak
@pytest.mark.slow
def test_soak_host_memory_bounded(monkeypatch):
    """Long-lived channel: the replay log trims at every flush and the
    text pool repacks, so host memory stays bounded by the flush cadence
    + live content — not by total history (VERDICT r2 weak #5)."""
    from fluidframework_tpu.server import merge_host as mh

    monkeypatch.setattr(mh, "_TEXT_REPACK_MIN", 4096)
    host = KernelMergeHost(merge_slots=64, flush_threshold=64)
    key = ("doc", "default", "text")
    seq = 0
    rng = random.Random(0)
    max_log = 0
    for i in range(6000):
        seq += 1
        if rng.random() < 0.6:
            op = {"type": "insert", "pos": 0, "text": "abcdefgh"}
        else:
            op = {"type": "remove", "start": 0, "end": 4}
        host.ingest("doc", _op_message(seq, seq - 1, f"c{i % 4}", op,
                                       msn=seq - 1))
        max_log = max(max_log, len(host._merge_rows[key].raw_log))
    host.flush()
    row = host._merge_rows[key]
    # ~48k chars inserted over the run; the log never exceeds one flush
    # window and the pool holds only (re-packable) referenced slices.
    assert max_log <= 2 * host.flush_threshold
    assert len(row.raw_log) == 0
    assert row.pool.text.used[row.row] < 40_000
    assert host.stats["compactions"] > 0
    # State stayed exact throughout.
    oracle = __import__(
        "fluidframework_tpu.dds.mergetree",
        fromlist=["MergeEngine"]).MergeEngine()
    rng = random.Random(0)
    s = 0
    for i in range(6000):
        s += 1
        if rng.random() < 0.6:
            oracle.apply_remote({"type": "insert", "pos": 0,
                                 "text": "abcdefgh"}, s, s - 1, f"c{i % 4}")
        else:
            oracle.apply_remote({"type": "remove", "start": 0, "end": 4},
                                s, s - 1, f"c{i % 4}")
    assert host.text(*key) == oracle.get_text()


def test_overflow_after_trimmed_log_seeds_from_device():
    """Slot overflow long after the replay log was trimmed: the scalar
    engine must seed EXACTLY from the device row (segments, tombstones,
    props) + the unapplied tail — full history is gone."""
    host = KernelMergeHost(merge_slots=256, flush_threshold=8,
                           max_client_slots=32)
    oracle = __import__(
        "fluidframework_tpu.dds.mergetree",
        fromlist=["MergeEngine"]).MergeEngine()
    seq = 0

    def both(op, client):
        nonlocal seq
        seq += 1
        host.ingest("doc", _op_message(seq, seq - 1, client, op))
        oracle.apply_remote(op, seq, seq - 1, client)

    rng = random.Random(1)
    for i in range(60):  # many flushes -> raw_log trimmed repeatedly
        both({"type": "insert", "pos": rng.randrange(i * 3 + 1),
              "text": f"<{i}>"}, f"c{i % 4}")
    both({"type": "annotate", "start": 0, "end": 10,
          "props": {"bold": True}}, "c0")
    key = ("doc", "default", "text")
    assert len(host._merge_rows[key].raw_log) < 60
    # Now blow the client-slot ceiling.
    for i in range(host.max_client_slots + 2):
        both({"type": "insert", "pos": 0, "text": f"[{i}]"}, f"x{i}")
    assert host.stats["overflow_routed"] == 1
    assert host.text(*key) == oracle.get_text()
    # Scalar-served continues exactly.
    both({"type": "remove", "start": 2, "end": 9}, "x0")
    both({"type": "insert", "pos": 4, "text": "tail"}, "c1")
    assert host.text(*key) == oracle.get_text()
    runs = host.rich_text(*key)
    assert any(props == {"bold": True} for _, props in runs)


def test_scalar_channel_readmitted_to_device():
    """The overflow escape is not one-way (VERDICT r2 weak #7): once the
    departed writers' segments compact away (window advance), the channel
    re-encodes onto a device row and serves on device again — exactly."""
    host = KernelMergeHost(merge_slots=256, flush_threshold=8,
                           max_client_slots=32)
    oracle = __import__(
        "fluidframework_tpu.dds.mergetree",
        fromlist=["MergeEngine"]).MergeEngine()
    seq = 0

    def both(op, client, msn=None):
        nonlocal seq
        seq += 1
        host.ingest("doc", _op_message(seq, seq - 1, client, op,
                                       msn=msn if msn is not None
                                       else seq - 1))
        oracle.apply_remote(op, seq, seq - 1, client)
        oracle.update_min_seq(msn if msn is not None else seq - 1)

    # Blow the ceiling: 37 distinct writers, one insert each at pos 0.
    n_writers = host.max_client_slots + 5
    for i in range(n_writers):
        both({"type": "insert", "pos": 0, "text": f"<{i}>"}, f"w{i}")
    key = ("doc", "default", "text")
    assert host.stats["overflow_routed"] == 1
    assert host._merge_rows[key].scalar is not None

    # Two surviving clients remove everything the departed writers wrote
    # and keep editing; the window advances past the removals, zamboni
    # compacts the old writers' segments away.
    text_len = len(host.text(*key))
    both({"type": "remove", "start": 0, "end": text_len}, "keeper-a")
    both({"type": "insert", "pos": 0, "text": "fresh "}, "keeper-b")
    both({"type": "annotate", "start": 0, "end": 5,
          "props": {"kept": True}}, "keeper-a", msn=seq)
    both({"type": "insert", "pos": 6, "text": "start"}, "keeper-a",
         msn=seq)
    host.flush()
    row = host._merge_rows[key]
    assert host.stats["readmissions"] == 1
    assert row.scalar is None and row.pool is not None
    assert host.text(*key) == oracle.get_text() == "fresh start"

    # Device-served again: later ops run through the kernel and match.
    device_before = host.stats["device_ops"]
    both({"type": "insert", "pos": 5, "text": "er"}, "keeper-b", msn=seq)
    both({"type": "remove", "start": 0, "end": 2}, "keeper-a", msn=seq)
    assert host.text(*key) == oracle.get_text()
    assert host.stats["device_ops"] > device_before
    runs = host.rich_text(*key)
    assert any(props == {"kept": True} for _, props in runs)


def test_128_writers_device_served():
    """BASELINE config 2's shape — 1 doc x 128 distinct writers — stays
    ON the device path: the overlap planes grow (32 slots/word -> 4
    words), nothing routes to scalar, and the converged text is
    byte-identical to the scalar oracle. Matches the reference's client
    scale (config.json:39 allows 1M clients/doc; conflictFarm.spec.ts
    stresses 32)."""
    host = KernelMergeHost(merge_slots=256, flush_threshold=16)
    oracle = __import__(
        "fluidframework_tpu.dds.mergetree",
        fromlist=["MergeEngine"]).MergeEngine()
    rng = random.Random(7)
    seq = 0
    n_writers = 128
    for i in range(n_writers):
        seq += 1
        op = {"type": "insert", "pos": rng.randrange(3 * i + 1),
              "text": f"<{i}>"}
        host.ingest("doc", _op_message(seq, seq - 1, f"w{i}", op))
        oracle.apply_remote(op, seq, seq - 1, f"w{i}")
    # Interleaved concurrent removes/annotates from every writer band so
    # the overlap planes actually carry bits in words 1-3.
    for i in range(0, n_writers, 7):
        seq += 1
        op = {"type": "remove", "start": i, "end": i + 3}
        host.ingest("doc", _op_message(seq, seq - 8, f"w{i}", op))
        oracle.apply_remote(op, seq, seq - 8, f"w{i}")
    key = ("doc", "default", "text")
    host.flush()
    row = host._merge_rows[key]
    assert host.stats["overflow_routed"] == 0
    assert host.stats["scalar_ops"] == 0
    assert host.stats["device_ops"] > 0
    assert row.scalar is None
    assert row.pool.client_capacity >= n_writers
    assert host.text(*key) == oracle.get_text()
    # Overlap-remove concurrency across high slots resolves identically.
    for i in (40, 80, 120):
        seq += 1
        op = {"type": "remove", "start": 0, "end": 2}
        host.ingest("doc", _op_message(seq, seq - 3, f"w{i}", op))
        oracle.apply_remote(op, seq, seq - 3, f"w{i}")
    assert host.text(*key) == oracle.get_text()
    assert host.stats["overflow_routed"] == 0


def test_annotate_and_markers_materialize():
    host = KernelMergeHost(flush_threshold=100)
    server = LocalCollabServer(merge_host=host)
    c1 = make_doc(server, "doc")
    text, _ = get_parts(c1)
    text.insert_text(0, "hello world")
    text.annotate_range(0, 5, {"bold": True})
    text.insert_marker(5, ref_type="tile", marker_id="m1")
    assert host.text("doc", "default", "text") == "hello world"
    runs = host.rich_text("doc", "default", "text")
    assert ("hello", {"bold": True}) in runs
    assert ("\x00", None) in runs


def test_summarize_materializes_from_device():
    host = KernelMergeHost(flush_threshold=100)
    server = LocalCollabServer(merge_host=host)
    c1 = make_doc(server, "doc")
    text, root = get_parts(c1)
    text.insert_text(0, "abc")
    root.set("x", 1)
    summary = host.summarize("doc")
    channels = summary["datastores"]["default"]
    assert channels["text"]["kind"] == "mergeTree"
    assert "".join(t for t, _ in channels["text"]["content"]) == "abc"
    assert channels["root"]["entries"] == {"x": 1}
    assert summary["sequence_number"] > 0


def test_long_lived_doc_stays_in_bucket_via_coalesce():
    """A long-lived document whose window keeps advancing must NOT climb
    buckets forever: under capacity pressure the host repacks the text
    pool and runs the coalescing zamboni, so slot demand tracks the
    collab window, not total history (mergeTree.ts:1412 pack analog)."""
    host = KernelMergeHost(merge_slots=64, flush_threshold=48)
    oracle = __import__(
        "fluidframework_tpu.dds.mergetree",
        fromlist=["MergeEngine"]).MergeEngine()
    rng = random.Random(3)
    seq = 0
    length = 0
    for i in range(3000):
        seq += 1
        if length > 30 and rng.random() < 0.45:
            start = rng.randrange(length - 8)
            op = {"type": "remove", "start": start,
                  "end": start + rng.randrange(1, 9)}
            length -= op["end"] - op["start"]
        else:
            text = "abcdefgh"[:rng.randrange(1, 8)]
            op = {"type": "insert", "pos": rng.randrange(length + 1),
                  "text": text}
            length += len(text)
        host.ingest("doc", _op_message(seq, seq - 1, f"c{i % 4}", op,
                                       msn=seq - 1))
        oracle.apply_remote(op, seq, seq - 1, f"c{i % 4}")
        oracle.update_min_seq(seq - 1)
    host.flush()
    key = ("doc", "default", "text")
    row = host._merge_rows[key]
    # ~1650 inserts x 2 slots would demand a 8192-slot bucket without
    # coalescing; the window is 1 op deep, so the table stays small.
    assert row.pool.slots <= 256, row.pool.slots
    assert host.stats["compactions"] > 0
    assert host.text(*key) == oracle.get_text()


def test_matrix_cell_run_fast_path_with_compaction():
    """A settled grid under cell-write storms is served by the handle-
    indexed planes (one step tick per flush, nothing grows); a structural
    op in the same flush as cells stays on the device path, and the
    serving tick's zamboni keeps the vector tables at window size."""
    host = KernelMergeHost(flush_threshold=10**9, matrix_vec_slots=64,
                           matrix_handles=16)
    server = LocalCollabServer(merge_host=host)
    rng = random.Random(3)
    c1 = Container.create_detached(LocalDocumentService(server, "doc"))
    c1.runtime.create_datastore("default").create_channel(
        "grid", SharedMatrix.channel_type)
    c1.attach()
    c2 = Container.load(LocalDocumentService(server, "doc"))
    m1, m2 = get_matrix(c1), get_matrix(c2)
    m1.insert_rows(0, 8)
    m1.insert_cols(0, 8)
    host.flush()
    ticks = host.metrics.counter("matrix.step_ticks").value
    grows = host.metrics.counter("matrix.state_grows").value
    # Cell-only storm with repeated keys: the latest write of a cell wins.
    for _ in range(40):
        m = m1 if rng.random() < 0.5 else m2
        m.set_cell(rng.randrange(8), rng.randrange(4), rng.randrange(99))
    host.flush()
    assert host.metrics.counter("matrix.step_ticks").value == ticks + 1
    assert host.metrics.counter("matrix.state_grows").value == grows
    assert grid_of(m1) == grid_of(m2)
    assert host.matrix_grid("doc", "default", "grid") == grid_of(m1)
    # Structural churn between cell writes, flushed together: the device
    # path serves it all and removed rows' slots pack away.
    for _ in range(6):
        for _ in range(5):
            m1.insert_rows(2, 1)
            m2.set_cell(rng.randrange(8), rng.randrange(8),
                        rng.randrange(99))
            m1.remove_rows(2, 1)
        host.flush()
        assert grid_of(m1) == grid_of(m2)
        assert host.matrix_grid("doc", "default", "grid") == grid_of(m1)
    assert host.metrics.counter("matrix.ops_scalar").value == 0
    assert host._matrix_vec_slots == 64  # the zamboni kept the table
    assert host._matrix_handles[0] > 16  # 30 inserted rows grew handles


@pytest.mark.parametrize("seed", range(3))
def test_precompaction_uses_applied_msn(seed):
    """Compaction that runs BEFORE a flush's pending ops apply (the
    capacity pass and the block pre-tick rebalance) drops tombstones at
    the MSN of the applied frontier, never at the newest pending MSN: an
    early pending op may reference a seq below it (ref lags 4, MSN lags
    5 per message, 32-op ticks)."""
    from fluidframework_tpu.dds.mergetree import MergeEngine

    rng = random.Random(seed)
    host = KernelMergeHost(merge_slots=64, flush_threshold=10**9)
    oracle = MergeEngine()
    for seq in range(1, 32 * 7 + 1):
        writer = f"w{rng.randrange(6)}"
        ref = rng.randrange(max(seq - 4, 0), seq)
        length = len(oracle.get_text(ref, writer))
        if length > 8 and rng.random() < 0.35:
            start = rng.randrange(length - 4)
            op = {"type": "remove", "start": start,
                  "end": start + rng.randint(1, 4)}
        else:
            op = {"type": "insert", "pos": rng.randint(0, length),
                  "text": "abcd"[:rng.randint(1, 4)]}
        msn = max(0, seq - 5)
        host.ingest("doc", _op_message(seq, ref, writer, op, msn=msn))
        oracle.apply_remote(op, seq, ref, writer)
        oracle.update_min_seq(msn)
        if seq % 32 == 0:
            host.flush()
            assert host.text("doc", "default", "text") == \
                oracle.get_text(), seq
    assert host.stats["scalar_ops"] == 0
    assert host.stats["compactions"] + host.stats["rebalances"] > 0
