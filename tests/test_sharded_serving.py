"""Multi-host serving assembly (parallel/serving.py): simulated host
processes own contiguous doc ranges, feed one mesh-sharded fused
deli+merger tick, and harvest only their own rows — the
partitionManager.ts scale-out shape over a jax Mesh."""

import jax
import numpy as np
import pytest

from fluidframework_tpu.parallel.mesh import make_mesh
from fluidframework_tpu.parallel.serving import ShardedServing


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    assert len(devices) >= 8, "conftest provisions a virtual 8-device mesh"
    return make_mesh(devices[:8])


def test_hosts_own_disjoint_contiguous_ranges(mesh):
    serving = ShardedServing(mesh, num_docs=32, k=4, num_hosts=4)
    covered = []
    for port in serving.hosts:
        covered.extend(range(port.start, port.stop))
        assert serving.route(port.start).host_id == port.host_id
    assert covered == list(range(32))


def test_sharded_tick_matches_unsharded_reference(mesh):
    """Bit-identical map state: the same op stream through (a) the
    sharded multi-host serving loop and (b) a single-device run."""
    num_docs, k = 16, 8
    rng = np.random.default_rng(0)
    stream = {row: (rng.integers(0, 1 << 20, k).astype(np.uint32) << 12
                    | (row % 8) << 2)
              for row in range(num_docs)}

    serving = ShardedServing(mesh, num_docs=num_docs, k=k, num_hosts=2)
    serving.join_all()
    for row, words in stream.items():
        serving.submit(row, words, first_cseq=1)
    harvest = serving.tick()
    assert all(n == k for rows in harvest.values()
               for (n, _f, _l) in rows.values())

    single = ShardedServing(make_mesh(jax.devices()[:1]),
                            num_docs=num_docs, k=k, num_hosts=1)
    single.join_all()
    for row, words in stream.items():
        single.submit(row, words, first_cseq=1)
    single.tick()
    assert np.array_equal(serving.map_rows(), single.map_rows())
    assert np.array_equal(np.asarray(serving.seq_state.seq),
                          np.asarray(single.seq_state.seq))


def test_harvest_is_shard_local_and_outputs_sharded(mesh):
    serving = ShardedServing(mesh, num_docs=16, k=4, num_hosts=2)
    serving.join_all()
    words = np.full(4, 5 << 12, np.uint32)
    for row in range(16):
        serving.submit(row, words, first_cseq=1)
    harvest = serving.tick()
    for port in serving.hosts:
        assert set(harvest[port.host_id]) \
            == set(range(port.start, port.stop))
    devices = {s.device
               for s in serving.map_state.value.addressable_shards}
    assert len(devices) == 8


def test_foreign_row_submission_rejected(mesh):
    serving = ShardedServing(mesh, num_docs=16, k=4, num_hosts=2)
    with pytest.raises(KeyError):
        serving.route(99)
    serving.submit(3, np.zeros(2, np.uint32), first_cseq=1)
    with pytest.raises(ValueError, match="already pending"):
        serving.submit(3, np.zeros(2, np.uint32), first_cseq=3)


def test_kernel_dedup_across_sharded_ticks(mesh):
    """At-least-once delivery: a host resending its tick verbatim gets
    everything IGNORED by the sharded sequencer (clientSeq dedup)."""
    serving = ShardedServing(mesh, num_docs=8, k=4, num_hosts=2)
    serving.join_all()
    words = np.full(4, 9 << 12, np.uint32)
    for row in range(8):
        serving.submit(row, words, first_cseq=1)
    first = serving.tick()
    for row in range(8):
        serving.submit(row, words, first_cseq=1)  # verbatim resend
    second = serving.tick(now=3)
    for port in serving.hosts:
        for row in range(port.start, port.stop):
            assert first[port.host_id][row][0] == 4
            assert second[port.host_id][row][0] == 0  # all duplicates


@pytest.mark.parametrize("text_slots", [0, 32], ids=["map_tick",
                                                     "mixed_tick"])
def test_tick_acks_its_own_tick_and_flush_is_empty(mesh, text_slots):
    """The synchronous contract: tick() returns the acks of the tick it
    ran, its durable records already appended, and flush() has nothing
    left to return. Covers both device programs (the map-only storm tick
    and the fused mixed tick)."""
    from fluidframework_tpu.ops import mergetree_kernel as mtk

    num_docs, k = 8, 4
    serving = ShardedServing(mesh, num_docs=num_docs, k=k, num_hosts=2,
                             num_clients=2, text_slots=text_slots)
    serving.join_all()
    last = {row: 1 for row in range(num_docs)}  # the join's seq
    for t in range(3):
        for row in range(num_docs):
            if text_slots and row % 2:
                serving.submit_text(
                    row, [dict(kind=mtk.MT_INSERT, pos=0, text="ab")] * k,
                    first_cseq=1 + t * k, ref_seq=last[row])
            else:
                words = np.full(k, (row << 2) | ((t + 1) << 12), np.uint32)
                serving.submit(row, words, first_cseq=1 + t * k,
                               ref_seq=last[row])
        harvest = serving.tick()
        for port in serving.hosts:
            rows = harvest[port.host_id]
            assert set(rows) == set(range(port.start, port.stop))
            for row, ack in rows.items():
                assert ack == (k, last[row] + 1, last[row] + k), (t, row)
                rec = serving.durable[row][-1]
                assert (rec["n_seq"], rec["first"], rec["last"]) == ack
                assert serving.durable_offset(row) == t + 1
                last[row] = ack[2]
        assert serving.flush() == []
        assert serving.ledger.records()[-1]["depth"] == 0
    assert serving.ledger.records()[-1]["tick"] == 2


def test_global_metrics_psum(mesh):
    serving = ShardedServing(mesh, num_docs=16, k=4, num_hosts=4)
    serving.join_all()
    for row in range(16):
        serving.submit(row, np.full(4, 2 << 12, np.uint32), first_cseq=1)
    serving.tick()
    metrics = serving.global_metrics()
    assert metrics["seq"] == 16 * 5  # join + 4 ops per doc
    assert metrics["present"] == 16


def test_host_kill_resume_rebalance(mesh):
    """Serving-host failover (VERDICT r3 item 6): checkpoint host 1,
    keep serving (durable log grows past the checkpoint), kill it, hand
    its doc range to host 0, restore from checkpoint + durable-log
    replay — no sequence regression, converged map rows, and the next
    tick continues seq assignment exactly where the log ended."""
    rng = np.random.default_rng(5)
    num_docs, k = 16, 8

    def words_for(row, t):
        slots = (np.arange(k) + t) % 8
        vals = 1000 * (t + 1) + row * 10 + np.arange(k)
        return ((slots.astype(np.uint32) << 2)
                | (vals.astype(np.uint32) << 12)).astype(np.uint32)

    serving = ShardedServing(mesh, num_docs=num_docs, k=k, num_hosts=2)
    serving.join_all()
    # Ticks 0-1: full traffic on every row.
    for t in range(2):
        for row in range(num_docs):
            serving.submit(row, words_for(row, t), first_cseq=1 + t * k)
        serving.tick()
    cp = serving.checkpoint_host(1)
    # Tick 2: more traffic AFTER the checkpoint (the durable tail).
    for row in range(num_docs):
        serving.submit(row, words_for(row, 2), first_cseq=1 + 2 * k)
    serving.tick()
    final_rows = serving.map_rows().copy()
    final_seq = np.asarray(serving.seq_state.seq).copy()
    durable = serving.durable

    # The replacement assembly: host 1 is dead; host 0 owns everything.
    revived = ShardedServing(mesh, num_docs=num_docs, k=k, num_hosts=2)
    revived.join_all()
    # Host 0's rows re-run the full log (its own recovery, offset 0);
    # host 1's rows restore from the checkpoint + tail replay.
    revived.rebalance_from(1, 0)
    assert revived.route(num_docs - 1).host_id == 0
    # host 0 replay from scratch (its durable log, offset 0):
    for t in range(3):
        for row in range(0, 8):
            revived.submit(row, words_for(row, t), first_cseq=1 + t * k)
        revived.tick()
    # host 1 rows: checkpoint + tail.
    revived.restore_host(cp, durable, serving._durable_base)

    got_rows = revived.map_rows()
    got_seq = np.asarray(revived.seq_state.seq)
    assert np.array_equal(got_seq, final_seq), (got_seq, final_seq)
    assert np.array_equal(got_rows, final_rows)

    # Continued service: the next tick's first seq extends the history.
    for row in range(num_docs):
        revived.submit(row, words_for(row, 3), first_cseq=1 + 3 * k)
    harvest = revived.tick()
    merged = {**harvest[0], **harvest[1]}
    for row in range(num_docs):
        n_seq, first, last = merged[row]
        assert n_seq == k
        assert first == final_seq[row] + 1, (row, first, final_seq[row])


def test_durable_log_trims_to_checkpoint_horizon(mesh):
    """Log retention: after checkpointing, records below the horizon are
    retired (bounded host memory); restores against the trimmed prefix
    fail loudly, restores from the checkpoint still replay exactly."""
    serving = ShardedServing(mesh, num_docs=8, k=4, num_hosts=1)
    serving.join_all()
    words = np.array([(1 << 12) | (0 << 2), (2 << 12) | (1 << 2),
                      (3 << 12) | (2 << 2), (4 << 12) | (3 << 2)],
                     np.uint32)
    for t in range(3):
        for r in range(8):
            serving.submit(r, words, first_cseq=1 + t * 4)
        serving.tick()
    cp = serving.checkpoint_host(0)
    for r in range(8):
        serving.submit(r, words, first_cseq=13)
    serving.tick()
    assert serving.durable_offset(0) == 4
    serving.trim_durable(cp["log_offsets"])
    assert len(serving.durable[0]) == 1  # only the post-checkpoint tick
    assert serving.durable_offset(0) == 4  # absolute cursor unmoved

    want_seq = np.asarray(serving.seq_state.seq).copy()
    revived = ShardedServing(mesh, num_docs=8, k=4, num_hosts=1)
    revived.join_all()
    revived.restore_host(cp, serving.durable, serving._durable_base)
    assert np.array_equal(np.asarray(revived.seq_state.seq), want_seq)

    # A checkpoint OLDER than the horizon must refuse, not corrupt.
    stale = dict(cp, log_offsets={r: 0 for r in range(8)})
    third = ShardedServing(mesh, num_docs=8, k=4, num_hosts=1)
    third.join_all()
    with pytest.raises(ValueError):
        third.restore_host(stale, serving.durable, serving._durable_base)


def test_durable_retention_bounds_log_without_checkpoints(mesh):
    """An assembly nobody checkpoints must not grow its durable log with
    total history: automatic retention keeps the tail bounded and the
    absolute offsets consistent."""
    serving = ShardedServing(mesh, num_docs=8, k=4, num_hosts=1,
                             durable_retention_ticks=5)
    serving.join_all()
    words = np.array([(7 << 12)], np.uint32)
    for t in range(12):
        serving.submit(0, words, first_cseq=1 + t)
        serving.tick()
    assert len(serving.durable[0]) == 5
    assert serving.durable_offset(0) == 12
    assert serving._durable_base[0] == 7


def test_shard_residency_oversubscribed_churn(mesh):
    """ShardResidency (ISSUE 9): a registered doc population 5x the
    device row pool serves through hydrate/evict churn with every doc's
    converged value preserved, resident count bounded by the pool, and
    idle shrink freeing rows."""
    from fluidframework_tpu.parallel.serving import ShardResidency

    num_rows = 4
    serving = ShardedServing(make_mesh(jax.devices()[:1]),
                             num_docs=num_rows, k=4, num_hosts=2,
                             num_clients=2, map_slots=8)
    res = ShardResidency(serving, join_slots=(0,))
    docs = [f"doc-{i}" for i in range(5 * num_rows)]
    want = {}
    for rnd in range(2):
        for i, doc in enumerate(docs):
            row = res.resolve(doc)
            assert serving.hosts[res.host_for(doc)].owns(row)
            value = (rnd * 37 + i) % 97 + 1
            words = np.array(
                [np.uint32(value) << 12 | np.uint32(1) << 2], np.uint32)
            serving.submit(row, words, first_cseq=rnd + 1)
            serving.tick()
            want[doc] = value
    assert res.resident_count() <= num_rows
    assert res.stats["evictions"] > 0
    assert res.stats["cold_hydrations"] > 0
    # Every doc's value survived its evict/re-hydrate round trips.
    for doc in docs:
        row = res.resolve(doc)
        got = int(np.asarray(serving.map_state.value)[row, 1])
        assert got == want[doc], doc
    # Idle shrink: one resident per host, rows recycled to the free list.
    res.evict_idle(keep_per_host=1)
    assert res.resident_count() <= 2
    assert sum(len(f) for f in res._free.values()) >= num_rows - 2


def test_shard_residency_refuses_pending_evict(mesh):
    from fluidframework_tpu.parallel.serving import ShardResidency

    serving = ShardedServing(make_mesh(jax.devices()[:1]), num_docs=2,
                             k=4, num_hosts=1, map_slots=8)
    res = ShardResidency(serving)
    row = res.resolve("doc-a")
    serving.submit(row, np.array([(5 << 12) | (1 << 2)], np.uint32),
                   first_cseq=1)
    with pytest.raises(ValueError):
        res.evict("doc-a")
    serving.tick()
    res.evict("doc-a")  # settles after the tick
    assert not res.is_resident("doc-a")


def test_shard_residency_resolve_skips_pending_victims(mesh):
    """A full host range with a pending-submission LRU resident must
    evict the next evictable doc, not crash on the pinned one."""
    from fluidframework_tpu.parallel.serving import ShardResidency

    serving = ShardedServing(make_mesh(jax.devices()[:1]), num_docs=2,
                             k=4, num_hosts=1, map_slots=8)
    res = ShardResidency(serving)
    row_a = res.resolve("doc-a")  # LRU after doc-b resolves
    res.resolve("doc-b")
    serving.submit(row_a, np.array([(5 << 12) | (1 << 2)], np.uint32),
                   first_cseq=1)
    row_c = res.resolve("doc-c")  # must evict doc-b, not doc-a
    assert res.is_resident("doc-a") and res.is_resident("doc-c")
    assert not res.is_resident("doc-b")
    assert row_c != row_a


def test_megadoc_lanes_match_single_row_twin(mesh):
    """Lane placement in the serving assembly (ISSUE 12): one logical
    doc spread over 4 ROWS (device lanes) through the doc-space
    combiner must converge byte-identically — entries AND doc-seq ack
    quads — with a single-row twin serving the same writer batches
    sequentially, while dup resends and gap batches never touch a
    lane."""
    from fluidframework_tpu.parallel.serving import MegaDocLanes

    k, writers = 6, 6
    serving = ShardedServing(mesh, num_docs=8, k=k, num_hosts=1,
                             num_clients=4, map_slots=16)
    serving.join_all(slots=list(range(4)))
    lanes = MegaDocLanes(serving, lane_rows=[0, 1, 2, 3])

    twin = ShardedServing(mesh, num_docs=8, k=k, num_hosts=1,
                          num_clients=writers + 1, map_slots=16)
    twin.join_all(slots=list(range(writers)))
    # Writers join up front on BOTH sides (each join revs the doc seq).
    for w in range(writers):
        lanes.join(f"writer-{w}")

    rng = np.random.default_rng(42)
    cseqs = {w: 1 for w in range(writers)}
    prev = {}
    mega_acks, twin_acks = [], []
    twin_seq = 0
    for r in range(4):
        for w in range(writers):
            client = f"writer-{w}"
            action = rng.choice(["fresh", "fresh", "dup", "gap"])
            words = (rng.integers(0, 1 << 20, k).astype(np.uint32) << 12
                     | (rng.integers(0, 16, k).astype(np.uint32) << 2))
            if action == "dup" and w in prev:
                cseq0, words = prev[w]
            elif action == "gap":
                cseq0 = cseqs[w] + 3
            else:
                cseq0 = cseqs[w]
                cseqs[w] += k
                prev[w] = (cseq0, words)
            dec = lanes.submit(client, words, cseq0, ref_seq=1)
            mega_acks.append((r, w, dec.n_seq, dec.first, dec.last))
            # Twin: the same batch on ONE row, its own tick (the
            # single-lane shape), writer = its own client slot.
            h = twin.submit(0, words, cseq0, ref_seq=1, client_slot=w)
            harvest = twin.tick()
            n_ok, first, last = harvest[0][0]
            twin_seq = last if n_ok else twin_seq
            twin_acks.append((r, w, n_ok,
                              first if n_ok else 2**31 - 1, last))
        serving.flush()
    twin.flush()
    serving.flush()
    assert mega_acks == twin_acks
    twin_vals = {s: int(v) for s, v in enumerate(
        np.asarray(twin.map_state.value[0]))
        if np.asarray(twin.map_state.present[0])[s]}
    assert lanes.entries() == twin_vals
    # The lanes really spread the doc: >1 row holds sequenced state.
    active_rows = {row for row in lanes.rows
                   if int(np.asarray(serving.seq_state.seq[row])) > 0}
    assert len(active_rows) > 1


def test_shard_residency_live_migration_2_to_4_hosts(mesh):
    """Live placement in the device-lane tier (ISSUE 13): genesis on 2
    of 4 host ranges, activation + PlacementController rebalance moves
    docs via the cold-record carrier — every value preserved, every
    doc's row inside its NEW owner's range, genesis hashes never
    silently re-route."""
    from fluidframework_tpu.parallel.placement import PlacementController
    from fluidframework_tpu.parallel.serving import ShardResidency

    serving = ShardedServing(make_mesh(jax.devices()[:1]), num_docs=8,
                             k=4, num_hosts=4, map_slots=8)
    res = ShardResidency(serving, active_hosts=(0, 1))
    docs = [f"doc-{i}" for i in range(8)]
    want = {}
    for i, doc in enumerate(docs):
        row = res.resolve(doc)
        assert res.host_for(doc) in (0, 1)
        value = 10 + i
        serving.submit(row, np.array([(value << 12) | (1 << 2)],
                                     np.uint32), first_cseq=1)
        serving.tick()
        want[doc] = value
    serving.flush()
    before = {d: res.host_for(d) for d in docs}
    res.activate_host(2)
    res.activate_host(3)
    # Activation alone must not re-route anything (sticky genesis).
    assert {d: res.host_for(d) for d in docs} == before
    ctrl = PlacementController(res, max_moves_per_round=8)
    report = ctrl.rebalance()
    assert report["converged"], report
    assert set(report["docs_per_host"]) == {0, 1, 2, 3}
    assert res.stats["migrations"] >= 2
    assert len(res.blackouts_s) == res.stats["migrations"]
    for doc in docs:
        row = res.resolve(doc)
        assert serving.hosts[res.host_for(doc)].owns(row)
        got = int(np.asarray(serving.map_state.value)[row, 1])
        assert got == want[doc], doc


def test_shard_residency_migrate_refuses_pending_and_rolls_on(mesh):
    """A doc with a pending (unticked) submission refuses migration —
    tick first, then the move succeeds and serving resumes on the new
    host with the doc's cseq dedup intact."""
    from fluidframework_tpu.parallel.serving import ShardResidency

    serving = ShardedServing(make_mesh(jax.devices()[:1]), num_docs=4,
                             k=4, num_hosts=2, map_slots=8)
    res = ShardResidency(serving)
    doc = "doc-a"
    row = res.resolve(doc)
    src = res.host_for(doc)
    dst = 1 - src
    serving.submit(row, np.array([(5 << 12) | (1 << 2)], np.uint32),
                   first_cseq=1)
    with pytest.raises(ValueError):
        res.migrate(doc, dst)
    serving.tick()
    serving.flush()
    new_row = res.migrate(doc, dst)
    assert res.host_for(doc) == dst
    assert serving.hosts[dst].owns(new_row)
    # Dedup survives the move: a verbatim resend sequences zero ops.
    serving.submit(new_row, np.array([(5 << 12) | (1 << 2)], np.uint32),
                   first_cseq=1)
    harvest = serving.tick()
    serving.flush()
    assert int(np.asarray(serving.map_state.value)[new_row, 1]) == 5
