"""All-family fused sharded serving (parallel/serving.py + the mixed
storm tick): one SPMD device program tickets AND applies map, merge-tree
text, matrix and tree rows over the mesh — the reference's
one-deltas-stream-for-all-op-types contract (deli/lambda.ts:82 tickets
every op type; scriptorium/lambda.ts:16 consumes them uniformly;
partition scale-out applies to all documents,
lambdas-driver/src/kafka-service/partitionManager.ts:24)."""

import jax
import numpy as np
import pytest

from fluidframework_tpu.ops import matrix_kernel as mxk
from fluidframework_tpu.ops import mergetree_blocks as mtb
from fluidframework_tpu.ops import mergetree_kernel as mtk
from fluidframework_tpu.ops import tree_kernel as tk
from fluidframework_tpu.parallel.mesh import make_mesh
from fluidframework_tpu.parallel.serving import HostPort, ShardedServing
from fluidframework_tpu.parallel.serving import _plane_rows


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    assert len(devices) >= 8, "conftest provisions a virtual 8-device mesh"
    return make_mesh(devices[:8])


def make_mixed(mesh, num_docs=16, num_hosts=2, k=8):
    serving = ShardedServing(
        mesh, num_docs=num_docs, k=k, num_hosts=num_hosts, num_clients=2,
        map_slots=16, text_slots=64, matrix_vec_slots=32,
        matrix_handles=16, tree_slots=16)
    serving.join_all(slots=(0, 1))
    return serving


def family_of(row):
    return ("map", "text", "matrix", "tree")[row % 4]


TEXT_TICKS = [
    # (client, ops) per tick; inserts carry text, positions exercise
    # splits + concurrent-frame placement across clients.
    (0, [dict(kind=mtk.MT_INSERT, pos=0, text="hello world"),
         dict(kind=mtk.MT_INSERT, pos=5, text=", dear")]),
    (1, [dict(kind=mtk.MT_REMOVE, pos=0, end=5),
         dict(kind=mtk.MT_INSERT, pos=0, text="HI")]),
    (0, [dict(kind=mtk.MT_ANNOTATE, pos=0, end=4, prop_key=1, prop_val=7),
         dict(kind=mtk.MT_INSERT, pos=8, text="!!")]),
]

MATRIX_TICKS = [
    (0, [dict(target=mxk.MX_ROWS, kind=mtk.MT_INSERT, pos=0, count=2),
         dict(target=mxk.MX_COLS, kind=mtk.MT_INSERT, pos=0, count=2),
         dict(target=mxk.MX_CELL, row=0, col=0, value=11),
         dict(target=mxk.MX_CELL, row=1, col=1, value=22)]),
    (1, [dict(target=mxk.MX_CELL, row=0, col=1, value=33),
         dict(target=mxk.MX_ROWS, kind=mtk.MT_REMOVE, pos=1, end=2)]),
]

TREE_TICKS = [
    (0, [dict(kind=tk.TREE_INSERT, node=1, parent=0, trait=1, payload=5),
         dict(kind=tk.TREE_INSERT, node=2, parent=0, trait=1, payload=6)]),
    (1, [dict(kind=tk.TREE_INSERT_BEFORE, node=3, parent=2, trait=1,
              payload=7),
         dict(kind=tk.TREE_SET_VALUE, node=1, payload=9)]),
]


def drive_mixed(serving, num_docs, ticks=3):
    """Submit each row its family's scripted traffic; return last seqs."""
    cseq = {row: {0: 0, 1: 0} for row in range(num_docs)}
    ref = {row: 2 for row in range(num_docs)}  # post-join doc seq
    for t in range(ticks):
        submitted = []
        for row in range(num_docs):
            fam = family_of(row)
            if fam == "map":
                words = ((np.uint32(row % 8) << 2)
                         | (np.arange(4, dtype=np.uint32) + 100 * t) << 12)
                serving.submit(row, words, first_cseq=cseq[row][0] + 1,
                               ref_seq=ref[row], client_slot=0)
                cseq[row][0] += len(words)
                submitted.append((row, len(words)))
            elif fam == "text" and t < len(TEXT_TICKS):
                client, ops = TEXT_TICKS[t]
                serving.submit_text(row, ops, cseq[row][client] + 1,
                                    ref_seq=ref[row], client_slot=client)
                cseq[row][client] += len(ops)
                submitted.append((row, len(ops)))
            elif fam == "matrix" and t < len(MATRIX_TICKS):
                client, ops = MATRIX_TICKS[t]
                serving.submit_matrix(row, ops, cseq[row][client] + 1,
                                      ref_seq=ref[row], client_slot=client)
                cseq[row][client] += len(ops)
                submitted.append((row, len(ops)))
            elif fam == "tree" and t < len(TREE_TICKS):
                client, ops = TREE_TICKS[t]
                serving.submit_tree(row, ops, cseq[row][client] + 1,
                                    ref_seq=ref[row], client_slot=client)
                cseq[row][client] += len(ops)
                submitted.append((row, len(ops)))
        harvest = serving.tick()
        merged = {}
        for rows in harvest.values():
            merged.update(rows)
        for row, n in submitted:
            n_ok, first, last = merged[row]
            assert n_ok == n, (t, row, merged[row])
            ref[row] = last  # client saw its ack: next frame refs it
    return ref


def reference_text_state(slots=64):
    """The same text stream through the raw kernel with host-assigned
    seqs — the oracle for the on-device ticket windows."""
    state = mtk.init_state(1, slots, 4, mtk.overlap_words_for(2))
    pool = mtk.TextPool(1)
    seq = 2  # post-join
    ref = 2
    for client, ops in TEXT_TICKS:
        encoded = []
        for op in ops:
            op = dict(op)
            seq += 1
            if op.get("kind") == mtk.MT_INSERT:
                text = op.pop("text")
                op["pool_start"] = pool.append(0, text)
                op["text_len"] = len(text)
            op.update(seq=seq, ref_seq=ref, client=client)
            encoded.append(op)
        batch = mtk.make_merge_op_batch([encoded], 1, len(encoded))
        state = mtk.apply_tick(state, batch)
        ref = seq
    return state, pool


def reference_matrix_state(vec_slots=32, handles=16):
    state = mxk.init_state(1, vec_slots, handles,
                           overlap_words=mtk.overlap_words_for(2))
    alloc = mxk.HandleAllocator(1)
    seq, ref = 2, 2
    for client, ops in MATRIX_TICKS:
        encoded = []
        for op in ops:
            op = dict(op)
            seq += 1
            if (op.get("target") in (mxk.MX_ROWS, mxk.MX_COLS)
                    and op.get("kind") == mtk.MT_INSERT):
                op["handle_base"] = alloc.alloc(0, op.get("count", 1))
            op.update(seq=seq, ref_seq=ref, client=client)
            encoded.append(op)
        batch = mxk.make_matrix_op_batch([encoded], 1, len(encoded))
        state = mxk.apply_tick(state, batch)
        ref = seq
    return state


def reference_tree_state(slots=16):
    state = tk.init_state(1, slots)
    for _client, ops in TREE_TICKS:
        batch = tk.make_tree_op_batch([list(ops)], 1, len(ops))
        state, _out = tk.apply_tick(state, batch)
    return state


def row_planes(state, row):
    port = HostPort(-1, row, row + 1)
    return jax.tree.map(lambda a: _plane_rows(a, port), state)


def test_mixed_population_matches_per_family_kernels(mesh):
    """16 docs (4 of each family) served by ONE fused SPMD tick over 8
    devices match the raw per-family kernels run with the oracle seq
    assignment — the ticket windows and every family's apply leg are
    bit-exact under sharding."""
    num_docs = 16
    serving = make_mixed(mesh, num_docs=num_docs)
    drive_mixed(serving, num_docs)

    ref_text, ref_pool = reference_text_state()
    ref_mx = reference_matrix_state()
    ref_tree = reference_tree_state()
    expected_text = mtk.materialize(ref_text, ref_pool, 0)
    assert expected_text  # the script must leave visible text

    first_text = None
    for row in range(num_docs):
        fam = family_of(row)
        if fam == "text":
            # The block serving table rebalances at each tick's MSN, so
            # plane equality against the flat oracle is not meaningful;
            # the contract is byte-identical TEXT vs the flat kernel,
            # bitwise-identical block state across same-traffic rows,
            # and exact summaries (no incremental drift).
            got = jax.tree.map(np.asarray,
                               row_planes(serving.merge_state, row))
            if first_text is None:
                first_text = got
            else:
                for a, b in zip(jax.tree.leaves(got),
                                jax.tree.leaves(first_text)):
                    assert np.array_equal(a, b), row
            rebuilt = mtb.recompute_summaries(got)
            for field in ("blk_live_len", "blk_max_seq", "blk_tomb",
                          "count"):
                assert np.array_equal(
                    np.asarray(getattr(got, field)),
                    np.asarray(getattr(rebuilt, field))), (row, field)
            assert serving.text_of(row) == expected_text
        elif fam == "matrix":
            got = row_planes(serving.matrix_state, row)
            flat_got = jax.tree.leaves(got)
            flat_ref = jax.tree.leaves(jax.tree.map(np.asarray, ref_mx))
            for g, r in zip(flat_got, flat_ref):
                assert np.array_equal(np.asarray(g), np.asarray(r)), row
        elif fam == "tree":
            got = row_planes(serving.tree_state, row)
            for field in tk.TreeState._fields:
                assert np.array_equal(
                    np.asarray(getattr(got, field)),
                    np.asarray(getattr(ref_tree, field))), (row, field)

    # Every family's state stays sharded across all 8 devices.
    for state in (serving.merge_state, serving.matrix_state,
                  serving.tree_state):
        leaf = jax.tree.leaves(state)[0]
        assert len({s.device for s in leaf.addressable_shards}) == 8


def test_mixed_dedup_resend_is_idempotent(mesh):
    """At-least-once delivery: resending an already-acked text frame
    verbatim sequences ZERO ops (clientSeq dedup in the closed-form
    ticket) and leaves the segment table untouched."""
    serving = make_mixed(mesh, num_docs=16)
    row = 1  # text row
    ops = [dict(kind=mtk.MT_INSERT, pos=0, text="abc")]
    serving.submit_text(row, ops, first_cseq=1, ref_seq=2, client_slot=0)
    serving.tick()
    before = jax.tree.map(np.asarray, row_planes(serving.merge_state, row))
    text_before = serving.text_of(row)

    # The resend: same cseq, same ops. Pool grows (the host cannot know
    # it is a dup before the ticket) but NO op sequences and no segment
    # changes.
    serving.submit_text(row, ops, first_cseq=1, ref_seq=2, client_slot=0)
    harvest = serving.tick()
    merged = {}
    for rows in harvest.values():
        merged.update(rows)
    assert merged[row] == (0, 0, 0)
    after = row_planes(serving.merge_state, row)
    for field in mtb.BlockMergeState._fields:
        assert np.array_equal(np.asarray(getattr(after, field)),
                              np.asarray(getattr(before, field))), field
    assert serving.text_of(row) == text_before


def test_mixed_kill_resume_rebalance_with_text(mesh):
    """Serving-host failover over a MIXED population (text + map +
    matrix + tree rows): checkpoint host 1, keep serving, kill it,
    rebalance its range to host 0, restore from checkpoint +
    durable-log replay — the text rows' segment tables, pools and
    materialized strings all survive, and seq assignment resumes with no
    regression."""
    num_docs = 16
    serving = make_mixed(mesh, num_docs=num_docs)
    # Tick 0-1 traffic, checkpoint after tick 1, then tick 2 (the tail).
    cseq = {row: {0: 0, 1: 0} for row in range(num_docs)}
    ref = {row: 2 for row in range(num_docs)}

    def play(serving, cseq, ref, t):
        for row in range(num_docs):
            fam = family_of(row)
            if fam == "map":
                words = ((np.uint32(row % 8) << 2)
                         | (np.arange(4, dtype=np.uint32) + 7 * t) << 12)
                serving.submit(row, words, cseq[row][0] + 1, ref[row], 0)
                cseq[row][0] += 4
            elif fam == "text":
                client, ops = TEXT_TICKS[t]
                serving.submit_text(row, ops, cseq[row][client] + 1,
                                    ref[row], client)
                cseq[row][client] += len(ops)
            elif fam == "matrix":
                client, ops = MATRIX_TICKS[t % len(MATRIX_TICKS)]
                if t < len(MATRIX_TICKS):
                    serving.submit_matrix(row, ops, cseq[row][client] + 1,
                                          ref[row], client)
                    cseq[row][client] += len(ops)
            else:
                client, ops = TREE_TICKS[t % len(TREE_TICKS)]
                if t < len(TREE_TICKS):
                    serving.submit_tree(row, ops, cseq[row][client] + 1,
                                        ref[row], client)
                    cseq[row][client] += len(ops)
        harvest = serving.tick()
        merged = {}
        for rows in harvest.values():
            merged.update(rows)
        for row, (n_ok, _f, last) in merged.items():
            if n_ok:
                ref[row] = last

    for t in range(2):
        play(serving, cseq, ref, t)
    cp = serving.checkpoint_host(1)
    play(serving, cseq, ref, 2)

    final_seq = np.asarray(serving.seq_state.seq).copy()
    final_texts = {row: serving.text_of(row)
                   for row in range(num_docs) if family_of(row) == "text"}
    assert any(final_texts.values())
    durable = serving.durable

    revived = make_mixed(mesh, num_docs=num_docs)
    revived.rebalance_from(1, 0)
    # Host 0's own rows (0-7) recover by re-running their full log.
    cseq2 = {row: {0: 0, 1: 0} for row in range(num_docs)}
    ref2 = {row: 2 for row in range(num_docs)}
    for t in range(3):
        for row in range(8):
            fam = family_of(row)
            if fam == "map":
                words = ((np.uint32(row % 8) << 2)
                         | (np.arange(4, dtype=np.uint32) + 7 * t) << 12)
                revived.submit(row, words, cseq2[row][0] + 1, ref2[row], 0)
                cseq2[row][0] += 4
            elif fam == "text":
                client, ops = TEXT_TICKS[t]
                revived.submit_text(row, ops, cseq2[row][client] + 1,
                                    ref2[row], client)
                cseq2[row][client] += len(ops)
            elif fam == "matrix" and t < len(MATRIX_TICKS):
                client, ops = MATRIX_TICKS[t]
                revived.submit_matrix(row, ops, cseq2[row][client] + 1,
                                      ref2[row], client)
                cseq2[row][client] += len(ops)
            elif fam == "tree" and t < len(TREE_TICKS):
                client, ops = TREE_TICKS[t]
                revived.submit_tree(row, ops, cseq2[row][client] + 1,
                                    ref2[row], client)
                cseq2[row][client] += len(ops)
        harvest = revived.tick()
        merged = {}
        for rows in harvest.values():
            merged.update(rows)
        for row, (n_ok, _f, last) in merged.items():
            if n_ok:
                ref2[row] = last
    # Host 1's rows: checkpoint + durable tail through the real tick.
    revived.restore_host(cp, durable, serving._durable_base)

    assert np.array_equal(np.asarray(revived.seq_state.seq), final_seq)
    for row, text in final_texts.items():
        assert revived.text_of(row) == text, row
    for field in mtb.BlockMergeState._fields:
        assert np.array_equal(
            np.asarray(getattr(revived.merge_state, field)),
            np.asarray(getattr(serving.merge_state, field))), field
    for g, r in zip(jax.tree.leaves(revived.matrix_state),
                    jax.tree.leaves(serving.matrix_state)):
        assert np.array_equal(np.asarray(g), np.asarray(r))
    for field in tk.TreeState._fields:
        assert np.array_equal(
            np.asarray(getattr(revived.tree_state, field)),
            np.asarray(getattr(serving.tree_state, field))), field

    # Continued service on a restored text row: seq extends, text grows.
    row = 9  # host-1 text row, now owned by host 0
    assert revived.route(row).host_id == 0
    revived.submit_text(row, [dict(kind=mtk.MT_INSERT, pos=0, text="Z")],
                        first_cseq=cseq[row][0] + 1,
                        ref_seq=int(final_seq[row]), client_slot=0)
    harvest = revived.tick()
    merged = {}
    for rows in harvest.values():
        merged.update(rows)
    n_ok, first, _last = merged[row]
    assert n_ok == 1 and first == final_seq[row] + 1
    assert revived.text_of(row) == "Z" + final_texts[row]


def test_matrix_handles_survive_failover(mesh):
    """The vector-handle allocator is host state: after restore the next
    submit_matrix insert must NOT reuse a handle live in the restored
    device planes (review finding r5)."""
    serving = make_mixed(mesh, num_docs=16)
    row = 2  # matrix row
    cseq = 0
    ref = 2
    for t in range(2):
        client, ops = MATRIX_TICKS[t]
        # single client lane: renumber cseq over lane 0
        serving.submit_matrix(row, ops, cseq + 1, ref, 0)
        cseq += len(ops)
        harvest = serving.tick()
        merged = {}
        for rows in harvest.values():
            merged.update(rows)
        ref = merged[row][2]
    assert serving._mx_handles[row] == 4
    cp = serving.checkpoint_host(0)

    revived = make_mixed(mesh, num_docs=16)
    revived.restore_host(cp, serving.durable, serving._durable_base)
    assert revived._mx_handles[row] == 4  # rebuilt from device planes
    # A fresh row insert draws handle 4, not 0.
    revived.submit_matrix(
        row, [dict(target=mxk.MX_ROWS, kind=mtk.MT_INSERT, pos=0,
                   count=1),
              dict(target=mxk.MX_CELL, row=0, col=0, value=77)],
        cseq + 1, ref, 0)
    harvest = revived.tick()
    got = jax.tree.map(np.asarray, row_planes(revived.matrix_state, row))
    new_mask = np.asarray(got.rows.pool_start[0]) == 4
    assert new_mask.any()  # the new vector run carries handle 4
    # New row (handle 4) sits at visible index 0 with the cell write;
    # the surviving old row (handle 0) keeps its cells below it.
    grid = mxk.materialize_grid(got, 0, {i: i for i in range(128)})
    assert grid == [[77, None], [11, 33]], grid


def test_text_capacity_guard_and_compact(mesh):
    """Admission rejects a text batch whose worst-case slot growth would
    silently overflow the device table; compact_text() (the device
    zamboni at the sequencer's MSN floor) restores headroom."""
    serving = ShardedServing(mesh, num_docs=8, k=4, num_hosts=1,
                             num_clients=2, text_slots=16)
    serving.join_all(slots=(0, 1))
    row, cseq, ref = 0, 0, 2
    # Each tick: insert + remove (the remove tombstones, collab window
    # advances with acks, so compaction can reclaim).
    for t in range(3):
        ops = [dict(kind=mtk.MT_INSERT, pos=0, text="ab"),
               dict(kind=mtk.MT_REMOVE, pos=0, end=2)]
        serving.submit_text(row, ops, cseq + 1, ref, 0)
        cseq += 2
        harvest = serving.tick()
        ref = harvest[0][row][2]
    with pytest.raises(ValueError, match="compact_text"):
        serving.submit_text(
            row, [dict(kind=mtk.MT_INSERT, pos=0, text="x")] * 3,
            cseq + 1, ref, 0)
    serving.compact_text()
    assert serving._text_high[row] < 6
    serving.submit_text(
        row, [dict(kind=mtk.MT_INSERT, pos=0, text="x")] * 3,
        cseq + 1, ref, 0)
    harvest = serving.tick()
    assert harvest[0][row][0] == 3
    assert serving.text_of(row) == "xxx"


def test_retune_text_geometry_live_serving(mesh):
    """Round-11 geometry autotuning on the sharded serving path: a
    head-concentrated stream arms the fused incremental rebalance
    (observable device-true through the kstats plane →
    ``rebalance_stats``), the between-ticks retune re-blocks through the
    packed-flat seam without changing any served byte, and serving
    continues identically on the new geometry."""
    serving = ShardedServing(mesh, num_docs=8, k=32, num_hosts=1,
                             num_clients=2, text_slots=256)
    serving.join_all(slots=(0, 1))
    row, cseq, ref = 0, 0, 2
    for _t in range(2):
        ops = [dict(kind=mtk.MT_INSERT, pos=0, text="x")] * 32
        serving.submit_text(row, ops, cseq + 1, ref, 0)
        cseq += 32
        harvest = serving.tick()
        assert harvest[0][row][0] == 32
        ref = harvest[0][row][2]
    # The rebalance fire count rides the EXISTING kstats readback — the
    # observed-locality signal the retune keys on is device-true.
    assert serving.rebalance_stats["fired"] >= 1
    assert serving.observed_head_fraction() > 0.0
    before = serving.text_of(row)
    geom0 = serving.text_geometry
    geom1 = serving.retune_text_geometry(1.0)
    assert geom1 != geom0
    assert tuple(serving.merge_state.length.shape[1:]) == geom1
    # Pure re-layout: no served byte moved.
    assert serving.text_of(row) == before
    # Deterministic + idempotent in (state, head_fraction).
    assert serving.retune_text_geometry(1.0) == geom1
    ops = [dict(kind=mtk.MT_INSERT, pos=0, text="y")] * 32
    serving.submit_text(row, ops, cseq + 1, ref, 0)
    harvest = serving.tick()
    assert harvest[0][row][0] == 32
    assert serving.text_of(row) == "y" * 32 + before
