"""Batched matrix kernel: differential tests against live SharedMatrix
op streams (BASELINE config 4 — matrix.ts:547 processCore,
permutationvector.ts:38 row/col OT, byte-identical converged cells)."""

import random

import pytest

from fluidframework_tpu.dds.matrix import SharedMatrix
from fluidframework_tpu.drivers.local_driver import LocalDocumentService
from fluidframework_tpu.ops import matrix_kernel as mxk
from fluidframework_tpu.ops import mergetree_kernel as mtk
from fluidframework_tpu.runtime.container import Container
from fluidframework_tpu.server.local_server import LocalCollabServer
from tests.test_matrix import get_matrix, grid_of


def make_empty_matrix_doc(server, doc_id):
    """Attach empty so EVERY edit rides the sequenced stream (a detached
    matrix ships its initial rows via snapshot, invisible to a replay)."""
    service = LocalDocumentService(server, doc_id)
    container = Container.create_detached(service)
    container.runtime.create_datastore("default").create_channel(
        "grid", SharedMatrix.channel_type)
    container.attach()
    return container


def random_matrix_edit(rng, matrix: SharedMatrix):
    r = rng.random()
    if r < 0.55 and matrix.row_count and matrix.col_count:
        matrix.set_cell(rng.randrange(matrix.row_count),
                        rng.randrange(matrix.col_count),
                        rng.choice(["a", "b", "c", 1, 2.5]))
    elif r < 0.70:
        matrix.insert_rows(rng.randint(0, matrix.row_count),
                           rng.randint(1, 3))
    elif r < 0.85:
        matrix.insert_cols(rng.randint(0, matrix.col_count),
                           rng.randint(1, 3))
    elif r < 0.93 and matrix.row_count:
        pos = rng.randrange(matrix.row_count)
        matrix.remove_rows(pos, min(rng.randint(1, 2),
                                    matrix.row_count - pos))
    elif matrix.col_count:
        pos = rng.randrange(matrix.col_count)
        matrix.remove_cols(pos, min(rng.randint(1, 2),
                                    matrix.col_count - pos))


def replay_through_kernel(server, doc_ids, vec_slots=256, handles=64):
    n = len(doc_ids)
    rows = mxk.HandleAllocator(n)
    cols = mxk.HandleAllocator(n)
    client_slots: dict = {}
    val_ids: dict = {}
    streams = [mxk.encode_matrix_log(server.get_deltas(doc, 0), d, rows,
                                     cols, client_slots, val_ids)
               for d, doc in enumerate(doc_ids)]
    val_rev: list = [None] + [None] * len(val_ids)
    for rep, vid in val_ids.items():
        val_rev[vid] = eval(rep)  # repr of simple literals round-trips
    state = mxk.init_state(n, vec_slots=vec_slots, row_handles=handles)
    k = 16
    longest = max((len(s) for s in streams), default=0)
    for start in range(0, longest, k):
        chunk = [s[start:start + k] for s in streams]
        state = mxk.apply_tick(
            state, mxk.make_matrix_op_batch(chunk, n, k))
    margins = mxk.capacity_margin(state)
    assert (margins["rows"] >= 0).all() and (margins["cols"] >= 0).all()
    assert max(rows.next + cols.next) <= handles
    return state, val_rev


@pytest.mark.parametrize("seed", range(4))
def test_matrix_kernel_matches_replicas(seed):
    rng = random.Random(seed)
    n_docs = 2
    server = LocalCollabServer()
    docs = []
    for d in range(n_docs):
        c1 = make_empty_matrix_doc(server, f"doc{d}")
        others = [Container.load(LocalDocumentService(server, f"doc{d}"))
                  for _ in range(2)]
        docs.append([c1] + others)
        get_matrix(c1).insert_rows(0, 2)
        get_matrix(c1).insert_cols(0, 2)

    for _round in range(4):
        for containers in docs:
            paused = [c for c in containers if rng.random() < 0.3]
            for c in paused:
                c.inbound.pause()
            for _ in range(rng.randrange(3, 7)):
                random_matrix_edit(rng, get_matrix(
                    containers[rng.randrange(len(containers))]))
            for c in paused:
                c.inbound.resume()

    expected = []
    for containers in docs:
        grids = [grid_of(get_matrix(c)) for c in containers]
        assert all(g == grids[0] for g in grids)
        expected.append(grids[0])

    state, val_rev = replay_through_kernel(
        server, [f"doc{d}" for d in range(n_docs)])
    for d in range(n_docs):
        got = mxk.materialize_grid(state, d, val_rev)
        assert got == expected[d], (seed, d, got, expected[d])


def test_matrix_kernel_concurrent_row_insert_shifts_cells():
    """A cell write whose refSeq predates a concurrent row insert resolves
    in its submitter's frame (the row it addressed, not the shifted one)."""
    server = LocalCollabServer()
    c1 = make_empty_matrix_doc(server, "doc")
    m1 = get_matrix(c1)
    m1.insert_rows(0, 2)
    m1.insert_cols(0, 1)
    c2 = Container.load(LocalDocumentService(server, "doc"))
    m2 = get_matrix(c2)

    c1.inbound.pause()
    c2.inbound.pause()
    m1.insert_rows(0, 1)     # shifts rows down for everyone once sequenced
    m2.set_cell(1, 0, "x")   # addressed pre-shift row index 1
    c1.inbound.resume()
    c2.inbound.resume()

    assert grid_of(m1) == grid_of(m2)
    state, val_rev = replay_through_kernel(server, ["doc"])
    assert mxk.materialize_grid(state, 0, val_rev) == grid_of(m1)


def test_matrix_kernel_write_to_removed_row_drops():
    server = LocalCollabServer()
    c1 = make_empty_matrix_doc(server, "doc")
    m1 = get_matrix(c1)
    m1.insert_rows(0, 2)
    m1.insert_cols(0, 1)
    c2 = Container.load(LocalDocumentService(server, "doc"))
    m2 = get_matrix(c2)

    c1.inbound.pause()
    c2.inbound.pause()
    m1.remove_rows(0, 1)
    m2.set_cell(0, 0, "dead")  # lands on the removed row's handle
    c1.inbound.resume()
    c2.inbound.resume()

    assert grid_of(m1) == grid_of(m2)
    state, val_rev = replay_through_kernel(server, ["doc"])
    assert mxk.materialize_grid(state, 0, val_rev) == grid_of(m1)


def replay_through_step_kernel(server, doc_ids, vec_slots=256, handles=64,
                               k=16):
    """Same replay as replay_through_kernel but through the step layout
    (vector ops scanned, cells resolved against the table history),
    chunked so ticks continue served documents like the host's."""
    n = len(doc_ids)
    rows = mxk.HandleAllocator(n)
    cols = mxk.HandleAllocator(n)
    client_slots: dict = {}
    val_ids: dict = {}
    streams = [mxk.encode_matrix_log(server.get_deltas(doc, 0), d, rows,
                                     cols, client_slots, val_ids)
               for d, doc in enumerate(doc_ids)]
    val_rev: list = [None] + [None] * len(val_ids)
    for rep, vid in val_ids.items():
        val_rev[vid] = eval(rep)
    state = mxk.init_state(n, vec_slots=vec_slots, row_handles=handles)
    longest = max((len(s) for s in streams), default=0)
    for start in range(0, longest, k):
        chunk = [s[start:start + k] for s in streams]
        state = mxk.apply_tick_steps(
            state, mxk.make_matrix_step_batch(chunk, n))
    return state, val_rev


def build_random_doc(seed, writers=3, rounds=5, lo=6, hi=12):
    """One matrix doc edited by ``writers`` live replicas with paused
    inbound queues (stale refs): the server and the converged grid."""
    rng = random.Random(seed)
    server = LocalCollabServer()
    c1 = make_empty_matrix_doc(server, "doc")
    containers = [c1] + [Container.load(LocalDocumentService(server, "doc"))
                         for _ in range(writers - 1)]
    get_matrix(c1).insert_rows(0, 2)
    get_matrix(c1).insert_cols(0, 2)
    for _round in range(rounds):
        paused = [c for c in containers if rng.random() < 0.4]
        for c in paused:
            c.inbound.pause()
        for _ in range(rng.randrange(lo, hi)):
            random_matrix_edit(rng, get_matrix(
                containers[rng.randrange(len(containers))]))
        for c in paused:
            c.inbound.resume()
    grids = [grid_of(get_matrix(c)) for c in containers]
    assert all(g == grids[0] for g in grids)
    return server, grids[0]


def ref_ops(server, doc="doc"):
    """The doc's sequenced matrix ops as the plain reference takes them."""
    from fluidframework_tpu.protocol.messages import MessageType
    return [(m.sequence_number, m.reference_sequence_number, m.client_id,
             m.contents["contents"]["contents"])
            for m in server.get_deltas(doc, 0)
            if m.type == MessageType.OPERATION]


@pytest.mark.parametrize("seed", range(3))
def test_matrix_step_kernel_matches_flat_and_replicas(seed):
    """The step layout must produce the SAME converged state as the
    per-op kernel and the live replicas — including concurrent cells
    with stale refs (paused containers), each resolved in its own
    frame against the table as of its position."""
    server, want = build_random_doc(100 + seed)
    flat_state, val_rev = replay_through_kernel(server, ["doc"])
    step_state, val_rev2 = replay_through_step_kernel(server, ["doc"])
    assert mxk.materialize_grid(step_state, 0, val_rev2) == want
    # Full state equality, not just the materialized view.
    import numpy as np
    import jax
    for a, b in zip(jax.tree.leaves(flat_state),
                    jax.tree.leaves(step_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("seed", range(3))
def test_cell_run_kernel_matches_per_op(seed):
    """An all-cells tick (the settled-grid storm shape: one vector-less
    step, many cells) through the step layout materializes the same grid
    as the per-op flat layout, including within-tick duplicate keys
    (the latest write wins) and writes to removed rows (dropped)."""
    rng = random.Random(seed)
    n_docs, grid = 3, 6
    setup = []
    for _ in range(n_docs):
        setup.append([
            dict(target=mxk.MX_ROWS, kind=mtk.MT_INSERT, pos=0,
                 count=grid, handle_base=0, seq=1, ref_seq=0, client=0),
            dict(target=mxk.MX_COLS, kind=mtk.MT_INSERT, pos=0,
                 count=grid, handle_base=0, seq=2, ref_seq=1, client=0),
            # One removed row: cells aimed at it must drop on both paths.
            dict(target=mxk.MX_ROWS, kind=mtk.MT_REMOVE, pos=1, end=2,
                 seq=3, ref_seq=2, client=0),
        ])
    state_a = mxk.init_state(n_docs, vec_slots=16, row_handles=8)
    state_b = mxk.init_state(n_docs, vec_slots=16, row_handles=8)
    batch = mxk.make_matrix_op_batch(setup, n_docs, 4)
    state_a = mxk.apply_tick(state_a, batch)
    state_b = mxk.apply_tick(state_b, batch)

    seq = 4
    for _tick in range(3):
        per_op = []
        for d in range(n_docs):
            cells = []
            for _ in range(rng.randrange(8, 24)):
                # Some writers still address the removed row (ref 2).
                ref = 2 if rng.random() < 0.2 else seq - 1
                cells.append(dict(target=mxk.MX_CELL,
                                  row=rng.randrange(grid - 1),
                                  col=rng.randrange(grid),
                                  value=rng.randrange(1, 50), seq=seq,
                                  ref_seq=ref, client=1 + d))
                seq += 1
            per_op.append(cells)
        state_a = mxk.apply_tick_steps(
            state_a, mxk.make_matrix_step_batch(per_op, n_docs))
        state_b = mxk.apply_tick(
            state_b, mxk.make_matrix_op_batch(per_op, n_docs, 24))

    val_rev = list(range(64))
    for d in range(n_docs):
        grid_a = mxk.materialize_grid(state_a, d, val_rev)
        grid_b = mxk.materialize_grid(state_b, d, val_rev)
        assert grid_a == grid_b, (seed, d)


@pytest.mark.parametrize("seed", range(6))
def test_ref_matrix_matches_replicas(seed):
    """The benchmark's plain reference (no program imports) replays the
    sequenced log to every live replica's grid, stale refs included."""
    from benchmark import ref_matrix

    server, want = build_random_doc(200 + seed, writers=4, rounds=6,
                                    lo=4, hi=10)
    assert ref_matrix.replay(ref_ops(server)) == want


@pytest.mark.parametrize("seed", range(4))
def test_step_kernel_matches_ref_matrix(seed):
    """The handle-indexed kernel in the step layout against the plain
    reference, in ticks of every width from one op to the whole log."""
    from benchmark import ref_matrix

    server, want = build_random_doc(300 + seed)
    assert ref_matrix.replay(ref_ops(server)) == want
    for k in (1, 5, 64):
        state, val_rev = replay_through_step_kernel(server, ["doc"], k=k)
        assert mxk.materialize_grid(state, 0, val_rev) == want, k


@pytest.mark.parametrize("seed", range(3))
def test_serving_tick_zamboni_matches_ref_matrix(seed):
    """matrix_tick (the host's tick: steps, then the coalescing zamboni
    at each doc's MSN) keeps the table at window size and stays exact."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import ref_matrix

    server, want = build_random_doc(400 + seed, rounds=8)
    rows, cols = mxk.HandleAllocator(1), mxk.HandleAllocator(1)
    val_ids: dict = {}
    stream = mxk.encode_matrix_log(server.get_deltas("doc", 0), 0, rows,
                                   cols, {}, val_ids)
    msn = {m.sequence_number: m.minimum_sequence_number
           for m in server.get_deltas("doc", 0)}
    val_rev = [None] * (len(val_ids) + 1)
    for rep, vid in val_ids.items():
        val_rev[vid] = eval(rep)
    state = mxk.init_state(1, vec_slots=128, row_handles=64)
    applied_msn = -1
    for start in range(0, len(stream), 8):
        chunk = stream[start:start + 8]
        state, counts = mxk.matrix_tick(
            state, mxk.make_matrix_step_batch([chunk], 1, t=8, n=8),
            jnp.asarray([applied_msn], jnp.int32))
        applied_msn = msn[chunk[-1]["seq"]]
        assert int(np.asarray(counts).max()) <= 128 - 16
    assert mxk.materialize_grid(state, 0, val_rev) == want
    assert ref_matrix.replay(ref_ops(server)) == want


def test_control_order_break_changes_the_grid():
    """The benchmark's control (the last causally ordered pair of
    structural ops applied the other way round) reads a different grid
    than the sequenced order on a doc where that pair moves cells."""
    from benchmark import ref_matrix

    ops = [(1, 0, "a", {"target": "rows", "type": "insert", "pos": 0,
                        "count": 4}),
           (2, 1, "a", {"target": "cols", "type": "insert", "pos": 0,
                        "count": 2}),
           (3, 2, "a", {"target": "cell", "row": 2, "col": 0,
                        "value": 7}),
           (4, 3, "b", {"target": "rows", "type": "remove", "start": 0,
                        "end": 1}),
           (5, 4, "a", {"target": "rows", "type": "insert", "pos": 2,
                        "count": 1})]
    good = ref_matrix.replay(ops)
    assert good == [[None, None], [7, None], [None, None], [None, None]]
    assert ref_matrix.replay(ref_matrix.swap_last_structural(ops)) != good
