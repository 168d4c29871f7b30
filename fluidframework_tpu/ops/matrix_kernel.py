"""Batched SharedMatrix apply kernel — composed from the merge-tree kernel.

Reference parity: packages/dds/matrix/src/matrix.ts:547 (``processCore``)
and permutationvector.ts:38 — a matrix is two permutation vectors (rows,
cols), each a merge-tree whose segments carry runs of storage handles, plus
an LWW cell table keyed (rowHandle, colHandle). TPU composition:

  * rows / cols = two :class:`~fluidframework_tpu.ops.mergetree_kernel.
    MergeState` tables. A segment's ``pool_start`` field holds the FIRST
    handle of its run (runs are contiguous because sequenced inserts
    allocate handles in document order — the deterministic allocation rule
    of dds/matrix.py); splits inherit ``pool_start + offset`` for free.
  * (row, col) → handle resolution = the merge kernel's masked-prefix-sum
    position lookup, evaluated in each cell write's own (refSeq, client)
    visibility frame — matrix.ts's adjustPosition.
  * cells = per-document planes ``value[B, HR, HC]`` and ``seq[B, HR, HC]``
    indexed by (row handle, col handle). A write is a scatter; a removed
    row or col needs no cell work, because its handle simply stops being
    visible (as upstream, whose cells outlive their handles until the
    handle table recycles them). HR and HC are the handle capacities; the
    host grows them before a tick whose inserts would pass them.

One tick, per document: a ``lax.scan`` applies the tick's vector ops in
order and records the axis tables after each (a [T+1, S] history); every
cell write then resolves its handles against the table as of its own
sequence position (the history row after the vector ops sequenced before
it) in its own frame — one vectorized [N, S] lookup per axis, with no
per-cell scan. Sequenced order makes the LWW fold a plain overwrite
(matrix.ts isLatestPendingWrite collapses on the server-side converged
stream): of several writes to one cell in a tick the latest wins, then one
scatter writes the winners. Documents batch on the leading axis.

Differential tests feed live SharedMatrix op streams (tests/
test_matrix_kernel.py) and assert the materialized grid matches every
converged replica cell-for-cell.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import mergetree_kernel as mtk

I32 = jnp.int32

MX_ROWS = 0
MX_COLS = 1
MX_CELL = 2

_BIG = np.int32(2**31 - 1)


class MatrixState(NamedTuple):
    """Per-document matrix state: axis tables [B, S], cell planes
    [B, HR, HC] indexed by (row handle, col handle)."""

    rows: mtk.MergeState
    cols: mtk.MergeState
    cell_val: jax.Array    # i32[B, HR, HC] interned value id (0 = unset)
    cell_seq: jax.Array    # i32[B, HR, HC] seq of the winning write


class MatrixOpBatch(NamedTuple):
    """One tick of sequenced matrix ops, padded to K per doc. Axes [B, K]."""

    valid: jax.Array        # bool
    target: jax.Array       # i32 MX_*
    kind: jax.Array         # i32 MT_INSERT/MT_REMOVE (vector ops)
    pos: jax.Array          # i32 vector position / range start
    end: jax.Array          # i32 range end (remove)
    count: jax.Array        # i32 inserted run length
    handle_base: jax.Array  # i32 first handle of an inserted run
    row: jax.Array          # i32 (cell)
    col: jax.Array          # i32 (cell)
    value: jax.Array        # i32 interned value id (cell)
    seq: jax.Array          # i32
    ref_seq: jax.Array      # i32
    client: jax.Array       # i32 client slot


class MatrixStepBatch(NamedTuple):
    """One tick in the step layout: the tick's vector ops [B, T] in order,
    and its cell writes [B, N] in order, each carrying ``c_step``, the
    number of the tick's vector ops sequenced before it (so a cell
    resolves against the tables after exactly those ops) and its own
    (ref, client) frame. A structural op in flight costs one scan step,
    never the cells around it."""

    vec_valid: jax.Array    # bool[B, T]
    kind: jax.Array         # i32[B, T] MT_INSERT/MT_REMOVE
    target: jax.Array       # i32[B, T] MX_ROWS/MX_COLS
    pos: jax.Array          # i32[B, T]
    end: jax.Array          # i32[B, T]
    count: jax.Array        # i32[B, T]
    handle_base: jax.Array  # i32[B, T]
    seq: jax.Array          # i32[B, T]
    ref_seq: jax.Array      # i32[B, T]
    client: jax.Array       # i32[B, T]
    c_valid: jax.Array      # bool[B, N]
    c_step: jax.Array       # i32[B, N] vector ops of the tick before it
    c_row: jax.Array        # i32[B, N]
    c_col: jax.Array        # i32[B, N]
    c_value: jax.Array      # i32[B, N]
    c_seq: jax.Array        # i32[B, N]
    c_ref: jax.Array        # i32[B, N]
    c_client: jax.Array     # i32[B, N]


_VEC_FIELDS = ("kind", "target", "pos", "end", "count", "handle_base",
               "seq", "ref_seq", "client")
_CELL_FIELDS = {"c_row": "row", "c_col": "col", "c_value": "value",
                "c_seq": "seq", "c_ref": "ref_seq", "c_client": "client"}
#: Axis-table planes a cell's handle lookup reads.
_FRAME = ("valid", "length", "ins_seq", "ins_client", "rem_seq",
          "rem_client", "pool_start")


class _VecOp(NamedTuple):
    """Adapter to the merge-tree kernel's per-op field names."""

    valid: jax.Array
    kind: jax.Array
    pos: jax.Array
    end: jax.Array
    seq: jax.Array
    ref_seq: jax.Array
    client: jax.Array
    pool_start: jax.Array
    text_len: jax.Array
    prop_key: jax.Array
    prop_val: jax.Array


def init_state(num_docs: int, vec_slots: int = 64, row_handles: int = 64,
               col_handles: int | None = None,
               overlap_words: int = 1) -> MatrixState:
    b = num_docs
    hr = row_handles
    hc = row_handles if col_handles is None else col_handles
    if hr * hc >= _BIG:
        raise ValueError(f"cell plane {hr} x {hc} overflows i32 keys")
    return MatrixState(
        rows=mtk.init_state(b, vec_slots, num_props=1,
                            overlap_words=overlap_words),
        cols=mtk.init_state(b, vec_slots, num_props=1,
                            overlap_words=overlap_words),
        cell_val=jnp.zeros((b, hr, hc), I32),
        cell_seq=jnp.zeros((b, hr, hc), I32),
    )


# -- device tick --------------------------------------------------------------


def _walk(rows: mtk.MergeState, cols: mtk.MergeState, op: _VecOp):
    """ONE merge walk on the select-merged axis, gated back per target:
    an op touches exactly one of rows/cols, so the other keeps its
    planes (under vmap both branches of a switch would run anyway)."""
    is_rows = op.target == MX_ROWS
    is_cols = op.target == MX_COLS
    sel = jax.tree.map(lambda r, c: jnp.where(is_rows, r, c), rows, cols)
    walked = mtk._apply_op(sel, op.op)
    rows = jax.tree.map(
        lambda new, old: jnp.where(op.op.valid & is_rows, new, old),
        walked, rows)
    cols = jax.tree.map(
        lambda new, old: jnp.where(op.op.valid & is_cols, new, old),
        walked, cols)
    return rows, cols


class _TargetedOp(NamedTuple):
    target: jax.Array
    op: _VecOp


def _frame(s: mtk.MergeState) -> tuple:
    return tuple(getattr(s, f) for f in _FRAME) + (s.rem_overlap,)


def _resolve(hist: tuple, step, pos, ref, client):
    """Handle at visible position ``pos[N]`` of each cell, in its own
    (ref, client) frame, on the history table ``step[N]`` (planes
    [T+1, S], overlap [T+1, S, W]). -1 = no handle (a concurrently
    removed row or col: matrix.ts:547's None-handle drop)."""
    (valid, length, ins_seq, ins_client, rem_seq, rem_client,
     pool_start, overlap) = hist
    num_words = overlap.shape[-1]
    c = jnp.clip(client, 0, mtk.OVERLAP_WORD_BITS * num_words - 1)
    # The writer's overlap word only, gathered per cell: [N, S].
    word = jnp.take_along_axis(
        overlap[step], (c >> 5)[:, None, None], axis=2)[..., 0]
    bit = (word >> (c & 31)[:, None]) & 1
    ref_, cl = ref[:, None], client[:, None]
    ins_vis = valid[step] & ((ins_seq[step] <= ref_)
                             | (ins_client[step] == cl))
    rs = rem_seq[step]
    removed = (rs != mtk.NONE_SEQ) & ((rs <= ref_)
                                      | (rem_client[step] == cl)
                                      | (bit == 1))
    vis = jnp.where(ins_vis & ~removed, length[step], 0)
    cum = jnp.cumsum(vis, axis=1) - vis
    p = pos[:, None]
    inside = (cum <= p) & (p < cum + vis)
    handle = jnp.sum(jnp.where(inside, pool_start[step] + p - cum, 0),
                     axis=1)
    return jnp.where(jnp.any(inside, axis=1), handle, -1)


def _doc_tick(rows: mtk.MergeState, cols: mtk.MergeState, steps):
    """One document: the vector scan with its table history, then every
    cell's (row handle, col handle)."""
    zeros = jnp.zeros_like(steps.kind)
    ops = _TargetedOp(steps.target, _VecOp(
        valid=steps.vec_valid, kind=steps.kind, pos=steps.pos,
        end=steps.end, seq=steps.seq, ref_seq=steps.ref_seq,
        client=steps.client, pool_start=steps.handle_base,
        text_len=steps.count, prop_key=zeros, prop_val=zeros))

    def one(carry, op):
        r, c = _walk(*carry, op)
        return (r, c), (_frame(r), _frame(c))

    (rows_out, cols_out), (hist_r, hist_c) = jax.lax.scan(
        one, (rows, cols), ops)
    hist_r = jax.tree.map(lambda h0, h: jnp.concatenate([h0[None], h]),
                          _frame(rows), hist_r)
    hist_c = jax.tree.map(lambda h0, h: jnp.concatenate([h0[None], h]),
                          _frame(cols), hist_c)
    rh = _resolve(hist_r, steps.c_step, steps.c_row, steps.c_ref,
                  steps.c_client)
    ch = _resolve(hist_c, steps.c_step, steps.c_col, steps.c_ref,
                  steps.c_client)
    return rows_out, cols_out, rh, ch


def _write_cells(val, seq, rh, ch, steps):
    """Scatter each document's winning writes into its planes: of several
    writes to one cell in the tick the LAST (sequenced order) wins — a
    stable sort by cell key keeps order within a key."""
    b, hr, hc = val.shape
    write = (steps.c_valid & (rh >= 0) & (ch >= 0) & (rh < hr)
             & (ch < hc))
    key = jnp.where(write, rh * hc + ch, _BIG)
    sk, sv, ss = jax.lax.sort((key, steps.c_value, steps.c_seq),
                              dimension=1, num_keys=1, is_stable=True)
    nxt = jnp.concatenate([sk[:, 1:], jnp.full((b, 1), _BIG, I32)], axis=1)
    win = (sk != _BIG) & (sk != nxt)
    r = jnp.where(win, sk // hc, hr)   # a loser's row lands out of bounds
    c = jnp.where(win, sk % hc, 0)
    d = jnp.broadcast_to(jnp.arange(b, dtype=I32)[:, None], sk.shape)
    return (val.at[d, r, c].set(sv, mode="drop"),
            seq.at[d, r, c].set(ss, mode="drop"))


def _tick(state: MatrixState, steps: MatrixStepBatch) -> MatrixState:
    rows, cols, rh, ch = jax.vmap(_doc_tick)(state.rows, state.cols, steps)
    val, seq = _write_cells(state.cell_val, state.cell_seq, rh, ch, steps)
    return MatrixState(rows=rows, cols=cols, cell_val=val, cell_seq=seq)


def steps_from_ops(ops: MatrixOpBatch) -> MatrixStepBatch:
    """The flat per-op layout as steps (traceable): every op position is
    a scan step whose vector op is masked to vector targets, and the cell
    at position k reads the history after ops 0..k-1."""
    is_cell = ops.target == MX_CELL
    k = ops.valid.shape[1]
    return MatrixStepBatch(
        vec_valid=ops.valid & ~is_cell,
        **{f: getattr(ops, f) for f in _VEC_FIELDS},
        c_valid=ops.valid & is_cell,
        c_step=jnp.broadcast_to(jnp.arange(k, dtype=I32), ops.valid.shape),
        **{f: getattr(ops, src) for f, src in _CELL_FIELDS.items()})


@jax.jit
def apply_tick(state: MatrixState, ops: MatrixOpBatch) -> MatrixState:
    """Apply one tick of sequenced matrix ops (flat layout) for every
    document. The input state stays valid (the serving tick,
    :func:`matrix_tick`, donates it instead)."""
    return _tick(state, steps_from_ops(ops))


@jax.jit
def apply_tick_steps(state: MatrixState,
                     steps: MatrixStepBatch) -> MatrixState:
    """Apply one tick in the step layout — same converged state as
    :func:`apply_tick` on the equivalent flat stream (differentially
    pinned by tests/test_matrix_kernel.py)."""
    return _tick(state, steps)


@functools.partial(jax.jit, donate_argnums=(0,))
def matrix_tick(state: MatrixState, steps: MatrixStepBatch, min_seq):
    """The serving host's tick: apply the steps, then zamboni both axis
    tables at each document's MSN ``min_seq[B]`` (tombstones below it
    drop, adjacent acked runs with contiguous handles coalesce — the
    tables stay at collab-window size at a fixed shape). Returns the
    state and each document's slot count per axis, i32[B, 2], for the
    host's capacity check. The state is donated: the planes update in
    place, a tick never copies them."""
    state = _tick(state, steps)
    rows = mtk.compact(state.rows, min_seq, coalesce=True)
    cols = mtk.compact(state.cols, min_seq, coalesce=True)
    return (state._replace(rows=rows, cols=cols),
            jnp.stack([rows.count, cols.count], axis=1))


def capacity_margin(state: MatrixState) -> dict[str, np.ndarray]:
    """Free vector slots per document per axis (a vector op consumes up
    to 2; overflow is silent, so the serving host checks before the
    tick, as it checks handles against the planes' shape)."""
    return {"rows": mtk.capacity_margin(state.rows),
            "cols": mtk.capacity_margin(state.cols)}


# -- host-side encode / materialize -------------------------------------------


class HandleAllocator:
    """Per-document sequential handle allocation for an axis — mirrors the
    deterministic in-sequence-order rule of dds/matrix.py so device handle
    runs match every scalar replica."""

    def __init__(self, num_docs: int) -> None:
        self.next = [0] * num_docs

    def alloc(self, doc: int, count: int) -> int:
        base = self.next[doc]
        self.next[doc] += count
        return base


def make_matrix_op_batch(ops_per_doc: list[list[dict]], num_docs: int,
                         k: int) -> MatrixOpBatch:
    fields = {name: np.zeros((num_docs, k), np.int32)
              for name in ("target", "kind", "pos", "end", "count",
                           "handle_base", "row", "col", "value", "seq",
                           "ref_seq", "client")}
    valid = np.zeros((num_docs, k), np.bool_)
    for d, doc_ops in enumerate(ops_per_doc):
        assert len(doc_ops) <= k, f"tick overflow: {len(doc_ops)} > {k}"
        for i, op in enumerate(doc_ops):
            valid[d, i] = True
            for name in fields:
                fields[name][d, i] = op.get(name, 0)
    return MatrixOpBatch(valid=jnp.asarray(valid),
                         **{n: jnp.asarray(v) for n, v in fields.items()})


def split_steps(doc_ops: list[dict]) -> tuple[list[dict], list[dict]]:
    """One document's sequenced kernel ops → (vector ops, cells), each
    cell dict gaining ``step``: the vector ops sequenced before it."""
    vec, cells = [], []
    for op in doc_ops:
        if op["target"] == MX_CELL:
            cells.append(dict(op, step=len(vec)))
        else:
            vec.append(op)
    return vec, cells


def make_matrix_step_batch(ops_per_doc: list[list[dict]], num_docs: int,
                           t: int | None = None,
                           n: int | None = None) -> MatrixStepBatch:
    """Encode per-doc op lists into the step layout, padded to ``t``
    vector ops and ``n`` cells per document (default: the largest)."""
    split = [split_steps(ops) for ops in ops_per_doc]
    split += [([], [])] * (num_docs - len(split))
    t = t or max([len(v) for v, _ in split] + [1])
    n = n or max([len(c) for _, c in split] + [1])
    vec = {f: np.zeros((num_docs, t), np.int32) for f in _VEC_FIELDS}
    vec_valid = np.zeros((num_docs, t), np.bool_)
    cell = {f: np.zeros((num_docs, n), np.int32)
            for f in ("c_step", *_CELL_FIELDS)}
    c_valid = np.zeros((num_docs, n), np.bool_)
    for d, (vops, cops) in enumerate(split):
        if len(vops) > t or len(cops) > n:
            raise ValueError(f"doc {d}: {len(vops)} vector ops / "
                             f"{len(cops)} cells exceed the tick ({t}, {n})")
        for i, op in enumerate(vops):
            vec_valid[d, i] = True
            for f in _VEC_FIELDS:
                vec[f][d, i] = op.get(f, 0)
        for i, op in enumerate(cops):
            c_valid[d, i] = True
            cell["c_step"][d, i] = op["step"]
            for f, src in _CELL_FIELDS.items():
                cell[f][d, i] = op.get(src, 0)
    return MatrixStepBatch(
        vec_valid=jnp.asarray(vec_valid),
        **{f: jnp.asarray(a) for f, a in vec.items()},
        c_valid=jnp.asarray(c_valid),
        **{f: jnp.asarray(a) for f, a in cell.items()})


def encode_matrix_op(channel_op: dict, base: dict, alloc_rows, alloc_cols,
                     intern) -> list[dict]:
    """ONE wire op → kernel op dicts — the single wire-format decoder
    shared by the replay harness (encode_matrix_log) and the serving host
    (merge_host._ingest_matrix). ``alloc_rows``/``alloc_cols`` are
    count→handle_base callables; ``intern`` maps a cell value to its id
    (0 reserved for None/cleared)."""
    target = channel_op["target"]
    if target in ("rows", "cols"):
        alloc = alloc_rows if target == "rows" else alloc_cols
        tcode = MX_ROWS if target == "rows" else MX_COLS
        if channel_op["type"] == "insert":
            count = channel_op["count"]
            return [dict(base, target=tcode, kind=mtk.MT_INSERT,
                         pos=channel_op["pos"], count=count,
                         handle_base=alloc(count))]
        if channel_op["type"] == "insertGroup":
            # Regenerated split insert: one kernel op per fragment, handles
            # allocated in the fragments' document order (matching the
            # scalar applier).
            return [dict(base, target=tcode, kind=mtk.MT_INSERT,
                         pos=pos, count=count, handle_base=alloc(count))
                    for pos, count in channel_op["ranges"]]
        if channel_op["type"] == "removeGroup":
            return [dict(base, target=tcode, kind=mtk.MT_REMOVE,
                         pos=start, end=end)
                    for start, end in channel_op["ranges"]]
        return [dict(base, target=tcode, kind=mtk.MT_REMOVE,
                     pos=channel_op["start"], end=channel_op["end"])]
    return [dict(base, target=MX_CELL, row=channel_op["row"],
                 col=channel_op["col"], value=intern(channel_op["value"]))]


def encode_matrix_log(messages, doc: int, rows: HandleAllocator,
                      cols: HandleAllocator, client_slots: dict,
                      val_ids: dict) -> list[dict]:
    """Sequenced OPERATION messages of one matrix channel → kernel op dicts.

    ``val_ids`` interns cell values (id 0 reserved for None/cleared); the
    caller keeps the reverse table for materialization.
    """
    from ..protocol.messages import MessageType

    def intern(value):
        return 0 if value is None else val_ids.setdefault(
            repr(value), len(val_ids) + 1)

    out = []
    for m in messages:
        if m.type != MessageType.OPERATION:
            continue
        channel_op = m.contents["contents"]["contents"]
        slot = client_slots.setdefault(m.client_id, len(client_slots))
        base = dict(seq=m.sequence_number,
                    ref_seq=m.reference_sequence_number, client=slot)
        out.extend(encode_matrix_op(
            channel_op, base,
            lambda count: rows.alloc(doc, count),
            lambda count: cols.alloc(doc, count), intern))
    return out


def _axis_handles(s: mtk.MergeState, doc: int) -> list[int]:
    """Live handles of one axis in document order (acked view)."""
    valid = np.asarray(s.valid[doc])
    length = np.asarray(s.length[doc])
    rem = np.asarray(s.rem_seq[doc])
    start = np.asarray(s.pool_start[doc])
    handles: list[int] = []
    for i in range(valid.shape[0]):
        if valid[i] and rem[i] == mtk.NONE_SEQ and length[i] > 0:
            handles.extend(range(int(start[i]), int(start[i] + length[i])))
    return handles


def materialize_grid(state: MatrixState, doc: int,
                     val_rev) -> list[list]:
    """Converged dense grid of one document (None = unset cell)."""
    rows = _axis_handles(state.rows, doc)
    cols = _axis_handles(state.cols, doc)
    if not rows or not cols:
        return [[] for _ in rows]
    val = np.asarray(state.cell_val[doc])[np.ix_(rows, cols)]
    return [[None if v == 0 else val_rev[v] for v in line]
            for line in val.tolist()]
