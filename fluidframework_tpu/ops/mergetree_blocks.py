"""Block-structured device merge table — O(S/Bk + Bk) per-op text apply.

The flat kernel (:mod:`mergetree_kernel`) pays O(S) data movement per
``lax.scan`` step: every split/place shifts ~a dozen full [S] planes and
every position resolve is a length-S prefix sum. The reference never
does that — its whole perf design is the branching-factor-7 block tree
with per-block partial lengths (mergeTree.ts:350 ``MaxNodesInBlock``,
partialLengths.ts:63), and the repo's run-batch experiment diagnosed the
TPU re-expression: "a two-level block-structured table (touch one block
+ block summaries per op, O(S/Bk + Bk))" (mergetree_runs.py:45-48).
This module is that table. ``dds/mergetree.py``'s settled-block index
is the host-side prototype of the same layout.

Layout: ``[B, NB, Bk]`` — NB blocks of Bk slots per document, document
order = block-major. Valid slots form a PACKED PREFIX of each block
(``blk_count``); per-block summary planes ``[B, NB]`` carry

  * ``blk_count``    — occupied slots (live + in-window tombstones),
  * ``blk_live_len`` — summed length of live (never-removed) slots,
  * ``blk_max_seq``  — newest visibility-affecting seq in the block
                       (max of ins_seq and set rem_seq),
  * ``blk_tomb``     — tombstone count (rebalance pressure signal).

Per op, position resolution is two-level: a block whose
``blk_max_seq <= ref_seq`` is COLD — every insert in it is covered by
the frame and every removal counts, so its visible length for ANY
(ref, client) frame is exactly ``blk_live_len`` (the same argument that
makes the scalar engine's settled blocks frame-independent, generalized
to per-op frames: overlap bits and client identity only matter for
mutations above the ref, and those mark their block hot via
``blk_max_seq``). The [NB] summary row + one [Bk] within-block scan
replace the flat kernel's [S] prefix sums, and the split/insert data
movement is a ``dynamic_slice``/``dynamic_update_slice`` of ONE [Bk]
block across all ~12 planes instead of a full-table shift — O(S/Bk+Bk)
per structural phase. Range marks (remove/annotate) stay masked writes
over the ops' range (inherently O(range)); the per-slot frame masks are
cheap elementwise passes whose cost the summaries bound in the Pallas
twin (:mod:`mergetree_blocks_pallas` keeps everything VMEM-resident).

Semantics are the sequential split/split/place/mark composition of
:func:`mergetree_kernel._apply_op_spec` re-expressed blockwise, so the
block kernel, the flat kernel and the scalar ``MergeEngine`` pin
byte-identical converged text (tests/test_mergetree_blocks.py).

Capacity: an op needs room in its target block (up to +2 slots). When a
block is full the op does NOT apply: the per-doc sticky ``overflow``
output records the first failed op index and every later op of that doc
no-ops, leaving the state exactly at the pre-overflow frontier — the
serving host replays the tail through the flat kernel and re-blocks
(server/merge_host.py). The fused per-tick rebalance (:func:`rebalance`
— drop dead tombstones, pack, redistribute uniformly, recompute
summaries from scratch) keeps per-block headroom bounded across ticks,
so overflow is the pathological everything-hits-one-block case, not the
steady state.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import mergetree_kernel as mtk
from .mergetree_runs import _spread_right

I32 = jnp.int32
NONE_SEQ = mtk.NONE_SEQ
MT_INSERT = mtk.MT_INSERT
MT_REMOVE = mtk.MT_REMOVE

#: "no overflow" sentinel for the per-doc first-overflow op index.
OVF_NONE = np.int32(2**31 - 1)

_SLOT_PLANES = ("length", "ins_seq", "ins_client", "rem_seq",
                "rem_client", "pool_start")
_SUMM = ("blk_count", "blk_live_len", "blk_max_seq", "blk_tomb")
_FILL = {"length": 0, "ins_seq": 0, "ins_client": -1,
         "rem_seq": int(NONE_SEQ), "rem_client": -1, "pool_start": 0}


class BlockMergeState(NamedTuple):
    """Two-level segment table. Slot planes [B, NB, Bk] (+trailing P/W
    axes, matching MergeState field order); summaries [B, NB]."""

    length: jax.Array       # i32[B, NB, Bk]
    ins_seq: jax.Array
    ins_client: jax.Array
    rem_seq: jax.Array      # NONE_SEQ = live
    rem_client: jax.Array
    rem_overlap: jax.Array  # i32[B, NB, Bk, W]
    pool_start: jax.Array
    prop_val: jax.Array     # i32[B, NB, Bk, P]
    blk_count: jax.Array    # i32[B, NB] occupied (packed prefix)
    blk_live_len: jax.Array  # i32[B, NB] Σ length of live slots
    blk_max_seq: jax.Array  # i32[B, NB] newest ins/rem seq (0 = none)
    blk_tomb: jax.Array     # i32[B, NB] tombstone count
    count: jax.Array        # i32[B] total occupied slots


def init_state(num_docs: int, num_blocks: int, block_slots: int,
               num_props: int = 4, overlap_words: int = 1
               ) -> BlockMergeState:
    b, nb, bk = num_docs, num_blocks, block_slots
    return BlockMergeState(
        length=jnp.zeros((b, nb, bk), I32),
        ins_seq=jnp.zeros((b, nb, bk), I32),
        ins_client=jnp.full((b, nb, bk), -1, I32),
        rem_seq=jnp.full((b, nb, bk), NONE_SEQ, I32),
        rem_client=jnp.full((b, nb, bk), -1, I32),
        rem_overlap=jnp.zeros((b, nb, bk, max(1, overlap_words)), I32),
        pool_start=jnp.zeros((b, nb, bk), I32),
        prop_val=jnp.zeros((b, nb, bk, num_props), I32),
        blk_count=jnp.zeros((b, nb), I32),
        blk_live_len=jnp.zeros((b, nb), I32),
        blk_max_seq=jnp.zeros((b, nb), I32),
        blk_tomb=jnp.zeros((b, nb), I32),
        count=jnp.zeros((b,), I32),
    )


def client_capacity(state: BlockMergeState) -> int:
    return mtk.OVERLAP_WORD_BITS * state.rem_overlap.shape[-1]


class BlockPrims:
    """Axis primitives of the per-doc step. The Pallas twin swaps in
    pltpu.roll / log-shift scans (mergetree_blocks_pallas.PltPrims);
    integer adds make both cumsum orders bit-identical."""

    @staticmethod
    def roll(x: jax.Array, shift: int, axis: int) -> jax.Array:
        return jnp.roll(x, shift, axis=axis)

    @staticmethod
    def cumsum_excl(x: jax.Array, axis: int) -> jax.Array:
        return jnp.cumsum(x, axis=axis) - x

    @staticmethod
    def take_block(x: jax.Array, b: jax.Array) -> jax.Array:
        """Row ``b`` ([1, 1]) of the block axis (-2) → [..., 1, Bk]."""
        start = (0,) * (x.ndim - 2) + (b[0, 0], 0)
        return lax.dynamic_slice(x, start, x.shape[:-2] + (1, x.shape[-1]))

    @staticmethod
    def put_block(x: jax.Array, blk: jax.Array, b: jax.Array) -> jax.Array:
        start = (0,) * (x.ndim - 2) + (b[0, 0], 0)
        return lax.dynamic_update_slice(x, blk, start)


# -- per-doc frame math --------------------------------------------------------
#
# Per-doc shapes: planes [NB, Bk]; prop [P, NB, Bk]; overlap [W, NB, Bk];
# summaries [NB, 1] (block axis on sublanes — no transposes anywhere);
# op fields / count / ovf [1, 1]. The same function bodies run under
# jax.vmap (XLA path) and inside the Pallas grid program (VMEM twin).


def _iota2(shape, dim):
    return lax.broadcasted_iota(I32, shape, dim)


def _min2(x):
    """Min over both axes, keepdims → [1, 1] (Pallas-safe two-stage)."""
    return jnp.min(jnp.min(x, axis=1, keepdims=True), axis=0,
                   keepdims=True)


def _sum2(x):
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0,
                   keepdims=True)


def _at(mask, x):
    """Value of x at the single True of mask → [1, 1]."""
    return _sum2(jnp.where(mask, x, 0))


def _summ_at(summ_col, b):
    """summ_col [NB, 1] at block b [1, 1] → [1, 1]."""
    nb_i = _iota2(summ_col.shape, 0)
    return jnp.sum(jnp.where(nb_i == b, summ_col, 0), axis=0,
                   keepdims=True)


def _overlap_bit(overlap, client):
    """client's remover bit per slot. overlap [W, NB, Bk], client [1,1]
    → [NB, Bk]. Arithmetic >> is fine: ``& 1`` keeps one bit."""
    w = overlap.shape[0]
    c = jnp.clip(client, 0, mtk.OVERLAP_WORD_BITS * w - 1)
    word_ids = lax.broadcasted_iota(I32, overlap.shape, 0)
    sel = jnp.sum(jnp.where(word_ids == (c >> 5)[None], overlap, 0),
                  axis=0)
    return (sel >> (c & 31)) & 1


def _overlap_mask(shape, client):
    """[W, NB, Bk] planes with client's bit set in its word."""
    w = shape[0]
    c = jnp.clip(client, 0, mtk.OVERLAP_WORD_BITS * w - 1)
    word_ids = lax.broadcasted_iota(I32, shape, 0)
    bit = jnp.left_shift(I32(1), (c & 31))          # [1, 1]
    return jnp.where(word_ids == (c >> 5)[None], bit[None], 0)


def _frame(p, overlap, summ, ref, client, prims):
    """(occupied, vis, gcum) for one (ref, client) frame.

    The two-level prefix: per-block visible length is ``blk_live_len``
    verbatim for COLD blocks (blk_max_seq <= ref — every insert covered,
    every removal counts, client identity and overlap bits irrelevant
    because those only modulate mutations ABOVE the ref) and a [Bk]
    reduction for hot ones; global slot positions compose a [NB] block
    prefix with a per-block [Bk] prefix instead of one [S] scan."""
    occ = _iota2(p["length"].shape, 1) < summ["blk_count"]  # [NB,1]→bcast
    ins_vis = occ & ((p["ins_seq"] <= ref) | (p["ins_client"] == client))
    ob = _overlap_bit(overlap, client)
    removed_vis = ((p["rem_seq"] != NONE_SEQ)
                   & ((p["rem_seq"] <= ref) | (p["rem_client"] == client)
                      | (ob == 1)))
    vis = jnp.where(ins_vis & ~removed_vis, p["length"], 0)
    hot = summ["blk_max_seq"] > ref                          # [NB, 1]
    bvl = jnp.where(hot, jnp.sum(vis, axis=1, keepdims=True),
                    summ["blk_live_len"])
    blk_cum = prims.cumsum_excl(bvl, 0)                      # [NB, 1]
    wcum = prims.cumsum_excl(vis, 1)                         # [NB, Bk]
    return occ, vis, blk_cum + wcum


def _first_slot(mask):
    """(flat index [1,1], block [1,1], slot [1,1], has [1,1]) of the
    first True in document order (block-major)."""
    nb, bk = mask.shape
    flat = _iota2(mask.shape, 0) * bk + _iota2(mask.shape, 1)
    f = _min2(jnp.where(mask, flat, nb * bk))
    has = f < nb * bk
    b = f // bk
    return f, b, f - b * bk, has


def _block_update(arrs, b, edit, prims):
    """Slice block ``b`` of every array in ``arrs`` ([NB, Bk] or
    [F, NB, Bk]), run ``edit`` on the [*, 1, Bk] slices, write back.
    The O(Bk) structural data movement of the table."""
    blocks = jax.tree.map(lambda x: prims.take_block(x, b), arrs)
    blocks = edit(blocks)
    return jax.tree.map(lambda x, blk: prims.put_block(x, blk, b),
                        arrs, blocks)


def _summ_add(col, b, delta):
    """col [NB, 1] += delta [1, 1] at block b [1, 1]."""
    nb_i = _iota2(col.shape, 0)
    return jnp.where(nb_i == b, col + delta, col)


def _split_at(p, prop, overlap, summ, count, pos, ref, client, act,
              prims):
    """Interior split at visible position ``pos`` (the _split_at of the
    flat spec, blockwise). Returns updated arrays + overflow [1,1]."""
    bk = p["length"].shape[1]
    occ, vis, gcum = _frame(p, overlap, summ, ref, client, prims)
    inside = (gcum < pos) & (pos < gcum + vis)
    _f, b, i, has = _first_slot(inside)
    off = pos - _at(inside, gcum)
    want = act & has
    room = _summ_at(summ["blk_count"], b) < bk
    overflow = want & ~room
    do = want & room
    head_removed = _at(inside, (p["rem_seq"] != NONE_SEQ).astype(I32))

    def edit(blocks):
        planes, bprop, bover = blocks
        bk_i = _iota2((1, bk), 1)
        shift = do & (bk_i >= i + 1)
        is_head = do & (bk_i == i)
        is_tail = do & (bk_i == i + 1)

        def sh(x):
            r = prims.roll(x, 1, x.ndim - 1)
            cond = shift if x.ndim == 2 else shift[None]
            return jnp.where(cond, r, x)

        out = {name: sh(arr) for name, arr in planes.items()}
        out["length"] = jnp.where(
            is_head, off, jnp.where(is_tail, out["length"] - off,
                                    out["length"]))
        out["pool_start"] = jnp.where(is_tail, out["pool_start"] + off,
                                      out["pool_start"])
        return out, sh(bprop), sh(bover)

    p, prop, overlap = _block_update((p, prop, overlap), b, edit, prims)
    summ = dict(summ)
    summ["blk_count"] = _summ_add(summ["blk_count"], b, do.astype(I32))
    summ["blk_tomb"] = _summ_add(summ["blk_tomb"], b,
                                 jnp.where(do, head_removed, 0))
    # Live length is split-invariant (head off + tail len-off), as is
    # blk_max_seq (both halves copy the parent's seqs).
    count = count + do.astype(I32)
    return p, prop, overlap, summ, count, overflow


def _place(p, prop, overlap, summ, count, frame, op, act, prims):
    """Insert placement at an existing boundary (breakTie candidate scan
    of the flat spec): first doc-order slot with gcum == pos that is not
    an acked-dead tombstone; else append at the document end (the last
    occupied block's tail, spilling into the next empty block)."""
    nb, bk = p["length"].shape
    occ, _vis, gcum = frame
    dead = (p["rem_seq"] != NONE_SEQ) & (p["rem_seq"] <= op["ref_seq"])
    cand = occ & ~dead & (gcum == op["pos"])
    _f, b_c, i_c, hasc = _first_slot(cand)
    nonempty = summ["blk_count"] > 0                         # [NB, 1]
    nb_i = _iota2(summ["blk_count"].shape, 0)
    last = jnp.max(jnp.where(nonempty, nb_i, 0), axis=0, keepdims=True)
    last_fill = _summ_at(summ["blk_count"], last)
    full = last_fill >= bk
    b_a = jnp.where(full, last + 1, last)
    i_a = jnp.where(full, 0, last_fill)
    no_spill = full & (last + 1 >= nb)
    b = jnp.where(hasc, b_c, b_a)
    i = jnp.where(hasc, i_c, i_a)
    room = (_summ_at(summ["blk_count"], b) < bk) & (b < nb)
    overflow = act & (~room | (~hasc & no_spill))
    do = act & ~overflow

    # The fresh segment lands AT slot i (before the slot that held the
    # boundary): slots >= i+1 read their left neighbour, slot i takes
    # the op's fields — matching the flat kernel's placement index.
    def edit(blocks):
        planes, bprop, bover = blocks
        bk_i = _iota2((1, bk), 1)
        shift = do & (bk_i >= i + 1)
        is_new = do & (bk_i == i)

        def sh(x):
            r = prims.roll(x, 1, x.ndim - 1)
            cond = shift if x.ndim == 2 else shift[None]
            return jnp.where(cond, r, x)

        fresh = {"length": op["text_len"], "ins_seq": op["seq"],
                 "ins_client": op["client"], "rem_seq": I32(NONE_SEQ),
                 "rem_client": I32(-1), "pool_start": op["pool_start"]}
        out = {name: jnp.where(is_new, fresh[name], sh(arr))
               for name, arr in planes.items()}
        return (out, jnp.where(is_new[None], 0, sh(bprop)),
                jnp.where(is_new[None], 0, sh(bover)))

    p, prop, overlap = _block_update((p, prop, overlap), b, edit, prims)
    summ = dict(summ)
    do_i = do.astype(I32)
    summ["blk_count"] = _summ_add(summ["blk_count"], b, do_i)
    summ["blk_live_len"] = _summ_add(summ["blk_live_len"], b,
                                     jnp.where(do, op["text_len"], 0))
    summ["blk_max_seq"] = jnp.where(
        (nb_i == b) & do, jnp.maximum(summ["blk_max_seq"], op["seq"]),
        summ["blk_max_seq"])
    count = count + do_i
    return p, prop, overlap, summ, count, overflow


def _mark(p, overlap, summ, frame, op, act):
    """markRangeRemoved over [pos, end): earliest remove owns rem_seq,
    concurrent removers join the overlap bitmask."""
    _occ, vis, gcum = frame
    in_range = act & (vis > 0) & (gcum >= op["pos"]) & (gcum < op["end"])
    fresh = in_range & (p["rem_seq"] == NONE_SEQ)
    again = in_range & (p["rem_seq"] != NONE_SEQ)
    bits = _overlap_mask(overlap.shape, op["client"])
    p = dict(p)
    p["rem_seq"] = jnp.where(fresh, op["seq"], p["rem_seq"])
    p["rem_client"] = jnp.where(fresh, op["client"], p["rem_client"])
    overlap = jnp.where(again[None], overlap | bits, overlap)
    summ = dict(summ)
    fresh_i = fresh.astype(I32)
    summ["blk_live_len"] = summ["blk_live_len"] - jnp.sum(
        jnp.where(fresh, p["length"], 0), axis=1, keepdims=True)
    summ["blk_tomb"] = summ["blk_tomb"] + jnp.sum(fresh_i, axis=1,
                                                  keepdims=True)
    any_fresh = jnp.sum(fresh_i, axis=1, keepdims=True) > 0
    summ["blk_max_seq"] = jnp.where(
        any_fresh, jnp.maximum(summ["blk_max_seq"], op["seq"]),
        summ["blk_max_seq"])
    # Overlap joins never touch the summaries: an "again" slot is
    # visible in this frame, so its rem_seq > ref and the block is
    # already hot for every frame its overlap bit could matter to.
    return p, overlap, summ


def _annotate(prop, frame, op, act):
    """LWW property write over [pos, end) (seq order ⇒ plain overwrite;
    value 0 deletes). Never changes visibility, so no summary edits."""
    _occ, vis, gcum = frame
    in_range = act & (vis > 0) & (gcum >= op["pos"]) & (gcum < op["end"])
    plane_ids = lax.broadcasted_iota(I32, prop.shape, 0)
    write = in_range[None] & (plane_ids == op["prop_key"][None])
    return jnp.where(write, op["prop_val"][None], prop)


def block_apply_doc(p, prop, overlap, summ, count, ovf, op, op_index,
                    prims=BlockPrims):
    """One sequenced op on one document's block table — the sequential
    split/split/place/mark/annotate composition of the flat spec
    (_apply_op_spec), each structural phase touching ONE block. Ops are
    atomic: an op whose target block is full reverts entirely, records
    ``op_index`` in the sticky ``ovf`` and gates every later op of the
    doc (the host replays the tail through the flat kernel)."""
    opvalid = op["valid"] != 0
    act0 = opvalid & (ovf == OVF_NONE)
    is_ins = op["kind"] == MT_INSERT
    is_rem = op["kind"] == MT_REMOVE
    orig = (p, prop, overlap, summ, count)

    p1, p2 = op["pos"], jnp.where(is_ins, I32(-1), op["end"])
    p, prop, overlap, summ, count, of1 = _split_at(
        p, prop, overlap, summ, count, p1, op["ref_seq"], op["client"],
        act0, prims)
    p, prop, overlap, summ, count, of2 = _split_at(
        p, prop, overlap, summ, count, p2, op["ref_seq"], op["client"],
        act0 & ~of1, prims)
    ofs = of1 | of2
    # One shared frame serves place AND mark/annotate: the gates are
    # kind-disjoint, and _place only mutates insert docs' tables.
    frame = _frame(p, overlap, summ, op["ref_seq"], op["client"], prims)
    p, prop, overlap, summ, count, of3 = _place(
        p, prop, overlap, summ, count, frame, op, act0 & ~ofs & is_ins,
        prims)
    ofs = ofs | of3
    p, overlap, summ = _mark(p, overlap, summ, frame, op,
                             act0 & ~ofs & is_rem)
    prop = _annotate(prop, frame, op,
                     act0 & ~ofs & ~is_ins & ~is_rem)

    failed = act0 & ofs

    def keep(new, old):
        cond = failed
        while cond.ndim < new.ndim:
            cond = cond[None]
        return jnp.where(cond, old, new)

    p = {name: keep(arr, orig[0][name]) for name, arr in p.items()}
    prop = keep(prop, orig[1])
    overlap = keep(overlap, orig[2])
    summ = {name: keep(arr, orig[3][name]) for name, arr in summ.items()}
    count = jnp.where(failed, orig[4], count)
    ovf = jnp.where(failed, op_index, ovf)
    return p, prop, overlap, summ, count, ovf


# -- XLA tick ------------------------------------------------------------------


def _process_doc_blocks(p, prop, overlap, summ, count, ops):
    """Scan one document's tick (ops fields [K]); returns final arrays
    + the first-overflow op index [1, 1]."""
    k = ops["kind"].shape[0]

    def step(carry, xs):
        p, prop, overlap, summ, count, ovf = carry
        op_arr, idx = xs
        op = {name: op_arr[j].reshape(1, 1)
              for j, name in enumerate(_OP_FIELDS)}
        out = block_apply_doc(p, prop, overlap, summ, count, ovf, op,
                              idx.reshape(1, 1))
        return out, ()

    ops_mat = jnp.stack([ops[name].astype(I32) for name in _OP_FIELDS],
                        axis=1)                                # [K, F]
    ovf0 = jnp.full((1, 1), OVF_NONE, I32)
    carry, _ = lax.scan(step, (p, prop, overlap, summ, count, ovf0),
                        (ops_mat, jnp.arange(k, dtype=I32)))
    return carry


_OP_FIELDS = ("valid", "kind", "pos", "end", "seq", "ref_seq", "client",
              "pool_start", "text_len", "prop_key", "prop_val")


def _apply_tick_impl(state: BlockMergeState, ops: mtk.MergeOpBatch):
    """Inlineable tick body (jit-wrapped below; _mixed_tick fuses it)."""
    def per_doc(p, prop, overlap, summ, count, op_fields):
        p, prop, overlap, summ, count, ovf = _process_doc_blocks(
            p, prop, overlap, summ, count, op_fields)
        return p, prop, overlap, summ, count, ovf[0, 0]

    p = {name: getattr(state, name) for name in _SLOT_PLANES}
    # Per-doc layout puts the feature axes (props / overlap words) in
    # front so the [NB, Bk] block geometry stays trailing everywhere.
    prop = jnp.transpose(state.prop_val, (0, 3, 1, 2))
    overlap = jnp.transpose(state.rem_overlap, (0, 3, 1, 2))
    summ = {name: getattr(state, name)[:, :, None] for name in _SUMM}
    count = state.count[:, None, None]
    op_fields = {name: getattr(ops, name).astype(I32)
                 for name in _OP_FIELDS}
    p, prop, overlap, summ, count, ovf = jax.vmap(per_doc)(
        p, prop, overlap, summ, count, op_fields)
    new = state._replace(
        **{name: p[name] for name in _SLOT_PLANES},
        prop_val=jnp.transpose(prop, (0, 2, 3, 1)),
        rem_overlap=jnp.transpose(overlap, (0, 2, 3, 1)),
        **{name: summ[name][:, :, 0] for name in _SUMM},
        count=count[:, 0, 0])
    return new, ovf


@jax.jit
def apply_tick_blocks(state: BlockMergeState, ops: mtk.MergeOpBatch
                      ) -> tuple[BlockMergeState, jax.Array]:
    """Apply one tick of sequenced ops per document. Returns the new
    state and the per-doc first-overflow op index ([B] i32; OVF_NONE
    when the whole tick applied)."""
    return _apply_tick_impl(state, ops)


# -- flat-layout bridge --------------------------------------------------------


def flat_view(state: BlockMergeState) -> mtk.MergeState:
    """The gapped flat [B, S] view (S = NB*Bk, document order preserved;
    block tails appear as invalid slots). Every flat consumer —
    materialize, the scalar seed, the host repack, compact — works on
    this view unchanged; ``count`` is total occupied, NOT a high-water
    mark, so don't feed it to the flat kernel's apply path."""
    b, nb, bk = state.length.shape
    occ = (lax.broadcasted_iota(I32, (b, nb, bk), 2)
           < state.blk_count[:, :, None])

    def rs(x):
        return jnp.reshape(x, (b, nb * bk) + x.shape[3:])

    valid = rs(occ)
    mask2 = lambda x, fill: jnp.where(valid, rs(x), fill)
    mask3 = lambda x, fill: jnp.where(valid[..., None], rs(x), fill)
    return mtk.MergeState(
        valid=valid,
        length=mask2(state.length, 0),
        ins_seq=mask2(state.ins_seq, 0),
        ins_client=mask2(state.ins_client, -1),
        rem_seq=mask2(state.rem_seq, NONE_SEQ),
        rem_client=mask2(state.rem_client, -1),
        rem_overlap=mask3(state.rem_overlap, 0),
        pool_start=mask2(state.pool_start, 0),
        prop_val=mask3(state.prop_val, 0),
        count=state.count,
    )


def recompute_summaries(state: BlockMergeState) -> BlockMergeState:
    """Exact summaries from the slot planes + blk_count (the from-scratch
    rebuild — rebalance ends here, and the invariant tests pin the
    incremental per-op updates against it)."""
    b, nb, bk = state.length.shape
    occ = (lax.broadcasted_iota(I32, (b, nb, bk), 2)
           < state.blk_count[:, :, None])
    removed = occ & (state.rem_seq != NONE_SEQ)
    live = occ & ~removed
    mut_seq = jnp.where(
        occ, jnp.maximum(state.ins_seq,
                         jnp.where(removed, state.rem_seq, 0)), 0)
    return state._replace(
        blk_live_len=jnp.sum(jnp.where(live, state.length, 0), axis=2),
        blk_max_seq=jnp.max(mut_seq, axis=2),
        blk_tomb=jnp.sum(removed.astype(I32), axis=2),
        count=jnp.sum(state.blk_count, axis=1),
    )


def from_flat(flat: mtk.MergeState, num_blocks: int) -> BlockMergeState:
    """Re-block a PACKED flat state (valid = prefix of count — compact
    output) into NB uniformly-filled blocks: slot i lands in block
    i // fill at offset i % fill with fill = ceil(count/NB), a monotone
    rightward spread (log-shift cascade, no gathers)."""
    b, s = flat.length.shape
    bk = s // num_blocks
    assert num_blocks * bk == s, (num_blocks, s)
    n = flat.count
    fill = jnp.maximum(1, -(-n // num_blocks))          # ceil, per doc
    num_props = flat.prop_val.shape[2]
    num_words = flat.rem_overlap.shape[2]

    def one(doc_planes, n_d, fill_d):
        iota = jnp.arange(s, dtype=I32)
        shift = jnp.where(iota < n_d, (bk - fill_d) * (iota // fill_d),
                          0)
        return _spread_right(doc_planes, shift, max_shift=s)

    planes = [flat.length, flat.ins_seq, flat.ins_client, flat.rem_seq,
              flat.rem_client, flat.pool_start, flat.prop_val,
              flat.rem_overlap]
    moved = jax.vmap(one)(planes, n, fill)
    blk_i = jnp.arange(num_blocks, dtype=I32)
    blk_count = jnp.clip(n[:, None] - blk_i[None] * fill[:, None], 0,
                         fill[:, None]).astype(I32)
    occ = (lax.broadcasted_iota(I32, (b, num_blocks, bk), 2)
           < blk_count[:, :, None])

    def blocked(x, fill_value):
        x = jnp.reshape(x, (b, num_blocks, bk) + x.shape[2:])
        cond = occ if x.ndim == 3 else occ[..., None]
        return jnp.where(cond, x, fill_value)

    state = BlockMergeState(
        length=blocked(moved[0], 0),
        ins_seq=blocked(moved[1], 0),
        ins_client=blocked(moved[2], -1),
        rem_seq=blocked(moved[3], NONE_SEQ),
        rem_client=blocked(moved[4], -1),
        pool_start=blocked(moved[5], 0),
        prop_val=blocked(moved[6], 0),
        rem_overlap=blocked(moved[7], 0),
        blk_count=blk_count,
        blk_live_len=jnp.zeros((b, num_blocks), I32),
        blk_max_seq=jnp.zeros((b, num_blocks), I32),
        blk_tomb=jnp.zeros((b, num_blocks), I32),
        count=n,
    )
    return recompute_summaries(state)


def _rebalance_impl(state: BlockMergeState, min_seq: jax.Array,
                    coalesce: bool = False) -> BlockMergeState:
    nb = state.length.shape[1]
    packed = mtk.compact(flat_view(state), min_seq, coalesce)
    return from_flat(packed, nb)


@functools.partial(jax.jit, static_argnames=("coalesce",))
def rebalance(state: BlockMergeState, min_seq: jax.Array,
              coalesce: bool = False) -> BlockMergeState:
    """The block zamboni: drop tombstones at/below min_seq[B]
    (optionally coalescing adjacent acked runs — the flat compact's
    pack, mergeTree.ts:1412), then redistribute the survivors uniformly
    so every block regains Bk - ceil(count/NB) headroom, and rebuild
    the summaries from scratch. Pure device work."""
    return _rebalance_impl(state, min_seq, coalesce)


# -- incremental rebalance (round 11) ------------------------------------------
#
# The from-scratch ``_rebalance_impl`` (compact → from_flat → summary
# rebuild) is exact but pays two full log2(S) shift cascades over every
# plane — and on head-concentrated streams the danger trigger fires
# nearly every tick (BENCH_r06: the serving path LOSES to the flat
# kernel at S=8192, 0.65×). The incremental form below restores the
# per-block-headroom invariant (ADVICE item 4) by spilling ONLY overfull
# blocks into their neighbors with LOCAL log-shift spreads (per-block
# circular rolls — log2(Bk) stages instead of log2(S), one direction in
# the common case), defers the tombstone zamboni off the hot tick behind
# a ``blk_tomb`` pressure threshold, and updates summaries only for the
# blocks the spill touched; cold blocks keep their planes BIT-identical
# (the ADVICE item 3 exactness proof never re-derives). The decision and
# the spill are functions of the state alone (plus the static tick
# width), so a durable-log replay re-decides and re-lays-out
# byte-identically.
#
# One conveyor step moves each overfull block's excess one block over —
# SIMULTANEOUSLY across all blocks, so a chain of at-cap blocks shifts
# like a belt in a single step. The occupied slots' document order is
# preserved exactly (right-step: a block's TAIL ranks prepend to its
# right neighbor; left-step: a block's HEAD ranks append to its left
# neighbor), so the flat_view sequence of occupied slots — the semantic
# state — is untouched: the spill is a pure re-layout.

#: blk_tomb pressure denominator: the deferred zamboni (full rebalance,
#: which drops acked tombstones) fires once tombstones occupy >= 1/4 of
#: a document's total block capacity. Below that, the fused tick only
#: re-layouts (tombstone drops stay off the hot tick).
TOMB_PRESSURE_DEN = 4


def _bcast(cond: jax.Array, x: jax.Array) -> jax.Array:
    while cond.ndim < x.ndim:
        cond = cond[None]
    return cond


def _blk_circ_shift(x: jax.Array, amount: jax.Array,
                    left: bool) -> jax.Array:
    """Circular per-block shift of the trailing [NB, Bk] axes by a
    per-block ``amount`` [NB, 1] — log2(Bk) masked rolls. Each stage is
    a pure per-row permutation (roll-or-not per block), so the composed
    result is an exact circular shift: no collision analysis needed,
    unlike the monotone threshold cascades of the full pack/spread."""
    bk = x.shape[-1]
    step = 1
    while step < bk:
        m = (amount & step) != 0
        x = jnp.where(_bcast(m, x),
                      jnp.roll(x, -step if left else step, axis=-1), x)
        step *= 2
    return x


def _spill_counts(c: jax.Array, cap, nb_i: jax.Array
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Counts-only conveyor plan (right step then left step) along the
    block axis (last axis of ``c``). Returns (counts after right,
    right excess e, left excess h) — the movement replays exactly these
    amounts, and maybe_rebalance simulates them for feasibility."""
    nb = c.shape[-1]
    e = jnp.where(nb_i == nb - 1, 0, jnp.maximum(c - cap, 0))
    c1 = c - e + jnp.roll(e, 1, axis=-1)
    h = jnp.where(nb_i == 0, 0, jnp.maximum(c1 - cap, 0))
    return c1, e, h


def _doc_spill_right(p, prop, overlap, summ, cap):
    """One right conveyor step on one document: every block's excess
    over ``cap`` (its tail — the largest ranks) prepends to its right
    neighbor, whose own stayers shift right to make room. Per-doc
    shapes as in the tick body (planes [NB, Bk], summaries [NB, 1])."""
    c = summ["blk_count"]
    nb = c.shape[0]
    nb_i = _iota2(c.shape, 0)
    e = jnp.where(nb_i == nb - 1, 0, jnp.maximum(c - cap, 0))
    keep = c - e
    a = jnp.roll(e, 1, axis=0)           # arrivals (row 0 gets e[-1]=0)
    keep_prev = jnp.roll(keep, 1, axis=0)
    touched = (e > 0) | (a > 0)          # [NB, 1]

    def move(x, fill):
        # Arrivals: left neighbor's occupied tail [keep_prev, keep_prev
        # + a) lands at offsets [0, a); stayers shift right by a.
        prev = _blk_circ_shift(jnp.roll(x, 1, axis=-2), keep_prev,
                               left=True)
        mine = _blk_circ_shift(x, a, left=False)
        off = lax.broadcasted_iota(I32, x.shape[-2:], 1)
        out = jnp.where(_bcast(off < a, x), prev,
                        jnp.where(_bcast((off >= a) & (off < a + keep), x),
                                  mine, fill))
        return jnp.where(_bcast(touched, x), out, x)

    p = {name: move(arr, _FILL[name]) for name, arr in p.items()}
    prop = move(prop, 0)
    overlap = move(overlap, 0)
    summ = dict(summ)
    summ["blk_count"] = keep + a
    return p, prop, overlap, summ, touched


def _doc_spill_left(p, prop, overlap, summ, cap):
    """The mirror step: every block's excess HEAD (its smallest ranks)
    appends to its left neighbor's tail — the tail-hot shape (block 0
    cannot take this path; the feasibility gate falls back to the full
    rebalance when neither direction restores the cap)."""
    c = summ["blk_count"]
    nb = c.shape[0]
    nb_i = _iota2(c.shape, 0)
    h = jnp.where(nb_i == 0, 0, jnp.maximum(c - cap, 0))
    keep = c - h
    a = jnp.roll(h, -1, axis=0)          # arrivals (last row gets h[0]=0)
    touched = (h > 0) | (a > 0)

    def move(x, fill):
        # Stayers shift left by h; arrivals are the right neighbor's
        # head [0, a), landing at offsets [keep, keep + a).
        nxt = _blk_circ_shift(jnp.roll(x, -1, axis=-2), keep, left=False)
        mine = _blk_circ_shift(x, h, left=True)
        off = lax.broadcasted_iota(I32, x.shape[-2:], 1)
        out = jnp.where(_bcast(off < keep, x), mine,
                        jnp.where(_bcast(off < keep + a, x), nxt, fill))
        return jnp.where(_bcast(touched, x), out, x)

    p = {name: move(arr, _FILL[name]) for name, arr in p.items()}
    prop = move(prop, 0)
    overlap = move(overlap, 0)
    summ = dict(summ)
    summ["blk_count"] = keep + a
    return p, prop, overlap, summ, touched


def _doc_refresh_summaries(p, summ, touched):
    """Exact summaries for the spill-touched blocks only; cold blocks
    keep their carried values bit-identically (they are already exact —
    the selection documents and enforces the touched-only contract)."""
    occ = _iota2(p["length"].shape, 1) < summ["blk_count"]
    removed = occ & (p["rem_seq"] != NONE_SEQ)
    live = occ & ~removed
    mut = jnp.where(occ, jnp.maximum(p["ins_seq"],
                                     jnp.where(removed, p["rem_seq"], 0)),
                    0)
    summ = dict(summ)
    summ["blk_live_len"] = jnp.where(
        touched, jnp.sum(jnp.where(live, p["length"], 0), axis=1,
                         keepdims=True), summ["blk_live_len"])
    summ["blk_max_seq"] = jnp.where(
        touched, jnp.max(mut, axis=1, keepdims=True), summ["blk_max_seq"])
    summ["blk_tomb"] = jnp.where(
        touched, jnp.sum(removed.astype(I32), axis=1, keepdims=True),
        summ["blk_tomb"])
    return summ


def _incremental_spill_impl(state: BlockMergeState, tick_k: int
                            ) -> tuple[BlockMergeState, jax.Array]:
    """Batch incremental re-layout: right conveyor step always, left
    step only when the batch still has over-cap blocks (one lax.cond —
    the head-hot common case pays a single one-directional spill).
    Returns (state', blocks_touched i32 scalar). Occupied-slot document
    order is preserved exactly; nothing is dropped."""
    b, nb, bk = state.length.shape
    cap = I32(bk - (2 * tick_k + 2))

    p = {name: getattr(state, name) for name in _SLOT_PLANES}
    prop = jnp.transpose(state.prop_val, (0, 3, 1, 2))
    overlap = jnp.transpose(state.rem_overlap, (0, 3, 1, 2))
    summ = {name: getattr(state, name)[:, :, None] for name in _SUMM}

    def vspill(step, args):
        return jax.vmap(lambda p, pr, ov, sm: step(p, pr, ov, sm, cap)
                        )(*args)

    p, prop, overlap, summ, t_r = vspill(_doc_spill_right,
                                         (p, prop, overlap, summ))

    def left(args):
        return vspill(_doc_spill_left, args)

    def skip(args):
        p, prop, overlap, summ = args
        return p, prop, overlap, summ, jnp.zeros_like(t_r)

    # The left mirror runs only when the right pass alone did not
    # restore the cap somewhere in the BATCH (a real cond, outside the
    # vmap) — the head-hot common case pays one one-directional spill.
    need_left = jnp.any(summ["blk_count"] > cap)
    p, prop, overlap, summ, t_l = lax.cond(need_left, left, skip,
                                           (p, prop, overlap, summ))
    touched = t_r | t_l
    summ = jax.vmap(_doc_refresh_summaries)(p, summ, touched)
    new = state._replace(
        **{name: p[name] for name in _SLOT_PLANES},
        prop_val=jnp.transpose(prop, (0, 2, 3, 1)),
        rem_overlap=jnp.transpose(overlap, (0, 2, 3, 1)),
        **{name: summ[name][:, :, 0] for name in _SUMM})
    return new, jnp.sum(touched.astype(I32))


def _maybe_rebalance_impl(state: BlockMergeState, min_seq: jax.Array,
                          tick_k: int
                          ) -> tuple[BlockMergeState, jax.Array]:
    """Shared body of maybe_rebalance/maybe_rebalance_stats (inlined by
    storm._mixed_tick). Decision, spill and zamboni are all functions of
    the state + the static tick width, so replay re-decides identically:

      * no block above cap = Bk - (2*tick_k + 2)  → no-op,
      * over-cap blocks, conveyor plan feasible, tombstones light
                                                  → incremental spill,
      * conveyor infeasible (table genuinely near capacity, or the hot
        edge blocked) OR blk_tomb pressure ≥ capacity/TOMB_PRESSURE_DEN
                                                  → full rebalance (the
        deferred zamboni: drop acked tombstones, uniform redistribution,
        from-scratch summaries).

    Returns (state', rstats i32[2] = [rebalance_fired, blocks_touched])
    — the device counters the serving kstats plane exports."""
    b, nb, bk = state.length.shape
    headroom = 2 * tick_k + 2
    cap = I32(bk - headroom)
    c = state.blk_count
    nb_i = lax.broadcasted_iota(I32, c.shape, 1)
    danger = jnp.any(jnp.max(c, axis=1) + headroom > bk)
    c1, e, h = _spill_counts(c, cap, nb_i)
    c2 = c1 - h + jnp.roll(h, -1, axis=-1)
    local_ok = jnp.all(c2 <= cap)
    tomb_heavy = jnp.any(state.blk_tomb.sum(axis=1) * TOMB_PRESSURE_DEN
                         >= nb * bk)
    branch = jnp.where(danger,
                       jnp.where(local_ok & ~tomb_heavy, 1, 2), 0)

    def none_fn(s, _ms):
        return s, I32(0)

    def incr_fn(s, _ms):
        return _incremental_spill_impl(s, tick_k)

    def full_fn(s, ms):
        return _rebalance_impl(s, ms), I32(b * nb)

    state, touched = lax.switch(branch, (none_fn, incr_fn, full_fn),
                                state, min_seq)
    rstats = jnp.stack(((branch > 0).astype(I32), touched))
    return state, rstats


@functools.partial(jax.jit, static_argnames=("tick_k",))
def maybe_rebalance_stats(state: BlockMergeState, min_seq: jax.Array,
                          tick_k: int
                          ) -> tuple[BlockMergeState, jax.Array]:
    """maybe_rebalance + the device rstats pair ([fired, blocks_touched]
    i32[2]) that rides the serving tick's kstats readback."""
    return _maybe_rebalance_impl(state, min_seq, tick_k)


@functools.partial(jax.jit, static_argnames=("tick_k",))
def maybe_rebalance(state: BlockMergeState, min_seq: jax.Array,
                    tick_k: int) -> BlockMergeState:
    """The FUSED per-tick form (storm._mixed_tick): act only when some
    document's fullest block could no longer absorb a worst-case next
    tick (2 slots/op, all ``tick_k`` ops in one block) — and then prefer
    the INCREMENTAL neighbor spill over the from-scratch rebuild (see
    :func:`_maybe_rebalance_impl` for the decision ladder). Keeps the
    no-overflow guarantee of choose_block_geometry; the steady state —
    edits spread across blocks — pays one [B, NB] max per tick.
    Deterministic in the state, so durable-log replays re-decide
    identically."""
    return _maybe_rebalance_impl(state, min_seq, tick_k)[0]


#: Debug gate for to_flat's truncation guard: the guard reads
#: max(count) back to the host, which SYNCS the device stream — on the
#: overflow-replay / conversion hot paths that turns an async re-block
#: into a blocking round trip. Callers there guarantee slots >= live
#: count structurally (they size ``slots`` FROM the count), so the
#: guard is a debug assertion, armed by FFTPU_DEBUG_TO_FLAT=1 (tests
#: arm it) or by flipping this module flag.
DEBUG_TO_FLAT = os.environ.get("FFTPU_DEBUG_TO_FLAT", "") not in ("", "0")


def to_flat(state: BlockMergeState, slots: int | None = None
            ) -> mtk.MergeState:
    """PACKED flat state (gaps squeezed out) — the layout the
    sequence-parallel sharded path (ops/mergetree_sharded.py) and the
    host overflow replay consume. ``slots`` pads/truncates the slot axis
    (must hold every occupied slot — debug-checked only, see
    :data:`DEBUG_TO_FLAT`; the check forces a host sync)."""
    packed = mtk.compact(flat_view(state),
                         jnp.full((state.count.shape[0],), -1, I32))
    if slots is not None and slots != packed.valid.shape[1]:
        b, s = packed.valid.shape
        assert slots >= s or not DEBUG_TO_FLAT or bool(
            np.asarray(jnp.max(packed.count)) <= slots), "truncating live slots"
        def fit(x, fill):
            if slots >= x.shape[1]:
                pad = [(0, 0)] * x.ndim
                pad[1] = (0, slots - x.shape[1])
                return jnp.pad(x, pad, constant_values=fill)
            return x[:, :slots]
        packed = mtk.MergeState(
            valid=fit(packed.valid, False),
            length=fit(packed.length, 0),
            ins_seq=fit(packed.ins_seq, 0),
            ins_client=fit(packed.ins_client, -1),
            rem_seq=fit(packed.rem_seq, NONE_SEQ),
            rem_client=fit(packed.rem_client, -1),
            rem_overlap=fit(packed.rem_overlap, 0),
            pool_start=fit(packed.pool_start, 0),
            prop_val=fit(packed.prop_val, 0),
            count=packed.count,
        )
    return packed


# -- host helpers --------------------------------------------------------------


def bk_for_locality(tick_k: int, head_fraction: float = 0.0) -> int:
    """Lane-multiple (128) block width for a serving table: first grown
    until a WORST-CASE tick (2 slots/op, all ``tick_k`` ops in one
    block) fits — the capacity floor, never capped — then grown further
    so the hot block absorbs 1..4 ticks per spill at the observed
    head-concentration fraction (the autotune lever, capped at 4096
    lanes so pathological concentration cannot explode one block). The
    single source of the Bk-scaling rule: choose_block_geometry and
    KernelMergeHost.autotune_block_geometry must agree on it or the
    per-op and serving paths would autotune the same locality to
    different geometries."""
    worst = 2 * tick_k + 8
    bk = 128
    while bk < worst + 8:
        bk *= 2
    absorb = 1 + int(round(3 * min(1.0, max(0.0, head_fraction))))
    while bk < worst + 8 + 2 * tick_k * (absorb - 1) and bk < 4096:
        bk *= 2
    return bk


def choose_block_geometry(min_slots: int, tick_k: int = 0,
                          head_fraction: float = 0.0) -> tuple[int, int]:
    """(NB, Bk) for a serving text table admitting ``min_slots`` total
    slots with up to ``tick_k`` ops per tick. Bk is a lane multiple
    (128) with room for a WORST-CASE tick — every op (2 slots each)
    landing in one block — on top of the uniform fill the per-tick
    rebalance restores, so a capacity-checked serving tick can never hit
    the overflow path.

    ``head_fraction`` is the OBSERVED op locality (the fraction of ticks
    whose rebalance trigger fired — the serving hosts estimate it from
    the ``rebalance_fired`` device kstat). Head-concentrated streams
    refill ONE block every tick, so the trigger fires at every tick at
    the base geometry; scaling Bk up gives the hot block R = 1..4 ticks
    of absorption per spill, amortizing the rebalance R× while the
    per-op apply cost only grows by the O(Bk) structural phase. At
    head_fraction=0.0 the geometry is exactly the historical one."""
    worst = 2 * tick_k + 8
    bk = bk_for_locality(tick_k, head_fraction)
    usable = bk - worst
    nb = max(1, -(-min_slots // usable))
    return nb, bk


def capacity_margin(state: BlockMergeState) -> np.ndarray:
    """Free slots per document (total across blocks; the per-tick
    rebalance redistributes them). The serving host pairs this with
    ``max_block_fill`` to decide when to rebalance before a tick."""
    _b, nb, bk = state.length.shape
    return np.asarray(nb * bk - state.count)


def max_block_fill(state: BlockMergeState) -> np.ndarray:
    """Fullest block per document — the overflow-risk signal."""
    return np.asarray(jnp.max(state.blk_count, axis=1))


def materialize(state: BlockMergeState, pool: mtk.TextPool,
                doc: int) -> str:
    """Converged text of one document (acked view)."""
    return mtk.materialize(flat_view(state), pool, doc)


def host_block_row(arrays: dict, num_blocks: int, block_slots: int
                   ) -> dict:
    """Numpy re-block of one row's FLAT plane dict (MergeState fields,
    gaps allowed) into block layout + exact summaries — the write_row /
    migration path of the block pools. Returns BlockMergeState fields
    minus the batch axis."""
    nb, bk = num_blocks, block_slots
    valid = np.asarray(arrays["valid"]).astype(bool)
    idxs = np.flatnonzero(valid)
    n = len(idxs)
    assert n <= nb * bk, (n, nb, bk)
    fill = max(1, -(-n // nb))
    out = {}
    shapes = {"prop_val": np.asarray(arrays["prop_val"]).shape[1:],
              "rem_overlap": np.asarray(arrays["rem_overlap"]).shape[1:]}
    for name in _SLOT_PLANES + ("prop_val", "rem_overlap"):
        src = np.asarray(arrays[name])
        fill_val = 0 if name in shapes else _FILL[name]
        dst = np.full((nb, bk) + shapes.get(name, ()), fill_val,
                      np.int32)
        for j, slot in enumerate(idxs):
            dst[j // fill, j % fill] = src[slot]
        out[name] = dst
    blk_count = np.clip(n - np.arange(nb) * fill, 0, fill).astype(
        np.int32)
    occ = np.arange(bk)[None, :] < blk_count[:, None]
    removed = occ & (out["rem_seq"] != int(NONE_SEQ))
    live = occ & ~removed
    out["blk_count"] = blk_count
    out["blk_live_len"] = np.sum(np.where(live, out["length"], 0),
                                 axis=1).astype(np.int32)
    out["blk_max_seq"] = np.max(
        np.where(occ, np.maximum(out["ins_seq"],
                                 np.where(removed, out["rem_seq"], 0)),
                 0), axis=1, initial=0).astype(np.int32)
    out["blk_tomb"] = np.sum(removed, axis=1).astype(np.int32)
    out["count"] = np.int32(n)
    return out
