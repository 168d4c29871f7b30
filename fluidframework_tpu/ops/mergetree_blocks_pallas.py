"""Pallas TPU twin of the block-structured merge table — VMEM-resident.

One grid program per document: the program DMAs its doc's [NB, Bk]
planes + [NB, 1] summary columns into VMEM ONCE, applies all K
sequenced ops with the SAME per-doc step the XLA path scans
(:func:`mergetree_blocks.block_apply_doc` — shared body, so the twin
cannot drift semantically; the differential test still pins every plane
bit-for-bit), and writes back ONCE. HBM traffic per tick is O(B·S)
regardless of K, and inside VMEM each op's structural phase moves one
[Bk] block while position resolution runs over the [NB] summary column
+ one block — the O(S/Bk + Bk) layout contract realized where it
matters (the flat Pallas kernel still paid O(S) VPU work per op for its
full-table shifts and length-S scan chains; here the serialized scan
chains are length NB and Bk).

Only the axis primitives differ from the XLA path (`PltPrims`):
``pltpu.roll`` for the within-block shifts and a log-shift scan for the
exclusive prefix sums — integer adds, so both cumsum orders are exact
and the twin stays bit-identical.

Shapes (see /opt/skills/guides/pallas_guide.md): planes are i32 with
(8, 128) tiles riding the trailing (NB, Bk) axes — size Bk to a lane
multiple (the serving pools use Bk = 128) and NB to a sublane multiple
for efficiency. Every block's trailing two dims equal its array's (the
TPU tiling rule), so per-doc scalars ride a squeezed leading doc axis:
summaries [B, NB, 4], count [B, 1, 1], ops [B, F, K]. Mosaic lowers no
dynamic slice of a value, so the block read/write selects the block row
with a mask over the short block axis (``PltPrims.take_block``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mergetree_kernel as mtk
from .mergetree_blocks import (
    _OP_FIELDS,
    _SLOT_PLANES,
    _SUMM,
    OVF_NONE,
    BlockMergeState,
    block_apply_doc,
)

I32 = jnp.int32


class PltPrims:
    """Mosaic twins of mergetree_blocks.BlockPrims."""

    @staticmethod
    def roll(x: jax.Array, shift: int, axis: int) -> jax.Array:
        return pltpu.roll(x, shift=shift, axis=axis)

    @staticmethod
    def cumsum_excl(x: jax.Array, axis: int) -> jax.Array:
        n = x.shape[axis]
        idx = lax.broadcasted_iota(I32, x.shape, axis)
        total = x
        shift = 1
        while shift < n:
            total = total + jnp.where(
                idx >= shift, pltpu.roll(total, shift=shift, axis=axis), 0)
            shift *= 2
        return total - x

    # Mosaic has no dynamic value slices: the block row is selected and
    # written back by a mask over the (short) block axis. One nonzero
    # term per integer sum, so the result is exact.
    @staticmethod
    def take_block(x: jax.Array, b: jax.Array) -> jax.Array:
        rows = lax.broadcasted_iota(I32, x.shape, x.ndim - 2)
        return jnp.sum(jnp.where(rows == b, x, 0), axis=x.ndim - 2,
                       keepdims=True)

    @staticmethod
    def put_block(x: jax.Array, blk: jax.Array, b: jax.Array) -> jax.Array:
        rows = lax.broadcasted_iota(I32, x.shape, x.ndim - 2)
        return jnp.where(rows == b, blk, x)


def _tick_kernel(*refs, num_ops: int):
    plane_refs = refs[:6]
    prop_ref, overlap_ref, summ_ref, count_ref, op_ref = refs[6:11]
    out_plane_refs = refs[11:17]
    (out_prop_ref, out_overlap_ref, out_summ_ref, out_count_ref,
     out_ovf_ref) = refs[17:22]

    planes = {name: ref[:] for name, ref in zip(_SLOT_PLANES, plane_refs)}
    prop = prop_ref[:]
    overlap = overlap_ref[:]
    # Fields are picked out of the packs by masked reductions, never by
    # unaligned slices: Mosaic cannot broadcast a [1, 1] value that sits
    # at a nonzero sublane/lane offset, and needs 128-aligned dynamic
    # lane slices. One nonzero term per integer sum keeps them exact.
    summ_pack = summ_ref[:]                                  # [NB, 4]
    summ_lane = lax.broadcasted_iota(I32, summ_pack.shape, 1)
    summ = {name: jnp.sum(jnp.where(summ_lane == j, summ_pack, 0), axis=1,
                          keepdims=True)
            for j, name in enumerate(_SUMM)}
    count = count_ref[:]
    op_pack = op_ref[:]                                      # [F, K]
    op_lane = lax.broadcasted_iota(I32, op_pack.shape, 1)
    op_row = lax.broadcasted_iota(I32, (op_pack.shape[0], 1), 0)

    def body(k, carry):
        planes, prop, overlap, summ, count, ovf = carry
        col = jnp.sum(jnp.where(op_lane == k, op_pack, 0), axis=1,
                      keepdims=True)                          # [F, 1]
        op = {name: jnp.sum(jnp.where(op_row == j, col, 0), axis=0,
                            keepdims=True)
              for j, name in enumerate(_OP_FIELDS)}
        idx = jnp.zeros((1, 1), I32) + k
        return block_apply_doc(planes, prop, overlap, summ, count, ovf,
                               op, idx, prims=PltPrims)

    # Serving flushes front-pack ops, so a dynamic trip count skips the
    # invalid tail at zero per-step cost.
    is_valid = (op_row == _OP_FIELDS.index("valid")) & (op_pack != 0)
    last_valid = jnp.max(jnp.where(is_valid, op_lane + 1, 0))
    ovf0 = jnp.full((1, 1), OVF_NONE, I32)
    planes, prop, overlap, summ, count, ovf = lax.fori_loop(
        0, jnp.minimum(last_valid, num_ops), body,
        (planes, prop, overlap, summ, count, ovf0))
    for name, ref in zip(_SLOT_PLANES, out_plane_refs):
        ref[:] = planes[name]
    out_prop_ref[:] = prop
    out_overlap_ref[:] = overlap
    out_summ = jnp.zeros(summ_pack.shape, I32)
    for j, name in enumerate(_SUMM):
        out_summ = jnp.where(summ_lane == j, summ[name], out_summ)
    out_summ_ref[:] = out_summ
    out_count_ref[:] = count
    out_ovf_ref[:] = ovf


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_tick_blocks_pallas(state: BlockMergeState,
                             ops: mtk.MergeOpBatch,
                             interpret: bool = False
                             ) -> tuple[BlockMergeState, jax.Array]:
    """Drop-in replacement for mergetree_blocks.apply_tick_blocks.
    Returns (state', first-overflow op index [B])."""
    b, nb, bk = state.length.shape
    p = state.prop_val.shape[3]
    w = state.rem_overlap.shape[3]
    k = ops.kind.shape[1]

    planes = [getattr(state, name) for name in _SLOT_PLANES]
    prop = jnp.transpose(state.prop_val, (3, 0, 1, 2))      # [P, B, NB, Bk]
    overlap = jnp.transpose(state.rem_overlap, (3, 0, 1, 2))
    # Per-doc scalars ride a squeezed leading doc axis so every block's
    # trailing two dims equal the array's own (the TPU tiling rule):
    # summaries [B, NB, 4], count [B, 1, 1], ops [B, F, K].
    summ = jnp.stack([getattr(state, name) for name in _SUMM], axis=2)
    count = state.count[:, None, None]
    op_pack = jnp.stack([getattr(ops, name).astype(I32)
                         for name in _OP_FIELDS], axis=1)
    n_summ, n_op = len(_SUMM), len(_OP_FIELDS)

    def doc_spec(*shape):
        return pl.BlockSpec((None, *shape),
                            lambda i: (i,) + (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    plane_spec = doc_spec(nb, bk)
    prop_spec = pl.BlockSpec((p, None, nb, bk), lambda i: (0, i, 0, 0),
                             memory_space=pltpu.VMEM)
    overlap_spec = pl.BlockSpec((w, None, nb, bk), lambda i: (0, i, 0, 0),
                                memory_space=pltpu.VMEM)
    summ_spec = doc_spec(nb, n_summ)
    scalar_spec = doc_spec(1, 1)

    out = pl.pallas_call(
        functools.partial(_tick_kernel, num_ops=k),
        grid=(b,),
        in_specs=[plane_spec] * 6 + [prop_spec, overlap_spec, summ_spec,
                                     scalar_spec, doc_spec(n_op, k)],
        out_specs=[plane_spec] * 6 + [prop_spec, overlap_spec, summ_spec,
                                      scalar_spec, scalar_spec],
        out_shape=(
            [jax.ShapeDtypeStruct((b, nb, bk), I32)] * 6
            + [jax.ShapeDtypeStruct((p, b, nb, bk), I32),
               jax.ShapeDtypeStruct((w, b, nb, bk), I32),
               jax.ShapeDtypeStruct((b, nb, n_summ), I32)]
            + [jax.ShapeDtypeStruct((b, 1, 1), I32)] * 2),
        input_output_aliases={i: i for i in range(10)},
        interpret=interpret,
    )(*planes, prop, overlap, summ, count, op_pack)

    new = state._replace(
        **{name: arr for name, arr in zip(_SLOT_PLANES, out[:6])},
        prop_val=jnp.transpose(out[6], (1, 2, 3, 0)),
        rem_overlap=jnp.transpose(out[7], (1, 2, 3, 0)),
        **{name: out[8][:, :, j] for j, name in enumerate(_SUMM)},
        count=out[9][:, 0, 0])
    return new, out[10][:, 0, 0]


def default_interpret() -> bool:
    """Pallas TPU kernels need a real TPU; elsewhere run interpreted."""
    return jax.default_backend() != "tpu"


def apply_tick_blocks_best(state: BlockMergeState, ops: mtk.MergeOpBatch
                           ) -> tuple[BlockMergeState, jax.Array]:
    """Fastest correct block tick for the current backend: the Pallas
    VMEM kernel on TPU, the XLA vmap-scan path everywhere else
    (interpret-mode Pallas only serves the differential tests)."""
    from .mergetree_blocks import apply_tick_blocks
    if default_interpret():
        return apply_tick_blocks(state, ops)
    return apply_tick_blocks_pallas(state, ops)


def serve_tick_blocks_best(state: BlockMergeState, ops: mtk.MergeOpBatch,
                           min_seq: jax.Array, tick_k: int
                           ) -> tuple[BlockMergeState, jax.Array,
                                      jax.Array]:
    """One SERVING-path step: the best apply for this backend followed
    by the conditional maintenance ladder (incremental neighbor spill /
    deferred zamboni — mergetree_blocks.maybe_rebalance_stats), exactly
    the per-tick composition storm._mixed_tick fuses. The maintenance
    leg is shared XLA on every backend — its per-block circular shifts
    and summary selects sit OUTSIDE the VMEM grid program, so the twin
    stays bit-pinned to the XLA path through rebalances by
    construction. Returns (state', overflow[B], rstats i32[2])."""
    from .mergetree_blocks import maybe_rebalance_stats
    state, ovf = apply_tick_blocks_best(state, ops)
    state, rstats = maybe_rebalance_stats(state, min_seq, tick_k)
    return state, ovf, rstats
