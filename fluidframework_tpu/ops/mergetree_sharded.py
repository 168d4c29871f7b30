"""Sequence-parallel merge-tree — the SEGMENT axis sharded over the mesh.

The docs-axis sharding (parallel/mesh.py) scales document COUNT with
zero collectives; this module scales document SIZE: one huge document's
segment table is split across the mesh's chips, and the merge walk runs
as a cooperative SPMD program — the collaboration framework's analog of
sequence/context parallelism for long sequences (ring attention's role
in ML stacks; SURVEY §5.7's block-tree → prefix-scan mapping taken to
its distributed conclusion, exploiting the same associativity the
reference's PartialSequenceLengths.combine has — partialLengths.ts:69):

  * position transforms = DISTRIBUTED exclusive prefix sums: local scan
    + all-gathered shard totals (the classic two-level scan);
  * the insert walk's first-candidate select = local masked min of
    global indices + a pmin across shards;
  * per-op scalars (offsets, placement index, counts) = psum/pmin
    reductions — replicated-consistent on every shard;
  * the split/place data movement = local shifts + ppermute edge
    exchange with the neighbouring shard (segments that cross a shard
    boundary ride one hop of ICI — the "ring" step).

Semantics come from the SAME merge_apply_vec the Pallas kernel runs
(mergetree_pallas): this module only swaps the segment-axis primitives
(LanePrims → collective twins), so single-chip, Pallas, and sharded
paths cannot drift apart. Differential test:
tests/test_mergetree_sharded.py (bit-identical to the unsharded kernel
on live + random streams over the virtual 8-device mesh).

Block-table compatibility: single-chip pools serve from the
block-structured table (ops/mergetree_blocks.py — O(S/Bk + Bk) per op)
whose BLOCK axis would not shard meaningfully (a distributed block
resolve re-introduces the collectives per op the summaries exist to
avoid), so sequence-parallel pools keep the FLAT layout this module
shards and documents convert at the pool boundary:
:func:`from_block_state` packs a block table into the flat layout when
a document outgrows one chip, and ``mergetree_blocks.from_flat``
re-blocks it if it ever shrinks back — both exact, pinned by
tests/test_mergetree_blocks.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .mergetree_kernel import MergeOpBatch, MergeState
from .mergetree_pallas import _OPS, _PLANES, merge_apply_vec

I32 = jnp.int32
SEGS_AXIS = "segs"


def make_seg_mesh(devices=None) -> Mesh:
    """1-D mesh over the SEGMENT axis (long-document scale-out)."""
    import numpy as np

    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (SEGS_AXIS,))


class ShardPrims:
    """Collective twins of mergetree_pallas.LanePrims for a segment axis
    sharded over ``axis_name`` (built inside shard_map)."""

    def __init__(self, axis_name: str, num_shards: int,
                 local_lanes: int) -> None:
        self.axis = axis_name
        self.n = num_shards
        self.local = local_lanes
        self.global_lanes = num_shards * local_lanes
        self.offset = jax.lax.axis_index(axis_name) * local_lanes

    def lane_iota(self, shape: tuple) -> jax.Array:
        return (jax.lax.broadcasted_iota(I32, shape, len(shape) - 1)
                + self.offset)

    def excl_cumsum(self, x: jax.Array) -> jax.Array:
        # Two-level distributed scan: local inclusive scan, then add the
        # exclusive sum of the preceding shards' totals.
        local_inc = jnp.cumsum(x, axis=-1)
        total = local_inc[..., -1:]
        gathered = jax.lax.all_gather(total, self.axis)  # [n, ..., 1]
        shard_ids = jax.lax.broadcasted_iota(I32, (self.n,), 0)
        mask = shard_ids < jax.lax.axis_index(self.axis)
        shape = (self.n,) + (1,) * (gathered.ndim - 1)
        offset = jnp.sum(jnp.where(mask.reshape(shape), gathered, 0),
                         axis=0)
        return local_inc - x + offset

    def first_true(self, mask: jax.Array) -> jax.Array:
        lane = self.lane_iota(mask.shape)
        local = jnp.min(jnp.where(mask, lane, self.global_lanes),
                        axis=-1, keepdims=True)
        return jax.lax.pmin(local, self.axis)

    def any_(self, mask: jax.Array) -> jax.Array:
        local = jnp.any(mask, axis=-1, keepdims=True)
        return jax.lax.pmax(local.astype(I32), self.axis) != 0

    def gather(self, x: jax.Array, idx: jax.Array) -> jax.Array:
        lane = self.lane_iota(x.shape)
        local = jnp.sum(jnp.where(lane == idx, x, 0), axis=-1,
                        keepdims=True)
        return jax.lax.psum(local, self.axis)

    def roll(self, field: jax.Array, shift: int) -> jax.Array:
        # Global circular roll: local roll + the previous shard's tail
        # rides one ppermute hop (ICI ring step).
        edge = field[..., -shift:]
        perm = [(i, (i + 1) % self.n) for i in range(self.n)]
        received = jax.lax.ppermute(edge, self.axis, perm)
        rolled = jnp.roll(field, shift, axis=-1)
        lane = jax.lax.broadcasted_iota(I32, field.shape,
                                        field.ndim - 1)
        pad = jnp.concatenate(
            [received,
             jnp.zeros(field.shape[:-1] + (field.shape[-1] - shift,),
                       field.dtype)], axis=-1)
        return jnp.where(lane < shift, pad, rolled)


def _step_factory(prims: ShardPrims):
    def step(carry, op):
        planes, prop, overlap, count = carry
        new_planes, new_prop, new_overlap, new_count = merge_apply_vec(
            planes, prop, overlap, count, op, prims=prims)
        return (new_planes, new_prop, new_overlap, new_count), ()

    return step


@functools.partial(jax.jit, static_argnames=("mesh",))
def apply_tick_sharded(state: MergeState, ops: MergeOpBatch,
                       mesh: Mesh) -> MergeState:
    """apply_tick with the SEGMENT axis sharded over ``mesh``.

    state planes shard on their last segment axis; ops and per-doc
    scalars replicate. Bit-identical to mergetree_kernel.apply_tick.
    """
    num_shards = mesh.devices.size
    b, s = state.length.shape
    assert s % num_shards == 0, (
        f"segment capacity {s} must divide over {num_shards} shards")
    local = s // num_shards
    # ShardPrims.roll exchanges at most one neighbour hop of `shift`
    # lanes (merge_apply_vec shifts by <= 2).
    assert local >= 2, (
        f"need >= 2 segment slots per shard, have {local}")

    def tick(*flat):
        planes = dict(zip(_PLANES, flat[:7]))
        prop = flat[7]
        overlap = flat[8]
        count = flat[9]
        op_arrays = dict(zip(_OPS, flat[10:]))
        prims = ShardPrims(SEGS_AXIS, num_shards, local)
        ops_t = {name: arr.T[:, :, None] for name, arr in
                 op_arrays.items()}  # [K, B, 1] scan leaves
        (planes, prop, overlap, count), _ = jax.lax.scan(
            _step_factory(prims), (planes, prop, overlap, count),
            ops_t)
        return tuple(planes[name] for name in _PLANES) + (
            prop, overlap, count)

    seg = PartitionSpec(None, SEGS_AXIS)
    seg3 = PartitionSpec(None, None, SEGS_AXIS)
    rep = PartitionSpec()
    in_specs = (seg,) * 7 + (seg3, seg3, rep) + (rep,) * 11
    out_specs = (seg,) * 7 + (seg3, seg3, rep)

    flat_in = tuple(
        getattr(state, name).astype(I32) for name in _PLANES) + (
        jnp.transpose(state.prop_val, (2, 0, 1)),  # [P, B, S]
        jnp.transpose(state.rem_overlap, (2, 0, 1)),  # [W, B, S]
        state.count[:, None].astype(I32),
    ) + tuple(getattr(ops, name).astype(I32) for name in _OPS)

    out = jax.shard_map(tick, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)(*flat_in)

    named = dict(zip(_PLANES, out[:7]))
    return MergeState(
        valid=named["valid"] != 0,
        length=named["length"],
        ins_seq=named["ins_seq"],
        ins_client=named["ins_client"],
        rem_seq=named["rem_seq"],
        rem_client=named["rem_client"],
        rem_overlap=jnp.transpose(out[8], (1, 2, 0)),
        pool_start=named["pool_start"],
        prop_val=jnp.transpose(out[7], (1, 2, 0)),
        count=out[9][:, 0],
    )


def from_block_state(block_state, slots: int | None = None
                     ) -> MergeState:
    """Pack a block-structured table into the flat layout this module
    shards (the doc-outgrew-one-chip migration source). ``slots`` pads
    to the target sharded pool's segment capacity."""
    from .mergetree_blocks import to_flat
    return to_flat(block_state, slots)


def shard_merge_state(state: MergeState, mesh: Mesh) -> MergeState:
    """Place a MergeState with the segment axis sharded (prop on dim 1)."""
    seg = NamedSharding(mesh, PartitionSpec(None, SEGS_AXIS))
    seg_prop = NamedSharding(mesh, PartitionSpec(None, SEGS_AXIS, None))
    rep = NamedSharding(mesh, PartitionSpec())
    return MergeState(
        valid=jax.device_put(state.valid, seg),
        length=jax.device_put(state.length, seg),
        ins_seq=jax.device_put(state.ins_seq, seg),
        ins_client=jax.device_put(state.ins_client, seg),
        rem_seq=jax.device_put(state.rem_seq, seg),
        rem_client=jax.device_put(state.rem_client, seg),
        rem_overlap=jax.device_put(state.rem_overlap, seg_prop),
        pool_start=jax.device_put(state.pool_start, seg),
        prop_val=jax.device_put(state.prop_val, seg_prop),
        count=jax.device_put(state.count, rep),
    )
