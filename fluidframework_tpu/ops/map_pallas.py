"""Pallas TPU kernel for the batched SharedMap LWW fold — VMEM-resident.

Reference parity: mapKernel.ts:510 tryProcessMessage set/delete/clear on
the converged stream, same as :mod:`map_kernel`. The XLA path computes the
per-tick winner with a dense [B, K, S] broadcast-compare; at storm scale
(10k docs x K ops x S slots) those intermediates are gigabytes of HBM
traffic per tick and dominate the fused serving tick. This kernel holds
one doc block's planes in VMEM and folds the K ops with [S, D] passes —
HBM traffic drops to the planes + the 4-byte/op words, period.

Layout mirrors sequencer_pallas: DOCS ON LANES. State planes are [S, D]
(slots ride sublanes), per-doc scalars are [1, D] rows, and the packed op
words are [K, D] so step k reads one dynamic SUBLANE slice — no masked
reductions in the hot loop.

The fold takes a per-doc VALID WINDOW [lo, hi) and a seq base: op k in
the window applies with seq = seq_base + 1 + (k - lo). The plain words
path uses lo=0, hi=counts; the fused storm tick passes lo=dups,
hi=dups+n_seq straight from the closed-form sequencer
(:func:`sequencer.storm_tickets`) so tickets never leave the device.

Pinned to :func:`map_kernel.apply_tick_words` by differential test
(tests/test_map_pallas.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .map_kernel import MAP_CLEAR, MAP_SET, MapState
from .mergetree_pallas import default_interpret

I32 = jnp.int32


def _fold_kernel(words_ref, lo_ref, hi_ref, base_ref,
                 present_ref, value_ref, vseq_ref, cleared_ref,
                 out_present_ref, out_value_ref, out_vseq_ref,
                 out_cleared_ref, *, num_ops: int):
    words = words_ref[:]          # [K, D] i32 packed kind|slot|value
    lo = lo_ref[:]                # [1, D]
    hi = hi_ref[:]                # [1, D]
    base = base_ref[:]            # [1, D] seq before the first windowed op
    present = present_ref[:]      # [S, D] i32 (bool plane)
    value = value_ref[:]
    vseq = vseq_ref[:]
    cleared_seq = cleared_ref[:]  # [1, D]

    shape = present.shape
    k_iota = jax.lax.broadcasted_iota(I32, words.shape, 0)
    in_window = (k_iota >= lo) & (k_iota < hi)
    kind_all = words & 3
    is_clear = in_window & (kind_all == MAP_CLEAR)
    last_clear = jnp.max(jnp.where(is_clear, k_iota, -1), axis=0,
                         keepdims=True)  # [1, D]
    cleared = last_clear >= 0
    # The clear barrier blanks every slot; surviving ops re-populate.
    cbc = jnp.broadcast_to(cleared, shape)
    present = jnp.where(cbc, 0, present)
    vseq = jnp.where(cbc, -1, vseq)
    cleared_seq = jnp.where(cleared, base + 1 + last_clear - lo,
                            cleared_seq)
    eff_lo = jnp.maximum(lo, last_clear + 1)

    slot_iota = jax.lax.broadcasted_iota(I32, shape, 0)
    touched = jnp.zeros(shape, I32)
    val_acc = value

    def body(k, carry):
        present, val_acc, vseq, touched = carry
        wk = words_ref[pl.ds(k, 1), :]            # [1, D]
        kind = wk & 3
        slot = (wk >> 2) & 0x3FF
        val = (wk >> 12) & 0xFFFFF
        live = (k >= eff_lo) & (k < hi) & (kind != MAP_CLEAR)
        is_set = kind == MAP_SET
        m = (slot_iota == jnp.broadcast_to(slot, shape)) \
            & jnp.broadcast_to(live, shape)
        set_b = jnp.broadcast_to(is_set.astype(I32), shape)
        present = jnp.where(m, set_b, present)
        val_acc = jnp.where(m & (set_b != 0), jnp.broadcast_to(val, shape),
                            val_acc)
        vseq = jnp.where(m, jnp.broadcast_to(base + 1 + k - lo, shape),
                         vseq)
        touched = jnp.where(m, 1, touched)
        return present, val_acc, vseq, touched

    # Front-packed ticks: stop at the deepest window end in the block.
    last = jnp.minimum(jnp.max(hi), num_ops)
    first = jnp.maximum(jnp.min(eff_lo), 0)
    present, val_acc, vseq, touched = jax.lax.fori_loop(
        first, last, body, (present, val_acc, vseq, touched))

    out_present_ref[:] = present
    # The value plane moves ONLY when the slot's winner is a set (the XLA
    # path gathers the winner then writes sets only): a slot whose last
    # live op is a delete keeps its PRE-TICK value even if an earlier
    # in-tick set wrote it.
    out_value_ref[:] = jnp.where((touched != 0) & (present != 0),
                                 val_acc, value)
    out_vseq_ref[:] = vseq
    out_cleared_ref[:] = cleared_seq


def _pad_lanes(x: jax.Array, bp: int, fill) -> jax.Array:
    if x.shape[-1] == bp:
        return x
    pads = [(0, 0)] * (x.ndim - 1) + [(0, bp - x.shape[-1])]
    return jnp.pad(x, pads, constant_values=fill)


def fold_words(state: MapState, words: jax.Array, lo: jax.Array,
               hi: jax.Array, base_seq: jax.Array,
               block_docs: int = 512, interpret: bool = False) -> MapState:
    """The VMEM LWW fold as a composable op (callable inside a larger
    jit — the fused storm tick does). ``words`` [B, K]; ``lo``/``hi``
    give each doc's valid op window; ``base_seq`` is the doc seq before
    the first windowed op."""
    b, s = state.present.shape
    k = words.shape[1]
    sp = -(-s // 8) * 8  # i32 sublane tile
    # VMEM budget: Mosaic double-buffers the inputs across grid steps, so
    # the dominant [K, D] words block costs 2*4*K*D bytes; deep ticks
    # (K >= 4096) must shrink the doc block to stay under the ~16MB
    # scoped-vmem limit.
    d_vmem = max(128, (12 << 20) // (8 * (k + 4 * sp)) // 128 * 128)
    d = min(block_docs, d_vmem, max(128, -(-b // 128) * 128))
    bp = -(-b // d) * d

    def plane(x, fill):
        return _pad_lanes(x.astype(I32).T, bp, fill)  # [S, B] -> padded

    def row(x, fill):
        return _pad_lanes(x.astype(I32)[None, :], bp, fill)

    planes = [
        jnp.pad(plane(state.present, 0), ((0, sp - s), (0, 0))),
        jnp.pad(plane(state.value, 0), ((0, sp - s), (0, 0))),
        jnp.pad(plane(state.vseq, -1), ((0, sp - s), (0, 0)),
                constant_values=-1),
    ]
    cleared = row(state.cleared_seq, -1)
    words_t = _pad_lanes(words.astype(I32).T, bp, 0)  # [K, D]
    lo_r, hi_r, base_r = row(lo, 0), row(hi, 0), row(base_seq, 0)

    word_spec = pl.BlockSpec((k, d), lambda i: (0, i),
                             memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, d), lambda i: (0, i),
                            memory_space=pltpu.VMEM)
    plane_spec = pl.BlockSpec((sp, d), lambda i: (0, i),
                              memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        functools.partial(_fold_kernel, num_ops=k),
        grid=(bp // d,),
        in_specs=[word_spec] + [row_spec] * 3
        + [plane_spec] * 3 + [row_spec],
        out_specs=[plane_spec] * 3 + [row_spec],
        out_shape=(
            [jax.ShapeDtypeStruct((sp, bp), jnp.int32)] * 3
            + [jax.ShapeDtypeStruct((1, bp), jnp.int32)]),
        input_output_aliases={4: 0, 5: 1, 6: 2, 7: 3},
        interpret=interpret,
    )(words_t, lo_r, hi_r, base_r, *planes, cleared)

    return MapState(
        present=out[0][:s, :b].T != 0,
        value=out[1][:s, :b].T,
        vseq=out[2][:s, :b].T,
        cleared_seq=out[3][0, :b],
    )


@functools.partial(jax.jit, static_argnames=("block_docs", "interpret"))
def apply_tick_words_pallas(state: MapState, words: jax.Array,
                            counts: jax.Array, base_seq: jax.Array,
                            block_docs: int = 512,
                            interpret: bool = False) -> MapState:
    """Drop-in replacement for :func:`map_kernel.apply_tick_words`."""
    zeros = jnp.zeros_like(counts)
    return fold_words(state, words, zeros, counts, base_seq,
                      block_docs=block_docs, interpret=interpret)
