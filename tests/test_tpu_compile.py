"""Compile the serving kernels for a v5e that is only described to the
compiler (no chip attached): the TPU compiler refuses here what interpret
mode never checks — block shapes off the (8, 128) tiling, unsupported
Mosaic ops, programs over VMEM. Shapes are the repo's serving shapes.
Every program must hold a ``tpu_custom_call`` (the Pallas kernel was
compiled, not an XLA fallback).

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every xdist worker imports this module.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fluidframework_tpu.ops import map_kernel as mk
from fluidframework_tpu.ops import map_pallas as mp
from fluidframework_tpu.ops import matrix_kernel as mxk
from fluidframework_tpu.ops import mergetree_blocks as mtb
from fluidframework_tpu.ops import mergetree_blocks_pallas as mtbp
from fluidframework_tpu.ops import mergetree_kernel as mtk
from fluidframework_tpu.ops import sequencer as seqk
from fluidframework_tpu.ops import sequencer_pallas as seqp

I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _shapes(sharding, fn, *args, **kwargs):
    return _on(sharding, jax.eval_shape(lambda: fn(*args, **kwargs)))


def _array(sharding, shape, dtype=I32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _batch(sharding, cls, shape, bool_fields, tail=None):
    """A [B, K] op batch of NamedTuple ``cls``; ``tail`` maps fields to
    their own shapes."""
    tail = tail or {}
    return cls(**{name: _array(sharding, tail.get(name, shape),
                               jnp.bool_ if name in bool_fields else I32)
                  for name in cls._fields})


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [1024, 4096])
def test_map_fold_compiles(one_chip, k):
    b = 10_240
    state = _shapes(one_chip, mk.init_state, b, 32)
    words = _array(one_chip, (b, k), jnp.uint32)
    vec = _array(one_chip, (b,))
    _assert_kernel(mp.apply_tick_words_pallas.lower(
        state, words, vec, vec).compile())


def test_sequencer_kernel_compiles(one_chip):
    b, k = 10_240, 64
    state = _shapes(one_chip, seqk.init_state, b)
    ops = _batch(one_chip, seqk.OpBatch, (b, k),
                 {"valid", "has_contents", "can_summarize", "can_evict",
                  "is_nack_future"})
    _assert_kernel(seqp.process_batch_pallas.lower(state, ops).compile())


@pytest.mark.parametrize("docs,writers", [(8192, 8), (4096, 128)])
def test_block_mergetree_kernel_compiles(one_chip, docs, writers):
    k, slots = 32, 512
    nb, bk = mtb.choose_block_geometry(slots, k)
    state = _shapes(one_chip, mtb.init_state, docs, nb, bk,
                    overlap_words=mtk.overlap_words_for(writers))
    ops = _batch(one_chip, mtk.MergeOpBatch, (docs, k), {"valid"})
    _assert_kernel(mtbp.apply_tick_blocks_pallas.lower(state, ops).compile())


def test_matrix_step_kernel_compiles(one_chip):
    """The serving host's handle-indexed matrix tick (XLA: vector scan,
    [N, S] handle lookup, plane scatter, zamboni) at the matrix_grid_1k
    cell's shapes: 8 grids, 2,048-slot vector tables and handle planes,
    256 writers, the largest tick (64 vector ops, 1,024 cells). The
    state is donated, so the planes are updated in place."""
    b, slots, handles, t, n = 8, 2048, 2048, 64, 1024
    state = _shapes(one_chip, mxk.init_state, b, slots, handles,
                    overlap_words=mtk.overlap_words_for(257))
    steps = _batch(one_chip, mxk.MatrixStepBatch, (b, t),
                   {"vec_valid", "c_valid"},
                   tail={name: (b, n) for name in mxk.MatrixStepBatch._fields
                         if name.startswith("c_")})
    compiled = mxk.matrix_tick.lower(state, steps,
                                     _array(one_chip, (b,))).compile()
    mem = compiled.memory_analysis()
    plane = b * handles * handles * 4
    assert mem.alias_size_in_bytes >= 2 * plane  # both planes in place
    assert mem.temp_size_in_bytes < 4 * 2**30


def test_storm_tick_compiles_with_pallas_fold(one_chip, monkeypatch):
    """The fused serving tick (deli ticket + map fold) at the e2e storm
    shape, with the Pallas leg that a TPU host takes."""
    from fluidframework_tpu.server import storm

    monkeypatch.setattr(mp, "default_interpret", lambda: False)
    b, k = 10_240, 1024
    seq_state = _shapes(one_chip, seqk.init_state, b, 2)
    map_state = _shapes(one_chip, mk.init_state, b, 64)
    vec = _array(one_chip, (b,))
    words = _array(one_chip, (b, k), jnp.uint32)
    tick = jax.jit(storm._storm_tick.__wrapped__, donate_argnums=(0, 1))
    _assert_kernel(tick.lower(seq_state, map_state, vec, vec, vec, vec, vec,
                              vec, words, vec).compile())
