"""The bytes functions against the operands the program's Pallas calls
really take, read from their jaxprs at shapes without padding, and at the
cells' shapes."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import kernel_bytes


def _pallas_bytes(fn, *args) -> int:
    """Bytes of every operand and result of the one pallas_call that
    ``fn(*args)`` traces to."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    calls = [e for e in _walk(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 1, [e.primitive.name for e in calls]
    eqn = calls[0]
    size = lambda v: int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
    return sum(map(size, eqn.invars)) + sum(map(size, eqn.outvars))


def _walk(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                yield from _walk(inner)


def test_map_fold_bytes_are_the_kernel_operands():
    from fluidframework_tpu.ops import map_kernel as mk
    from fluidframework_tpu.ops import map_pallas as mp

    docs, k, slots = 1024, 64, 32
    state = mk.init_state(docs, slots)
    words = jnp.zeros((docs, k), jnp.uint32)
    vec = jnp.zeros((docs,), jnp.int32)
    got = _pallas_bytes(
        lambda s, w, lo, hi, b: mp.fold_words(s, w, lo, hi, b,
                                              interpret=True),
        state, words, vec, vec, vec)
    assert kernel_bytes.map_fold_bytes(docs, k, slots) == got


def test_cell_shapes():
    # map_storm_10k.flood: 10,240 docs x 512 ops x 32 slots, ~29 MB a tick.
    assert kernel_bytes.map_fold_bytes(10240, 512, 32) == 4 * (
        10240 * 512 + 3 * 10240 + 2 * (3 * 10240 * 32 + 10240))
