"""Persistent compilation cache (utils/compile_cache): the directory is
the one JAX_COMPILATION_CACHE_DIR names, else one fixed path inside the
checkout; and a DONATED tick deserialized from that cache runs
repeatedly with identical results (jaxlib 0.4.37 double-freed donated
buffers on the second run of a cache-loaded executable — checked gone
on jax 0.9.0, CPU and TPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from fluidframework_tpu.utils import compile_cache


def test_cache_round_tripped_donated_tick_runs_twice_identically():
    """The serving shape: a donated jit compiled once, dropped from the
    in-memory caches, then loaded back from the persistent cache and
    executed repeatedly (the shape that double-freed)."""
    compile_cache.enable()

    def make():
        @functools.partial(jax.jit, donate_argnums=(0,))
        def tick(state, words):
            return state + jnp.sum(words), state * 0 + words
        return tick

    w = jnp.arange(8, dtype=jnp.int32)
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        make()(jnp.zeros(8, jnp.int32), w)
        jax.clear_caches()
        tick = make()
        s1, out1 = tick(jnp.zeros(8, jnp.int32), w)
        s2, out2 = tick(s1, w)  # second run of the SAME executable
        s3, out3 = tick(s2, w)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
    assert np.array_equal(np.asarray(out1), np.asarray(out3))
    assert np.asarray(s3)[0] == 3 * 28


def test_cache_dir_is_env_or_checkout():
    """conftest enabled the cache: the directory in effect is the env
    var's where set (JAX reads it), else the fixed in-checkout path."""
    import os

    path = compile_cache.enable()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    assert path == (env or compile_cache.CHECKOUT_DIR)
    assert jax.config.jax_compilation_cache_dir == path
