"""Persistent XLA compilation cache for serving hosts and test farms.

The serving host's device programs recompile whenever a pool migrates to
a new shape bucket (segment slots, op-batch k, prop planes, overlap
words all double on demand). Within one process the in-memory jit cache
dedups identical shapes; across restarts — a serving host rolling, a
farm re-running, bench.py re-invoked — every bucket shape would pay its
full XLA compile again. The reference ships its lambdas warm for the
same reason (a routerlicious pod restart does not re-JIT V8 code from
scratch); here the equivalent is JAX's persistent compilation cache
keyed by HLO fingerprint.

Call :func:`enable` before first device use. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no directory; otherwise the cache sits at one fixed path inside the
checkout (the path is part of the cache key, so it must not move).
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")

_active_dir: str | None = None


def enable() -> str:
    """Idempotently turn on the persistent compilation cache; returns the
    directory in effect."""
    global _active_dir
    if _active_dir is not None:
        return _active_dir
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Serving-host programs include many sub-second helpers (row writes,
    # margin reads) that still dominate a farm's wall clock in aggregate;
    # cache everything non-trivial rather than only the big kernels.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _active_dir = path
    return path
