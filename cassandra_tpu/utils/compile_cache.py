"""Where JAX's persistent compilation cache lives.

A cold device-engine compaction pays minutes of XLA compilation
(sort + reconcile, the resident round, the compress scan); the
persistent cache turns that into file reads on the next start. The
directory is part of the cache key, so it must not move between runs:
`JAX_COMPILATION_CACHE_DIR` when the deployment sets it (JAX reads the
variable itself — nothing is set in code then), else `.jax_cache` at
the root of the checkout. Never a temp name, a pid or a time.

Called once at process start by every entry point that runs device
programs (tools/noded.py, bench.py, chip_smoke.py, the device scripts).
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure() -> str:
    """Returns the cache directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
