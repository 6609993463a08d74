"""JAX's persistent compilation cache, placed from outside.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory.  Otherwise the cache lives at one fixed path
inside the checkout, ``<repo>/.jax_cache/`` (git-ignored): the path is part
of what a later run looks up, so it is never built from a temporary name,
a pid or the time.  Entry points (``chip_smoke.py``, the benchmarks,
``examples/net_pipeline.py``) call :func:`enable_compile_cache` first; the
tests do not.
"""

from __future__ import annotations

import collections
import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

#: Persistent-cache lookups since :func:`enable_compile_cache`:
#: ``hits`` and ``misses``.
CACHE_EVENTS: collections.Counter = collections.Counter()

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_listening = False


def _on_event(event: str, **_) -> None:
    name = _EVENTS.get(event)
    if name:
        CACHE_EVENTS[name] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile; return its path."""
    global _listening
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program: a cold chip run is mostly compilation, and the
    # small kernels add up.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path
