"""Where compiled programs are kept between processes.

One rule, applied by every entry point (chip_smoke.py, bench.py, the
example drivers, tests/conftest.py): where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here sets another directory;
where it is not, the cache is ``<checkout>/.jax_cache`` (git-ignored).
The path is part of every cache key, so it is fixed — never a temporary
directory, a pid or a time — and two processes of one checkout share it.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileStats:
    """Counts this process's XLA compilations from the moment it is
    made, through ``jax.monitoring``: how many programs were compiled or
    fetched (``compilations``), how many of those the persistent cache
    answered (``cache_hits``), how many it stored (``cache_writes``),
    and the seconds spent (``compile_seconds``, retrieval included).
    Two ``snapshot()``s bracket a window: a steady-state window's
    ``compilations`` difference is zero."""

    def __init__(self):
        import jax.monitoring

        self._n = {"compilations": 0, "cache_hits": 0, "cache_writes": 0,
                   "compile_seconds": 0.0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._n["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._n["cache_writes"] += 1

    def _on_duration(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self._n["compilations"] += 1
            self._n["compile_seconds"] += duration_secs

    def snapshot(self) -> dict:
        return dict(self._n)
