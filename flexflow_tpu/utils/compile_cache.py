"""Where compiled programs are kept between processes.

One rule, applied by every entry point (chip_smoke.py, benchmark/run.py,
the example drivers, tests/conftest.py): where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here sets another directory;
where it is not, the cache is ``<checkout>/.jax_cache`` (git-ignored).
The path is part of every cache key, so it is fixed — never a temporary
directory, a pid or a time — and two processes of one checkout share it.
"""

from __future__ import annotations

import os
import re

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    import jax

    # An executable carries the metadata it was compiled with: the
    # `ff.` scopes that runtime/profiling.step_scopes reads back.  JAX
    # leaves metadata out of the key by default, so a program that
    # differs from a cached one only in its scopes would be handed the
    # cached one, with the other's names (or none): keep it in the key.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # ... and metadata holds source files: name them from the checkout's
    # root, so that the key does not follow the checkout's path.
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(_CHECKOUT + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileStats:
    """Counts this process's XLA compilations from the moment it is
    made: how many programs were compiled or fetched (``compilations``),
    how many of those the persistent cache answered (``cache_hits``),
    how many it stored (``cache_writes``), and the seconds spent
    (``compile_seconds``, retrieval included).  They are differences of
    the process-wide totals that ``runtime/profiling``'s one
    ``jax.monitoring`` listener keeps; an instance registers nothing.
    Two ``snapshot()``s bracket a window: a steady-state window's
    ``compilations`` difference is zero."""

    def __init__(self):
        self._since = self._totals()

    @staticmethod
    def _totals() -> dict:
        from ..runtime.profiling import compile_totals  # imports jax

        return compile_totals()

    def snapshot(self) -> dict:
        return {k: v - self._since[k] for k, v in self._totals().items()}
