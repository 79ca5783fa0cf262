"""Test harness: an 8-device virtual CPU mesh.

The reference has no way to test multi-node without a cluster (SURVEY.md
§4); this framework tests every sharding path on a fake mesh of 8 CPU
devices via --xla_force_host_platform_device_count, so the full SOAP
strategy space is exercised in CI with no TPU attached.
"""

import os
import tempfile

# Must be set before the XLA CPU client initializes.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Tests invoking soap_report (any config) must not overwrite the repo's
# committed calibration-priority hints (flexflow_tpu/simulator/
# report_keys.json) with their tiny test configs.  Per-session temp dir:
# concurrent suites (or stale files from another user) must not share
# one fixed /tmp path.
os.environ.setdefault(
    "FF_REPORT_KEYS_PATH",
    os.path.join(tempfile.mkdtemp(prefix="ff_test_report_keys_"),
                 "report_keys.json"))

import jax  # noqa: E402

# Tests run on the virtual CPU mesh whatever the machine has (the tier-1
# command also sets JAX_PLATFORMS=cpu; this covers a bare ``pytest``).
jax.config.update("jax_platforms", "cpu")

from flexflow_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

# Persistent compile cache (the one every entry point shares): the
# suite's wall time is dominated by XLA compiles of the fused SPMD train
# steps; a warm cache cuts re-runs by minutes.  Keyed by HLO+flags, so
# code changes re-compile as needed.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
# The helper keys the cache by metadata too, for the sake of profiles.
# Tests build the same tiny models from many call sites, and a key that
# holds the call site would compile each anew; the tests that read
# metadata back (test_step_scopes.py, benchmark/test_benchmark_tracing.py)
# turn it on for themselves.
jax.config.update("jax_compilation_cache_include_metadata_in_key", False)

# The cache's put() writes the entry straight to its final name
# (LRUCache.put -> Path.write_bytes).  A test process killed mid-write —
# suite timeout, OOM kill, ^C — leaves a TRUNCATED entry under the real
# key, and every later process that deserializes it dies inside jaxlib;
# one poisoned entry turns the whole suite red until someone deletes the
# cache dir by hand.  Make the write crash-atomic: stage under a
# pid-suffixed temp key, then os.replace onto the final name.
try:
    from jax._src import lru_cache as _lru

    _CACHE_SUF = getattr(_lru, "_CACHE_SUFFIX", "-cache")
    _ATIME_SUF = getattr(_lru, "_ATIME_SUFFIX", "-atime")
    _orig_put = _lru.LRUCache.put

    def _crash_atomic_put(self, key, val):
        tmp_key = f"{key}.tmp{os.getpid()}"
        _orig_put(self, tmp_key, val)
        for suf in (_CACHE_SUF, _ATIME_SUF):
            src, dst = self.path / f"{tmp_key}{suf}", self.path / f"{key}{suf}"
            try:
                if dst.exists():        # another process won the race
                    src.unlink()
                else:
                    os.replace(src, dst)
            except OSError:
                pass                    # best-effort: it's only a cache

    _lru.LRUCache.put = _crash_atomic_put
except Exception:                       # jax internals moved: skip hardening
    pass

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration test (deselect with -m 'not slow')")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs
