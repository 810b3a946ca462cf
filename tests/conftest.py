"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is unavailable in CI; per SURVEY.md §4 strategy 3 we
exercise the sharded sweep on N virtual CPU devices via
--xla_force_host_platform_device_count. Must run before the first jax import.
"""

import atexit
import os
import shutil
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# The compile plane keeps its persistent XLA cache (and its marker
# sidecars) where JAX_COMPILATION_CACHE_DIR says, and JAX reads that
# variable when it is imported. Point it at a throwaway directory here,
# before the first jax import, so the suite never reads — or pollutes —
# the checkout's .jax_cache or an operator's cache (a stale marker would
# flip compile.persistent_hit in exact-counter tests). Unconditional, like
# the tuning cache below; subprocess children inherit it.
_XLA_CACHE = tempfile.mkdtemp(prefix="pypulsar_tpu_test_xla_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _XLA_CACHE
atexit.register(shutil.rmtree, _XLA_CACHE, ignore_errors=True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _lockdep_strict(monkeypatch):
    """Round 19: the whole suite (and every subprocess it spawns —
    children inherit the env) runs under PYPULSAR_TPU_LOCKDEP=strict,
    so ANY lock-acquisition-order cycle the survey/multihost/prefetch
    paths produce raises LockOrderError instead of warning. An explicit
    operator setting wins (so `PYPULSAR_TPU_LOCKDEP=off make test`
    still works); lockdep-mode tests monkeypatch their own value."""
    from pypulsar_tpu.resilience import locks

    if "PYPULSAR_TPU_LOCKDEP" not in os.environ:
        monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "strict")
    locks.reset()  # per-test: re-resolve mode, isolate the order graph
    yield


@pytest.fixture(autouse=True)
def _hermetic_tuning(tmp_path_factory, monkeypatch):
    """Round 17: the CLIs consult the persisted tuning cache by default.
    Point every test at a throwaway cache file (never the developer's
    ~/.cache winners — a tuned chunk length would silently change the
    geometry under golden tests) and start from an empty tuned overlay,
    unless the test pins the knob itself."""
    from pypulsar_tpu.tune import knobs

    # unconditional: a developer's exported PYPULSAR_TPU_TUNE_CACHE must
    # not leak their real winners into golden tests (tests that need a
    # specific cache path monkeypatch it themselves, which overrides)
    monkeypatch.setenv(
        "PYPULSAR_TPU_TUNE_CACHE",
        str(tmp_path_factory.mktemp("tune") / "tune.json"))
    knobs.clear_tuned()
    yield
    knobs.clear_tuned()
