"""Round-22 compilation plane: the bucket-size ladder, the plane_jit
AOT executable registry, warm-pool precompile hooks, bucket-crossing
checkpoint resume, and the cross-process persistent XLA cache."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pypulsar_tpu.compile import (
    bucket_floor, bucket_rows, bucket_size, buckets_enabled, plane_jit,
    register_warmer, warm_stage, warmable_stages,
)
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.parallel import make_sweep_plan

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SPAWN_PROBE: list = []  # cached (ok, detail), once per session


def _require_spawn():
    """Capability gate (same as test_multihost): spawn-less sandboxes
    skip the subprocess integration tests instead of failing red."""
    if not _SPAWN_PROBE:
        env = dict(os.environ)
        env["PYTHONPATH"] = (_REPO + os.pathsep
                             + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
        env["JAX_PLATFORMS"] = "cpu"
        try:
            proc = subprocess.run(
                [sys.executable, "-c", "import pypulsar_tpu; print('OK')"],
                env=env, capture_output=True, text=True, timeout=120)
            _SPAWN_PROBE.append(
                (proc.returncode == 0 and "OK" in proc.stdout,
                 proc.stderr.strip().splitlines()[-1][-200:]
                 if proc.stderr.strip() else ""))
        except (OSError, subprocess.TimeoutExpired) as e:
            _SPAWN_PROBE.append((False, f"{type(e).__name__}: {e}"))
    ok, detail = _SPAWN_PROBE[0]
    if not ok:
        pytest.skip("environment capability: cannot spawn python "
                    f"subprocesses ({detail})")


# ---------------------------------------------------------------------------
# the bucket ladder


def test_bucket_ladder_values():
    assert buckets_enabled()
    # ceil to {2^k} U {3*2^k}; floor is the same ladder rounded down
    for n, (floor, ceil) in {1: (1, 1), 2: (2, 2), 3: (3, 3), 4: (4, 4),
                             5: (4, 6), 6: (6, 6), 7: (6, 8), 9: (8, 12),
                             13: (12, 16), 17: (16, 24), 23: (16, 24),
                             100: (96, 128)}.items():
        assert bucket_size(n) == ceil, n
        assert bucket_floor(n) == floor, n
    # idempotent: every ladder value maps to itself
    for v in (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128):
        assert bucket_size(v) == v == bucket_floor(v)


def test_bucket_rows_respects_multiple():
    # ladder first, then up to the mesh multiple
    assert bucket_rows(5) == 6
    assert bucket_rows(5, multiple=4) == 8
    assert bucket_rows(9, multiple=8) == 16
    assert bucket_rows(0) == 0


def test_bucket_disable_knob(monkeypatch):
    monkeypatch.setenv("PYPULSAR_TPU_COMPILE_BUCKETS", "0")
    assert not buckets_enabled()
    # bucket_size stays the pure ladder function; the knob gates the
    # call sites (bucket_rows / bucket_floor)
    assert bucket_floor(5) == 5
    # disabled, bucket_rows degrades to the plain multiple round-up
    assert bucket_rows(5, multiple=4) == 8
    assert bucket_rows(5) == 5


# ---------------------------------------------------------------------------
# plane_jit AOT registry


def test_plane_jit_second_dispatch_is_registry_hit():
    f = plane_jit(lambda x: (x * 2.0 + 1.0).sum(), name="t_second")
    x = jnp.ones((8, 16), jnp.float32)
    with telemetry.session() as tlm:
        first = np.asarray(f(x))
        t1 = tlm.counter_totals()
    assert t1.get("compile.cache_miss", 0) == 1
    assert t1.get("compile.cache_hit", 0) == 0
    assert t1.get("compile.ms", 0) > 0
    with telemetry.session() as tlm:
        second = np.asarray(f(x))
        t2 = tlm.counter_totals()
    assert t2.get("compile.cache_miss", 0) == 0  # the warm-leg contract
    assert t2.get("compile.cache_hit", 0) == 1
    np.testing.assert_array_equal(first, second)
    assert f.cache_size() == 1


def test_plane_jit_warm_precompiles_without_dispatch():
    f = plane_jit(lambda x: jnp.fft.rfft(x).real.sum(axis=-1),
                  name="t_warm")
    spec = jax.ShapeDtypeStruct((4, 64), np.float32)
    with telemetry.session() as tlm:
        assert f.warm(spec) is True
        assert f.warm(spec) is False  # already resident
        t1 = tlm.counter_totals()
    assert t1.get("compile.cache_miss", 0) == 1
    # the real dispatch at the warmed geometry never compiles
    with telemetry.session() as tlm:
        f(jnp.ones((4, 64), jnp.float32))
        t2 = tlm.counter_totals()
    assert t2.get("compile.cache_miss", 0) == 0
    assert t2.get("compile.cache_hit", 0) == 1


def test_plane_jit_positional_and_kwarg_calls_share_one_entry():
    f = plane_jit(lambda x, n: x * n, static_argnames=("n",),
                  name="t_bind")
    x = jnp.ones((4,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(f(x, 3)),
                                  np.asarray(f(x, n=3)))
    assert f.cache_size() == 1  # sig.bind canonicalizes the call forms


def test_plane_jit_aot_knob_off_falls_back_to_plain_jit(monkeypatch):
    monkeypatch.setenv("PYPULSAR_TPU_COMPILE_AOT", "0")
    f = plane_jit(lambda x: x + 1.0, name="t_off")
    np.testing.assert_array_equal(
        np.asarray(f(jnp.zeros(3, jnp.float32))), np.ones(3, np.float32))
    assert f.cache_size() == 0


def test_plane_jit_traced_input_falls_back():
    inner = plane_jit(lambda x: x * 2.0, name="t_traced")

    @jax.jit
    def outer(x):
        return inner(x) + 1.0  # tracers are unkeyable -> plain jit

    with telemetry.session() as tlm:
        y = np.asarray(outer(jnp.ones(4, jnp.float32)))
        totals = tlm.counter_totals()
    np.testing.assert_array_equal(y, np.full(4, 3.0, np.float32))
    assert totals.get("compile.aot_fallback", 0) >= 1
    assert inner.cache_size() == 0


# ---------------------------------------------------------------------------
# warm-pool registry


def test_warm_stage_registry_and_error_accounting():
    # the production warmers self-register at module import
    import pypulsar_tpu.fold.engine  # noqa: F401
    import pypulsar_tpu.parallel.sweep  # noqa: F401

    assert {"fold", "sweep"} <= set(warmable_stages())
    assert warm_stage("no_such_stage", n_samples=1) == 0

    from pypulsar_tpu.compile import plane

    def _boom(**_geometry):
        raise RuntimeError("boom")

    register_warmer("_test_boom", _boom)
    try:
        with telemetry.session() as tlm:
            assert warm_stage("_test_boom") == 0  # never raises
            assert tlm.counter_totals().get("compile.warm_error", 0) == 1
    finally:
        with plane._warmers_lock:
            plane._warmers.pop("_test_boom", None)


def test_fold_warmer_covers_the_real_dispatch():
    from pypulsar_tpu.fold.engine import fold_parts_batch

    T, nbins, npart, batch = 4096, 16, 4, 5
    with telemetry.session() as tlm:
        n = warm_stage("fold", n_samples=T, downsamp=1, fold_nbins=nbins,
                       fold_npart=npart, fold_batch=batch)
        warmed = tlm.counter_totals().get("compile.cache_miss", 0)
    assert n >= 0 and warmed == n
    # real dispatch at the warmed geometry: bucket_rows(batch) rows
    series = np.random.RandomState(0).randn(T).astype(np.float32)
    K = bucket_rows(batch)
    bins = np.random.RandomState(1).randint(0, nbins, (K, T)).astype(np.int32)
    with telemetry.session() as tlm:
        fold_parts_batch(jnp.asarray(series), jnp.asarray(bins),
                         nbins, npart)
        totals = tlm.counter_totals()
    assert totals.get("compile.cache_miss", 0) == 0
    assert totals.get("compile.cache_hit", 0) >= 1


# ---------------------------------------------------------------------------
# end-to-end: sweeps and checkpoints


def _toy_obs(C=16, T=9000, seed=3):
    rng = np.random.RandomState(seed)
    freqs = (1500.0 - 4.0 * np.arange(C)).astype(np.float64)
    data = rng.randn(C, T).astype(np.float32)
    return freqs, data


def _block_gen(data, plan, payload):
    ov = plan.min_overlap
    T = data.shape[1]
    pos = 0
    while pos < T:
        n = min(payload + ov, T - pos)
        yield pos, data[:, pos:pos + n]
        pos += payload


def test_sweep_second_run_has_zero_compile_miss():
    """The headline contract: a second run at an already-seen geometry
    never compiles on the critical path."""
    from pypulsar_tpu.parallel.sweep import sweep_stream

    freqs, data = _toy_obs()
    dms = np.linspace(0.0, 40.0, 12)
    plan = make_sweep_plan(dms, freqs, 1e-3, nsub=8, group_size=4)
    baseline = data.mean(axis=1, keepdims=True).astype(np.float32)
    payload = 2048

    with telemetry.session():
        r1 = sweep_stream(plan, _block_gen(data, plan, payload), payload,
                          chan_major=True, baseline=baseline)
    with telemetry.session() as tlm:
        r2 = sweep_stream(plan, _block_gen(data, plan, payload), payload,
                          chan_major=True, baseline=baseline)
        totals = tlm.counter_totals()
    assert totals.get("compile.cache_miss", 0) == 0
    assert totals.get("compile.cache_hit", 0) >= 1
    np.testing.assert_array_equal(r1.snr, r2.snr)
    np.testing.assert_array_equal(r1.peak_sample, r2.peak_sample)


def test_checkpoint_resume_across_bucket_shapes(tmp_path):
    """A checkpoint written under one padded group count resumes under
    another byte-identically: the fingerprint hashes real trials only,
    and padded trials replicate the last real DM, so the bucket ladder
    is an execution detail a resume may legally change."""
    from pypulsar_tpu.parallel.sweep import (
        SweepCheckpoint, padded_group_count, sweep_stream,
    )

    freqs, data = _toy_obs()
    dms = np.linspace(0.0, 40.0, 20)  # 5 groups of 4
    baseline = data.mean(axis=1, keepdims=True).astype(np.float32)
    payload = 2048
    kw = dict(nsub=8, group_size=4)
    # what the bucketing callers would pick (5 -> ladder 6) vs natural
    assert padded_group_count(5, 1) == 6
    plan_bkt = make_sweep_plan(dms, freqs, 1e-3, pad_groups_to=6, **kw)
    plan_nat = make_sweep_plan(dms, freqs, 1e-3, **kw)
    assert plan_bkt.n_trials != plan_nat.n_trials
    assert plan_bkt.n_real_trials == plan_nat.n_real_trials == 20

    ref = sweep_stream(plan_nat, _block_gen(data, plan_nat, payload),
                       payload, chan_major=True, baseline=baseline)

    class Killed(Exception):
        pass

    def killing_blocks(plan, n_before_kill):
        for i, (pos, blk) in enumerate(_block_gen(data, plan, payload)):
            if i >= n_before_kill:
                raise Killed()
            yield pos, blk

    ck = str(tmp_path / "bucket.ckpt.npz")
    with pytest.raises(Killed):
        sweep_stream(plan_bkt, killing_blocks(plan_bkt, 4), payload,
                     chan_major=True, baseline=baseline,
                     checkpoint=SweepCheckpoint(ck, every=1),
                     max_pending=1)
    assert os.path.exists(ck)

    res = sweep_stream(plan_nat, _block_gen(data, plan_nat, payload),
                       payload, chan_major=True, baseline=baseline,
                       checkpoint=SweepCheckpoint(ck, every=1))
    np.testing.assert_array_equal(res.snr, ref.snr)
    np.testing.assert_array_equal(res.peak_sample, ref.peak_sample)
    np.testing.assert_array_equal(res.mean, ref.mean)
    np.testing.assert_array_equal(res.std, ref.std)


# ---------------------------------------------------------------------------
# cross-process persistent cache

_CHILD = """
import json
import jax.numpy as jnp
from pypulsar_tpu.compile import plane_jit
from pypulsar_tpu.obs import telemetry

@plane_jit
def f(x):
    return (x * 2.0 + 1.0).sum()

with telemetry.session() as tlm:
    f(jnp.ones((16, 8), jnp.float32))
    print("TOTALS " + json.dumps(tlm.counter_totals()))
"""


def test_persistent_cache_shared_across_processes(tmp_path):
    """Two processes given one JAX_COMPILATION_CACHE_DIR: the second
    one's compile is a cross-host persistent hit, and both the XLA
    cache entries and the plane's markers live under that directory
    and nowhere else."""
    _require_spawn()
    cache = tmp_path / "xla"
    env = dict(os.environ)
    env["PYTHONPATH"] = (_REPO + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    env.pop("PYPULSAR_TPU_COMPILE_CACHE", None)

    def run():
        proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("TOTALS ")][-1]
        return json.loads(line[len("TOTALS "):])

    t1 = run()
    assert t1.get("compile.cache_miss", 0) == 1
    assert t1.get("compile.persistent_hit", 0) == 0
    markers = sorted(os.listdir(cache / "plane"))
    assert markers and all(m.endswith(".json") for m in markers)
    assert [n for n in os.listdir(cache) if n != "plane"], \
        "no XLA cache entry under JAX_COMPILATION_CACHE_DIR"
    t2 = run()
    # fresh process: the in-process registry is cold (one miss), but the
    # executable comes off the shared persistent cache
    assert t2.get("compile.cache_miss", 0) == 1
    assert t2.get("compile.persistent_hit", 0) >= 1


_PLACEMENT_CHILD = """
import json
import jax
from pypulsar_tpu.compile import plane

seen = []
_update = jax.config.update
jax.config.update = lambda k, v: (seen.append(k), _update(k, v))[1]
try:
    got = plane.configure_persistent_cache()
    err = None
except Exception as e:
    got, err = None, f"{type(e).__name__}: {e}"
print("PLACED " + json.dumps({
    "dir": got, "err": err, "default": plane.DEFAULT_CACHE_DIR,
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "set_dir_in_code": "jax_compilation_cache_dir" in seen}))
"""


def _placement(env_overrides):
    _require_spawn()
    env = dict(os.environ)
    env["PYTHONPATH"] = (_REPO + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    env["JAX_PLATFORMS"] = "cpu"
    for k in ("JAX_COMPILATION_CACHE_DIR", "PYPULSAR_TPU_COMPILE_CACHE"):
        env.pop(k, None)
    env.update(env_overrides)
    proc = subprocess.run([sys.executable, "-c", _PLACEMENT_CHILD],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("PLACED ")][-1]
    return json.loads(line[len("PLACED "):])


def test_cache_dir_from_environment_is_never_set_in_code(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the plane follows the directory
    JAX read from it and calls jax.config.update for it nowhere."""
    got = _placement({"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "c")})
    assert got["err"] is None
    assert got["dir"] == got["jax_dir"] == str(tmp_path / "c")
    assert not got["set_dir_in_code"]


def test_cache_dir_default_is_one_fixed_path_in_the_checkout():
    """Variable unset: one fixed path under the checkout root — the
    path is part of XLA's cache key, so no temp name, pid or time."""
    got = _placement({})
    assert got["err"] is None
    assert got["default"] == os.path.join(_REPO, ".jax_cache")
    assert got["dir"] == got["jax_dir"] == got["default"]
    assert got["set_dir_in_code"]


@pytest.mark.parametrize("value,want_err", [
    ("0", None), ("off", None), ("/some/old/cache/path", "ValueError")])
def test_cache_knob_keeps_only_its_off_switch(tmp_path, value, want_err):
    """The knob no longer takes a directory: off values disable the
    set-up, anything else that is not 'on' is an error, not a silent
    'no cache'."""
    got = _placement({"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "c"),
                      "PYPULSAR_TPU_COMPILE_CACHE": value})
    assert got["dir"] is None and not got["set_dir_in_code"]
    if want_err is None:
        assert got["err"] is None
    else:
        assert got["err"].startswith(want_err)


def test_aot_execution_error_propagates_argument_mismatch_retraces():
    """A compiled executable that rejects its arguments (TypeError /
    ValueError, raised before it runs) goes to plain jit, counted; an
    exception while it EXECUTES propagates instead of being retried."""

    @plane_jit
    def f(x):
        return x + 1.0

    x = jnp.ones((4,), jnp.float32)
    with telemetry.session() as tlm:
        f(x)
        (key,) = list(f._compiled)

        def rejects(*a):
            raise TypeError("argument mismatch")

        f._compiled[key] = rejects
        np.testing.assert_array_equal(np.asarray(f(x)), 2.0)
        assert tlm.counter_totals().get("compile.aot_fallback", 0) == 1

        def device_error(*a):
            raise RuntimeError("INTERNAL: device halted")

        f._compiled[key] = device_error
        with pytest.raises(RuntimeError, match="device halted"):
            f(x)
        assert tlm.counter_totals().get("compile.aot_fallback", 0) == 1


def test_plane_jit_names_the_module_and_scopes_its_operations():
    """What the profiler prints follows the wrapper's name: the module is
    jit_<name>, every operation sits under "<stage>.<name>", and the
    signature static_argnames bind against is still the original's."""
    from pypulsar_tpu.compile import PlaneJit

    def run(x, n):
        return (x * 2.0).sum() * n

    pj = PlaneJit(run, stage="accel", name="accel_stage",
                  static_argnames=("n",))
    x = jnp.ones(4, jnp.float32)
    lowered = pj._jit.lower(x, n=3)
    text = lowered.as_text(debug_info=True)
    assert "module @jit_accel_stage" in text
    assert "jit(accel_stage)/accel.accel_stage/mul" in text
    assert "HloModule jit_accel_stage" in lowered.compile().as_text()
    assert float(pj(x, 3)) == float(pj(x, n=3)) == 24.0
    assert pj.cache_size() == 1  # both call forms, one registry entry
    # without name= the function's own name is kept, with its stage
    anon = PlaneJit(run, stage="sweep", static_argnames=("n",))
    assert "jit(run)/sweep.run/" in anon._jit.lower(x, n=1).as_text(
        debug_info=True)


def test_jit_compiles_covers_the_planes_misses():
    """jit.compiles (JAX's own report) counts at least what the plane's
    registry counts as misses, and none on a registry hit."""
    f = plane_jit(lambda x: (x * 7.0 + 2.0).sum(), name="t_jit_vs_plane")
    x = jnp.ones((4, 4), jnp.float32)
    with telemetry.session() as tlm:
        f(x)
        cold = tlm.counter_totals()
    with telemetry.session() as tlm:
        f(x)
        warm = tlm.counter_totals()
    assert cold["jit.compiles"] >= cold["compile.cache_miss"] == 1
    assert warm.get("jit.compiles", 0) == 0 == warm.get("compile.cache_miss", 0)


_PERSISTENT_PROBE = """
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from pypulsar_tpu.obs import telemetry
x = jnp.ones(64)
with telemetry.session() as t:
    jax.jit(lambda v: (v * 3.0 - 1.0).sum())(x).block_until_ready()
    c = t.counter_totals()
print("COUNTS", int(c.get("jit.compiles", 0)),
      int(c.get("jit.persistent_hits", 0)))
"""


def test_jit_compiles_includes_reads_from_the_persistent_cache(tmp_path):
    """What jit.compiles counts on this JAX: programs that missed the
    in-memory caches, built OR read from disk; jit.persistent_hits says
    how many were reads (PERF.md states it so)."""
    _require_spawn()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env["PYTHONPATH"] = (_REPO + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    seen = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", _PERSISTENT_PROBE],
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-1500:]
        line = next(ln for ln in proc.stdout.splitlines()
                    if ln.startswith("COUNTS"))
        seen.append(tuple(int(v) for v in line.split()[1:]))
    assert seen[0] == (1, 0)  # built
    assert seen[1] == (1, 1)  # read from disk, still counted as a compile
