"""Auto-tuning subsystem (round 17): knob registry precedence, the
persisted geometry-keyed cache's durability contract, the bounded
deterministic searcher, and the science-invariance acceptance gate
(candidate/.pfd artifacts byte-identical across tuned configs of the
same engine — tuning may only move throughput knobs, never results)."""

import glob
import json
import os
import threading

import numpy as np
import pytest

from pypulsar_tpu import tune
from pypulsar_tpu.tune import cache as tcache
from pypulsar_tpu.tune import knobs
from pypulsar_tpu.tune.search import coordinate_search


@pytest.fixture
def cache(tmp_path, monkeypatch):
    monkeypatch.setenv("PYPULSAR_TPU_TUNE_CACHE",
                       str(tmp_path / "tune.json"))
    return tune.TuneCache()


# ---------------------------------------------------------------------------
# knob registry: the read-path precedence contract


def _distinct_values(k):
    """(env_string, tuned_value) both distinct from the declared
    default, typed for knob ``k``."""
    if k.ktype == "int":
        base = int(k.default or 0)
        return str(base + 3), base + 7
    if k.ktype == "float":
        base = float(k.default or 0.0)
        return str(base + 3.5), base + 7.5
    return "envv", "tunedv"


def test_env_beats_tuned_beats_default_for_every_knob(monkeypatch):
    """The acceptance bullet: env var > cache (tuned) > default, pinned
    for EVERY registered knob. Non-invariant (results-affecting) knobs
    additionally REFUSE tuned values — a cache file can never flip an
    engine or a mode."""
    for k in knobs.all_knobs():
        monkeypatch.delenv(k.env, raising=False)
        knobs.clear_tuned()
        assert knobs.env_value(k.env) == k.default, k.env

        envs, tuned = _distinct_values(k)
        applied = knobs.apply_tuned({k.env: tuned})
        if k.invariant:
            assert applied == {k.env: tuned}, k.env
            assert knobs.env_value(k.env) == tuned, k.env
        else:
            assert applied == {}, k.env
            assert knobs.env_value(k.env) == k.default, k.env

        monkeypatch.setenv(k.env, envs)
        got = knobs.env_value(k.env)
        expect = k.parse(envs) if k.ktype != "str" else envs
        assert got == expect, k.env  # env wins over tuned AND default
        knobs.clear_tuned()


def test_typo_tolerant_numeric_fallthrough(monkeypatch):
    """A garbage numeric env value falls through to tuned, then to the
    default — the fleet-wide 'a bad knob must never abort' contract."""
    monkeypatch.setenv("PYPULSAR_TPU_SWEEP_CHUNK", "not-a-number")
    assert knobs.env_int("PYPULSAR_TPU_SWEEP_CHUNK") == 1 << 18
    knobs.apply_tuned({"PYPULSAR_TPU_SWEEP_CHUNK": 65536})
    assert knobs.env_int("PYPULSAR_TPU_SWEEP_CHUNK") == 65536
    knobs.clear_tuned()


def test_trial_overlay_is_thread_local_and_scoped():
    knobs.apply_tuned({"PYPULSAR_TPU_ACCEL_BATCH": 16})
    seen = {}
    with knobs.trial_overrides({"PYPULSAR_TPU_ACCEL_BATCH": 4}):
        assert knobs.env_int("PYPULSAR_TPU_ACCEL_BATCH") == 4

        def other():
            seen["other"] = knobs.env_int("PYPULSAR_TPU_ACCEL_BATCH")

        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["other"] == 16  # the overlay never escapes its thread
    assert knobs.env_int("PYPULSAR_TPU_ACCEL_BATCH") == 16
    knobs.clear_tuned()


def test_unregistered_name_keeps_env_float_compat(monkeypatch):
    """health.env_float is now a re-export: unregistered names keep the
    historical (raw env, default argument) behavior."""
    from pypulsar_tpu.resilience.health import env_float

    monkeypatch.delenv("X_TUNE_COMPAT", raising=False)
    assert env_float("X_TUNE_COMPAT", 3.0) == 3.0
    monkeypatch.setenv("X_TUNE_COMPAT", "junk")
    assert env_float("X_TUNE_COMPAT", 3.0) == 3.0
    monkeypatch.setenv("X_TUNE_COMPAT", "1.5")
    assert env_float("X_TUNE_COMPAT", 3.0) == 1.5


def test_chunk_knob_resolves_pow2(monkeypatch):
    """PYPULSAR_TPU_SWEEP_CHUNK: registry default == the historical
    constant; odd values round UP to a power of two; a degenerate value
    floors at 2^12."""
    from pypulsar_tpu.parallel.sweep import (DEFAULT_CHUNK_FFT_LEN,
                                             chunk_fft_len)

    assert knobs.knob("PYPULSAR_TPU_SWEEP_CHUNK").default \
        == DEFAULT_CHUNK_FFT_LEN
    monkeypatch.delenv("PYPULSAR_TPU_SWEEP_CHUNK", raising=False)
    assert chunk_fft_len() == DEFAULT_CHUNK_FFT_LEN
    monkeypatch.setenv("PYPULSAR_TPU_SWEEP_CHUNK", "100000")
    assert chunk_fft_len() == 131072
    monkeypatch.setenv("PYPULSAR_TPU_SWEEP_CHUNK", "8")
    assert chunk_fft_len() == 1 << 12


# ---------------------------------------------------------------------------
# search-domain policy (the science-invariance contract's enforcement)


def test_fourier_engine_excludes_chunk_from_search(monkeypatch):
    """Measured (round 17): .dat bytes are chunk-length-invariant for
    gather but NOT for fourier (FFT rounding is chunk-length-
    dependent, the fact staged.py fingerprints). The searcher must
    therefore never move the chunk under fourier."""
    monkeypatch.delenv("PYPULSAR_TPU_SWEEP_CHUNK", raising=False)
    gather = {k.env for k in knobs.searchable_knobs("sweep", "gather")}
    fourier = {k.env for k in knobs.searchable_knobs("sweep", "fourier")}
    assert "PYPULSAR_TPU_SWEEP_CHUNK" in gather
    assert "PYPULSAR_TPU_SWEEP_CHUNK" not in fourier


def test_env_pinned_knob_is_never_searched(monkeypatch):
    monkeypatch.setenv("PYPULSAR_TPU_ACCEL_BATCH", "16")
    names = {k.env for k in knobs.searchable_knobs("accel")}
    assert "PYPULSAR_TPU_ACCEL_BATCH" not in names
    monkeypatch.delenv("PYPULSAR_TPU_ACCEL_BATCH")
    names = {k.env for k in knobs.searchable_knobs("accel")}
    assert "PYPULSAR_TPU_ACCEL_BATCH" in names


def test_results_affecting_knobs_have_no_domain():
    """Selection knobs (engine, specfuse mode, shift backend …) are
    declared non-invariant and must never carry a search domain."""
    for k in knobs.all_knobs():
        if not k.invariant:
            assert not k.domain, k.env


# ---------------------------------------------------------------------------
# bounded deterministic search


class _FakeClock:
    """Deterministic stand-in for the searcher's ``time`` module: the
    measure advances it by the table value, so trial 'walls' are exact
    regardless of machine load."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        return self.t


def _table_measure(table, calls, clock=None):
    """A pure 'wall time' read from a lookup table — what the searcher
    sees, minus the noise. With ``clock`` the duration is injected
    exactly; without it the measure just records the probe."""

    def measure():
        cfg = {k.env: knobs.env_value(k.env)
               for k in knobs.all_knobs("accel")}
        key = (cfg["PYPULSAR_TPU_ACCEL_BATCH"],
               cfg["PYPULSAR_TPU_ACCEL_HBM"])
        calls.append(key)
        if clock is not None:
            clock.t += table(key)

    return measure


def test_coordinate_search_is_bounded_and_deterministic(monkeypatch):
    for env in ("PYPULSAR_TPU_ACCEL_BATCH", "PYPULSAR_TPU_ACCEL_HBM"):
        monkeypatch.delenv(env, raising=False)
    knobs.clear_tuned()

    import pypulsar_tpu.tune.search as search_mod

    def table(key):
        batch, hbm = key
        return 0.02 * abs(batch - 8) / 8 + 0.04 + \
            (0.0 if hbm == 2e9 else 0.02)

    runs = []
    for _ in range(2):
        clock = _FakeClock()
        monkeypatch.setattr(search_mod, "time", clock)
        calls = []
        res = coordinate_search(
            "accel", _table_measure(table, calls, clock), budget=10,
            repeats=1)
        assert res.n_trials <= 10
        runs.append((res.best, res.n_trials, calls))
    assert runs[0] == runs[1]  # deterministic end to end
    best = runs[0][0]
    assert best["PYPULSAR_TPU_ACCEL_BATCH"] == 8
    assert best["PYPULSAR_TPU_ACCEL_HBM"] == 2e9
    # tuned_config stores only knobs moved OFF baseline
    clock = _FakeClock()
    monkeypatch.setattr(search_mod, "time", clock)
    res = coordinate_search("accel", _table_measure(table, [], clock),
                            budget=10, repeats=1)
    assert set(res.tuned_config()) == {"PYPULSAR_TPU_ACCEL_BATCH",
                                       "PYPULSAR_TPU_ACCEL_HBM"}


def test_search_early_cutoff_abandons_regressing_direction(monkeypatch):
    """A steep regression past ``cutoff x best`` must stop that
    direction without spending the rest of its domain values."""
    import pypulsar_tpu.tune.search as search_mod

    for env in ("PYPULSAR_TPU_ACCEL_BATCH", "PYPULSAR_TPU_ACCEL_HBM"):
        monkeypatch.delenv(env, raising=False)
    knobs.clear_tuned()

    def table(key):
        batch, _ = key
        return 0.002 if batch == 32 else 0.02  # everything else awful

    calls = []
    clock = _FakeClock()
    monkeypatch.setattr(search_mod, "time", clock)
    coordinate_search("accel", _table_measure(table, calls, clock),
                      budget=50, repeats=1, cutoff=1.35)
    batches = [b for b, _ in calls]
    # direction above 32: 64 regresses 10x -> cutoff; below: 16
    # regresses -> cutoff; 8 never probed
    assert 8 not in batches


# ---------------------------------------------------------------------------
# the persisted cache: durability contract


def test_cache_roundtrip_and_key_components(cache):
    key = tune.make_key("sweep", nchan=64, nsamp=60000, dtype="nbits32",
                        engine="gather")
    cache.store(key, {"PYPULSAR_TPU_SWEEP_CHUNK": 65536},
                {"n_trials": 5})
    ent = cache.lookup(key)
    assert ent["config"]["PYPULSAR_TPU_SWEEP_CHUNK"] == 65536
    # nsamp buckets to the next pow2: nearby lengths share the entry
    assert tune.make_key("sweep", nchan=64, nsamp=65536, dtype="nbits32",
                         engine="gather") == key
    # EVERY changed key component forces a re-search (lookup misses)
    for other in (
            tune.make_key("sweep", nchan=128, nsamp=60000,
                          dtype="nbits32", engine="gather"),
            tune.make_key("sweep", nchan=64, nsamp=90000,
                          dtype="nbits32", engine="gather"),
            tune.make_key("sweep", nchan=64, nsamp=60000,
                          dtype="nbits8", engine="gather"),
            tune.make_key("sweep", nchan=64, nsamp=60000,
                          dtype="nbits32", engine="fourier"),
            tune.make_key("accel", nchan=64, nsamp=60000,
                          dtype="nbits32", engine="gather"),
    ):
        assert other != key
        assert cache.lookup(other) is None


def test_cache_key_embeds_jax_and_schema_version(cache, monkeypatch):
    key = tune.make_key("sweep", nchan=64, nsamp=60000)
    cache.store(key, {"PYPULSAR_TPU_SWEEP_CHUNK": 65536})
    monkeypatch.setattr(tcache, "_jax_version", lambda: "9.9.99")
    assert tune.make_key("sweep", nchan=64, nsamp=60000) != key
    monkeypatch.undo()
    monkeypatch.setattr(tcache, "SCHEMA_VERSION", 2)
    assert tune.make_key("sweep", nchan=64, nsamp=60000) != key


@pytest.mark.parametrize("garbage", [
    "{torn", "[]", '{"schema": 99, "entries": {}}',
    '{"entries": "nope"}', ""])
def test_corrupt_cache_is_rebuilt_not_crashed(cache, garbage):
    key = tune.make_key("accel", nsamp=8192, zmax=20)
    cache.store(key, {"PYPULSAR_TPU_ACCEL_BATCH": 8})
    with open(cache.path, "w") as f:
        f.write(garbage)
    assert cache.lookup(key) is None  # miss, not crash
    cache.store(key, {"PYPULSAR_TPU_ACCEL_BATCH": 16})  # rebuilds
    assert cache.lookup(key)["config"]["PYPULSAR_TPU_ACCEL_BATCH"] == 16
    data = json.load(open(cache.path))
    assert data["schema"] == tcache.SCHEMA_VERSION


def test_concurrent_writers_do_not_clobber(cache):
    """N threads storing distinct keys: the file ends valid JSON with
    ALL entries present (read-merge-write under the lock + atomic
    replace), not last-writer-wins."""
    keys = [tune.make_key("accel", nsamp=1 << (10 + i), zmax=20)
            for i in range(8)]
    threads = [threading.Thread(
        target=cache.store, args=(k, {"PYPULSAR_TPU_ACCEL_BATCH": 8 + i}))
        for i, k in enumerate(keys)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    entries = cache.entries()
    assert set(keys) <= set(entries)
    for i, k in enumerate(keys):
        assert entries[k]["config"]["PYPULSAR_TPU_ACCEL_BATCH"] == 8 + i


def test_apply_cached_installs_hit_and_survives_broken_cache(
        cache, monkeypatch):
    knobs.clear_tuned()
    key = tune.make_key("accel", nsamp=16384, zmax=20)
    cache.store(key, {"PYPULSAR_TPU_ACCEL_BATCH": 8,
                      "PYPULSAR_TPU_SPECFUSE_MODE": "decimate"})
    applied = tune.apply_cached("accel", nsamp=16384, zmax=20)
    # the throughput knob lands; the results-affecting one is REFUSED
    assert applied == {"PYPULSAR_TPU_ACCEL_BATCH": 8}
    assert knobs.env_int("PYPULSAR_TPU_ACCEL_BATCH") == 8
    knobs.clear_tuned()
    # tuning off: no consult at all
    monkeypatch.setenv("PYPULSAR_TPU_TUNE", "off")
    assert tune.apply_cached("accel", nsamp=16384, zmax=20) == {}
    monkeypatch.delenv("PYPULSAR_TPU_TUNE")
    # unreadable cache directory: defaults, never a raise
    monkeypatch.setenv("PYPULSAR_TPU_TUNE_CACHE", "/dev/null/nope.json")
    assert tune.apply_cached("accel", nsamp=16384, zmax=20) == {}


def test_autotune_cache_hit_runs_zero_trials(cache, monkeypatch):
    """The bench's structural gate in miniature: a search populates the
    key, the second consult serves it with ZERO trials and bumps
    tune.cache_hit."""
    from pypulsar_tpu.obs import telemetry

    monkeypatch.setenv("PYPULSAR_TPU_TUNE", "search")
    knobs.clear_tuned()
    calls = []

    def table(key):
        return 0.001

    with telemetry.session() as s:
        tune.autotune("accel", nsamp=4096, zmax=20,
                      measure=_table_measure(table, calls), cache=cache,
                      budget=5)
        trials_after_search = s.counter_totals().get("tune.trials", 0)
        assert 0 < trials_after_search <= 5
        assert s.counter_totals().get("tune.cache_miss", 0) == 1
        knobs.clear_tuned()
        tune.autotune("accel", nsamp=4096, zmax=20,
                      measure=_table_measure(table, calls), cache=cache)
        assert s.counter_totals().get("tune.trials", 0) \
            == trials_after_search  # zero new trials
        assert s.counter_totals().get("tune.cache_hit", 0) == 1
    knobs.clear_tuned()


# ---------------------------------------------------------------------------
# science invariance: the acceptance gate


def _pulsar_fil(tmp_path, C=32, T=16384, dt=5e-4, dm=40.0,
                period=0.1024, amp=10.0, seed=5):
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.ops import numpy_ref

    rng = np.random.RandomState(seed)
    freqs = 1500.0 - 4.0 * np.arange(C)
    data = rng.randn(T, C).astype(np.float32) * 2.0 + 30.0
    bins = numpy_ref.bin_delays(dm, freqs, dt)
    for t0 in np.arange(0.01, T * dt, period):
        s = int(t0 / dt)
        for c in range(C):
            idx = s + bins[c]
            if idx < T:
                data[idx, c] += amp
    fn = str(tmp_path / "psr.fil")
    hdr = dict(nchans=C, tsamp=dt, fch1=float(freqs[0]),
               foff=float(freqs[1] - freqs[0]), tstart=55000.0, nbits=32,
               nifs=1, source_name="PSR")
    filterbank.write_filterbank(fn, hdr, data)
    return fn


def _run_chain(fil, outbase, tuned_config, fold=False):
    """sweep --accel-search --write-dats under ``tuned_config``
    (installed exactly as a cache hit would), then optionally foldbatch
    the DM-40 fundamental. Returns {relpath: bytes} of every candidate
    and .pfd artifact."""
    from pypulsar_tpu.cli import foldbatch as cli_fold
    from pypulsar_tpu.cli import sweep as cli_sweep

    knobs.clear_tuned()
    knobs.apply_tuned(tuned_config)
    try:
        assert cli_sweep.main(
            [fil, "-o", outbase, "--lodm", "0", "--dmstep", "10",
             "--numdms", "8", "-s", "8", "--group-size", "4",
             "--threshold", "8", "--engine", "gather", "--write-dats",
             "--accel-search", "--accel-zmax", "20", "--accel-numharm",
             "2", "--accel-sigma", "3"]) == 0
        if fold:
            candfile = outbase + "_cands.txt"
            with open(candfile, "w") as f:
                f.write("0.1024 40.0\n")
            assert cli_fold.main(
                ["--cands", candfile, "--datbase", outbase, "-o",
                 outbase, "-n", "32", "--npart", "8"]) == 0
    finally:
        knobs.clear_tuned()
    out = {}
    for pat in ("_DM*.cand", "_DM*.txtcand", ".cands", "*.pfd"):
        for fn in sorted(glob.glob(outbase + pat)):
            out[os.path.basename(fn)[len(os.path.basename(outbase)):]] \
                = open(fn, "rb").read()
    return out


def test_science_invariant_across_tuned_configs(tmp_path, monkeypatch):
    """THE acceptance gate: two different tuned configs drawn from the
    legal search domain (chunk + batch + budgets moved) produce
    BYTE-identical candidate tables and .pfd archives for the same
    engine — tuning moves throughput only, never results."""
    monkeypatch.chdir(tmp_path)
    for env in ("PYPULSAR_TPU_SWEEP_CHUNK", "PYPULSAR_TPU_ACCEL_BATCH",
                "PYPULSAR_TPU_ACCEL_HBM"):
        monkeypatch.delenv(env, raising=False)
    monkeypatch.setenv("PYPULSAR_TPU_DATS_RESIDENT_LIMIT", "0")
    fil = _pulsar_fil(tmp_path)
    cfg_a = {"PYPULSAR_TPU_SWEEP_CHUNK": 4096,
             "PYPULSAR_TPU_ACCEL_BATCH": 4,
             "PYPULSAR_TPU_ACCEL_HBM": 2e9}
    cfg_b = {"PYPULSAR_TPU_SWEEP_CHUNK": 8192,
             "PYPULSAR_TPU_ACCEL_BATCH": 8,
             "PYPULSAR_TPU_ACCEL_HBM": 8e9}
    # same BASENAME in two directories: the .pfd header embeds the .dat
    # basename, so equal names isolate the comparison to the science
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    arts_a = _run_chain(fil, str(tmp_path / "a" / "x"), cfg_a, fold=True)
    arts_b = _run_chain(fil, str(tmp_path / "b" / "x"), cfg_b, fold=True)
    assert set(arts_a) == set(arts_b) and arts_a
    assert any(k.endswith(".cand") for k in arts_a)
    assert any(k.endswith(".pfd") for k in arts_a)
    for name in sorted(arts_a):
        assert arts_a[name] == arts_b[name], \
            f"{name} differs across tuned configs"


def test_cli_sweep_consults_cache_at_run_geometry(tmp_path, monkeypatch,
                                                  capsys):
    """The entry-point contract: a cache entry at the file's actual
    geometry is applied by the sweep CLI automatically (no flags), and
    the applied chunk shows up in the effective payload."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("PYPULSAR_TPU_SWEEP_CHUNK", raising=False)
    cache_fn = str(tmp_path / "cache.json")
    monkeypatch.setenv("PYPULSAR_TPU_TUNE_CACHE", cache_fn)
    fil = _pulsar_fil(tmp_path, T=8192)
    c = tune.TuneCache()
    key = tune.make_key("sweep", nchan=32, nsamp=8192, dtype="nbits32",
                        engine="gather")
    c.store(key, {"PYPULSAR_TPU_SWEEP_CHUNK": 4096})
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.parallel import sweep as psweep

    seen = {}
    orig = psweep.default_chunk_payload

    def spy(plan, **kw):
        out = orig(plan, **kw)
        if kw.get("tuned", True):  # the series/handoff (tuned) path
            seen["payload"] = out + plan.min_overlap  # the resolved fft len
        return out

    monkeypatch.setattr(psweep, "default_chunk_payload", spy)
    monkeypatch.setenv("PYPULSAR_TPU_DATS_RESIDENT_LIMIT", "0")
    assert cli_sweep.main([fil, "-o", "t", "--lodm", "0", "--dmstep",
                           "10", "--numdms", "4", "-s", "8",
                           "--group-size", "4", "--threshold", "8",
                           "--engine", "gather", "--write-dats"]) == 0
    assert seen.get("payload") == 4096, seen
    knobs.clear_tuned()


def test_cli_sweep_online_search_mode_populates_cache(tmp_path,
                                                      monkeypatch):
    """PYPULSAR_TPU_TUNE=search: a stage's FIRST run at a new geometry
    pays the bounded trial budget and persists the winner; the second
    run at the same key is a pure cache hit with zero trials."""
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.obs import telemetry

    monkeypatch.chdir(tmp_path)
    cache_fn = str(tmp_path / "cache.json")
    monkeypatch.setenv("PYPULSAR_TPU_TUNE_CACHE", cache_fn)
    monkeypatch.setenv("PYPULSAR_TPU_TUNE", "search")
    monkeypatch.setenv("PYPULSAR_TPU_TUNE_TRIALS", "2")
    monkeypatch.setenv("PYPULSAR_TPU_DATS_RESIDENT_LIMIT", "0")
    fil = _pulsar_fil(tmp_path, T=4096)
    argv = [fil, "-o", "t", "--lodm", "0", "--dmstep", "10",
            "--numdms", "4", "-s", "8", "--group-size", "4",
            "--threshold", "8", "--engine", "gather", "--write-dats"]
    with telemetry.session() as s:
        assert cli_sweep.main(argv) == 0
        first = s.counter_totals()
        assert 0 < first.get("tune.trials", 0) <= 2
        entries = tune.TuneCache().entries()
        assert any("stage=sweep" in k for k in entries)
        knobs.clear_tuned()
        assert cli_sweep.main(argv) == 0
        second = s.counter_totals()
        assert second.get("tune.trials", 0) == first.get("tune.trials")
        assert second.get("tune.cache_hit", 0) \
            > first.get("tune.cache_hit", 0)
    knobs.clear_tuned()


def test_tune_cli_warm_then_sweep_consume_key_contract(tmp_path,
                                                       monkeypatch):
    """The warm-the-cache workflow: `tune --search --file obs.fil`
    must store keys cli/sweep's consult actually HITS (same nchan,
    nsamp bucket, dtype, engine derivation) — the round-17 drive
    caught a dtype mismatch here."""
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.cli import tune as cli_tune
    from pypulsar_tpu.obs import telemetry

    monkeypatch.chdir(tmp_path)
    cache_fn = str(tmp_path / "cache.json")
    monkeypatch.setenv("PYPULSAR_TPU_TUNE_CACHE", cache_fn)
    monkeypatch.setenv("PYPULSAR_TPU_DATS_RESIDENT_LIMIT", "0")
    fil = _pulsar_fil(tmp_path, T=4096)
    assert cli_tune.main(["--search", "--file", fil, "--stage", "sweep",
                          "--engine", "gather", "--trials", "2",
                          "--dm-count", "4", "--json"]) == 0
    knobs.clear_tuned()
    with telemetry.session() as s:
        assert cli_sweep.main(
            [fil, "-o", "t", "--lodm", "0", "--dmstep", "10",
             "--numdms", "4", "-s", "8", "--group-size", "4",
             "--threshold", "8", "--engine", "gather",
             "--write-dats"]) == 0
        assert s.counter_totals().get("tune.cache_hit", 0) >= 1, \
            "sweep consult missed the CLI-warmed entry (key drift)"
    knobs.clear_tuned()


def test_accelsearch_batch_auto_resolves_through_registry(monkeypatch,
                                                          tmp_path):
    """--batch auto takes the tuned registry default; a bad value exits
    2 at parse time; an explicit number stays untouched."""
    from pypulsar_tpu.cli import accelsearch as cli_accel

    p = cli_accel.build_parser()
    assert p.parse_args(["x.dat"]).batch == 1
    assert p.parse_args(["x.dat", "--batch", "7"]).batch == 7
    assert p.parse_args(["x.dat", "--batch", "auto"]).batch == "auto"
    with pytest.raises(SystemExit) as e:
        p.parse_args(["x.dat", "--batch", "thirty"])
    assert e.value.code == 2
    # 'auto' resolves through env > tuned > default in _apply_tuning
    args = p.parse_args([str(tmp_path / "missing.dat"), "--batch",
                         "auto"])
    knobs.apply_tuned({"PYPULSAR_TPU_ACCEL_BATCH": 16})
    try:
        cli_accel._apply_tuning(args)
        assert args.batch == 16
    finally:
        knobs.clear_tuned()


def test_accelpipe_default_batch_comes_from_registry():
    """sweep_accel_stream(batch=None) resolves the hand-pinned 32
    through the knob registry (satellite: tuned-default routing)."""
    import inspect

    from pypulsar_tpu.parallel.accelpipe import sweep_accel_stream

    sig = inspect.signature(sweep_accel_stream)
    assert sig.parameters["batch"].default is None
    assert knobs.knob("PYPULSAR_TPU_ACCEL_BATCH").default == 32
