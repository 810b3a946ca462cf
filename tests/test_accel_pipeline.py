"""Pipelined sweep->accel handoff tests (round 6): the streamed path's
candidate tables must be bit-identical to the .dat round trip, the
--write-dats tee must write the identical bytes, kill/resume through
--accel-skip-existing must reproduce the uninterrupted tables, and the
shared prefetch core must move only WHEN work happens, never values or
order."""

import glob
import os

import numpy as np
import pytest

from pypulsar_tpu.io import filterbank
from pypulsar_tpu.ops import numpy_ref


def _pulsar_fil(tmp_path, name="psr.fil", C=32, T=16384, dt=5e-4,
                dm=40.0, period=0.1024, amp=10.0, seed=5):
    """A .fil with an injected dispersed pulse train (P=102.4 ms at
    DM 40) — strong enough that the accel search recovers it at the
    fundamental through every prep path."""
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
    fn = str(tmp_path / name)
    hdr = dict(nchans=C, tsamp=dt, fch1=float(freqs[0]),
               foff=float(freqs[1] - freqs[0]), tstart=55000.0, nbits=32,
               nifs=1, source_name="PSR")
    filterbank.write_filterbank(fn, hdr, data)
    return fn


SWEEP_ARGS = ["--lodm", "0", "--dmstep", "10", "--numdms", "8",
              "-s", "8", "--group-size", "4", "--threshold", "8"]
ACCEL_ARGS = ["-z", "20", "-n", "2", "-s", "3"]
HANDOFF_ARGS = ["--accel-search", "--accel-zmax", "20",
                "--accel-numharm", "2", "--accel-sigma", "3",
                "--accel-batch", "4"]
# every chain contract below holds WITHIN an engine (cross-engine tables
# differ at f32 rounding): gather is what `auto` picks on the tests' CPU
# backend, fourier what it picks on the chip
both_engines = pytest.mark.parametrize("engine", ["gather", "fourier"])


def _run_dat_roundtrip(fil, outbase, monkeypatch, engine, extra_accel=()):
    """Reference chain: sweep --write-dats (streamed writer) ->
    accelsearch --batch over the .dats."""
    from pypulsar_tpu.cli import accelsearch as cli_accel
    from pypulsar_tpu.cli import sweep as cli_sweep

    monkeypatch.setenv("PYPULSAR_TPU_DATS_RESIDENT_LIMIT", "0")
    assert cli_sweep.main([fil, "-o", outbase, *SWEEP_ARGS,
                           "--engine", engine, "--write-dats"]) == 0
    dats = sorted(glob.glob(f"{outbase}_DM*.dat"))
    assert len(dats) == 8
    assert cli_accel.main([*dats, "--batch", "4", *ACCEL_ARGS,
                           *extra_accel]) == 0
    return sorted(glob.glob(f"{outbase}_DM*_ACCEL_20.cand"))


@both_engines
@pytest.mark.parametrize("device_prep", [True, False])
def test_stream_handoff_bit_identical_to_dat_roundtrip(tmp_path,
                                                       monkeypatch,
                                                       device_prep, engine):
    """The acceptance contract of the round-6 tentpole: the streamed
    sweep->accel path produces candidate tables BIT-IDENTICAL to the
    .dat write + re-read chain (the same chunk kernel feeds both), for
    both prep paths, and recovers the injected pulsar."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep

    prep_flags = ([] if device_prep else ["--no-device-prep"])
    a_cands = _run_dat_roundtrip(fil, "a", monkeypatch, engine,
                                 extra_accel=prep_flags)
    assert a_cands

    handoff_prep = ([] if device_prep else ["--no-accel-device-prep"])
    assert cli_sweep.main([fil, "-o", "b", *SWEEP_ARGS, *HANDOFF_ARGS,
                           "--engine", engine, "--accel-only",
                           *handoff_prep]) == 0
    for fa in a_cands:
        fb = "b" + os.path.basename(fa)[1:]
        assert os.path.exists(fb), fb
        assert open(fa, "rb").read() == open(fb, "rb").read(), fa
        ta, tb = fa[:-5] + ".txtcand", fb[:-5] + ".txtcand"
        assert open(ta).read() == open(tb).read(), ta

    # the injected pulsar (f0 = 1/0.1024 Hz) is in the DM-40 table — a
    # delta-like pulse train puts its power across MANY harmonics, so
    # accept any harmonic k*f0 (k integer) among the top candidates
    from pypulsar_tpu.io.prestocand import read_rzwcands

    T = 16384 * 5e-4
    cands = read_rzwcands("b_DM40.00_ACCEL_20.cand")
    f0 = 1.0 / 0.1024

    def is_harmonic(c):
        k = (c.r / T) / f0
        return k > 0.5 and abs(k - round(k)) < 0.02

    assert any(is_harmonic(c) and c.sig > 10 for c in cands[:10]), \
        "injected pulsar not recovered"


@both_engines
def test_stream_handoff_write_dats_tee_identical(tmp_path, monkeypatch,
                                                 engine):
    """--accel-search --write-dats tees the IDENTICAL .dat bytes the
    streamed writer would have produced (the tee is the same chunk
    stream, not a second implementation)."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("PYPULSAR_TPU_DATS_RESIDENT_LIMIT", "0")
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep

    eng = ["--engine", engine]
    assert cli_sweep.main([fil, "-o", "w", *SWEEP_ARGS, *eng,
                           "--write-dats"]) == 0
    assert cli_sweep.main([fil, "-o", "t", *SWEEP_ARGS, *HANDOFF_ARGS,
                           *eng, "--accel-only", "--write-dats"]) == 0
    dats = sorted(glob.glob("w_DM*.dat"))
    assert len(dats) == 8
    for fw in dats:
        ft = "t" + os.path.basename(fw)[1:]
        assert open(fw, "rb").read() == open(ft, "rb").read(), fw
        iw, it = fw[:-4] + ".inf", ft[:-4] + ".inf"
        # .inf sidecars agree apart from the basename line
        lw = [l for l in open(iw) if "Data file name" not in l]
        lt = [l for l in open(it) if "Data file name" not in l]
        assert lw == lt


@both_engines
def test_stream_handoff_kill_resume_bit_identical(tmp_path, monkeypatch,
                                                  engine):
    """A run killed mid-search (BaseException after the first batch — the
    serial fallback must NOT swallow it) resumes with
    --accel-skip-existing: finished trials are skipped, the rest are
    searched, and every final table is bit-identical to an uninterrupted
    run's."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.fourier import accelsearch as accel_mod

    run_args = [*SWEEP_ARGS, *HANDOFF_ARGS, "--engine", engine,
                "--accel-only"]
    # uninterrupted reference
    assert cli_sweep.main([fil, "-o", "r", *run_args]) == 0
    ref = {os.path.basename(f)[1:]: open(f, "rb").read()
           for f in sorted(glob.glob("r_DM*_ACCEL_20.cand"))}
    assert len(ref) == 8

    real_batch = accel_mod.accel_search_batch
    calls = {"n": 0}

    def dying_batch(*a, **kw):
        calls["n"] += 1
        if calls["n"] > 1:
            raise KeyboardInterrupt("simulated SIGINT mid-run")
        return real_batch(*a, **kw)

    monkeypatch.setattr(accel_mod, "accel_search_batch", dying_batch)
    with pytest.raises(KeyboardInterrupt):
        cli_sweep.main([fil, "-o", "k", *run_args])
    monkeypatch.setattr(accel_mod, "accel_search_batch", real_batch)
    done = sorted(glob.glob("k_DM*_ACCEL_20.cand"))
    assert 0 < len(done) < 8  # the kill landed mid-run

    # resume: finished trials skipped, the rest searched
    assert cli_sweep.main([fil, "-o", "k", *run_args,
                           "--accel-skip-existing"]) == 0
    got = {os.path.basename(f)[1:]: open(f, "rb").read()
           for f in sorted(glob.glob("k_DM*_ACCEL_20.cand"))}
    assert got == ref


def test_stream_handoff_ram_budget_slices(tmp_path, monkeypatch):
    """A series buffer over PYPULSAR_TPU_ACCEL_STREAM_RAM streams in DM
    slices (extra raw-file passes) with unchanged candidate tables —
    including a budget whose raw slice size (6) is NOT a multiple of the
    stage-1 group size (4): slices must align to group boundaries or the
    regrouped trials dedisperse at different group-mean DMs (review
    repro: 4/8 tables diverged before the alignment fix)."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep

    assert cli_sweep.main([fil, "-o", "f", *SWEEP_ARGS, *HANDOFF_ARGS,
                           "--accel-only"]) == 0
    fulls = sorted(glob.glob("f_DM*_ACCEL_20.cand"))
    assert len(fulls) == 8
    # budgets for a raw slice of 4 (aligned) and 6 (MISALIGNED vs the
    # --group-size 4 in SWEEP_ARGS; must round down to 4)
    for tag, trials_per_slice in (("s", 2), ("m", 6)):
        monkeypatch.setenv("PYPULSAR_TPU_ACCEL_STREAM_RAM",
                           str(4 * 16384 * trials_per_slice))
        assert cli_sweep.main([fil, "-o", tag, *SWEEP_ARGS,
                               *HANDOFF_ARGS, "--accel-only"]) == 0
        for ff in fulls:
            fs = tag + os.path.basename(ff)[1:]
            assert open(ff, "rb").read() == open(fs, "rb").read(), \
                (tag, ff)


def test_cli_accelsearch_prefetch_matches_inline(tmp_path, monkeypatch):
    """--prefetch 0 (inline prep) and the default background prefetch
    produce identical candidate files — the pipeline moves WHEN prep
    happens, never what the search sees."""
    monkeypatch.chdir(tmp_path)
    from pypulsar_tpu.cli import accelsearch as cli_accel
    from tests.test_accelsearch import _write_fake_dat

    rng = np.random.RandomState(21)
    N, dt = 1 << 14, 5e-4
    bases = []
    for ii in range(5):
        ts = rng.standard_normal(N).astype(np.float32)
        ts += 0.25 * np.cos(2 * np.pi * (33.0 + 6.0 * ii)
                            * np.arange(N) * dt).astype(np.float32)
        bases.append(_write_fake_dat(str(tmp_path / f"pp{ii}"), ts, dt))
    dats = [b + ".dat" for b in bases]
    argv = dats + ["--batch", "2", "-z", "10", "-n", "2", "-s", "3"]
    assert cli_accel.main(argv + ["--prefetch", "0"]) == 0
    inline = {b: open(b + "_ACCEL_10.cand", "rb").read() for b in bases}
    for b in bases:
        os.remove(b + "_ACCEL_10.cand")
    assert cli_accel.main(argv) == 0  # default --prefetch 4
    for b in bases:
        assert open(b + "_ACCEL_10.cand", "rb").read() == inline[b], b


def test_cli_accelsearch_device_prep_default_on(tmp_path, monkeypatch):
    """--batch >= 2 engages device prep by DEFAULT (round 6 flip under
    the matched-candidate contract); --no-device-prep opts out; --batch 1
    stays on the serial host path."""
    monkeypatch.chdir(tmp_path)
    from pypulsar_tpu.cli import accelsearch as cli_accel
    from pypulsar_tpu.fourier import kernels as _k
    from tests.test_accelsearch import _write_fake_dat

    rng = np.random.RandomState(22)
    N, dt = 1 << 13, 5e-4
    bases = []
    for ii in range(2):
        ts = rng.standard_normal(N).astype(np.float32)
        bases.append(_write_fake_dat(str(tmp_path / f"dd{ii}"), ts, dt))
    dats = [b + ".dat" for b in bases]

    calls = []
    real_prep = _k.prep_spectra_batch

    def spy(series, *a, **kw):
        calls.append(np.asarray(series).shape[0])
        return real_prep(series, *a, **kw)

    monkeypatch.setattr(_k, "prep_spectra_batch", spy)
    assert cli_accel.main(dats + ["--batch", "2", "-z", "8", "-n", "1",
                                  "-s", "4"]) == 0
    assert calls == [2], calls  # default-on for the grouped path
    calls.clear()
    for b in bases:
        os.remove(b + "_ACCEL_8.cand")
    assert cli_accel.main(dats + ["--batch", "2", "-z", "8", "-n", "1",
                                  "-s", "4", "--no-device-prep"]) == 0
    assert calls == [], calls
    for b in bases:
        os.remove(b + "_ACCEL_8.cand")
    assert cli_accel.main(dats + ["-z", "8", "-n", "1", "-s", "4"]) == 0
    assert calls == [], calls  # serial path never device-preps


def test_cli_sweep_accel_flag_validation(tmp_path, monkeypatch):
    """--accel-search composes only with the flat single-file mode."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path, name="v.fil", T=4096)
    from pypulsar_tpu.cli import sweep as cli_sweep

    with pytest.raises(SystemExit):
        cli_sweep.main([fil, "--ddplan", "--hidm", "100",
                        "--accel-search"])
    with pytest.raises(SystemExit):
        cli_sweep.main([fil, "--numdms", "4", "--accel-only"])
    with pytest.raises(SystemExit):
        cli_sweep.main([fil, fil, "--numdms", "4", "--accel-search"])


def test_prefetch_values_order_and_errors():
    """parallel.prefetch: values and order are identical to inline
    iteration; transform runs on the worker; producer errors re-raise at
    the consumer; an abandoned consumer stops the worker."""
    import threading
    import time

    from pypulsar_tpu.parallel.prefetch import prefetch

    seen_threads = set()

    def xf(x):
        seen_threads.add(threading.current_thread().name)
        return x * 2

    out = list(prefetch(iter(range(20)), depth=3, name="t", transform=xf))
    assert out == [2 * i for i in range(20)]
    assert seen_threads == {"pypulsar-t"}

    def bad():
        yield 1
        raise OSError("producer died")

    it = prefetch(bad(), depth=2, name="t2")
    assert next(it) == 1
    with pytest.raises(OSError, match="producer died"):
        list(it)

    produced = []

    def many():
        for i in range(1000):
            produced.append(i)
            yield i

    it = prefetch(many(), depth=2, name="t3")
    next(it)
    it.close()
    deadline = time.time() + 5.0
    while time.time() < deadline and any(
            t.name == "pypulsar-t3" and t.is_alive()
            for t in threading.enumerate()):
        time.sleep(0.05)
    assert len(produced) < 20


def test_prefetch_pending_depth_gauge(tmp_path):
    """Under an active telemetry session the prefetch queue fill lands on
    the {name}.pending_depth gauge — the acceptance evidence that the
    pipeline actually ran ahead."""
    import time

    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.parallel.prefetch import prefetch

    with telemetry.session() as tlm:
        src = prefetch(iter(range(8)), depth=2, name="gtest")
        first = next(src)
        time.sleep(0.2)  # let the worker fill the queue behind us
        rest = list(src)
        assert [first] + rest == list(range(8))
        gauges = tlm.gauge_values()
    assert "gtest.pending_depth" in gauges
    assert gauges["gtest.pending_depth"]["max"] >= 1


def test_stream_handoff_prefetch_zero_inline_identical(tmp_path,
                                                       monkeypatch):
    """--accel-prefetch 0 runs prep inline (no worker thread) with
    identical candidate tables."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep

    assert cli_sweep.main([fil, "-o", "p", *SWEEP_ARGS, *HANDOFF_ARGS,
                           "--accel-only"]) == 0
    assert cli_sweep.main([fil, "-o", "q", *SWEEP_ARGS, *HANDOFF_ARGS,
                           "--accel-only", "--accel-prefetch", "0"]) == 0
    fulls = sorted(glob.glob("p_DM*_ACCEL_20.cand"))
    assert len(fulls) == 8
    for fp in fulls:
        fq = "q" + os.path.basename(fp)[1:]
        assert open(fp, "rb").read() == open(fq, "rb").read(), fp


def test_stream_handoff_prep_failure_falls_back_serial(tmp_path,
                                                       monkeypatch):
    """A device-prep dispatch failing ON THE PREFETCH WORKER degrades
    that batch to the per-spectrum serial host-prep fallback instead of
    aborting the run (the error travels as a value through the queue)."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.fourier import kernels as _k

    # reference: the host-prep handoff (what the fallback computes)
    assert cli_sweep.main([fil, "-o", "h", *SWEEP_ARGS, *HANDOFF_ARGS,
                           "--accel-only", "--no-accel-device-prep"]) == 0
    ref = {os.path.basename(f)[1:]: open(f, "rb").read()
           for f in sorted(glob.glob("h_DM*_ACCEL_20.cand"))}
    assert len(ref) == 8

    def boom(series, *a, **kw):
        raise RuntimeError("synthetic device-prep failure")

    monkeypatch.setattr(_k, "prep_spectra_batch", boom)
    assert cli_sweep.main([fil, "-o", "x", *SWEEP_ARGS, *HANDOFF_ARGS,
                           "--accel-only"]) == 0
    got = {os.path.basename(f)[1:]: open(f, "rb").read()
           for f in sorted(glob.glob("x_DM*_ACCEL_20.cand"))}
    assert got == ref


def test_stream_handoff_auto_group_size_parity(tmp_path, monkeypatch):
    """With --group-size left at its auto default (0), the handoff
    resolves the SAME group size as the .dat chain (stage-1 groups
    dedisperse at the group mean DM, so a different group is a different
    series) — tables stay bit-identical without the explicit flag."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import accelsearch as cli_accel
    from pypulsar_tpu.cli import sweep as cli_sweep

    args = ["--lodm", "0", "--dmstep", "10", "--numdms", "8", "-s", "8",
            "--threshold", "8"]
    monkeypatch.setenv("PYPULSAR_TPU_DATS_RESIDENT_LIMIT", "0")
    assert cli_sweep.main([fil, "-o", "g", *args, "--write-dats"]) == 0
    dats = sorted(glob.glob("g_DM*.dat"))
    assert cli_accel.main([*dats, "--batch", "4", *ACCEL_ARGS]) == 0
    assert cli_sweep.main([fil, "-o", "n", *args, *HANDOFF_ARGS,
                           "--accel-only"]) == 0
    fulls = sorted(glob.glob("g_DM*_ACCEL_20.cand"))
    assert len(fulls) == 8
    for fg in fulls:
        fn = "n" + os.path.basename(fg)[1:]
        assert open(fg, "rb").read() == open(fn, "rb").read(), fg


# ---------------------------------------------------------------------------
# multi-chip: DM-sharded sweep->accel handoff (round 11)
# ---------------------------------------------------------------------------

_MESH_PROBE: list = []  # cached (ok, detail) — the same capability-probe
#                         pattern as test_distributed's CPU-collectives gate


def require_virtual_mesh(k):
    """Skip cleanly where fewer than k devices exist or the backend
    cannot execute an in-process shard_map (environment capability, not
    a code bug); cached per session. tests/conftest.py forces the
    8-virtual-device CPU recipe, so these normally run."""
    import jax

    if len(jax.devices()) < k:
        pytest.skip(f"environment capability: {len(jax.devices())} "
                    f"devices < {k} (needs "
                    f"--xla_force_host_platform_device_count)")
    if not _MESH_PROBE:
        try:
            import jax.numpy as jnp
            from jax.sharding import PartitionSpec as P

            from pypulsar_tpu.parallel import make_mesh

            mesh = make_mesh([2], ("dm",), devices=jax.devices()[:2])
            fn = jax.shard_map(lambda x: x * 2, mesh=mesh,
                               in_specs=(P("dm"),), out_specs=P("dm"))
            np.testing.assert_array_equal(
                np.asarray(fn(jnp.arange(4.0))), np.arange(4.0) * 2)
            _MESH_PROBE.append((True, ""))
        except Exception as e:  # noqa: BLE001 - capability, not a bug
            _MESH_PROBE.append((False, f"{type(e).__name__}: {e}"))
    ok, detail = _MESH_PROBE[0]
    if not ok:
        pytest.skip("environment capability: in-process shard_map "
                    "collectives unavailable: " + detail)


@pytest.mark.parametrize("numdms,mesh_k", [(8, 4), (6, 4)])
def test_stream_handoff_sharded_byte_identical(tmp_path, monkeypatch,
                                               numdms, mesh_k):
    """The multi-chip acceptance contract: `sweep --mesh k
    --accel-search` (DM-sharded dedispersion + batch-sharded prep +
    shard_map'd search, all over the same k devices) writes
    .cand/.txtcand/.dat artifacts BYTE-identical to the 1-device run —
    including the 6-trials-on-4-chips case, where both the trial groups
    and the dispatch batches pad to device multiples."""
    require_virtual_mesh(mesh_k)
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep

    args = ["--lodm", "0", "--dmstep", "10", "--numdms", str(numdms),
            "-s", "8", "--group-size", "4", "--threshold", "8",
            *HANDOFF_ARGS, "--accel-only", "--write-dats"]
    assert cli_sweep.main([fil, "-o", "s1", *args]) == 0
    assert cli_sweep.main([fil, "-o", "sk", *args,
                           "--mesh", str(mesh_k)]) == 0
    compared = 0
    for fa in sorted(glob.glob("s1_DM*")):
        if fa.endswith(".inf"):
            continue  # .inf embeds the basename; parity-checked elsewhere
        fb = "sk" + os.path.basename(fa)[2:]
        assert os.path.exists(fb), fb
        assert open(fa, "rb").read() == open(fb, "rb").read(), fa
        compared += 1
    assert compared == 3 * numdms  # .dat + .cand + .txtcand per trial


def test_sharded_handoff_stamps_device_telemetry(tmp_path, monkeypatch):
    """The sharded pipeline stamps device ids on its spans/counters so
    tlmsum's per-device section can show per-chip utilization."""
    require_virtual_mesh(2)
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.obs.summarize import load_records, summarize

    assert cli_sweep.main([fil, "-o", "t", "--lodm", "0", "--dmstep",
                           "10", "--numdms", "8", "-s", "8",
                           "--group-size", "4", "--threshold", "8",
                           *HANDOFF_ARGS, "--accel-only", "--mesh", "2",
                           "--telemetry", "t.jsonl"]) == 0
    s = summarize(load_records("t.jsonl"))
    assert sorted(s.device_busy) and len(s.device_busy) == 2
    for _d, (busy, nsp) in s.device_busy.items():
        assert busy > 0 and nsp > 0
    assert s.counters.get("device0.dedisperse.chunks", 0) >= 1
    assert s.counters.get("device1.accel.stream_batches", 0) >= 1


# ---------------------------------------------------------------------------
# spectral fusion: the fused sweep->accel handoff (round 15)
# ---------------------------------------------------------------------------


SPECTRAL_ARGS = [*HANDOFF_ARGS, "--accel-only", "--spectral"]


def _cand_bytes(prefix):
    return {os.path.basename(f)[len(prefix):]: open(f, "rb").read()
            for f in sorted(glob.glob(f"{prefix}_DM*_ACCEL_20.*cand"))}


@both_engines
@pytest.mark.parametrize("T,extra", [
    (16384, []),                      # single chunk, power-of-two
    (15000, ["--chunk", "4096"]),     # non-pow2 out_len + partial tail
])
def test_spectral_handoff_bit_identical_to_streamed(tmp_path, monkeypatch,
                                                    T, extra, engine):
    """The round-15 parity gate: `--spectral` (stitched regime, the
    default) writes candidate tables BIT-identical to the streamed
    device-prep handoff — including a non-power-of-two series length
    and a trailing partial chunk, the geometries where the decimated
    shortcut is structurally impossible and the stitch must carry the
    exact overlap-save windows. Under either engine: the stitch consumes
    the SAME chunk kernel the streamed path pulls to host, so engine
    choice cannot open a gap."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path, T=T)
    from pypulsar_tpu.cli import sweep as cli_sweep

    eng = ["--engine", engine]
    assert cli_sweep.main([fil, "-o", "s", *SWEEP_ARGS, *HANDOFF_ARGS,
                           *eng, "--accel-only", *extra]) == 0
    assert cli_sweep.main([fil, "-o", "f", *SWEEP_ARGS, *SPECTRAL_ARGS,
                           *eng, *extra]) == 0
    ref, got = _cand_bytes("s"), _cand_bytes("f")
    assert len(ref) == 16  # .cand + .txtcand per trial
    assert got == ref


def test_spectral_slice_budget_and_stitch_counters(tmp_path, monkeypatch):
    """A PYPULSAR_TPU_SPECFUSE_HBM budget below the whole trial set
    fuses in group-aligned DM slices (one extra raw pass each) with
    unchanged candidate tables, and the specfuse telemetry counters
    record the stitched chunks and the series bytes kept on device."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.obs.summarize import load_records, summarize
    from pypulsar_tpu.parallel.specfuse import spectral_trial_bytes

    assert cli_sweep.main([fil, "-o", "w", *SWEEP_ARGS,
                           *SPECTRAL_ARGS]) == 0
    # budget for exactly 4 trials/slice (aligned to --group-size 4)
    monkeypatch.setenv("PYPULSAR_TPU_SPECFUSE_HBM",
                       str(4 * spectral_trial_bytes(16384)))
    assert cli_sweep.main([fil, "-o", "v", *SWEEP_ARGS, *SPECTRAL_ARGS,
                           "--telemetry", "v.jsonl"]) == 0
    assert _cand_bytes("v") == _cand_bytes("w")
    s = summarize(load_records("v.jsonl"))
    assert s.counters.get("specfuse.chunks_stitched", 0) >= 2  # 2 slices
    # 8 trials x 16384 samples x 8 B (D2H pull + H2D re-ship elided)
    assert s.counters.get("specfuse.bytes_on_device") == 8 * 8 * 16384


@both_engines
def test_spectral_kill_resume_at_stitch_boundary(tmp_path, monkeypatch,
                                                 engine):
    """A kill AT THE NEW STAGE BOUNDARY (the specfuse.after_stitch
    fault point, second DM slice) resumes with --accel-skip-existing:
    the first slice's finished .cands are skipped, the rest are fused
    and searched, and every final table is bit-identical to an
    uninterrupted run."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.resilience import faultinject
    from pypulsar_tpu.resilience.faultinject import InjectedKill

    run_args = [*SWEEP_ARGS, *SPECTRAL_ARGS, "--engine", engine]
    assert cli_sweep.main([fil, "-o", "r", *run_args]) == 0
    ref = _cand_bytes("r")
    assert len(ref) == 16

    from pypulsar_tpu.parallel.specfuse import spectral_trial_bytes

    monkeypatch.setenv("PYPULSAR_TPU_SPECFUSE_HBM",
                       str(4 * spectral_trial_bytes(16384)))
    try:
        with pytest.raises(InjectedKill):
            cli_sweep.main([fil, "-o", "k", *run_args,
                            "--fault-inject",
                            "kill:specfuse.after_stitch:2"])
    finally:
        faultinject.reset()
    done = _cand_bytes("k")
    assert 0 < len(done) < 16  # first slice landed, second did not
    assert cli_sweep.main([fil, "-o", "k", *run_args,
                           "--accel-skip-existing"]) == 0
    assert _cand_bytes("k") == ref


@pytest.mark.parametrize("numdms,mesh_k", [(8, 4), (6, 4)])
def test_spectral_handoff_sharded_byte_identical(tmp_path, monkeypatch,
                                                 numdms, mesh_k):
    """`--spectral --mesh k`: the stitch buffer, the fused prep planes
    and the search all stay P('dm')-sharded over the k devices, and the
    candidate tables are BYTE-identical to the 1-device streamed run —
    including the 6-trials-on-4-chips case where trial groups pad to
    the device multiple."""
    require_virtual_mesh(mesh_k)
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.obs.summarize import load_records, summarize

    args = ["--lodm", "0", "--dmstep", "10", "--numdms", str(numdms),
            "-s", "8", "--group-size", "4", "--threshold", "8"]
    assert cli_sweep.main([fil, "-o", "s1", *args, *HANDOFF_ARGS,
                           "--accel-only"]) == 0
    assert cli_sweep.main([fil, "-o", "sk", *args, *SPECTRAL_ARGS,
                           "--mesh", str(mesh_k),
                           "--telemetry", "sk.jsonl"]) == 0
    ref, got = _cand_bytes("s1"), _cand_bytes("sk")
    assert len(ref) == 2 * numdms
    assert got == ref
    # per-device stamps land on the specfuse counters (PR 6 contract)
    s = summarize(load_records("sk.jsonl"))
    assert s.counters.get("device0.specfuse.chunks_stitched", 0) >= 1
    assert s.counters.get(f"device{mesh_k - 1}.specfuse.chunks_stitched",
                          0) >= 1


def test_spectral_decimate_matches_circular_reference():
    """The opt-in decimated regime's kernel contract: the per-trial
    decimated spectrum is EXACTLY (to f32 rounding) the T-point rfft of
    the two-stage CIRCULARLY dedispersed, mean-subtracted series — the
    Fourier-domain-dedispersion convention, which differs from the
    zero-padded linear engines only in the final max-shift samples
    (why decimate is opt-in rather than the parity default)."""
    import jax.numpy as jnp

    from pypulsar_tpu.ops.fourier_dedisperse import (
        fourier_chunk_len,
        sweep_chunk_spectra,
    )
    from pypulsar_tpu.parallel.sweep import make_sweep_plan

    rng = np.random.RandomState(0)
    C, T, dt = 16, 4096, 5e-4
    freqs = 1500.0 - 4.0 * np.arange(C)
    data = rng.randn(C, T).astype(np.float32) * 2.0 + 30.0
    dms = np.array([0.0, 10.0, 20.0, 30.0])
    plan = make_sweep_plan(dms, freqs, dt, nsub=8, group_size=2,
                           widths=(1,))
    need = T + plan.min_overlap
    n_fft = fourier_chunk_len(need)
    block = jnp.pad(jnp.asarray(data), ((0, 0), (0, need - T)))
    re_f, im_f = sweep_chunk_spectra(
        block, jnp.asarray(plan.stage1_bins),
        jnp.asarray(plan.stage2_bins), plan.nsub, n_fft, n_fft // T,
        T // 2 + 1, T)

    d64 = data.astype(np.float64)
    d64 = d64 - d64.mean(axis=1, keepdims=True)
    per = C // plan.nsub
    for gi in range(plan.stage1_bins.shape[0]):
        sub = np.zeros((plan.nsub, T))
        for c in range(C):
            sub[c // per] += np.roll(d64[c],
                                     -int(plan.stage1_bins[gi, c]))
        for ti in range(plan.group_size):
            d = gi * plan.group_size + ti
            if d >= len(dms):
                break
            ts = np.zeros(T)
            for sb in range(plan.nsub):
                ts += np.roll(sub[sb],
                              -int(plan.stage2_bins[gi, ti, sb]))
            ref = np.fft.rfft(ts)
            got = (np.asarray(re_f[d]).astype(np.float64)
                   + 1j * np.asarray(im_f[d]))
            err = np.abs(ref - got)
            err[0] = 0.0  # DC conventions differ; deredden overwrites it
            rms = np.sqrt((np.abs(ref) ** 2).mean())
            assert err.max() / rms < 2e-5, (d, err.max() / rms)


def test_spectral_decimate_optin_elides_fft_pairs(tmp_path, monkeypatch):
    """PYPULSAR_TPU_SPECFUSE_MODE=decimate on an eligible geometry
    (single fourier chunk, power-of-two T): the telemetry counters
    prove ZERO per-trial transforms (one irfft+rfft pair elided per
    trial), and the injected pulsar is still recovered at its DM."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.io.prestocand import read_rzwcands
    from pypulsar_tpu.obs.summarize import load_records, summarize

    monkeypatch.setenv("PYPULSAR_TPU_SPECFUSE_MODE", "decimate")
    assert cli_sweep.main([fil, "-o", "d", *SWEEP_ARGS, *SPECTRAL_ARGS,
                           "--engine", "fourier",
                           "--telemetry", "d.jsonl"]) == 0
    s = summarize(load_records("d.jsonl"))
    assert s.counters.get("specfuse.fft_pairs_elided") == 8
    assert not s.counters.get("specfuse.chunks_stitched")
    T = 16384 * 5e-4
    f0 = 1.0 / 0.1024
    cands = read_rzwcands("d_DM40.00_ACCEL_20.cand")

    def is_harmonic(c):
        k = (c.r / T) / f0
        return k > 0.5 and abs(k - round(k)) < 0.02

    assert any(is_harmonic(c) and c.sig > 10 for c in cands[:10])


def test_spectral_survey_dag_argv_composition():
    """The spectral survey DAG: the sweep stage swaps the .dat tee for
    --spectral, and the fold stage streams the RAW file with the
    sweep's series geometry AND its rfifind mask — a maskless fold
    would reintroduce the RFI the search excluded (review catch)."""
    from pypulsar_tpu.survey.dag import (
        SurveyConfig,
        _fold_argv,
        _mask_file,
        _sweep_argv,
    )
    from pypulsar_tpu.survey.state import Observation

    obs = Observation("b0", "/d/b0.fil", "/o/b0")
    cfg = SurveyConfig(accel_spectral=True, mask=True)
    sw = _sweep_argv(obs, cfg)
    assert "--spectral" in sw and "--write-dats" not in sw
    fa = _fold_argv(obs, cfg)
    assert fa[0] == obs.infile and "--datbase" not in fa
    assert fa[fa.index("--mask") + 1] == _mask_file(obs)
    assert "--mask" not in _fold_argv(
        obs, SurveyConfig(accel_spectral=True, mask=False))
    no_fuse = _fold_argv(obs, SurveyConfig(accel_spectral=False))
    assert "--datbase" in no_fuse and "--mask" not in no_fuse


def test_foldbatch_mask_is_stream_only(tmp_path, monkeypatch):
    """foldbatch --mask is rejected loudly for .dat/--datbase sources
    (those series were masked when written; silently ignoring the flag
    would fold a different stream than requested)."""
    monkeypatch.chdir(tmp_path)
    from pypulsar_tpu.cli import foldbatch as cli_fold

    open("c.txt", "w").write("0.1 40.0\n")
    with pytest.raises(SystemExit):
        cli_fold.main(["--cands", "c.txt", "--datbase", "x",
                       "--mask", "m.mask"])
    with pytest.raises(SystemExit):
        cli_fold.main(["x.dat", "--cands", "c.txt", "--mask", "m.mask"])


def test_spectral_flag_validation(tmp_path, monkeypatch):
    """--spectral composes only with --accel-search and excludes the
    flags that contradict fusion (--write-dats, --no-accel-device-prep)."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path, name="sv.fil", T=4096)
    from pypulsar_tpu.cli import sweep as cli_sweep

    with pytest.raises(SystemExit):
        cli_sweep.main([fil, "--numdms", "4", "--spectral"])
    with pytest.raises(SystemExit):
        cli_sweep.main([fil, "--numdms", "4", *HANDOFF_ARGS,
                        "--spectral", "--write-dats"])
    with pytest.raises(SystemExit):
        cli_sweep.main([fil, "--numdms", "4", *HANDOFF_ARGS,
                        "--spectral", "--no-accel-device-prep"])


def test_lease_devices_resolver_contract():
    """parallel.mesh.lease_devices: inside a device_lease only the
    leased chips are addressable (and over-asking raises); outside, the
    local device list is the pool."""
    require_virtual_mesh(3)
    import jax

    from pypulsar_tpu.parallel import mesh as mesh_mod

    local = jax.local_devices()
    assert mesh_mod.lease_devices(2) == local[:2]
    with mesh_mod.device_lease(local[2:3]):
        assert mesh_mod.lease_devices() == [local[2]]
        assert mesh_mod.lease_devices(1) == [local[2]]
        with pytest.raises(ValueError, match="lease"):
            mesh_mod.lease_devices(2)
        # nesting shadows then restores
        with mesh_mod.device_lease(local[:2]):
            assert mesh_mod.lease_devices(2) == local[:2]
        assert mesh_mod.lease_devices() == [local[2]]
    assert mesh_mod.lease_devices() == local
