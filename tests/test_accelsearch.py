"""Acceleration-search engine tests: z-response physics, significance
calibration, injection recovery (tone, drifting tone, pulse train, binary
orbit -> (P, Pdot)), and the CLI end-to-end loop into plot_accelcands.

Ground truth is direct synthesis (DFT of chirps / folded orbits), not
PRESTO: the reference repo contains no search engine to compare against
(it consumes PRESTO accelsearch output, bin/plot_accelcands.py:50-71)."""

import os

import numpy as np
import pytest

from pypulsar_tpu.fourier.accelsearch import (
    AccelSearchConfig,
    accel_search,
    candidate_sigma,
    equivalent_gaussian_sigma,
    power_threshold,
)
from pypulsar_tpu.fourier.zresponse import template_bank, z_halfwidth, z_response


# ---------------------------------------------------------------------------
# z-response physics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("z", [0.0, 3.0, 17.0, 60.0, -25.0])
def test_z_response_matches_direct_dft(z):
    """The Fresnel-integral response reproduces the DFT of a chirp."""
    N = 1 << 14
    r0 = 3000.25
    t = np.arange(N) / N
    sig = np.exp(2j * np.pi * (r0 * t + z * t * t / 2))
    X = np.fft.fft(sig)
    offs = np.arange(-80, 80, dtype=float)
    bins = (np.round(r0) + offs).astype(int)
    pred = N * z_response(z, bins - r0)
    err = np.abs(pred - X[bins]).max() / np.abs(X[bins]).max()
    assert err < 2e-3


def test_template_bank_unit_energy_and_matched_peak():
    """Templates are unit-energy; correlating a chirp spectrum with the
    matched template peaks at the mid-drift frequency and recovers >80%
    of the total signal power."""
    N = 1 << 16
    z = 60.0
    r0 = 20000.3
    t = np.arange(N) / N
    sig = np.exp(2j * np.pi * (r0 * t + z * t * t / 2))
    X = np.fft.fft(sig) / np.sqrt(N)  # total signal power N -> sum|X|^2 = N
    tb, hw = template_bank(np.array([z]), numbetween=2)
    np.testing.assert_allclose(
        np.sum(np.abs(tb) ** 2, axis=1), 1.0, rtol=1e-9)
    row = tb[0]
    rhats = np.arange(19990, 20070)
    C = np.array([np.sum(X[rh - hw:rh + hw] * row) for rh in rhats])
    P = np.abs(C) ** 2
    r_mid = r0 + z / 2
    assert abs(rhats[P.argmax()] - r_mid) <= 1.0
    # matched filter recovers most of the power (integer-grid sampling of
    # a fractional-bin signal costs ~25%; interbinning recovers it in the
    # real search)
    assert P.max() > 0.7 * N


def test_z_halfwidth_covers_support():
    for z in (0.0, 50.0, 200.0, -120.0):
        hw = z_halfwidth(z)
        offs = np.arange(-hw, hw, dtype=float) + z / 2
        resp = z_response(z, offs)
        assert np.sum(np.abs(resp) ** 2) > 0.95 * max(abs(z) / 2, 1.0) * (
            2.0 / max(abs(z), 2.0))  # most of the energy is inside


# ---------------------------------------------------------------------------
# significance calibration
# ---------------------------------------------------------------------------


def test_equivalent_gaussian_sigma_roundtrip():
    from scipy.special import log_ndtr

    for sigma in (1.0, 3.0, 8.0, 20.0, 38.0):
        logp = float(log_ndtr(-sigma))
        assert abs(equivalent_gaussian_sigma(logp) - sigma) < 1e-6


def test_power_threshold_inverts_candidate_sigma():
    for numsum in (1, 2, 4, 8):
        for sigma in (2.0, 5.0):
            p = power_threshold(sigma, numsum, numindep=1e5)
            back = candidate_sigma(p, numsum, numindep=1e5)
            assert abs(back - sigma) < 1e-3


def test_noise_false_alarm_rate():
    """Pure noise yields ~no candidates above 4 sigma."""
    rng = np.random.RandomState(42)
    N = 1 << 15
    ts = rng.standard_normal(N)
    fft = np.fft.rfft(ts) / np.sqrt(N)
    cands = accel_search(fft, 30.0, AccelSearchConfig(
        zmax=20.0, dz=2.0, numharm=2, sigma_min=4.0, seg_width=1 << 12))
    assert len(cands) <= 1  # P(any 4-sigma FA) is a few percent


def test_batched_search_matches_serial():
    """accel_search_batch == [accel_search(f) for f] candidate-for-
    candidate (VERDICT r3 item 2): the template banks are DM-independent,
    so batching B spectra into one dispatch a chunk must change no
    result."""
    from pypulsar_tpu.fourier.accelsearch import accel_search_batch

    rng = np.random.RandomState(7)
    N = 1 << 14
    T = N * 2 * 128e-6
    cfg = AccelSearchConfig(zmax=20.0, dz=2.0, numharm=4, sigma_min=2.5,
                            seg_width=1 << 12)
    ffts = []
    for b in range(3):
        ts = rng.standard_normal(2 * N).astype(np.float32)
        ts += 0.15 * np.sin(2 * np.pi * (40.0 + 13.0 * b)
                            * np.arange(2 * N) * 128e-6)
        ffts.append((np.fft.rfft(ts) / np.sqrt(2 * N))
                    .astype(np.complex64)[:N])
    serial = [accel_search(f, T, cfg) for f in ffts]
    batch = accel_search_batch(np.stack(ffts), T, cfg)
    assert [len(s) for s in serial] == [len(b) for b in batch]
    for s, bt in zip(serial, batch):
        assert s, "injection not detected"
        for cs, cb in zip(s, bt):
            assert abs(cs.r - cb.r) < 1e-6
            assert abs(cs.z - cb.z) < 1e-6
            assert abs(cs.power - cb.power) < 1e-3
            assert cs.numharm == cb.numharm


def test_batched_search_chunked_matches_unchunked():
    """A tiny HBM budget forces accel_search_batch to process the batch
    in per-stage chunks (the budget is enforced analytically up front,
    before any oversized allocation is tried);
    chunking must change no candidate."""
    from pypulsar_tpu.fourier.accelsearch import accel_search_batch

    rng = np.random.RandomState(11)
    N = 1 << 13
    T = N * 2 * 128e-6
    cfg = AccelSearchConfig(zmax=20.0, dz=2.0, numharm=2, sigma_min=2.5,
                            seg_width=1 << 11)
    ffts = []
    for b in range(3):
        ts = rng.standard_normal(2 * N).astype(np.float32)
        ts += 0.2 * np.sin(2 * np.pi * (60.0 + 11.0 * b)
                           * np.arange(2 * N) * 128e-6)
        ffts.append((np.fft.rfft(ts) / np.sqrt(2 * N))
                    .astype(np.complex64)[:N])
    ffts = np.stack(ffts)
    whole = accel_search_batch(ffts, T, cfg)
    chunked = accel_search_batch(ffts, T, cfg, hbm_budget_bytes=1)  # chunk=1
    assert [len(w) for w in whole] == [len(c) for c in chunked]
    for w, c in zip(whole, chunked):
        for cw, cc in zip(w, c):
            # chunk-size-dependent XLA fusion moves powers by last-ulp
            # amounts, which the parabola refinement amplifies to ~1e-6
            # in (r, z) — physically meaningless at dz=2
            assert abs(cw.r - cc.r) < 1e-5
            assert abs(cw.z - cc.z) < 1e-5
            assert abs(cw.power - cc.power) < 1e-3


def test_batched_search_sharded_matches_unsharded():
    """The shard_map'd batch runner (batch axis over the 'dm' mesh axis)
    reproduces the single-device batched result on the virtual CPU mesh."""
    import jax

    from pypulsar_tpu.fourier.accelsearch import accel_search_batch

    if len(jax.devices()) < 4:
        import pytest

        pytest.skip("needs >= 4 virtual devices")
    rng = np.random.RandomState(8)
    N = 1 << 13
    T = N * 2 * 128e-6
    cfg = AccelSearchConfig(zmax=20.0, dz=2.0, numharm=2, sigma_min=2.5,
                            seg_width=1 << 11)
    ffts = []
    for b in range(4):
        ts = rng.standard_normal(2 * N).astype(np.float32)
        ts += 0.2 * np.sin(2 * np.pi * (50.0 + 9.0 * b)
                           * np.arange(2 * N) * 128e-6)
        ffts.append((np.fft.rfft(ts) / np.sqrt(2 * N))
                    .astype(np.complex64)[:N])
    ffts = np.stack(ffts)
    plain = accel_search_batch(ffts, T, cfg)
    sharded = accel_search_batch(ffts, T, cfg, mesh_devices=4)
    assert [len(p) for p in plain] == [len(s) for s in sharded]
    for p, s in zip(plain, sharded):
        for cp, cs in zip(p, s):
            assert abs(cp.r - cs.r) < 1e-5
            assert abs(cp.power - cs.power) < 1e-2


# ---------------------------------------------------------------------------
# injection recovery
# ---------------------------------------------------------------------------


def test_recover_constant_tone():
    rng = np.random.RandomState(0)
    N = 1 << 16
    T = 32.0
    t = np.arange(N) * (T / N)
    f0 = 37.61
    ts = rng.standard_normal(N) + 0.12 * np.cos(2 * np.pi * f0 * t)
    fft = np.fft.rfft(ts) / np.sqrt(N)
    cands = accel_search(fft, T, AccelSearchConfig(
        zmax=20.0, dz=2.0, numharm=1, sigma_min=4.0, seg_width=1 << 12))
    assert cands, "tone not detected"
    best = cands[0]
    assert abs(best.freq(T) - f0) < 0.5 / T
    assert abs(best.z) <= 2.0


def test_recover_drifting_tone_r_and_z():
    rng = np.random.RandomState(1)
    N = 1 << 17
    T = 64.0
    t = np.arange(N) * (T / N)
    f0 = 113.37
    z_true = 60.0
    fdot = z_true / T ** 2
    ts = rng.standard_normal(N) + 0.1 * np.cos(
        2 * np.pi * (f0 * t + 0.5 * fdot * t * t))
    fft = np.fft.rfft(ts) / np.sqrt(N)
    cands = accel_search(fft, T, AccelSearchConfig(
        zmax=100.0, dz=2.0, numharm=1, sigma_min=4.0, seg_width=1 << 13))
    assert cands
    best = cands[0]
    r_mid = (f0 + 0.5 * fdot * T) * T
    assert abs(best.r - r_mid) < 1.0
    assert abs(best.z - z_true) <= 2.0
    # a zero-drift search at the same threshold must do worse on this signal
    c0 = accel_search(fft, T, AccelSearchConfig(
        zmax=0.0, dz=2.0, numharm=1, sigma_min=2.0, seg_width=1 << 13))
    p0 = max((c.power for c in c0 if abs(c.r - r_mid) < 40), default=0.0)
    assert best.power > 2.0 * p0


def test_harmonic_summing_beats_fundamental():
    """A narrow pulse train is found at higher significance by the H=8
    stage than by the fundamental alone, at the right frequency."""
    rng = np.random.RandomState(2)
    N = 1 << 17
    T = 64.0
    t = np.arange(N) * (T / N)
    P = 0.0737
    phase = (t / P) % 1.0
    prof = np.exp(-0.5 * ((phase - 0.3) / 0.02) ** 2)
    ts = rng.standard_normal(N) + 0.22 * prof
    fft = np.fft.rfft(ts) / np.sqrt(N)
    cands = accel_search(fft, T, AccelSearchConfig(
        zmax=20.0, dz=2.0, numharm=8, sigma_min=4.0, seg_width=1 << 13))
    assert cands
    best = cands[0]
    assert best.numharm == 8
    assert abs(best.freq(T) - 1.0 / P) < 1.0 / T
    f1 = [c for c in cands if c.numharm == 1
          and abs(c.freq(T) - 1.0 / P) < 2.0 / T]
    best_f1 = max((c.sigma for c in f1), default=0.0)
    assert best.sigma > best_f1


def test_recover_binary_p_and_pdot():
    """Inject a pulsar in a (locally linear) binary orbit; recover its
    apparent spin period and period derivative from (r, z)."""
    rng = np.random.RandomState(3)
    N = 1 << 17
    T = 512.0  # long integration so the drift spans many Fourier bins
    t = np.arange(N) * (T / N)
    f0 = 97.3  # Hz (Nyquist here is 128 Hz)
    # orbital line-of-sight acceleration: fdot = -f0 * a / c
    a_los = 500.0  # m/s^2 (tight compact binary near periastron)
    c = 299792458.0
    fdot = -f0 * a_los / c  # -1.62e-4 Hz/s -> z = fdot*T^2 = -42.5
    z_true = fdot * T * T
    ts = rng.standard_normal(N) + 0.1 * np.cos(
        2 * np.pi * (f0 * t + 0.5 * fdot * t * t))
    fft = np.fft.rfft(ts) / np.sqrt(N)
    cands = accel_search(fft, T, AccelSearchConfig(
        zmax=100.0, dz=2.0, numharm=1, sigma_min=4.0, seg_width=1 << 13))
    assert cands
    best = cands[0]
    f_mid_true = f0 + 0.5 * fdot * T
    f_rec = best.freq(T)
    fdot_rec = best.fdot(T)
    assert abs(f_rec - f_mid_true) < 0.5 / T
    assert abs(best.z - z_true) <= 2.0
    # period and period derivative: P = 1/f, Pdot = -fdot/f^2
    P_rec = 1.0 / f_rec
    Pdot_rec = -fdot_rec / f_rec ** 2
    P_true = 1.0 / f_mid_true
    Pdot_true = -fdot / f_mid_true ** 2
    assert abs(P_rec - P_true) / P_true < 1e-4
    assert abs(Pdot_rec - Pdot_true) / abs(Pdot_true) < 0.05
    # implied line-of-sight acceleration comes back out
    a_rec = -fdot_rec * c / f_rec
    assert abs(a_rec - a_los) / a_los < 0.05


# ---------------------------------------------------------------------------
# CLI end-to-end: accelsearch -> .cand -> plot_accelcands
# ---------------------------------------------------------------------------


def _write_fake_dat(base, ts, dt, obj="FAKE", dm=None):
    """One .dat + .inf pair with the standard fake-observatory header —
    the single place the CLI tests' fixture schema lives."""
    from pypulsar_tpu.io.datfile import write_dat
    from pypulsar_tpu.io.infodata import InfoData

    inf = InfoData()
    inf.epoch = 55000.0
    inf.dt = dt
    inf.N = len(ts)
    if dm is not None:
        inf.DM = dm
    inf.telescope = "Fake"
    inf.lofreq = 1400.0
    inf.BW = 100.0
    inf.numchan = 1
    inf.chan_width = 100.0
    inf.object = obj
    write_dat(base, ts, inf)
    return base


def test_cli_accelsearch_to_plot_accelcands(tmp_path, monkeypatch):
    import matplotlib

    matplotlib.use("Agg", force=True)
    from pypulsar_tpu.cli import accelsearch as cli_accel
    from pypulsar_tpu.cli import plot_accelcands as cli_plot
    from pypulsar_tpu.io.prestocand import read_rzwcands

    monkeypatch.chdir(tmp_path)
    rng = np.random.RandomState(4)
    N = 1 << 16
    dt = 5e-4
    T = N * dt
    t = np.arange(N) * dt
    f0 = 43.21
    inffns = []
    for ii in range(3):
        ts = rng.standard_normal(N).astype(np.float32)
        ts += 0.15 * np.cos(2 * np.pi * f0 * t).astype(np.float32)
        base = _write_fake_dat(str(tmp_path / f"beam{ii}"), ts, dt)
        inffns.append(base + ".inf")
        rc = cli_accel.main([base + ".dat", "-z", "0", "-n", "1",
                             "-s", "4"])
        assert rc == 0
        cands = read_rzwcands(base + "_ACCEL_0.cand")
        assert cands, "no candidates written"
        assert abs(cands[0].r / T - f0) < 1.0 / T
        assert os.path.exists(base + "_ACCEL_0.txtcand")

    # the clustering tool consumes our own pipeline's candidate files
    out = str(tmp_path / "cands.png")
    rc = cli_plot.main(inffns + ["-o", out, "--min-hits", "2"])
    assert rc == 0
    assert os.path.exists(out)


# ---------------------------------------------------------------------------
# jerk (w) search
# ---------------------------------------------------------------------------


def test_numeric_template_matches_analytic_at_w0():
    """FFT-synthesized templates reproduce the Fresnel-integral responses
    (independent validation paths agree)."""
    from pypulsar_tpu.fourier.zresponse import _numeric_response

    offs = np.arange(-60, 60, 0.5)
    for z in (0.0, 10.0, 60.0, -30.0):
        a = z_response(z, offs + z / 2.0)
        b = _numeric_response(z, 0.0, offs)
        assert np.abs(a - b).max() < 2e-3


def test_recover_jerk_signal_w_dimension():
    """A signal with second-order drift is recovered at the right (r, z, w)
    by the jerk search, and at much higher power than the z-only search."""
    rng = np.random.RandomState(9)
    N = 1 << 17
    T = 64.0
    t = np.arange(N) * (T / N)
    f0 = 151.31
    z_true, w_true = 20.0, 120.0
    fdot = z_true / T ** 2
    fddot = w_true / T ** 3
    ts = rng.standard_normal(N) + 0.12 * np.cos(
        2 * np.pi * (f0 * t + fdot * t * t / 2 + fddot * t ** 3 / 6))
    fft = np.fft.rfft(ts) / np.sqrt(N)

    cfg_w = AccelSearchConfig(zmax=40.0, dz=2.0, numharm=1, sigma_min=4.0,
                              seg_width=1 << 13, wmax=160.0, dw=40.0)
    cands = accel_search(fft, T, cfg_w)
    assert cands
    best = cands[0]
    f_mean_true = f0 + fdot * T / 2 + fddot * T * T / 6
    assert abs(best.freq(T) - f_mean_true) < 1.0 / T
    assert abs(best.z - z_true) <= cfg_w.dz + 1.0
    assert abs(best.w - w_true) <= cfg_w.dw
    assert abs(best.fddot(T) - fddot) <= cfg_w.dw / T ** 3

    cfg_z = AccelSearchConfig(zmax=40.0, dz=2.0, numharm=1, sigma_min=3.0,
                              seg_width=1 << 13)
    c_z = accel_search(fft, T, cfg_z)
    p_z = max((c.power for c in c_z
               if abs(c.freq(T) - f_mean_true) < 60.0 / T), default=0.0)
    assert best.power > 1.5 * p_z  # jerk templates recover what z-only loses


def test_cli_sift_clusters_across_dms(tmp_path, monkeypatch):
    """Per-DM accelsearch outputs sift into one .accelcands candidate that
    peaks at the injected DM, parseable by the reference-format reader."""
    from pypulsar_tpu.cli import accelsearch as cli_accel
    from pypulsar_tpu.cli import sift as cli_sift
    from pypulsar_tpu.io.accelcands import parse_candlist

    monkeypatch.chdir(tmp_path)
    rng = np.random.RandomState(17)
    N, dt = 1 << 15, 1e-3
    T = N * dt
    t = np.arange(N) * dt
    f0 = 29.17
    candfns = []
    # simulate three DM trials: signal strongest at the middle one
    for dm, amp in ((38.0, 0.12), (40.0, 0.3), (42.0, 0.12)):
        ts = rng.standard_normal(N).astype(np.float32)
        ts += amp * np.cos(2 * np.pi * f0 * t).astype(np.float32)
        base = _write_fake_dat(str(tmp_path / f"s_DM{dm:.2f}"), ts, dt,
                               obj="SIFT", dm=dm)
        rc = cli_accel.main([base + ".dat", "-z", "0", "-n", "1", "-s", "4"])
        assert rc == 0
        candfns.append(base + "_ACCEL_0.cand")

    out = str(tmp_path / "sifted.accelcands")
    rc = cli_sift.main(candfns + ["-o", out, "--min-hits", "2"])
    assert rc == 0
    cands = parse_candlist(out)
    assert cands, "no sifted candidates"
    best = cands[0]
    assert abs(1.0 / best.period - f0) < 1.0 / T
    assert best.dm == 40.0  # strongest trial wins the cluster
    assert len(best.dmhits) == 3
    hit_dms = sorted(h.dm for h in best.dmhits)
    assert hit_dms == [38.0, 40.0, 42.0]


def test_full_pipeline_fil_to_sifted_accelcands(tmp_path, monkeypatch):
    """The complete periodicity pipeline on one synthetic observation:
    .fil -> DM sweep (--write-dats) -> per-DM accelsearch -> sift ->
    .accelcands, recovering the injected (period, DM)."""
    from pypulsar_tpu.cli import accelsearch as cli_accel
    from pypulsar_tpu.cli import sift as cli_sift
    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.io.accelcands import parse_candlist
    from pypulsar_tpu.ops import numpy_ref

    monkeypatch.chdir(tmp_path)
    rng = np.random.RandomState(23)
    C, T, dt = 32, 1 << 15, 1e-3
    dm_true, f0 = 40.0, 23.31
    freqs = 1500.0 - 4.0 * np.arange(C)
    tsec = np.arange(T) * dt
    delays = numpy_ref.bin_delays(dm_true, freqs, dt) * dt
    data = rng.randn(T, C).astype(np.float32)
    for c in range(C):
        data[:, c] += 0.35 * np.cos(
            2 * np.pi * f0 * (tsec - delays[c])).astype(np.float32)
    hdr = dict(nchans=C, tsamp=dt, fch1=1500.0, foff=-4.0, tstart=55000.0,
               nbits=32, nifs=1, source_name="PIPE")
    filterbank.write_filterbank("obs.fil", hdr, data)

    rc = cli_sweep.main(["obs.fil", "-o", "obs", "--lodm", "32",
                         "--dmstep", "4", "--numdms", "5", "-s", "8",
                         "--group-size", "4", "--write-dats"])
    assert rc == 0
    candfns = []
    for dm in (32.0, 36.0, 40.0, 44.0, 48.0):
        datfn = f"obs_DM{dm:.2f}.dat"
        assert os.path.exists(datfn)
        rc = cli_accel.main([datfn, "-z", "0", "-n", "4", "-s", "3"])
        assert rc == 0
        candfns.append(f"obs_DM{dm:.2f}_ACCEL_0.cand")
    rc = cli_sift.main(candfns + ["-o", "obs.accelcands", "--min-hits", "2"])
    assert rc == 0
    cands = parse_candlist("obs.accelcands")
    assert cands
    best = cands[0]
    Tobs = T * dt
    assert abs(1.0 / best.period - f0) < 1.5 / Tobs
    assert abs(best.dm - dm_true) <= 4.0  # cluster peaks at the true DM
    assert len(best.dmhits) >= 3  # seen across neighbouring trials


# ---------------------------------------------------------------------------
# coarse-to-fine z search (VERDICT r4 item 1 stretch)
# ---------------------------------------------------------------------------


def test_coarse_grid_power_retention():
    """Calibration behind AccelSearchConfig.coarse_power_frac: a template
    one fine step (dz=2) off in z keeps ~95% of the matched power and one
    coarse step (2*dz -> worst mismatch 2 bins) keeps ~80%, independent
    of z — so a coarse pass thresholded at 0.7x the fine threshold
    cannot lose a fine-grid detection."""
    for z in (0.0, 50.0, 200.0):
        ret = []
        for dz in (1.0, 2.0):
            tb, _hw = template_bank(np.array([z, z + dz]), numbetween=2)
            a, b = tb[0], tb[2]  # integer-phase rows at z and z+dz
            num = np.abs(np.vdot(b, a)) ** 2
            den = np.vdot(a, a).real * np.vdot(b, b).real
            ret.append(num / den)
        assert ret[0] > 0.93  # fine-grid worst case (|dz/2| = 1 mismatch)
        assert ret[1] > 0.78  # coarse-grid worst case (2-bin mismatch)


def _drifting_train(rng, N, T, f0, z_true, amp=1.2, width_frac=0.05):
    """Noisy pulse train whose fundamental drifts z_true bins over T."""
    t = np.arange(N) * (T / N)
    fdot = z_true / T ** 2
    phase = (f0 * t + 0.5 * fdot * t * t) % 1.0
    ts = rng.standard_normal(N) + amp * (phase < width_frac)
    return (np.fft.rfft(ts) / np.sqrt(N)).astype(np.complex64)


def _cand_key(cands):
    return [(round(c.r, 4), round(c.z, 4), round(c.power, 2), c.numharm)
            for c in cands]


def test_coarse_fine_matches_full_serial():
    """coarse_dz preselection returns the identical candidate list: the
    fine pass re-evaluates selected segments with the same compiled
    stage program, so any difference would mean a segment was missed.
    z_true sits mid-between coarse grid points (worst mismatch)."""
    rng = np.random.RandomState(3)
    N = 1 << 16
    T = 64.0
    fft = _drifting_train(rng, N, T, f0=87.31, z_true=22.0)
    cfg = AccelSearchConfig(zmax=40.0, dz=2.0, numharm=4, sigma_min=3.0,
                            seg_width=1 << 12)
    full = accel_search(fft, T, cfg)
    cf = accel_search(
        fft, T, AccelSearchConfig(
            zmax=40.0, dz=2.0, numharm=4, sigma_min=3.0,
            seg_width=1 << 12, coarse_dz=4.0))
    assert full, "injection not detected"
    assert _cand_key(cf) == _cand_key(full)
    best = cf[0]
    assert abs(best.z - 22.0) <= 2.0


def test_coarse_fine_matches_full_batch():
    """The batched driver's coarse pass (hit-segment union over the
    batch) also reproduces the single-pass batched result."""
    from pypulsar_tpu.fourier.accelsearch import accel_search_batch

    rng = np.random.RandomState(5)
    N = 1 << 14
    T = 32.0
    ffts = np.stack([
        _drifting_train(rng, N, T, f0=61.0 + 7.0 * b, z_true=10.0)
        for b in range(3)])
    base = dict(zmax=20.0, dz=2.0, numharm=2, sigma_min=3.0,
                seg_width=1 << 12)
    full = accel_search_batch(ffts, T, AccelSearchConfig(**base))
    cf = accel_search_batch(
        ffts, T, AccelSearchConfig(**base, coarse_dz=4.0))
    assert any(full), "injection not detected"
    for f, c in zip(full, cf):
        assert _cand_key(c) == _cand_key(f)


def test_coarse_config_validation():
    """Out-of-regime coarse settings warn (no-op grid, uncalibrated
    spacing) or raise (bad threshold fraction) instead of silently
    degrading recall."""
    with pytest.warns(UserWarning, match="no effect"):
        AccelSearchConfig(dz=2.0, coarse_dz=2.0)
    with pytest.warns(UserWarning, match="no effect"):
        AccelSearchConfig(dz=2.0, coarse_dz=-4.0)  # sign slip
    with pytest.warns(UserWarning, match="retention"):
        AccelSearchConfig(dz=2.0, coarse_dz=8.0)
    with pytest.raises(ValueError):
        AccelSearchConfig(coarse_power_frac=0.0)


def test_coarse_fine_sharded_matches_sharded_single_pass():
    """coarse_dz composes with mesh sharding: the coarse pass and the
    refine pass both shard_map over the 'dm' axis and the result matches
    the sharded single-pass search."""
    import jax

    from pypulsar_tpu.fourier.accelsearch import accel_search_batch

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    rng = np.random.RandomState(6)
    N = 1 << 13
    T = 16.0
    ffts = np.stack([
        _drifting_train(rng, N, T, f0=71.0 + 5.0 * b, z_true=6.0)
        for b in range(4)])
    base = dict(zmax=12.0, dz=2.0, numharm=2, sigma_min=3.0,
                seg_width=1 << 11)
    single = accel_search_batch(ffts, T, AccelSearchConfig(**base),
                                mesh_devices=4)
    cf = accel_search_batch(ffts, T,
                            AccelSearchConfig(**base, coarse_dz=4.0),
                            mesh_devices=4)
    assert any(single), "injection not detected"
    for s, c in zip(single, cf):
        assert _cand_key(c) == _cand_key(s)


# ---------------------------------------------------------------------------
# device-side batched spectrum prep (rfft + deredden fused on device)
# ---------------------------------------------------------------------------


def test_prep_spectra_batch_matches_host_prep():
    """kernels.prep_spectra_batch (f32 device rfft + vmapped deredden)
    reproduces the CLI host path (f64 np.fft.rfft -> kernels.deredden)
    within the documented 2e-6 relative SNR contract, and
    accel_search_batch consumes the plane tuple directly with the same
    candidates as the host-prepped complex batch."""
    from pypulsar_tpu.fourier.accelsearch import accel_search_batch
    from pypulsar_tpu.fourier.kernels import deredden, prep_spectra_batch

    rng = np.random.RandomState(11)
    n = 1 << 15
    dt = 2.5e-4
    T = n * dt
    series = []
    for b in range(3):
        ts = rng.standard_normal(n).astype(np.float32)
        ts += 0.2 * np.sin(2 * np.pi * (37.0 + 9.0 * b)
                           * np.arange(n) * dt).astype(np.float32)
        series.append(ts)
    series = np.stack(series)

    re, im = prep_spectra_batch(series)
    dev = np.asarray(re) + 1j * np.asarray(im)
    host = np.stack([
        np.asarray(deredden(np.fft.rfft(s).astype(np.complex64)))
        for s in series])
    assert dev.shape == host.shape == (3, n // 2 + 1)
    # normalized-spectrum agreement away from the (unit-set) DC bin
    scale = np.abs(host).max()
    assert np.abs(dev - host).max() / scale < 2e-5

    cfg = AccelSearchConfig(zmax=20.0, dz=2.0, numharm=4, sigma_min=3.0,
                            seg_width=1 << 12)
    from_host = accel_search_batch(host, T, cfg)
    from_dev = accel_search_batch((re, im), T, cfg)
    assert [len(c) for c in from_host] == [len(c) for c in from_dev]
    for hs, ds in zip(from_host, from_dev):
        assert hs, "injection not detected"
        for ch, cd in zip(hs, ds):
            # r/z are sub-grid refined continuous values: f32-vs-f64 prep
            # noise moves them at the ~1e-7 level, not the grid cell
            assert abs(ch.r - cd.r) < 1e-3
            assert abs(ch.z - cd.z) < 1e-3
            assert ch.numharm == cd.numharm
            assert abs(ch.sigma - cd.sigma) <= 1e-3


def test_prep_spectra_batch_large_mean_parity():
    """A +1000-count DC offset (8-bit data sits far above zero) must not
    degrade the device prep: the per-series mean is subtracted on device
    before the f32 rfft (deredden overwrites bin 0 anyway, so the exact
    result is unchanged), keeping the f32 butterflies at fluctuation
    scale — same tolerance as the zero-mean parity test (ADVICE r5)."""
    from pypulsar_tpu.fourier.kernels import deredden, prep_spectra_batch

    rng = np.random.RandomState(17)
    n = 1 << 14
    dt = 2.5e-4
    series = []
    for b in range(2):
        ts = rng.standard_normal(n).astype(np.float32)
        ts += 0.2 * np.sin(2 * np.pi * (23.0 + 11.0 * b)
                           * np.arange(n) * dt).astype(np.float32)
        ts += 1000.0  # the large-mean regime the fix targets
        series.append(ts)
    series = np.stack(series)

    re, im = prep_spectra_batch(series)
    dev = np.asarray(re) + 1j * np.asarray(im)
    # host reference: f64 rfft (no DC-rounding problem) -> deredden
    host = np.stack([
        np.asarray(deredden(np.fft.rfft(s.astype(np.float64))
                            .astype(np.complex64)))
        for s in series])
    assert dev.shape == host.shape == (2, n // 2 + 1)
    scale = np.abs(host[:, 1:]).max()
    assert np.abs(dev[:, 1:] - host[:, 1:]).max() / scale < 2e-5
    assert np.allclose(dev[:, 0], 1.0)  # deredden's unit DC bin


def test_cli_device_prep_requires_batch(tmp_path):
    """--device-prep with --batch < 2 is a hard CLI error instead of a
    silent no-op (device prep only exists on the grouped batch path)."""
    import pytest

    from pypulsar_tpu.cli import accelsearch as cli_accel

    with pytest.raises(SystemExit) as exc:
        cli_accel.main([str(tmp_path / "x.dat"), "--device-prep"])
    assert exc.value.code == 2  # argparse error exit
    with pytest.raises(SystemExit) as exc:
        cli_accel.main([str(tmp_path / "x.dat"), "--device-prep",
                        "--batch", "1"])
    assert exc.value.code == 2


def test_cli_device_prep_matches_host_prep(tmp_path, monkeypatch):
    """cli accelsearch --batch --device-prep finds the same candidates
    as the default host-prep batch path on the same .dats."""
    from pypulsar_tpu.cli import accelsearch as cli_accel
    from pypulsar_tpu.io.prestocand import read_rzwcands

    monkeypatch.chdir(tmp_path)
    rng = np.random.RandomState(12)
    N = 1 << 15
    dt = 5e-4
    bases = []
    for ii in range(3):
        ts = rng.standard_normal(N).astype(np.float32)
        ts += 0.2 * np.cos(2 * np.pi * (41.0 + 7.0 * ii)
                           * np.arange(N) * dt).astype(np.float32)
        bases.append(_write_fake_dat(str(tmp_path / f"dp{ii}"), ts, dt))

    dats = [b + ".dat" for b in bases]
    # --no-device-prep: device prep is DEFAULT-ON for --batch >= 2 since
    # round 6, so the host-prep reference side must opt out explicitly
    rc = cli_accel.main(dats + ["--batch", "3", "-z", "20", "-n", "2",
                                "-s", "3", "--no-device-prep"])
    assert rc == 0
    host_cands = {b: read_rzwcands(b + "_ACCEL_20.cand") for b in bases}
    for b in bases:
        os.remove(b + "_ACCEL_20.cand")
    rc = cli_accel.main(dats + ["--batch", "3", "-z", "20", "-n", "2",
                                "-s", "3", "--device-prep"])
    assert rc == 0
    for b in bases:
        dev = read_rzwcands(b + "_ACCEL_20.cand")
        host = host_cands[b]
        assert host, "no candidates from host prep"
        assert len(dev) == len(host)
        for ch, cd in zip(host, dev):
            assert abs(ch.r - cd.r) < 1e-3
            assert abs(ch.z - cd.z) < 1e-3
            assert abs(ch.sig - cd.sig) < 1e-3


def test_cli_device_prep_hbm_cap_chunks_prep(tmp_path, monkeypatch):
    """A tiny PYPULSAR_TPU_ACCEL_HBM forces the device-prep flush to prep
    the group in budget-bounded slices (cap = budget // (24 * n)); the
    candidates must not change. Guards the review fix that stops a large
    --batch from out-allocating the search's own HBM budget during prep."""
    from pypulsar_tpu.cli import accelsearch as cli_accel
    from pypulsar_tpu.io.prestocand import read_rzwcands

    monkeypatch.chdir(tmp_path)
    rng = np.random.RandomState(13)
    N = 1 << 14
    dt = 5e-4
    bases = []
    for ii in range(4):
        ts = rng.standard_normal(N).astype(np.float32)
        ts += 0.25 * np.cos(2 * np.pi * (29.0 + 5.0 * ii)
                            * np.arange(N) * dt).astype(np.float32)
        bases.append(_write_fake_dat(str(tmp_path / f"cap{ii}"), ts, dt))
    dats = [b + ".dat" for b in bases]
    argv = dats + ["--batch", "4", "-z", "10", "-n", "1", "-s", "3",
                   "--device-prep"]

    # count prep dispatches through the symbol the CLI resolves at call
    # time, so the test FAILS if the cap slicing is removed
    from pypulsar_tpu.fourier import kernels as _k

    calls = []
    real_prep = _k.prep_spectra_batch

    def spy(series, *a, **kw):
        calls.append(np.asarray(series).shape[0])
        return real_prep(series, *a, **kw)

    monkeypatch.setattr(_k, "prep_spectra_batch", spy)

    monkeypatch.delenv("PYPULSAR_TPU_ACCEL_HBM", raising=False)
    assert cli_accel.main(argv) == 0
    assert calls == [4], calls  # unbounded budget: one whole-group prep
    whole = {b: [(round(c.r, 3), round(c.z, 3))
                 for c in read_rzwcands(b + "_ACCEL_10.cand")]
             for b in bases}
    for b in bases:
        os.remove(b + "_ACCEL_10.cand")
    # budget small enough that cap = max(1, budget // (24 * N)) == 1:
    # every spectrum preps in its own slice
    calls.clear()
    monkeypatch.setenv("PYPULSAR_TPU_ACCEL_HBM", str(24 * N))
    assert cli_accel.main(argv) == 0
    assert calls == [1, 1, 1, 1], calls
    for b in bases:
        got = [(round(c.r, 3), round(c.z, 3))
               for c in read_rzwcands(b + "_ACCEL_10.cand")]
        assert got == whole[b]


def test_cli_device_prep_batch_failure_falls_back_serial(tmp_path,
                                                         monkeypatch):
    """A failing device-prep batched dispatch degrades to per-file serial
    HOST-prep searches (re-reading each .dat) instead of failing the
    group — the poison-spectrum contract of the batched CLI, extended to
    series-kind groups."""
    from pypulsar_tpu.cli import accelsearch as cli_accel
    from pypulsar_tpu.io.prestocand import read_rzwcands

    monkeypatch.chdir(tmp_path)
    rng = np.random.RandomState(14)
    N = 1 << 14
    dt = 5e-4
    bases = []
    for ii in range(3):
        ts = rng.standard_normal(N).astype(np.float32)
        ts += 0.25 * np.cos(2 * np.pi * (31.0 + 4.0 * ii)
                            * np.arange(N) * dt).astype(np.float32)
        bases.append(_write_fake_dat(str(tmp_path / f"pf{ii}"), ts, dt))
    dats = [b + ".dat" for b in bases]

    from pypulsar_tpu.fourier import accelsearch as _accel_mod

    real_batch = _accel_mod.accel_search_batch
    boom = {"n": 0}

    def failing_batch(*a, **kw):
        boom["n"] += 1
        raise RuntimeError("synthetic batch failure")

    # the CLI imports accel_search_batch into its main() closure at call
    # time via `from ... import`, so patch the module attribute BEFORE
    # main() runs
    monkeypatch.setattr(_accel_mod, "accel_search_batch", failing_batch)
    rc = cli_accel.main(dats + ["--batch", "3", "-z", "10", "-n", "1",
                                "-s", "3", "--device-prep"])
    monkeypatch.setattr(_accel_mod, "accel_search_batch", real_batch)
    assert rc == 0 and boom["n"] >= 1
    fallback = {b: [(round(c.r, 3), round(c.z, 3))
                    for c in read_rzwcands(b + "_ACCEL_10.cand")]
                for b in bases}
    for b in bases:
        os.remove(b + "_ACCEL_10.cand")

    # reference: the healthy serial path on the same inputs
    rc = cli_accel.main(dats + ["-z", "10", "-n", "1", "-s", "3"])
    assert rc == 0
    for b in bases:
        got = [(round(c.r, 3), round(c.z, 3))
               for c in read_rzwcands(b + "_ACCEL_10.cand")]
        assert got == fallback[b], b


# ---------------------------------------------------------------------------
# the device-prep matched-candidate contract (VERDICT r5 item 2)
# ---------------------------------------------------------------------------


def _assert_candidate_contract(host_cands, dev_cands, floor, margin,
                               dr, dz, dsig):
    """The matched-candidate contract, as BENCHNOTES round-5 states it in
    prose for 53/64 files: every candidate above ``floor + margin`` on
    EITHER side has a partner on the other within (dr, dz, dsig), and no
    unpartnered candidate on either side exceeds ``floor + margin`` —
    i.e. device prep may flicker threshold-floor candidates but can
    neither gain nor lose an above-floor detection."""
    def matches(c, pool):
        return any(abs(c.r - o.r) < dr and abs(c.z - o.z) < dz
                   and abs(c.sigma - o.sigma) < dsig for o in pool)

    for a, b, side in ((host_cands, dev_cands, "host"),
                       (dev_cands, host_cands, "device")):
        for c in a:
            if not matches(c, b):
                assert c.sigma <= floor + margin, (
                    f"unmatched {side}-prep candidate above the "
                    f"floor+margin contract bound: r={c.r:.2f} "
                    f"z={c.z:.2f} sigma={c.sigma:.2f} "
                    f"(bound {floor + margin:.2f})")


def test_device_prep_candidate_contract():
    """Device-prep vs host-prep accel over a battery of synthetic
    spectra — constant tones, drifting tones, strong/weak/near-threshold
    amplitudes — asserting the matched-candidate contract that justifies
    flipping --device-prep default-on (VERDICT r5 item 2; documented in
    README next to the 2e-6 SNR contract)."""
    from pypulsar_tpu.fourier.accelsearch import accel_search_batch
    from pypulsar_tpu.fourier.kernels import (deredden, deredden_schedule,
                                              prep_spectra_batch)

    rng = np.random.RandomState(42)
    n = 1 << 15
    dt = 2.5e-4
    T = n * dt
    floor, margin = 3.0, 0.5
    cfg = AccelSearchConfig(zmax=20.0, dz=2.0, numharm=4, sigma_min=floor,
                            seg_width=1 << 12)
    t = np.arange(n) * dt
    battery = []
    # (f0 Hz, z bins over T, amplitude): strong, moderate, drifting both
    # ways, WEAK near the detection floor, and pure noise
    specs = [(37.0, 0.0, 0.30), (61.0, 0.0, 0.18),
             (43.0, 8.0, 0.25), (29.0, -12.0, 0.25),
             (53.0, 4.0, 0.10), (71.0, 0.0, 0.07),
             (47.0, 0.0, 0.0)]
    for f0, z, amp in specs:
        ts = rng.standard_normal(n).astype(np.float32)
        if amp > 0:
            fdot = z / (T * T)
            ts += amp * np.cos(2 * np.pi * (f0 * t
                                            + 0.5 * fdot * t * t)
                               ).astype(np.float32)
        battery.append(ts)
    series = np.stack(battery)

    schedule = deredden_schedule(n // 2 + 1)
    host = np.stack([
        np.asarray(deredden(np.fft.rfft(s).astype(np.complex64),
                            schedule=schedule))
        for s in series])
    host_out = accel_search_batch(host, T, cfg)
    dev_out = accel_search_batch(prep_spectra_batch(series, schedule),
                                 T, cfg)

    n_detecting = 0
    for hs, ds in zip(host_out, dev_out):
        _assert_candidate_contract(hs, ds, floor, margin,
                                   dr=0.5, dz=1.0, dsig=0.5)
        # count SPECTRA with an above-floor detection, not candidates:
        # one strong tone's harmonics must not mask the drifting/weak
        # spectra all going dark
        n_detecting += any(c.sigma > floor + margin for c in hs)
    assert n_detecting >= len(specs) - 2, \
        "battery too weak to exercise the contract"


# ---------------------------------------------------------------------------
# the harmonic ladder: one segment grid, every ratio bank once a segment
# ---------------------------------------------------------------------------


def _ladder_raw_hits(fft, T, cfg):
    """The serial ladder program's raw device output for ``fft`` with
    the geometry it ran on: (vals, zi, ri, neigh) each
    [n_seg, n_stages, Wn, k, ...]."""
    import jax.numpy as jnp

    from pypulsar_tpu.fourier import accelsearch as acc
    from pypulsar_tpu.ops.transfer import pull_host, split_complex

    N = len(fft)
    (zs, ws, stages, segw, rlo, rhi, banks, front, Np, _numindep,
     thresh) = acc._search_setup(N, T, cfg)
    grid_lo, n_seg, lo, hi = acc._ladder_grid(stages, rlo, rhi, N, segw)
    rungs, tfs, idxs = acc._ladder_banks(banks, stages, grid_lo, segw, front)
    f_re, f_im = split_complex(fft)
    spec_pad2 = acc._build_spec_pad(jnp.asarray(f_re), jnp.asarray(f_im),
                                    front, int(max(Np - N, 8)))
    runner = acc._make_ladder_runner(segw, len(zs), len(ws), cfg.topk,
                                     rungs, batched=False)
    tvals = np.array([thresh[H] for H in stages], dtype=np.float32)
    out = pull_host(*runner(spec_pad2, tfs, idxs, grid_lo, lo, hi, tvals,
                            jnp.arange(n_seg, dtype=jnp.int32)))
    geom = dict(zs=zs, ws=ws, stages=stages, segw=segw, rlo=rlo, rhi=rhi,
                banks=banks, front=front, Np=Np, grid_lo=grid_lo,
                n_seg=n_seg, lo=lo, hi=hi, thresh=tvals)
    return out, geom


def _scratch_plane(spec_pad, geom, H, si):
    """Stage ``H``'s plane of segment ``si`` built from nothing in
    float64: all H ratio banks b/H correlated, stretch-gathered and
    added, on the common grid; -inf where the stage is not valid."""
    from fractions import Fraction

    segw, front = geom["segw"], geom["front"]
    r0 = geom["grid_lo"] + si * segw
    rows = len(geom["zs"]) * len(geom["ws"])
    plane = np.zeros((rows, 2 * segw))
    for b in range(1, H + 1):
        tf2, hw, L, idx = geom["banks"][Fraction(b, H)]
        tf = tf2[0].astype(np.float64) + 1j * tf2[1].astype(np.float64)
        start = front + (b * r0) // H - hw
        corr = np.fft.ifft(np.fft.fft(spec_pad[start:start + L])[None] * tf,
                           axis=1)
        p = (np.abs(corr) ** 2).reshape(rows, 2 * L)
        plane += p[:, idx]
    s = geom["stages"].index(H)
    r_top = r0 + 0.5 * np.arange(2 * segw)
    valid = (r_top >= geom["lo"][s]) & (r_top < geom["hi"][s])
    return np.where(valid[None, :], plane, -np.inf)


@pytest.mark.parametrize("numharm,fhi,wmax", [
    (2, None, 0.0), (4, None, 0.0), (8, None, 0.0),
    (8, 19.0, 0.0),   # the stages' top_hi differ
    (2, None, 20.0),  # jerk rows interleaved in the plane
])
def test_ladder_matches_from_scratch_planes(numharm, fhi, wmax):
    """Every stage's raw device hits (value, z row, column, 3x3
    neighbourhood) sit on that stage's plane built FROM SCRATCH in
    float64 from the same banks on the common grid: the ladder's running
    sum is the same sum, and the per-stage column mask is the stage's
    own range. The strongest valid cell of every (segment, stage) over
    threshold is the device's first hit there."""
    rng = np.random.RandomState(17)
    N = (1 << 13) + 1
    T = 36.3  # rlo = 37: odd, so the grid starts below every stage's lo
    fft = _drifting_train(rng, 2 * (N - 1), T, f0=11.37, z_true=4.0,
                          amp=2.0)
    cfg = AccelSearchConfig(zmax=8.0, dz=2.0, numharm=numharm, fhi=fhi,
                            sigma_min=2.0, seg_width=1 << 10, wmax=wmax,
                            topk=16)
    (vals, zi, ri, neigh), g = _ladder_raw_hits(fft, T, cfg)
    Wn, front = len(g["ws"]), g["front"]
    assert g["grid_lo"] == numharm * (37 // numharm) < g["lo"].min()
    assert vals.shape == (g["n_seg"], len(g["stages"]), Wn, cfg.topk)
    if fhi:
        assert len(set(g["hi"].tolist())) == len(g["stages"])

    f = fft.astype(np.complex128)
    spec_pad = np.concatenate([np.conj(f[1:front + 1][::-1]), f,
                               np.zeros(g["Np"] - N)])
    n_hits = 0
    for si in range(g["n_seg"]):
        for s, H in enumerate(g["stages"]):
            ref = _scratch_plane(spec_pad, g, H, si)
            tol = 2e-5 * max(float(ref[np.isfinite(ref)].max(initial=1.0)),
                             1.0)
            for wi in range(Wn):
                sub = ref[wi::Wn]
                padded = np.pad(sub, 1, constant_values=-np.inf)
                v = vals[si, s, wi]
                ok = np.isfinite(v)
                # the strongest valid cell is a local maximum: over
                # threshold it is the first hit, under it there is none
                peak = sub.max()
                if peak > g["thresh"][s] + tol:
                    assert ok[0] and abs(v[0] - peak) <= tol
                elif peak < g["thresh"][s] - tol:
                    assert not ok.any()
                for j in np.nonzero(ok)[0]:
                    z, c = int(zi[si, s, wi, j]), int(ri[si, s, wi, j])
                    assert v[j] > g["thresh"][s]
                    assert abs(v[j] - sub[z, c]) <= tol
                    want = padded[z:z + 3, c:c + 3]
                    got = neigh[si, s, wi, j].astype(np.float64)
                    assert (np.isfinite(got) == np.isfinite(want)).all()
                    fin = np.isfinite(want)
                    assert np.abs(got[fin] - want[fin]).max() <= tol
                    n_hits += 1
    assert n_hits >= g["n_seg"], "spectrum too quiet to exercise the ladder"


def test_ladder_bank_passes_and_stage_mask(tmp_path):
    """A dispatch correlates every ratio bank ONCE a segment
    (``accel.bank_passes`` = segments x len(ratios): 8 banks at numharm
    8, not sum(H) = 15), its span says how many stages and banks it
    walked, and stage H admits no hit below H * rlo although every
    stage shares the grid that starts below them all."""
    from pypulsar_tpu.fourier.accelsearch import accel_search_batch
    from pypulsar_tpu.obs import telemetry

    rng = np.random.RandomState(23)
    N = (1 << 13) + 1
    T = 36.3
    # a strong train with harmonics below 8 * rlo = 296: bright
    # columns inside the grid's first segment that only stage 1 (and 2,
    # 4 above their own lo) may report
    fft = _drifting_train(rng, 2 * (N - 1), T, f0=1.05, z_true=0.0, amp=3.0)
    cfg = AccelSearchConfig(zmax=8.0, dz=2.0, numharm=8, sigma_min=2.0,
                            seg_width=1 << 10, topk=32)
    (vals, _zi, ri, _neigh), g = _ladder_raw_hits(fft, T, cfg)
    assert g["grid_lo"] == 32 and g["lo"].tolist() == [37, 74, 148, 296]
    seen_low = False
    for s, H in enumerate(g["stages"]):
        for si in range(g["n_seg"]):
            ok = np.isfinite(vals[si, s])
            r_top = g["grid_lo"] + si * g["segw"] + 0.5 * ri[si, s][ok]
            assert (r_top >= H * g["rlo"]).all()
            assert (r_top < g["hi"][s]).all()
            seen_low |= bool((r_top < 296).any())
    assert seen_low, "no hit under 8 * rlo: the mask was not exercised"

    import json

    trace = tmp_path / "ladder.jsonl"
    with telemetry.session(str(trace)) as tlm:
        accel_search_batch(np.stack([fft, fft]), T, cfg)
        assert tlm.counter_totals()["accel.bank_passes"] == g["n_seg"] * 8
        accel_search(fft, T, cfg)
        assert tlm.counter_totals()["accel.bank_passes"] == g["n_seg"] * 16
    spans = [r for r in map(json.loads, trace.read_text().splitlines())
             if r.get("name") in ("accel_stage", "accel_stage_batch")]
    assert sorted(r["name"] for r in spans) == ["accel_stage",
                                                "accel_stage_batch"]
    for r in spans:
        assert r["attrs"]["stages"] == 4 and r["attrs"]["banks"] == 8
        assert r["attrs"]["n_seg"] == g["n_seg"] and "H" not in r["attrs"]
