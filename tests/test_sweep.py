"""Sweep-engine tests: NumPy twin parity, chunked-streaming consistency,
multi-device sharding on the virtual CPU mesh, and end-to-end pulse recovery
(SURVEY.md §4 strategies 1-3)."""

import os

import numpy as np
import pytest

import jax

from pypulsar_tpu.core.spectra import Spectra
from pypulsar_tpu.ops import numpy_ref
from pypulsar_tpu.parallel import make_mesh, make_sweep_plan, sweep_spectra


def make_obs(C=64, T=4096, dt=1e-3, dm=80.0, seed=1, amp=6.0, t0=700):
    rng = np.random.RandomState(seed)
    freqs = (1500.0 - 2.0 * np.arange(C)).astype(np.float64)
    data = rng.randn(C, T).astype(np.float32)
    bins = numpy_ref.bin_delays(dm, freqs, dt)
    for c in range(C):
        idx = t0 + bins[c]
        if idx < T:
            data[c, idx] += amp
            if idx + 1 < T:
                data[c, idx + 1] += amp * 0.5
    return freqs, data


def twin_sweep_stats(data, plan, chunk_is_whole_T):
    """Float64 twin of _sweep_chunk_impl for a single whole-series chunk.

    Implements the sweep_stream SNR accumulation-order contract: per-channel
    baseline subtraction first (SNR is exactly invariant; end-of-data padding
    then sits at the baseline level), everything else in float64."""
    data = data - data.mean(axis=1, keepdims=True)
    C, T = data.shape
    W = max(plan.widths)
    out_len = T + W
    slack2 = plan.max_shift2
    need = out_len + slack2 + plan.max_shift1
    padded = np.zeros((C, need))
    padded[:, :T] = data
    per = C // plan.nsub
    D = plan.n_trials
    L1 = out_len + slack2
    s = np.zeros(D)
    ss = np.zeros(D)
    mb = np.zeros((D, len(plan.widths)))
    ab = np.zeros((D, len(plan.widths)), dtype=int)
    for gi in range(plan.n_groups):
        sliced = np.stack(
            [padded[c, plan.stage1_bins[gi, c] : plan.stage1_bins[gi, c] + L1] for c in range(C)]
        )
        sub = sliced.reshape(plan.nsub, per, L1).sum(axis=1)
        for ti in range(plan.group_size):
            d = gi * plan.group_size + ti
            ts = np.zeros(out_len)
            for si in range(plan.nsub):
                st = plan.stage2_bins[gi, ti, si]
                ts += sub[si, st : st + out_len]
            payload = ts[:T]
            s[d] = payload.sum()
            ss[d] = (payload ** 2).sum()
            cs = np.concatenate([[0.0], np.cumsum(ts)])
            for wi, w in enumerate(plan.widths):
                box = cs[w : w + T] - cs[:T]
                mb[d, wi] = box.max()
                ab[d, wi] = box.argmax()
    mean = s / T
    std = np.sqrt(np.maximum(ss / T - mean ** 2, 0.0))
    ws = np.array(plan.widths, dtype=np.float64)
    snr = (mb - ws[None, :] * mean[:, None]) / (
        np.sqrt(ws)[None, :] * np.where(std > 0, std, 1.0)[:, None]
    )
    return snr, ab


def test_sweep_matches_numpy_twin():
    # bound documented in the sweep_stream SNR accumulation-order contract:
    # f32-ulp-scale agreement with the float64 twin (measured ~1e-6 rel)
    freqs, data = make_obs()
    dms = np.linspace(0.0, 160.0, 48)
    spec = Spectra(freqs, 1e-3, data)
    res = sweep_spectra(spec, dms, nsub=16, group_size=8)
    plan = make_sweep_plan(dms, freqs, 1e-3, nsub=16, group_size=8)
    ref_snr, ref_ab = twin_sweep_stats(data, plan, True)
    np.testing.assert_allclose(res.snr, ref_snr[: len(dms)], rtol=5e-6, atol=1e-4)
    np.testing.assert_array_equal(res.peak_sample, ref_ab[: len(dms)])


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_sweep_snr_parity_with_dc_offset(engine):
    """The contract bound must hold for realistic offset data (8-bit PSRFITS
    levels ~100x sigma), not just zero-mean noise: the engine's internal
    per-channel baseline subtraction makes f32 rounding relative to the
    fluctuation scale. Without it the deviation is ~0.2 SNR units."""
    freqs, data = make_obs()
    data = data + np.float32(96.0)  # constant DC: SNR exactly invariant
    dms = np.linspace(0.0, 160.0, 48)
    res = sweep_spectra(Spectra(freqs, 1e-3, data), dms, nsub=16, group_size=8,
                        engine=engine)
    plan = make_sweep_plan(dms, freqs, 1e-3, nsub=16, group_size=8)
    ref_snr, ref_ab = twin_sweep_stats(data.astype(np.float64), plan, True)
    np.testing.assert_allclose(res.snr, ref_snr[: len(dms)], rtol=5e-6, atol=1e-4)
    np.testing.assert_array_equal(res.peak_sample, ref_ab[: len(dms)])
    # reported moments stay in original units
    assert abs(res.mean.mean() - 96.0 * len(freqs)) < 1.0


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_sweep_recovers_injection(engine):
    dm_true, t0 = 80.0, 700
    freqs, data = make_obs(dm=dm_true, t0=t0)
    dms = np.linspace(0.0, 160.0, 81)  # 2 pc/cm^3 steps
    res = sweep_spectra(Spectra(freqs, 1e-3, data), dms, nsub=16, group_size=8,
                        engine=engine)
    best = res.best(1)[0]
    assert abs(best["dm"] - dm_true) <= 4.0
    assert abs(best["sample"] - t0) <= 2
    assert best["snr"] > 15.0


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_chunked_equals_unchunked(engine):
    freqs, data = make_obs(T=4096)
    dms = np.linspace(0.0, 120.0, 32)
    spec = Spectra(freqs, 1e-3, data)
    full = sweep_spectra(spec, dms, nsub=16, group_size=8, engine=engine)
    chunked = sweep_spectra(spec, dms, nsub=16, group_size=8,
                            chunk_payload=1024, engine=engine)
    np.testing.assert_allclose(chunked.snr, full.snr, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(chunked.peak_sample, full.peak_sample)


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_sharded_sweep_matches_single_device(engine):
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    freqs, data = make_obs()
    dms = np.linspace(0.0, 120.0, 64)
    spec = Spectra(freqs, 1e-3, data)
    single = sweep_spectra(spec, dms, nsub=16, group_size=8, engine=engine)
    mesh = make_mesh(axis_names=("dm",))
    sharded = sweep_spectra(spec, dms, nsub=16, group_size=8, mesh=mesh,
                            engine=engine)
    np.testing.assert_allclose(sharded.snr, single.snr, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(sharded.peak_sample, single.peak_sample)


def test_plan_geometry():
    freqs = 1400.0 - 0.5 * np.arange(128)
    plan = make_sweep_plan(np.arange(100, dtype=float), freqs, 64e-6, nsub=32,
                           group_size=16, pad_groups_to=8)
    assert plan.n_groups == 8
    assert plan.n_trials == 128
    assert plan.n_real_trials == 100
    assert plan.stage1_bins.shape == (8, 128)
    assert plan.stage2_bins.shape == (8, 16, 32)
    assert (plan.stage1_bins >= 0).all() and (plan.stage2_bins >= 0).all()
    # higher DM -> larger max shift
    assert plan.stage2_bins[-1].max() >= plan.stage2_bins[0].max()


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_sharded_2d_matches_single_device(engine):
    """dm x time mesh with ppermute halo exchange == single-device result."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from pypulsar_tpu.parallel.sweep import make_sharded_sweep_chunk_2d, sweep_chunk

    freqs, data = make_obs(C=32, T=2048, dt=1e-3, dm=60.0)
    dms = np.linspace(0.0, 120.0, 32)
    plan = make_sweep_plan(dms, freqs, 1e-3, nsub=8, group_size=8, pad_groups_to=4)
    mesh = make_mesh([4, 2], ("dm", "time"))
    T = data.shape[1]
    nt = 2
    local_payload = T // nt
    W = max(plan.widths)
    overlap = plan.min_overlap
    assert overlap < local_payload

    fn2d = make_sharded_sweep_chunk_2d(mesh, plan.nsub, local_payload, overlap,
                                       plan.max_shift2, plan.widths,
                                       engine=engine)
    darr = jax.device_put(jnp.asarray(data), NamedSharding(mesh, P(None, "time")))
    s1 = jax.device_put(jnp.asarray(plan.stage1_bins), NamedSharding(mesh, P("dm")))
    s2 = jax.device_put(jnp.asarray(plan.stage2_bins), NamedSharding(mesh, P("dm")))
    s, ss, mb, ab = fn2d(darr, s1, s2)

    # single-device reference on the zero-padded whole series
    out_len = T + W
    need = out_len + plan.max_shift2 + plan.max_shift1
    padded = jnp.pad(jnp.asarray(data), ((0, 0), (0, need - T)))
    s0, ss0, mb0, ab0 = sweep_chunk(
        padded, jnp.asarray(plan.stage1_bins), jnp.asarray(plan.stage2_bins),
        plan.nsub, out_len, plan.max_shift2, plan.widths, T, engine=engine)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s0), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ss0), rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(np.asarray(mb), np.asarray(mb0), rtol=1e-4, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(ab), np.asarray(ab0))


def test_stream_rejects_short_interior_block():
    # interior blocks lacking the required overlap must raise, not silently
    # zero-pad (seam SNRs would be depressed with no error)
    from pypulsar_tpu.parallel.sweep import make_sweep_plan, sweep_stream

    freqs, data = make_obs(T=4096)
    dms = np.linspace(0.0, 120.0, 16)
    plan = make_sweep_plan(dms, freqs, 1e-3, nsub=16, group_size=8)
    chunk = 1024

    def bad_blocks():  # no overlap at all
        for pos in range(0, 4096, chunk):
            yield pos, data[:, pos : pos + chunk].T

    with pytest.raises(ValueError, match="interior block"):
        sweep_stream(plan, bad_blocks(), chunk)


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_chunked_short_remainder(engine):
    # T % chunk smaller than min_overlap: the penultimate block is short but
    # contains all remaining data, which is legal (end-of-data padding)
    freqs, data = make_obs(T=3 * 1024 + 32)
    dms = np.linspace(0.0, 120.0, 16)
    spec = Spectra(freqs, 1e-3, data)
    full = sweep_spectra(spec, dms, nsub=16, group_size=8, engine=engine)
    chunked = sweep_spectra(spec, dms, nsub=16, group_size=8,
                            chunk_payload=1024, engine=engine)
    np.testing.assert_allclose(chunked.snr, full.snr, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("engine", ["fourier"])
def test_sweep_engine_parity(engine):
    """The chip's chunk kernel reproduces the gather formulation."""
    import jax.numpy as jnp
    from pypulsar_tpu.parallel.sweep import _sweep_chunk_impl, sweep_chunk

    rng = np.random.RandomState(3)
    C, T, nsub, group = 32, 2048, 8, 4
    freqs = 1500.0 - 4.0 * np.arange(C)
    data = rng.randn(C, T).astype(np.float32)
    dms = np.linspace(0.0, 60.0, 8)
    plan = make_sweep_plan(dms, freqs, 1e-3, nsub=nsub, group_size=group)
    W = max(plan.widths)
    out_len = 1024 + W
    need = out_len + plan.max_shift2 + plan.max_shift1
    padded = jnp.asarray(np.pad(data, ((0, 0), (0, max(need - T, 0)))))
    args = (padded, jnp.asarray(plan.stage1_bins),
            jnp.asarray(plan.stage2_bins))
    kw = dict(nsub=plan.nsub, out_len=out_len, slack2=plan.max_shift2,
              widths=plan.widths, stat_len=1024)
    ref = [np.asarray(x) for x in _sweep_chunk_impl(*args, **kw)]
    got = [np.asarray(x) for x in sweep_chunk(*args, engine=engine, **kw)]
    for a, b in zip(ref, got):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("T, payload", [(6000, 2048), (6100, 1000)])
def test_sweep_stream_fourier_engine_end_to_end(T, payload):
    """Streamed multi-chunk sweep under engine='fourier' matches 'gather',
    also at a non-power-of-two chunk payload with a trailing partial
    chunk (6100 / 1000)."""
    from pypulsar_tpu.core.spectra import Spectra

    rng = np.random.RandomState(7)
    C = 32
    freqs = 1500.0 - 4.0 * np.arange(C)
    data = rng.randn(C, T).astype(np.float32)
    dms = np.linspace(0.0, 60.0, 16)
    spec = Spectra(freqs, 1e-3, data)
    a = sweep_spectra(spec, dms, nsub=8, group_size=4, chunk_payload=payload,
                      engine="gather")
    b = sweep_spectra(spec, dms, nsub=8, group_size=4, chunk_payload=payload,
                      engine="fourier")
    np.testing.assert_allclose(b.snr, a.snr, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(b.peak_sample, a.peak_sample)
    np.testing.assert_allclose(b.mean, a.mean, rtol=1e-5, atol=1e-5)


def test_fourier_engine_snr_tolerance():
    """The PUBLISHED parity contract (README "Golden parity"; bench JSON
    ``fourier_snr_rel_tol``; ops/fourier_dedisperse.py docstring): engine=
    'gather' is the bit-exact-SNR reference formulation; the TPU-default
    fourier engine agrees to <=2e-6 relative SNR (measured worst case 5e-7
    across seeds/geometries; ~1e-6 on-chip under chunk-dependent XLA
    fusion). This test pins the documented number itself (VERDICT r4
    item 7 — one value cited everywhere)."""
    from pypulsar_tpu.core.spectra import Spectra

    rng = np.random.RandomState(19)
    C, T = 64, 8192
    freqs = 1500.0 - 2.0 * np.arange(C)
    data = rng.randn(C, T).astype(np.float32)
    data[:, 4000:4004] += 4.0  # a real pulse so peak SNRs are O(10)
    dms = np.linspace(0.0, 80.0, 32)
    spec = Spectra(freqs, 1e-3, data)
    a = sweep_spectra(spec, dms, nsub=16, group_size=8, engine="gather")
    b = sweep_spectra(spec, dms, nsub=16, group_size=8, engine="fourier")
    rel = np.abs(b.snr - a.snr) / np.maximum(np.abs(a.snr), 1.0)
    assert rel.max() <= 2e-6, f"fourier SNR rel err {rel.max():.2e} > 2e-6"


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_checkpoint_kill_and_resume_bit_exact(tmp_path, engine):
    """A sweep killed mid-stream and resumed from its checkpoint reproduces
    the uninterrupted result bit-for-bit (VERDICT r2 item 7), and the
    engine is part of the checkpoint's fingerprint context: a run under
    the other engine does not resume it."""
    import shutil

    from pypulsar_tpu.parallel.sweep import SweepCheckpoint, sweep_stream

    rng = np.random.RandomState(11)
    C, T, payload = 32, 9000, 2048
    freqs = 1500.0 - 4.0 * np.arange(C)
    data = rng.randn(C, T).astype(np.float32)
    dms = np.linspace(0.0, 60.0, 16)
    plan = make_sweep_plan(dms, freqs, 1e-3, nsub=8, group_size=4)
    baseline = data.mean(axis=1, keepdims=True).astype(np.float32)

    def blocks():
        ov = plan.min_overlap
        pos = 0
        while pos < T:
            n = min(payload + ov, T - pos)
            yield pos, data[:, pos:pos + n]
            pos += payload

    ref = sweep_stream(plan, blocks(), payload, chan_major=True,
                       baseline=baseline, engine=engine)

    class Killed(Exception):
        pass

    def killing_blocks(n_before_kill):
        for i, (pos, blk) in enumerate(blocks()):
            if i >= n_before_kill:
                raise Killed()
            yield pos, blk

    ck_path = str(tmp_path / "sweep.ckpt.npz")
    ckpt = SweepCheckpoint(ck_path, every=1)
    with pytest.raises(Killed):
        # max_pending=1 so at least one chunk drains (and checkpoints)
        # before the stream dies
        sweep_stream(plan, killing_blocks(4), payload, chan_major=True,
                     baseline=baseline, checkpoint=ckpt, max_pending=1,
                     engine=engine)
    assert os.path.exists(ck_path), "checkpoint file not written"

    # the other engine, handed a copy of this checkpoint, restarts from
    # the first sample (the engines differ at f32 rounding, so resumed
    # accumulators would show) and still equals its own uninterrupted run
    other = "gather" if engine == "fourier" else "fourier"
    ck_other = str(tmp_path / "other.ckpt.npz")
    shutil.copy(ck_path, ck_other)
    o_ref = sweep_stream(plan, blocks(), payload, chan_major=True,
                         baseline=baseline, engine=other)
    o_got = sweep_stream(plan, blocks(), payload, chan_major=True,
                         baseline=baseline, engine=other,
                         checkpoint=SweepCheckpoint(ck_other, every=1))
    np.testing.assert_array_equal(o_got.snr, o_ref.snr)
    np.testing.assert_array_equal(o_got.mean, o_ref.mean)

    res = sweep_stream(plan, blocks(), payload, chan_major=True,
                       baseline=baseline, engine=engine,
                       checkpoint=SweepCheckpoint(ck_path, every=1))
    np.testing.assert_array_equal(res.snr, ref.snr)
    np.testing.assert_array_equal(res.peak_sample, ref.peak_sample)
    np.testing.assert_array_equal(res.mean, ref.mean)
    np.testing.assert_array_equal(res.std, ref.std)
    assert not os.path.exists(ck_path), "checkpoint not cleaned up"


def test_choose_group_size_scales_with_trial_density():
    from pypulsar_tpu.parallel import choose_group_size

    freqs = (1500.0 - 300.0 / 1024 * np.arange(1024)).astype(np.float64)
    dt = 64e-6
    # dDM ~ 0.031 / 0.12 / 7.9 pc/cm^3
    denser = np.linspace(0.0, 500.0, 16384)
    dense = np.linspace(0.0, 500.0, 4096)
    sparse = np.linspace(0.0, 500.0, 64)
    g_denser = choose_group_size(denser, freqs, dt, nsub=64)
    g_dense = choose_group_size(dense, freqs, dt, nsub=64)
    g_sparse = choose_group_size(sparse, freqs, dt, nsub=64)
    assert g_denser > g_dense > g_sparse  # monotone in trial density
    assert g_denser == 128  # hits max_group
    assert g_sparse <= 4
    assert choose_group_size([10.0], freqs, dt) == 1  # single trial
    # the chosen group's own smearing respects the bound
    from pypulsar_tpu.core import psrmath

    bw_sub = 300.0 / 64
    for g, dms in ((g_dense, dense), (g_sparse, sparse)):
        ddm = float(np.diff(dms)[0])
        # worst trial sits ((g-1)/2) steps from the group mean DM
        assert psrmath.dm_smear(((g - 1) / 2) * ddm, bw_sub,
                                float(freqs.min())) <= 1.0 * dt


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_checkpoint_resume_with_chunk_peaks(tmp_path, engine):
    """keep_chunk_peaks persists through a kill-and-resume: the multi-
    event list matches the uninterrupted run exactly, and a checkpoint
    written without peaks is not resumed into a peak run."""
    from pypulsar_tpu.parallel.sweep import SweepCheckpoint, sweep_stream

    rng = np.random.RandomState(13)
    C, T, payload = 32, 9000, 2048
    freqs = 1500.0 - 4.0 * np.arange(C)
    data = rng.randn(C, T).astype(np.float32)
    data[:, 1000] += 4.0  # chunk-0 event
    data[:, 7000] += 4.0  # chunk-3 event
    # 14 trials with group_size 4 -> padded to 16: n_real < n_trials
    # exercises the chunk-peak slice against the padded moment arrays
    dms = np.linspace(0.0, 60.0, 14)
    plan = make_sweep_plan(dms, freqs, 1e-3, nsub=8, group_size=4)
    baseline = data.mean(axis=1, keepdims=True).astype(np.float32)

    def blocks():
        ov = plan.min_overlap
        pos = 0
        while pos < T:
            n = min(payload + ov, T - pos)
            yield pos, data[:, pos:pos + n]
            pos += payload

    ref = sweep_stream(plan, blocks(), payload, chan_major=True,
                       baseline=baseline, keep_chunk_peaks=True,
                       engine=engine)
    ref_events = ref.events(5.0)
    assert len({e["sample"] // payload for e in ref_events}) >= 2

    class Killed(Exception):
        pass

    def killing_blocks(n):
        for i, (pos, blk) in enumerate(blocks()):
            if i >= n:
                raise Killed()
            yield pos, blk

    ck = str(tmp_path / "pk.ckpt.npz")
    with pytest.raises(Killed):
        sweep_stream(plan, killing_blocks(3), payload, chan_major=True,
                     baseline=baseline, keep_chunk_peaks=True,
                     checkpoint=SweepCheckpoint(ck, every=1),
                     max_pending=1, engine=engine)
    assert os.path.exists(ck)
    res = sweep_stream(plan, blocks(), payload, chan_major=True,
                       baseline=baseline, keep_chunk_peaks=True,
                       checkpoint=SweepCheckpoint(ck, every=1),
                       engine=engine)
    np.testing.assert_array_equal(res.chunk_snr, ref.chunk_snr)
    np.testing.assert_array_equal(res.chunk_sample, ref.chunk_sample)
    assert res.events(5.0) == ref_events

    # a peak-less checkpoint must not satisfy a keep_chunk_peaks resume
    ck2 = str(tmp_path / "nopk.ckpt.npz")
    with pytest.raises(Killed):
        sweep_stream(plan, killing_blocks(3), payload, chan_major=True,
                     baseline=baseline,
                     checkpoint=SweepCheckpoint(ck2, every=1),
                     max_pending=1, engine=engine)
    res2 = sweep_stream(plan, blocks(), payload, chan_major=True,
                        baseline=baseline, keep_chunk_peaks=True,
                        checkpoint=SweepCheckpoint(ck2, every=1),
                        engine=engine)
    np.testing.assert_array_equal(res2.chunk_snr, ref.chunk_snr)


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_checkpoint_fingerprint_mismatch_restarts(tmp_path, engine):
    """A checkpoint from different sweep parameters is ignored."""
    from pypulsar_tpu.parallel.sweep import SweepCheckpoint, sweep_stream

    rng = np.random.RandomState(12)
    C, T, payload = 32, 5000, 2048
    freqs = 1500.0 - 4.0 * np.arange(C)
    data = rng.randn(C, T).astype(np.float32)
    plan_a = make_sweep_plan(np.linspace(0, 60, 8), freqs, 1e-3,
                             nsub=8, group_size=4)
    plan_b = make_sweep_plan(np.linspace(0, 80, 8), freqs, 1e-3,
                             nsub=8, group_size=4)

    def blocks(plan):
        ov = plan.min_overlap
        pos = 0
        while pos < T:
            n = min(payload + ov, T - pos)
            yield pos, data[:, pos:pos + n]
            pos += payload

    ck = str(tmp_path / "x.npz")
    sweep_stream(plan_a, blocks(plan_a), payload, chan_major=True,
                 checkpoint=SweepCheckpoint(ck, every=1, cleanup=False),
                 engine=engine)
    ref_b = sweep_stream(plan_b, blocks(plan_b), payload, chan_major=True,
                         engine=engine)
    got_b = sweep_stream(plan_b, blocks(plan_b), payload, chan_major=True,
                         checkpoint=SweepCheckpoint(ck, every=1),
                         engine=engine)
    np.testing.assert_array_equal(got_b.snr, ref_b.snr)


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_ddplan_staged_checkpoint_resume(tmp_path, engine):
    """Killing a staged DDplan sweep mid-plan resumes completed steps from
    their done markers and reproduces the uninterrupted result."""
    from pypulsar_tpu.core.spectra import Spectra
    from pypulsar_tpu.parallel import staged
    from pypulsar_tpu.plan.ddplan import Observation

    rng = np.random.RandomState(13)
    C, T = 32, 16384
    dt = 1e-3
    freqs = 1500.0 - 4.0 * np.arange(C)
    data = rng.randn(C, T).astype(np.float32)
    spec = Spectra(freqs, dt, data)
    obs = Observation(dt=dt, fctr=float(freqs.mean()),
                      BW=float(freqs.max() - freqs.min() + 4.0), numchan=C)
    plan = obs.gen_ddplan(0.0, 400.0)
    assert len(plan.DDsteps) >= 2, "test needs a multi-step plan"

    ref = staged.sweep_ddplan(spec, plan, nsub=8, group_size=4,
                              engine=engine)

    base = str(tmp_path / "stg")
    # interrupt after the first step by making the second step fail once
    calls = {"n": 0}
    orig = staged._run_step

    def failing_run_step(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise KeyboardInterrupt()
        return orig(*a, **kw)

    staged._run_step = failing_run_step
    try:
        with pytest.raises(KeyboardInterrupt):
            staged.sweep_ddplan(spec, plan, nsub=8, group_size=4,
                                checkpoint_path=base, engine=engine)
    finally:
        staged._run_step = orig
    assert os.path.exists(base + ".step0.done.npz")

    got = staged.sweep_ddplan(spec, plan, nsub=8, group_size=4,
                              checkpoint_path=base, engine=engine)
    assert len(got.steps) == len(ref.steps)
    for sa, sb in zip(got.steps, ref.steps):
        np.testing.assert_array_equal(sa.result.snr, sb.result.snr)
        np.testing.assert_array_equal(sa.result.peak_sample,
                                      sb.result.peak_sample)
    assert not os.path.exists(base + ".step0.done.npz"), "markers not cleared"


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_sweep_resident_matches_streamed(engine):
    """The single-dispatch resident sweep is bit-identical to the streamed
    path at the same chunking (same per-chunk kernels, same host-order
    f64 accumulation)."""
    from pypulsar_tpu.parallel.sweep import sweep_resident

    freqs, data = make_obs(T=4096)
    dms = np.linspace(0.0, 120.0, 32)
    spec = Spectra(freqs, 1e-3, data)
    streamed = sweep_spectra(spec, dms, nsub=16, group_size=8,
                             chunk_payload=1024, engine=engine)
    resident = sweep_resident(spec, dms, nsub=16, group_size=8,
                              chunk_payload=1024, engine=engine)
    np.testing.assert_array_equal(resident.snr, streamed.snr)
    np.testing.assert_array_equal(resident.peak_sample, streamed.peak_sample)
    np.testing.assert_array_equal(resident.mean, streamed.mean)


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_sweep_resident_sharded_matches(engine):
    from pypulsar_tpu.parallel.sweep import sweep_resident

    freqs, data = make_obs(T=4096)
    dms = np.linspace(0.0, 120.0, 64)
    spec = Spectra(freqs, 1e-3, data)
    mesh = make_mesh(axis_names=("dm",))
    single = sweep_resident(spec, dms, nsub=16, group_size=8,
                            chunk_payload=2048, engine=engine)
    sharded = sweep_resident(spec, dms, nsub=16, group_size=8,
                             chunk_payload=2048, mesh=mesh, engine=engine)
    np.testing.assert_allclose(sharded.snr, single.snr, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(sharded.peak_sample, single.peak_sample)


def test_bench_budget_shapes():
    """bench.py's HBM budgeting: fits in the budget, power-of-two FFT
    lengths, sane pending depth (VERDICT r2 item 1)."""
    bench = _load_bench()

    C = 1024
    freqs = (1500.0 - 300.0 / C * np.arange(C)).astype(np.float64)
    dms = np.linspace(0.0, 500.0, 1024)
    plan = make_sweep_plan(dms, freqs, 64e-6, nsub=64, group_size=32)
    T, payload, n, max_pending = bench.budget_shapes(C, 1 << 21, plan, 16e9)
    assert n & (n - 1) == 0  # power of two
    assert payload == n - plan.min_overlap
    assert 1 <= max_pending <= 4
    # accounting: dataset + pending chunks + workspace within 75% of HBM
    total = 4 * C * T + max_pending * 4 * C * n + 3 * 4 * C * n
    assert total <= 0.80 * 16e9
    # a tiny budget still returns a usable (min-sized) configuration
    T2, payload2, n2, mp2 = bench.budget_shapes(C, 1 << 21, plan, 2e9)
    assert T2 >= payload2 and mp2 >= 1

    # analytic traffic is positive and scales with T
    b1 = bench.sweep_bytes(plan, C, T, payload, n, "fourier")
    b2 = bench.sweep_bytes(plan, C, 2 * T, payload, n, "fourier")
    assert 0 < b1 < b2


def _load_bench():
    import importlib.util
    import os as _os

    spec = importlib.util.spec_from_file_location(
        "bench", _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


@pytest.mark.parametrize("mode", [
    [], ["--quick"], ["--ab"], ["--accel"], ["--fold"], ["--waterfall"],
    ["--prepass"], ["--survey"], ["--tune"], ["--obs-overhead"]])
def test_bench_device_metric_modes_fail_without_a_tpu(mode, capsys):
    """A mode whose value is a time, a rate or a wall ratio has no value
    without the device: on the CPU bench.main() exits non-zero and prints
    `"ok": false` with the device's name — never a record under the
    metric's name, never a CPU re-run."""
    import json as _json

    bench = _load_bench()
    assert bench.main(mode) != 0
    rec = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["ok"] is False and "metric" not in rec
    assert rec["platform"] == "cpu" and rec["device_count"] >= 1
    assert "device_kind" in rec


def test_bench_peaks_table_refuses_unknown_device_kind():
    bench = _load_bench()
    assert bench.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        bench.device_peaks("cpu")

    class NoStats:
        def memory_stats(self):
            return None

    with pytest.raises(RuntimeError, match="bytes_limit"):
        bench.device_hbm_bytes(NoStats())


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_multi_event_chunk_peaks(engine):
    """keep_chunk_peaks records one event per (chunk, trial, width): two
    injected pulses in different chunks both appear in events(), while the
    single-best fields keep only the stronger."""
    rng = np.random.RandomState(51)
    C, T, dt, dm = 32, 8192, 1e-3, 60.0
    freqs = 1500.0 - 4.0 * np.arange(C)
    data = rng.randn(C, T).astype(np.float32)
    bins = numpy_ref.bin_delays(dm, freqs, dt)
    for t0, amp in ((1000, 10.0), (6000, 7.0)):
        for c in range(C):
            idx = t0 + bins[c]
            if idx < T:
                data[c, idx] += amp

    from pypulsar_tpu.parallel.sweep import sweep_stream

    dms = np.linspace(0.0, 120.0, 16)
    plan = make_sweep_plan(dms, freqs, dt, nsub=8, group_size=4)
    payload = 2048
    baseline = data.mean(axis=1, keepdims=True).astype(np.float32)

    def blocks():
        ov = plan.min_overlap
        pos = 0
        while pos < T:
            n = min(payload + ov, T - pos)
            yield pos, data[:, pos:pos + n]
            pos += payload

    res = sweep_stream(plan, blocks(), payload, chan_major=True,
                       baseline=baseline, keep_chunk_peaks=True,
                       engine=engine)
    events = res.events(8.0)
    assert events
    # both pulses present at a near-true DM
    near = [e for e in events if abs(e["dm"] - dm) <= 16.0]
    samples = {e["sample"] // 1000 for e in near}
    assert 1 in samples and 6 in samples, near
    # the single-best surface keeps only the stronger pulse
    di = int(np.argmin(np.abs(res.dms - dm)))
    wi = int(np.argmax(res.snr[di]))
    assert abs(res.peak_sample[di, wi] - 1000) < 50

    # without the flag, events() refuses
    res2 = sweep_stream(plan, blocks(), payload, chan_major=True,
                        baseline=baseline, engine=engine)
    with pytest.raises(ValueError):
        res2.events(8.0)


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_series_chunk_exact_shift(engine):
    """Every trial's series applies exactly the per-channel integer shift
    s1+s2 of the plan — checked against an f64 direct-shift sum
    (agreement at f32 rounding of the SUM, with zero shift/index error: a
    one-sample shift slip would show up as O(1) differences)."""
    from pypulsar_tpu.parallel.sweep import dedisperse_series_chunk

    rng = np.random.RandomState(7)
    C, nsub, group = 48, 8, 4  # non-pow2 nchan
    freqs = 1500.0 - 4.0 * np.arange(C)
    dms = np.linspace(0.0, 60.0, 10)  # pads to 12 trials
    plan = make_sweep_plan(dms, freqs, 1e-3, nsub=nsub, group_size=group)
    out_len = 512
    need = out_len + plan.max_shift2 + plan.max_shift1
    data = rng.randn(C, need).astype(np.float32)
    got = np.asarray(dedisperse_series_chunk(
        data, plan.stage1_bins, plan.stage2_bins, plan.nsub, out_len,
        plan.max_shift2, engine))
    per = C // plan.nsub
    tot = (plan.stage1_bins[:, None, :]
           + np.repeat(plan.stage2_bins, per, axis=2)).reshape(-1, C)
    d64 = data.astype(np.float64)
    for d in range(plan.n_trials):
        exact = np.zeros(out_len)
        for c in range(C):
            exact += d64[c, tot[d, c]:tot[d, c] + out_len]
        np.testing.assert_allclose(got[d], exact, rtol=2e-5, atol=2e-4)


def test_chunk_kernels_take_a_resolved_engine():
    """'auto' is decided once, at a public entry (resolve_engine); a
    chunk kernel handed 'auto' or a deleted engine's name raises and
    never picks a formulation inside a trace."""
    from pypulsar_tpu.parallel.sweep import (
        ENGINES,
        _dedisperse_series_impl,
        _sweep_chunk_impl,
        resolve_engine,
    )

    assert ENGINES == ("gather", "fourier")
    assert resolve_engine("auto") == "gather"  # the tests' CPU backend
    plan = make_sweep_plan(np.linspace(0.0, 120.0, 16),
                           1500.0 - 2.0 * np.arange(64), 1e-3, nsub=16,
                           group_size=8)
    data = np.zeros((64, 64), np.float32)
    for engine in "auto scan tree".split():
        with pytest.raises(ValueError, match="resolved engine"):
            _sweep_chunk_impl(data, plan.stage1_bins, plan.stage2_bins,
                              nsub=16, out_len=32, slack2=0, widths=(1,),
                              stat_len=32, engine=engine)
        with pytest.raises(ValueError, match="resolved engine"):
            _dedisperse_series_impl(data, plan.stage1_bins,
                                    plan.stage2_bins, 16, 32, 0, engine)
    with pytest.raises(ValueError, match="unknown sweep engine"):
        resolve_engine("tree")


@pytest.mark.parametrize("bad", "fourrier scan tree".split())
def test_cli_engine_validation(bad, capsys):
    """--engine is validated at ARGPARSE time against the ENGINES
    registry with a difflib closest-match hint (the cli/__main__
    unknown-tool pattern) and never reaches resolve_engine mid-run; the
    engines that left in PR 29 are refused like any unknown name."""
    from pypulsar_tpu.cli import sweep as cli_sweep

    with pytest.raises(SystemExit) as e:
        cli_sweep.main(["x.fil", "--numdms", "4", "--engine", bad])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert f"unknown sweep engine {bad!r}" in err
    assert "auto, gather, fourier" in err  # the registry listing
    if bad == "fourrier":
        assert "did you mean 'fourier'?" in err


def _plan_with_delay(dm_hi, nchan=64, dt=64e-6):
    """A two-trial plan whose top DM sets the overlap."""
    from pypulsar_tpu.parallel.sweep import make_sweep_plan

    freqs = 1500.0 - (300.0 / nchan) * np.arange(nchan)
    return make_sweep_plan([0.0, dm_hi], freqs, dt, nsub=8, group_size=2)


def test_default_chunk_payload_bounds():
    """Round-5 regression: the streaming default payload is BOUNDED
    (DEFAULT_CHUNK_FFT_LEN-derived) — the old whole-file default made a
    --chunk-less sweep of an hour-scale file try to build one ~2^26-
    sample chunk (a ~275 GB device buffer). The helper must also grow
    past overlaps that don't fit half the FFT."""
    from pypulsar_tpu.parallel.sweep import (DEFAULT_CHUNK_FFT_LEN,
                                             default_chunk_payload)

    plan = _plan_with_delay(100.0)
    assert 0 < plan.min_overlap < DEFAULT_CHUNK_FFT_LEN // 2
    assert default_chunk_payload(plan) \
        == DEFAULT_CHUNK_FFT_LEN - plan.min_overlap
    wide = _plan_with_delay(10000.0)  # overlap >= n/2
    assert wide.min_overlap >= DEFAULT_CHUNK_FFT_LEN // 2
    n = default_chunk_payload(wide) + wide.min_overlap
    assert n > DEFAULT_CHUNK_FFT_LEN and n & (n - 1) == 0
    assert wide.min_overlap < n // 2
