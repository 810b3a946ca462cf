"""Native RFI-mask generator tests (ops/rfifind.py): device-vs-NumPy
stat parity, sigma-clip detection of injected interference, mask-file
round-trip through the reference binary layout, and the CLI."""

import numpy as np
import pytest

from pypulsar_tpu.io.filterbank import write_filterbank
from pypulsar_tpu.io.rfimask import RfifindMask
from pypulsar_tpu.ops.rfifind import (
    RfiStats,
    block_stats,
    block_stats_numpy,
    clip_stats,
    mask_products,
    rfifind,
)

RNG = np.random.RandomState(11)


def make_rfi_data(C=64, nint=20, pts=512):
    """Unit-noise data with three injected interference modes:
    channel 37 loud (20x std), intervals 5-6 broadband (offset +30),
    channel 50 carrying a strong coherent tone (periodic RFI)."""
    T = nint * pts
    data = RNG.randn(C, T).astype(np.float32)
    data[37 % C] *= 20.0
    data[:, 5 * pts : 7 * pts] += 30.0
    t = np.arange(T)
    data[50 % C] += 12.0 * np.sin(2 * np.pi * t / 16.0).astype(np.float32)
    return data, pts


def test_block_stats_matches_numpy_twin():
    data = RNG.randn(8, 4 * 100).astype(np.float32)
    m, s, p = (np.asarray(x) for x in block_stats(data, 100))
    mr, sr, pr = block_stats_numpy(data, 100)
    assert m.shape == (4, 8)
    np.testing.assert_allclose(m, mr, atol=1e-5)
    np.testing.assert_allclose(s, sr, atol=1e-5)
    np.testing.assert_allclose(p, pr, rtol=2e-3)


def test_clip_flags_injected_rfi():
    data, pts = make_rfi_data()
    # hifreq_first=False: treat rows as already being in mask channel
    # order so the injected row indices map straight onto flag columns
    stats, flags, _ = rfifind(data, dt=1e-3, time=pts * 1e-3,
                              hifreq_first=False)
    assert stats.nint == 20 and stats.nchan == 64
    # loud channel: every interval's std is a bandpass outlier
    assert flags[:, 37].all()
    # broadband intervals: most channels' means are timeline outliers
    assert flags[5].mean() > 0.8 and flags[6].mean() > 0.8
    # coherent tone: Fourier max-power detector fires in every interval
    assert flags[:, 50].all()
    # clean cells stay clean (well under the whole-channel threshold)
    clean = np.delete(flags, [37, 50], axis=1)
    clean = np.delete(clean, [5, 6], axis=0)
    assert clean.mean() < 0.05


def test_mask_products_thresholds():
    flags = np.zeros((10, 16), dtype=bool)
    flags[:, 3] = True  # always-bad channel
    flags[7, :10] = True  # mostly-bad interval
    flags[2, 8] = True  # isolated block
    zc, zi, per_int = mask_products(flags, chanfrac=0.7, intfrac=0.3,
                                    extra_zap_chans=[12])
    assert zc == [3, 12]
    assert zi == [7]
    assert per_int[2] == [8]
    assert per_int[7] == []  # covered by the interval zap
    # globally zapped channels are excluded from per-interval lists
    assert all(3 not in chans for chans in per_int)
    # out-of-range extra zaps are rejected (a mask with them would crash
    # every consumer at load)
    with pytest.raises(ValueError):
        mask_products(flags, extra_zap_chans=[16])
    with pytest.raises(ValueError):
        mask_products(flags, extra_zap_chans=[-1])
    with pytest.raises(ValueError):
        mask_products(flags, extra_zap_ints=[10])


def test_end_to_end_mask_file(tmp_path):
    data, pts = make_rfi_data(C=32, nint=12, pts=256)
    dt = 64e-6
    hdr = dict(telescope_id=1, machine_id=2, source_name="FAKE",
               src_raj=0.0, src_dej=0.0, tstart=59000.0, tsamp=dt,
               fch1=1500.0, foff=-0.5, nchans=32, nbits=32, nifs=1)
    # SIGPROC foff<0 stores high-frequency-first: data here IS file order
    fn = str(tmp_path / "rfi.fil")
    write_filterbank(fn, hdr, data.T)

    from pypulsar_tpu.cli.rfifind import main as rfifind_main

    out = str(tmp_path / "test")
    assert rfifind_main([fn, "-o", out, "-t", str(pts * dt),
                         "--zapchan", "2"]) == 0

    mask = RfifindMask(out + "_rfifind.mask")
    assert mask.nchan == 32 and mask.nint == 12
    assert mask.ptsperint == pts
    assert mask.dtint == pytest.approx(pts * dt)
    assert mask.lofreq == pytest.approx(1500.0 - 0.5 * 31)
    # the .fil is foff<0 (file order = high-first); mask channels are
    # low-first, so loud data row 5 is mask channel 32-1-5 = 26
    assert {2, 31 - 37 % 32} <= mask.mask_zap_chans_set
    # the sample-mask expansion covers the broadband intervals
    chan_mask = mask.get_sample_mask(5 * pts, pts)
    assert chan_mask.all()
    stats = RfiStats.load(out + "_rfifind.stats.npz")
    assert stats.mean.shape == (12, 32)


def test_rfifind_psrfits_reader(tmp_path):
    """Mask generation from a PSRFITS file: the get_spectra fallback path
    (always flipped to low-first) finds the same loud channel."""
    from pypulsar_tpu.io import psrfits
    from pypulsar_tpu.ops.rfifind import rfifind as run_rfifind

    C, T = 16, 8 * 256
    rng = np.random.RandomState(4)
    # write_psrfits takes [chan, time] with ascending freqs (file order)
    data = rng.randn(C, T).astype(np.float32) * 2.0 + 10.0
    data[3] *= 25.0  # loud channel, file order = mask channel 3
    freqs = 1400.0 + 1.0 * np.arange(C)
    fn = str(tmp_path / "rfi.fits")
    psrfits.write_psrfits(fn, data, freqs, tsamp=1e-3,
                          nsamp_per_subint=256, nbits=32)
    with psrfits.PsrfitsFile(fn) as pf:
        stats, flags, _ = run_rfifind(pf, time=0.256)
    assert stats.nchan == C and stats.nint == 8
    assert flags[:, 3].all()
    clean = np.delete(flags, 3, axis=1)
    assert clean.mean() < 0.1


def test_rfifind_fbobs_multifile(tmp_path):
    """Mask generation across a multi-file observation (fbobs reader)."""
    from pypulsar_tpu.io.fbobs import FilterbankObs
    from pypulsar_tpu.io.filterbank import write_filterbank
    from pypulsar_tpu.ops.rfifind import rfifind as run_rfifind

    C, Tpart, dt = 16, 1024, 1e-3
    rng = np.random.RandomState(5)
    hdr = dict(telescope_id=1, machine_id=2, source_name="MULTI",
               src_raj=0.0, src_dej=0.0, tsamp=dt, fch1=1500.0,
               foff=-2.0, nchans=C, nbits=32, nifs=1)
    fns = []
    for i in range(3):
        data = rng.randn(Tpart, C).astype(np.float32)
        data[:, 2] *= 25.0  # loud in file order (hi-first row 2)
        fn = str(tmp_path / f"part{i}.fil")
        write_filterbank(fn, dict(hdr, tstart=56000.0 + i * Tpart * dt
                                  / 86400.0), data)
        fns.append(fn)
    obs = FilterbankObs(fns)
    stats, flags, _ = run_rfifind(obs, time=0.256)
    assert stats.nint == 12  # 3 files x 1024 samples / 256
    # file order hi-first: loud row 2 -> mask channel C-1-2
    assert flags[:, C - 1 - 2].all()
    assert stats.mjd == 56000.0


def test_partial_tail_interval_padding():
    # 3 full intervals + 60% of one more: the tail becomes interval 4
    data = RNG.randn(8, 3 * 200 + 120).astype(np.float32)
    stats, flags, _ = rfifind(data, dt=1e-3, time=0.2)
    assert stats.nint == 4
    # under half an interval is dropped instead
    data = RNG.randn(8, 3 * 200 + 50).astype(np.float32)
    stats, _, _ = rfifind(data, dt=1e-3, time=0.2)
    assert stats.nint == 3


def test_sweep_with_mask_suppresses_rfi():
    """rfifind mask -> sweep --mask loop: a loud RFI channel that drowns
    an injected dispersed pulse is masked out and the pulse recovers."""
    from pypulsar_tpu.core.spectra import Spectra
    from pypulsar_tpu.io.rfimask import RfifindMask, write_mask
    from pypulsar_tpu.ops import numpy_ref
    from pypulsar_tpu.parallel.staged import sweep_flat

    C, T, dt, dm_true = 32, 6144, 1e-3, 40.0
    rng = np.random.RandomState(3)
    freqs = (1500.0 - 4.0 * np.arange(C)).astype(np.float64)
    data = rng.randn(C, T).astype(np.float32)
    bins = numpy_ref.bin_delays(dm_true, freqs, dt)
    for c in range(C):
        idx = 900 + bins[c]
        if idx < T:
            data[c, idx] += 10.0
    # bursty RFI in channel 6 (hi-first): strong enough to dominate the
    # zero-DM end of the trial grid and inflate every trial's variance
    data[6, ::37] += 60.0

    stats, flags, _ = rfifind(data, dt=dt, time=512 * dt)
    lo_idx = C - 1 - 6
    assert flags[:, lo_idx].all()

    import tempfile, os
    with tempfile.TemporaryDirectory() as td:
        maskfn = os.path.join(td, "t.mask")
        zc, zi, per_int = mask_products(flags)
        write_mask(maskfn, nchan=stats.nchan, nint=stats.nint,
                   ptsperint=stats.ptsperint, zap_chans=zc, zap_ints=zi,
                   zap_chans_per_int=per_int)
        mask = RfifindMask(maskfn)

    spec = Spectra(freqs, dt, data)
    dms = np.arange(0.0, 80.0, 2.0)
    res_masked = sweep_flat(spec, dms, nsub=8, group_size=8,
                            rfimask=mask).best(1)[0]
    assert abs(res_masked["dm"] - dm_true) <= 4.0
    assert res_masked["snr"] > 7.0
    # unmasked control: the RFI channel's spikes beat the pulse
    res_raw = sweep_flat(spec, dms, nsub=8, group_size=8).best(1)[0]
    assert res_raw["snr"] < res_masked["snr"] or \
        abs(res_raw["dm"] - dm_true) > 4.0


def test_mask_tag_distinguishes_masks(tmp_path):
    """Checkpoint contexts must change when the applied mask changes —
    else a resume could mix masked and unmasked chunk results."""
    from pypulsar_tpu.io.rfimask import RfifindMask, write_mask
    from pypulsar_tpu.parallel.staged import _mask_tag

    assert _mask_tag(None) == ""
    fn1 = str(tmp_path / "a.mask")
    fn2 = str(tmp_path / "b.mask")
    write_mask(fn1, nchan=8, nint=4, ptsperint=100, zap_chans=[1])
    write_mask(fn2, nchan=8, nint=4, ptsperint=100, zap_chans=[2])
    t1 = _mask_tag(RfifindMask(fn1))
    t2 = _mask_tag(RfifindMask(fn2))
    assert t1.startswith("/mask=") and t1 != t2


def test_clip_stats_is_iterative():
    """A strong outlier block must not mask a moderate one: with a single
    pass the strong block inflates the IQR-scale; iteration re-judges."""
    nint, C = 30, 4
    mean = np.zeros((nint, C))
    mean[:, 0] = np.linspace(-0.01, 0.01, nint)
    mean[3, 0] = 1000.0
    mean[4, 0] = 0.2  # ~moderate outlier vs the 0.01-scale spread
    stats = RfiStats(mean=mean, std=np.ones((nint, C)),
                     maxpow=np.full((nint, C), 5.0), ptsperint=256,
                     dtint=1.0, lofreq=1400.0, df=1.0)
    flags = clip_stats(stats, time_sigma=10.0)
    assert flags[3, 0] and flags[4, 0]
    assert not flags[10, 0]


def _numpy_center_scale(x, good, axis):
    """(median, sigma) of every line's good cells by NumPy's own
    ``nanmedian`` / ``nanpercentile``, one Python call a line: what
    ``_robust_center_scale`` was, and the definition it is held to."""
    import warnings

    masked = np.where(good, x, np.nan)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # all-NaN slices
        med = np.nanmedian(masked, axis=axis, keepdims=True)
        q75 = np.nanpercentile(masked, 75, axis=axis, keepdims=True)
        q25 = np.nanpercentile(masked, 25, axis=axis, keepdims=True)
    med = np.where(np.isnan(med), 0.0, med)
    sigma = (q75 - q25) / 1.349
    sigma = np.where(np.isnan(sigma) | (sigma <= 0), np.inf, sigma)
    return med, sigma


# (700, 9): NumPy's nanmedian takes another path from 600 cells a line on
@pytest.mark.parametrize("shape", [(3, 5), (34, 1024), (700, 9), (21, 4096)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("share", [0.0, 0.1, 0.6, 0.97])
@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_center_scale_is_numpys_bit_for_bit(dtype, axis, share, shape):
    """One sort a table and axis gives every line the median and the
    quartiles that NumPy's per-line calls give, to the bit and in the same
    dtype: lines with no good cell, with one, two and three, a line of
    equal values (sigma -> inf), ties, and any share of flags."""
    from pypulsar_tpu.ops.rfifind import _robust_center_scale

    rng = np.random.RandomState(
        [shape[0], shape[1], axis, int(100 * share), dtype().itemsize])
    x = (rng.randn(*shape) * 3 + 100).astype(dtype)
    x[rng.rand(*shape) < 0.2] = 100  # ties, the middle elements among them
    good = rng.rand(*shape) >= share
    xl, gl = np.moveaxis(x, axis, 0), np.moveaxis(good, axis, 0)  # views
    nlines = xl.shape[1]
    for j in range(min(4, nlines)):  # line j keeps j good cells (or all)
        gl[:, j] = np.arange(len(gl)) < j
    if nlines > 4:
        xl[:, 4] = 7.0  # no scale at all
        gl[0, 4] = True
    got = _robust_center_scale(x, good, axis)
    want = _numpy_center_scale(x, good, axis)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == dtype and g.shape == w.shape
        assert np.array_equal(g, w)
    med, sigma = got
    assert med.ravel()[0] == 0 and np.isinf(sigma.ravel()[0])  # none good
    if nlines > 4:
        assert med.ravel()[4] == 7.0 and np.isinf(sigma.ravel()[4])


def _clip_every_line_every_pass(stats, time_sigma, max_iter=10):
    """clip_stats as it was written first: every pass judges every line
    again, by NumPy's per-line calls. The reference for the passes that
    redo only the lines a new flag fell on; returns (flags, passes)."""
    import math

    from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len

    B = fourier_chunk_len(stats.ptsperint) // 2
    q = 0.5 * math.erfc(4.0 / math.sqrt(2.0))
    flags = stats.maxpow > math.log(B / q)
    for n in range(1, max_iter + 1):
        good, new = ~flags, flags.copy()
        for x in (stats.mean, stats.std):
            for axis in (0, 1):
                med, sigma = _numpy_center_scale(x, good, axis)
                new |= np.abs(x - med) > time_sigma * sigma
        if np.array_equal(new, flags):
            break
        flags = new
    return flags, n


@pytest.mark.parametrize("time_sigma", [10.0, 4.0, 2.5])
@pytest.mark.parametrize("seed", range(6))
def test_clip_redoes_only_the_lines_a_flag_fell_on(seed, time_sigma):
    """Tables with outliers that cascade over up to ten passes, a dead
    channel among them: the same flags, cell for cell, as judging every
    line again in every pass."""
    rng = np.random.RandomState(seed)
    nint, nchan = rng.randint(5, 40), rng.randint(8, 200)
    mean = (rng.randn(nint, nchan) * 2 + 100).astype(np.float32)
    std = (np.abs(rng.randn(nint, nchan)) + 50).astype(np.float32)
    for _ in range(rng.randint(1, 12)):
        mean[rng.randint(nint), rng.randint(nchan)] += \
            rng.choice([15, 25, 40, 400]) * rng.choice([-1, 1])
        std[rng.randint(nint), rng.randint(nchan)] *= rng.choice([1.5, 3, 30])
    if seed % 2:
        mean[:, 3] = 7.0  # no scale at all along its timeline
    maxpow = (rng.exponential(1.0, size=(nint, nchan)) * 3).astype(
        np.float32)
    stats = RfiStats(mean=mean, std=std, maxpow=maxpow, ptsperint=64,
                     dtint=1.0, lofreq=300.0, df=1.0)
    want, passes = _clip_every_line_every_pass(stats, time_sigma)
    assert np.array_equal(clip_stats(stats, time_sigma=time_sigma), want)
    if time_sigma == 2.5:
        assert passes > 2  # the case the cache exists for


# ---------------------------------------------------------------------------
# raw ingest: a SIGPROC reader's blocks ship as the file holds them and
# are unpacked / transposed / widened / flipped on the device
# ---------------------------------------------------------------------------

_PTS = 64  # samples an interval (time 0.064 s at 1 ms)
_READ = 16 * _PTS  # samples a read (rfifind's ints_per_read default)
_TAILS = {"none": 0, "dropped": 20, "padded": 40}  # 20 < _PTS // 2 <= 40


def _toy_fil(path, nbits, foff, nsamp, nchan=16, seed=0):
    """A SIGPROC file of integer samples in the range ``nbits`` holds,
    with one saturated interval and one channel that carries a tone;
    returns (fn, number of reads rfifind makes of it)."""
    rng = np.random.RandomState(1000 * nbits + seed)
    top = min((1 << nbits) - 1, 255)
    data = rng.randint(0, top + 1, size=(nsamp, nchan))
    data[3 * _PTS:4 * _PTS] = top  # one interval saturated in every channel
    data[:, 5] = np.where(np.arange(nsamp) % 2, top, 0)  # a tone, all along
    fn = str(path / f"toy{nbits}.fil")
    write_filterbank(fn, dict(fch1=1500.0, foff=foff, nchans=nchan,
                              tsamp=1e-3, nbits=nbits, tstart=59000.0),
                     data.astype(np.float32))
    return fn, -(-nsamp // _READ)


def _run_both(fn, tmp_path, monkeypatch):
    """rfifind() over one FilterbankFile twice: as it is (raw blocks, the
    device ingest) and with the marker hidden on that same reader (the
    host path: _iter_file_blocks' host unpack + consume's float32
    staging). Returns the two (stats, flags, maskfn) triples."""
    from pypulsar_tpu.io.filterbank import FilterbankFile

    with FilterbankFile(fn) as reader:
        dev = rfifind(reader, time=_PTS * 1e-3,
                      outbase=str(tmp_path / "dev"))
        monkeypatch.setattr(reader, "BLOCK_ITER_ARRAYS", False,
                            raising=False)
        host = rfifind(reader, time=_PTS * 1e-3,
                       outbase=str(tmp_path / "host"))
    return dev, host


@pytest.mark.parametrize("tail", sorted(_TAILS))
@pytest.mark.parametrize("foff", [-2.0, 2.0], ids=["descending", "ascending"])
@pytest.mark.parametrize("nbits", [2, 4, 8, 32])
def test_raw_ingest_matches_host_path(tmp_path, monkeypatch, nbits, foff,
                                      tail):
    """Two and a half reads plus the tail: every statistic bit for bit,
    the .mask byte for byte, every array of the stats sidecar equal."""
    nsamp = 2 * _READ + 8 * _PTS + _TAILS[tail]
    fn, _ = _toy_fil(tmp_path, nbits, foff, nsamp)
    (dstats, dflags, dmask), (hstats, hflags, hmask) = _run_both(
        fn, tmp_path, monkeypatch)
    assert dstats.nint == 40 + (tail == "padded")
    for name in ("mean", "std", "maxpow"):
        d, h = getattr(dstats, name), getattr(hstats, name)
        assert d.dtype == h.dtype and d.shape == h.shape
        assert d.tobytes() == h.tobytes(), name
    assert np.array_equal(dflags, hflags)
    assert dflags[:, 15 - 5 if foff < 0 else 5].all()  # the tone's channel
    with open(dmask, "rb") as a, open(hmask, "rb") as b:
        assert a.read() == b.read()
    with np.load(str(tmp_path / "dev_rfifind.stats.npz")) as a, \
            np.load(str(tmp_path / "host_rfifind.stats.npz")) as b:
        assert sorted(a.files) == sorted(b.files)
        for key in a.files:
            assert a[key].dtype == b[key].dtype
            assert a[key].tobytes() == b[key].tobytes(), key


def test_raw_ingest_counts_blocks_and_packed_bytes(tmp_path):
    """A 2-bit file: one raw block a read, the link carries the file's
    packed bytes (whole intervals, plus the tail's pad rows) and not
    their float32 expansion, and the read counter the bytes on disk."""
    from pypulsar_tpu.io.filterbank import FilterbankFile
    from pypulsar_tpu.obs import telemetry

    nchan, nsamp = 16, 2 * _READ + 8 * _PTS + _TAILS["padded"]
    fn, reads = _toy_fil(tmp_path, 2, -2.0, nsamp, nchan=nchan)
    row = nchan * 2 // 8  # packed bytes a spectrum
    with FilterbankFile(fn) as reader, telemetry.session() as tlm:
        stats, _, _ = rfifind(reader, time=_PTS * 1e-3)
        counters = tlm.counter_totals()
        assert "rfifind.ingest" in tlm.stages
    assert reads == 3 and counters["rfifind.raw_blocks"] == reads
    assert counters["rfifind.intervals"] == stats.nint == 41
    assert counters["io.bytes_read"] == nsamp * row
    assert counters["h2d.bytes"] == 41 * _PTS * row  # 24 pad rows in it


@pytest.mark.parametrize("kind", ["psrfits", "fbobs", "array"])
def test_host_path_readers_take_no_raw_ingest(tmp_path, kind):
    """Readers without the marker, and array input, stay on the host
    path: no raw block, float32 on the link, the result as
    block_stats gives it for the same samples."""
    from pypulsar_tpu.obs import telemetry

    C, T, pts = 16, 4 * 256, 256
    rng = np.random.RandomState(6)
    data = (rng.randn(C, T) * 2.0 + 10.0).astype(np.float32)  # low-first
    if kind == "psrfits":
        from pypulsar_tpu.io import psrfits

        fn = str(tmp_path / "h.fits")
        psrfits.write_psrfits(fn, data, 1400.0 + np.arange(C), tsamp=1e-3,
                              nsamp_per_subint=256, nbits=32)
        source, kw = psrfits.PsrfitsFile(fn), {}
    elif kind == "fbobs":
        from pypulsar_tpu.io.fbobs import FilterbankObs

        fns = []
        for i in range(2):
            fn = str(tmp_path / f"h{i}.fil")
            half = data[::-1, i * T // 2:(i + 1) * T // 2].T  # hi-first
            write_filterbank(fn, dict(
                fch1=1400.0 + C - 1, foff=-1.0, nchans=C, tsamp=1e-3,
                nbits=32, tstart=56000.0 + i * (T // 2) * 1e-3 / 86400.0),
                half)
            fns.append(fn)
        source, kw = FilterbankObs(fns), {}
    else:
        source, kw = data, dict(dt=1e-3, hifreq_first=False)
    with telemetry.session() as tlm:
        stats, _, _ = rfifind(source, time=pts * 1e-3, **kw)
        counters = tlm.counter_totals()
        assert "rfifind.ingest" not in tlm.stages
    assert counters.get("rfifind.raw_blocks", 0) == 0
    assert counters["h2d.bytes"] == 4 * C * T
    m, s, p = (np.asarray(x) for x in block_stats(data, pts))
    assert stats.mean.tobytes() == m.tobytes()
    assert stats.std.tobytes() == s.tobytes()
    assert stats.maxpow.tobytes() == p.tobytes()


def test_one_ingest_function_for_sweep_and_mask():
    """The sweep's block source and the mask stage run the SAME device
    ingest (ops/ingest.py): no second copy of the unpack."""
    from pypulsar_tpu.ops import ingest
    from pypulsar_tpu.ops import rfifind as ops_rfifind
    from pypulsar_tpu.parallel import staged

    assert staged._ingest_tc is ops_rfifind._ingest_tc is ingest._ingest_tc
    assert staged._timed_reads is ops_rfifind._timed_reads
