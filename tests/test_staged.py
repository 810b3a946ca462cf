"""Staged DDplan execution + sweep CLI tests (VERDICT round-1 item 4:
configs[2] end-to-end from the command line)."""

import os

import numpy as np
import pytest

from pypulsar_tpu.core.spectra import Spectra
from pypulsar_tpu.io import filterbank
from pypulsar_tpu.ops import numpy_ref
from pypulsar_tpu.plan.ddplan import Observation


def synth_fil(tmp_path, C=64, T=8192, dt=1e-3, dm=60.0, t0=900, amp=7.0,
              seed=2, name="synth.fil"):
    rng = np.random.RandomState(seed)
    freqs = (1500.0 - 2.0 * np.arange(C)).astype(np.float64)
    data = rng.randn(T, C).astype(np.float32) + 50.0  # DC offset on purpose
    bins = numpy_ref.bin_delays(dm, freqs, dt)
    for c in range(C):
        idx = t0 + bins[c]
        for k, a in ((0, amp), (1, amp * 0.6)):
            if idx + k < T:
                data[idx + k, c] += a
    fn = str(tmp_path / name)
    hdr = dict(filterbank.DEFAULT_HEADER)
    hdr.update(nchans=C, fch1=freqs[0], foff=freqs[1] - freqs[0], tsamp=dt,
               nbits=32)
    filterbank.write_filterbank(fn, hdr, data)
    return fn, freqs, data


def test_sweep_ddplan_staged_recovers_injection(tmp_path):
    from pypulsar_tpu.parallel.staged import sweep_ddplan

    dm_true, t0, dt = 60.0, 900, 1e-3
    fn, freqs, _ = synth_fil(tmp_path, dm=dm_true, t0=t0, dt=dt)
    fil = filterbank.FilterbankFile(fn)
    bw = abs(freqs[0] - freqs[-1]) + 2.0
    obs = Observation(dt=dt, fctr=float(freqs.mean()), BW=bw,
                      numchan=len(freqs))
    plan = obs.gen_ddplan(0.0, 120.0)
    assert len(plan.DDsteps) >= 1
    staged = sweep_ddplan(fil, plan, nsub=16, group_size=8)
    # every step ran with its own downsampling
    assert [s.downsamp for s in staged.steps] == \
        [st.downsamp for st in plan.DDsteps][: len(staged.steps)]
    best = staged.best(1)[0]
    assert abs(best["dm"] - dm_true) <= 2 * plan.DDsteps[0].dDM + 1.0
    assert abs(best["time_sec"] - t0 * dt) <= 0.005
    assert best["snr"] > 10.0


def test_staged_step_equals_flat_sweep(tmp_path):
    """A one-step staged run must equal sweep_spectra on the same DMs."""
    from pypulsar_tpu.parallel import sweep_spectra
    from pypulsar_tpu.parallel.staged import sweep_ddplan

    fn, freqs, data = synth_fil(tmp_path, T=4096)
    fil = filterbank.FilterbankFile(fn)
    obs = Observation(dt=1e-3, fctr=float(freqs.mean()),
                      BW=abs(freqs[0] - freqs[-1]) + 2.0, numchan=len(freqs))
    plan = obs.gen_ddplan(0.0, 30.0)
    step0 = plan.DDsteps[0]
    staged = sweep_ddplan(fil, plan, nsub=16, group_size=8)
    if step0.downsamp == 1:
        spec = Spectra(freqs, 1e-3, np.ascontiguousarray(data.T))
        flat = sweep_spectra(spec, step0.DMs, nsub=16, group_size=8)
        np.testing.assert_allclose(staged.steps[0].result.snr, flat.snr,
                                   rtol=5e-6, atol=1e-4)


def test_staged_chunked_consistency(tmp_path):
    from pypulsar_tpu.parallel.staged import sweep_ddplan

    fn, freqs, _ = synth_fil(tmp_path, T=8192)
    fil = filterbank.FilterbankFile(fn)
    obs = Observation(dt=1e-3, fctr=float(freqs.mean()),
                      BW=abs(freqs[0] - freqs[-1]) + 2.0, numchan=len(freqs))
    plan = obs.gen_ddplan(0.0, 80.0)
    whole = sweep_ddplan(fil, plan, nsub=16, group_size=8)
    chunked = sweep_ddplan(fil, plan, nsub=16, group_size=8,
                           chunk_payload=2048)
    for a, b in zip(whole.steps, chunked.steps):
        # baseline comes from the first block (chunk-dependent), so the
        # guarantee here is detection-level consistency, not ulp parity
        np.testing.assert_allclose(a.result.snr, b.result.snr,
                                   rtol=1e-3, atol=5e-3)


def test_ship_ahead_disabled_matches_enabled(tmp_path, monkeypatch):
    """PYPULSAR_TPU_SHIP_AHEAD=0 (inline ship, single-threaded debugging
    path) produces bit-identical sweep results to the default background
    ship thread — threading must only move WHEN blocks ship, never what
    arrives or in what order."""
    from pypulsar_tpu.parallel.staged import sweep_flat

    fn, freqs, _ = synth_fil(tmp_path, T=8192, name="ship.fil")
    dms = np.linspace(0.0, 80.0, 16)
    fil = filterbank.FilterbankFile(fn)
    default = sweep_flat(fil, dms, nsub=16, group_size=8,
                         chunk_payload=2048)
    monkeypatch.setenv("PYPULSAR_TPU_SHIP_AHEAD", "0")
    inline = sweep_flat(filterbank.FilterbankFile(fn), dms, nsub=16,
                        group_size=8, chunk_payload=2048)
    a, b = default.steps[0].result, inline.steps[0].result
    np.testing.assert_array_equal(a.snr, b.snr)
    np.testing.assert_array_equal(a.peak_sample, b.peak_sample)


def test_ship_ahead_propagates_worker_errors():
    """An exception in the block producer (disk error, bad header)
    surfaces in the consumer instead of hanging or being swallowed by
    the ship thread."""
    import pytest

    from pypulsar_tpu.parallel.staged import _ship_ahead

    def bad_blocks():
        yield 0, np.zeros((4, 16), np.float32)
        raise OSError("disk pulled")

    it = _ship_ahead(bad_blocks())
    pos, _ = next(it)
    assert pos == 0
    with pytest.raises(OSError, match="disk pulled"):
        for _ in it:
            pass


def test_ship_ahead_abandoned_consumer_stops_worker():
    """Breaking out of the stream signals the ship thread to stop
    instead of shipping the remaining blocks (review r4: an abandoned
    57 GB sweep must not spend minutes shipping the rest of the file)."""
    import threading
    import time

    from pypulsar_tpu.parallel.staged import _ship_ahead

    produced = []

    def blocks():
        for i in range(1000):
            produced.append(i)
            yield i, np.zeros((4, 16), np.float32)

    it = _ship_ahead(blocks(), depth=2)
    next(it)
    it.close()  # GeneratorExit -> stop event + drain
    deadline = time.time() + 5.0
    while time.time() < deadline and any(
            t.name == "pypulsar-ship-ahead" and t.is_alive()
            for t in threading.enumerate()):
        time.sleep(0.05)
    assert not any(t.name == "pypulsar-ship-ahead" and t.is_alive()
                   for t in threading.enumerate())
    assert len(produced) < 20  # worker stopped early, not after 1000


def test_sweep_cli_flat_writes_cands(tmp_path, capsys):
    from pypulsar_tpu.cli import sweep as sweep_cli

    dm_true, t0, dt = 60.0, 900, 1e-3
    fn, _, _ = synth_fil(tmp_path, dm=dm_true, t0=t0, dt=dt)
    out = str(tmp_path / "out")
    rc = sweep_cli.main([fn, "-o", out, "--lodm", "0", "--dmstep", "2.5",
                         "--numdms", "48", "-s", "16", "--group-size", "8",
                         "--threshold", "8"])
    assert rc == 0
    cands = out + ".cands"
    assert os.path.exists(cands)
    rows = [ln.split() for ln in open(cands) if not ln.startswith("#")]
    assert rows, "threshold crossings expected for a 7-sigma injection"
    stdout = capsys.readouterr().out
    assert "DM" in stdout
    dms = [float(r[0]) for r in rows]
    snrs = [float(r[1]) for r in rows]
    assert any(abs(d - dm_true) <= 5.0 for d in dms)
    assert max(snrs) > 10.0


def test_sweep_cli_ddplan_mode(tmp_path):
    from pypulsar_tpu.cli import sweep as sweep_cli

    fn, _, _ = synth_fil(tmp_path, T=8192)
    out = str(tmp_path / "plan_out")
    rc = sweep_cli.main([fn, "-o", out, "--ddplan", "--lodm", "0",
                         "--hidm", "100", "-s", "16", "--group-size", "8"])
    assert rc == 0
    assert os.path.exists(out + ".cands")


def test_sweep_cli_write_dats(tmp_path):
    from pypulsar_tpu.cli import sweep as sweep_cli
    from pypulsar_tpu.io.datfile import Datfile

    fn, freqs, data = synth_fil(tmp_path, T=4096)
    out = str(tmp_path / "dats")
    rc = sweep_cli.main([fn, "-o", out, "--lodm", "0", "--dmstep", "30",
                         "--numdms", "2", "-s", "16", "--group-size", "8",
                         "--write-dats"])
    assert rc == 0
    for dm in (0.0, 30.0):
        base = f"{out}_DM{dm:.2f}"
        assert os.path.exists(base + ".dat") and os.path.exists(base + ".inf")
        df = Datfile(base + ".dat")
        ts = df.read_all()
        assert len(ts) == 4096
        if dm == 0.0:
            # DM 0: series is the plain channel sum
            np.testing.assert_allclose(ts, data.sum(axis=1), rtol=1e-5)


def test_sweep_cli_sharded_mesh(tmp_path):
    import jax

    from pypulsar_tpu.cli import sweep as sweep_cli

    assert len(jax.devices()) == 8
    fn, _, _ = synth_fil(tmp_path)
    out = str(tmp_path / "mesh_out")
    rc = sweep_cli.main([fn, "-o", out, "--lodm", "0", "--dmstep", "2.5",
                         "--numdms", "48", "-s", "16", "--group-size", "8",
                         "--mesh", "4"])
    assert rc == 0
    assert os.path.exists(out + ".cands")


def test_sweep_ddplan_2d_matches_1d(tmp_path):
    """The {dm, time} 2-D mesh staged execution reproduces the streamed
    1-D staged sweep (halo exchange over ppermute == host overlap-save)."""
    import jax

    from pypulsar_tpu.parallel import make_mesh
    from pypulsar_tpu.parallel.staged import sweep_ddplan, sweep_ddplan_2d

    assert len(jax.devices()) == 8
    rng = np.random.RandomState(21)
    C, T, dt = 32, 16384, 1e-3
    freqs = 1500.0 - 4.0 * np.arange(C)
    data = rng.randn(C, T).astype(np.float32)
    spec = Spectra(freqs, dt, data)
    obs = Observation(dt=dt, fctr=float(freqs.mean()),
                      BW=float(freqs.max() - freqs.min() + 4.0), numchan=C)
    plan = obs.gen_ddplan(0.0, 300.0)
    mesh = make_mesh([4, 2], ("dm", "time"))

    ref = sweep_ddplan(spec, plan, nsub=8, group_size=4)
    got = sweep_ddplan_2d(spec, plan, mesh, nsub=8, group_size=4)
    assert len(got.steps) == len(ref.steps)
    for sa, sb in zip(got.steps, ref.steps):
        # trial counts match (2d pads groups to the mesh; finalize trims)
        assert len(sa.result.dms) == len(sb.result.dms)
        np.testing.assert_allclose(sa.result.snr, sb.result.snr,
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(sa.result.peak_sample,
                                      sb.result.peak_sample)


def test_windowed_source_rejects_unaligned_window(tmp_path):
    """ADVICE r4: an interior window that is not a whole payload multiple
    would double-count seam samples in merged statistics — the source must
    fail loudly, not corrupt silently."""
    from pypulsar_tpu.parallel.staged import _ReaderSource

    fn, _, _ = synth_fil(tmp_path, T=8192)
    fil = filterbank.FilterbankFile(fn)
    src = _ReaderSource(fil, start=0, end=3000)  # interior, 3000 % 2048 != 0
    with pytest.raises(ValueError, match="whole multiple of payload"):
        next(src.chan_major_blocks(payload=2048, overlap=64))
    # tail windows may be ragged: the file end is the natural boundary
    src2 = _ReaderSource(fil, start=4096, end=8192)
    tail = _ReaderSource(fil, start=6144)  # end defaults to total
    assert sum(1 for _ in src2.chan_major_blocks(2048, 64)) == 2
    assert sum(1 for _ in tail.chan_major_blocks(2048, 64)) == 1


def test_masked_block_interval_lookup_past_int32(tmp_path):
    """ADVICE r4: the zap-interval lookup must be exact for file-absolute
    sample positions past 2^31 (int32 arange would overflow and index the
    wrong intervals)."""
    from pypulsar_tpu.parallel.staged import _masked_block

    rng = np.random.RandomState(5)
    C, L, pts = 8, 512, 1000
    # past int32, constructed so rem=800 and the block crosses into the
    # next interval at j=200
    pos = (2**31 // pts + 1) * pts + 800
    assert pos > 2**31 and pos % pts == 800
    nint = pos // pts + 2
    data = rng.randn(C, L).astype(np.float32)
    table = np.zeros((nint, C), dtype=bool)
    table[pos // pts + 1, 3] = True  # zap only the block's SECOND interval
    import jax.numpy as jnp
    base = min(pos // pts, nint - 1)
    got = np.asarray(_masked_block(jnp.asarray(data), jnp.asarray(table),
                                   base, pos % pts, pts))
    assert not np.array_equal(got, data)  # the zap actually landed
    # int64 host reference of the same clamped lookup + median-mid80 fill
    iv = np.minimum((pos + np.arange(L, dtype=np.int64)) // pts, nint - 1)
    mask = table[iv].T  # [C, L]
    from pypulsar_tpu.ops import numpy_ref
    ref = numpy_ref.masked(data, mask)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("zapped_int, filled", [(2, [2048, 4096]), (None, [])])
def test_mask_fill_blocks_counts_the_blocks_that_paid(tmp_path, zapped_int,
                                                      filled):
    """``mask.fill_blocks`` counts one per block that ran the fill
    program: the blocks whose intervals hold a zapped cell (here the
    two that reach into interval 2, the second through its overlap),
    and none under a mask that zaps nothing there."""
    from pypulsar_tpu.io.rfimask import RfifindMask, write_mask
    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.parallel.staged import _make_source

    fn, _freqs, _data = synth_fil(tmp_path)  # 8192 samples
    per_int = [np.array([], np.int64)] * 4
    if zapped_int is not None:
        per_int[zapped_int] = np.array([3, 11])
    maskfn = str(tmp_path / "fill.mask")
    write_mask(maskfn, nchan=64, nint=4, ptsperint=2048,
               zap_chans=np.array([], np.int64),
               zap_ints=np.array([], np.int64), zap_chans_per_int=per_int)
    fil = filterbank.FilterbankFile(fn)
    plain = {pos: np.asarray(block) for pos, block
             in _make_source(fil).chan_major_blocks(2048, 64)}
    with telemetry.session() as tlm:
        masked = {pos: np.asarray(block) for pos, block in _make_source(
            fil, RfifindMask(maskfn)).chan_major_blocks(2048, 64)}
        counted = tlm.counter_totals().get("mask.fill_blocks", 0)
    assert counted == len(filled)
    assert sorted(masked) == [0, 2048, 4096, 6144]
    changed = [pos for pos in sorted(masked)
               if not np.array_equal(masked[pos], plain[pos])]
    assert changed == filled


@pytest.mark.parametrize("nbits", [4, 2])
def test_sweep_packed_subbyte_matches_expanded_8bit(tmp_path, nbits):
    """VERDICT r4 item 2: a 4-bit (or 2-bit) PACKED file swept through
    the streamed path (device-side unpack in _ingest_tc) produces
    bit-identical results to the same values pre-expanded into an 8-bit
    file — while shipping 1/2 (1/4) of the bytes."""
    from pypulsar_tpu.parallel.staged import sweep_flat

    rng = np.random.RandomState(17)
    C, T, dt, dm_true = 64, 16384, 1e-3, 60.0
    freqs = (1500.0 - 2.0 * np.arange(C)).astype(np.float64)
    noise_hi, amp = (14, 2) if nbits == 4 else (3, 1)
    vals = rng.randint(0, noise_hi, size=(T, C)).astype(np.uint8)
    bins = numpy_ref.bin_delays(dm_true, freqs, dt)
    for c in range(C):
        for k in range(8):
            i = 900 + k + bins[c]
            if i < T:
                vals[i, c] += amp
    hdr = dict(filterbank.DEFAULT_HEADER)
    hdr.update(nchans=C, fch1=freqs[0], foff=-2.0, tsamp=dt)
    fn4 = str(tmp_path / "p4.fil")
    fn8 = str(tmp_path / "p8.fil")
    filterbank.write_filterbank(fn4, dict(hdr, nbits=nbits), vals)
    filterbank.write_filterbank(fn8, dict(hdr, nbits=8), vals)
    assert (os.stat(fn4).st_size - FilterbankFileHeaderSize(fn4)
            ) * (8 // nbits) == (os.stat(fn8).st_size
                                 - FilterbankFileHeaderSize(fn8))
    dms = np.linspace(0.0, 120.0, 16)
    r4 = sweep_flat(filterbank.FilterbankFile(fn4), dms, nsub=16,
                    group_size=8, chunk_payload=4096)
    r8 = sweep_flat(filterbank.FilterbankFile(fn8), dms, nsub=16,
                    group_size=8, chunk_payload=4096)
    a, b = r4.steps[0].result, r8.steps[0].result
    np.testing.assert_array_equal(a.snr, b.snr)
    np.testing.assert_array_equal(a.peak_sample, b.peak_sample)
    np.testing.assert_array_equal(a.mean, b.mean)
    # and the sweep still finds the injection
    best = r4.best(1)[0]
    assert abs(best["dm"] - dm_true) < 10.0 and best["snr"] > 8.0


def FilterbankFileHeaderSize(fn):
    return filterbank.FilterbankFile(fn).header_size


def test_write_dats_streamed_basic_and_windows(tmp_path):
    """Streamed .dat writer (VERDICT r4 items 1/3): DM-0 series equals
    the exact channel sum; window segments concatenate bit-exactly to
    the whole-file stream; .inf sidecars carry the full length."""
    from pypulsar_tpu.io.datfile import Datfile
    from pypulsar_tpu.parallel.staged import (write_dat_infs,
                                              write_dats_streamed)

    fn, freqs, data = synth_fil(tmp_path, T=8192)
    out = str(tmp_path / "sd")
    fil = filterbank.FilterbankFile(fn)
    # single-DM grids: the group centers on the trial itself, so the
    # two-stage series is the EXACT per-channel dedisperse (a multi-DM
    # group carries the engine's documented subband smearing instead)
    write_dats_streamed(out, fil, [0.0], nsub=16, group_size=8,
                        chunk_payload=2048)
    ts0 = Datfile(f"{out}_DM0.00.dat").read_all()
    assert len(ts0) == 8192
    np.testing.assert_allclose(ts0, data.sum(axis=1), rtol=1e-5, atol=1e-2)
    write_dats_streamed(out, fil, [60.0], nsub=16, group_size=8,
                        chunk_payload=2048)
    ts60 = Datfile(f"{out}_DM60.00.dat").read_all()
    # the injected pulse (t0=900 in synth_fil) dominates the series
    assert abs(int(np.argmax(ts60)) - 900) <= 2
    dms = np.array([0.0, 60.0])
    write_dats_streamed(out, fil, dms, nsub=16, group_size=8,
                        chunk_payload=2048)
    whole = np.fromfile(f"{out}_DM60.00.dat", np.float32)
    # two half-windows, written as segments, concatenate to the whole
    out2 = str(tmp_path / "sw")
    for rank, win in enumerate([(0, 4096), (4096, 8192)]):
        write_dats_streamed(out2, filterbank.FilterbankFile(fn), dms,
                            nsub=16, group_size=8, chunk_payload=2048,
                            window=win, suffix=f".w{rank}",
                            write_inf=False)
    parts = [np.fromfile(f"{out2}_DM60.00.w{r}.dat", np.float32)
             for r in (0, 1)]
    np.testing.assert_array_equal(np.concatenate(parts), whole)
    write_dat_infs(out2, fil, dms, 8192, fil.tsamp)
    from pypulsar_tpu.io.infodata import InfoData
    inf = InfoData(f"{out2}_DM60.00.inf")
    assert int(inf.N) == 8192


def test_sweep_flat_seek_resume_bit_exact(tmp_path, monkeypatch):
    """Kill-and-resume through sweep_flat's SEEK path (round 5): the
    resumed run re-roots the block stream at the checkpoint cursor
    instead of replaying (and re-shipping) the whole file, and the final
    result is bit-identical to the uninterrupted sweep."""
    from pypulsar_tpu.parallel import staged as staged_mod
    from pypulsar_tpu.parallel.staged import sweep_flat
    from pypulsar_tpu.parallel.sweep import SweepCheckpoint

    fn, freqs, _ = synth_fil(tmp_path, T=16384, name="seek.fil")
    dms = np.linspace(0.0, 80.0, 16)
    ckpt = str(tmp_path / "seek.ckpt")

    whole = sweep_flat(filterbank.FilterbankFile(fn), dms, nsub=16,
                       group_size=8, chunk_payload=2048).steps[0].result

    # crash once >= 4 chunks have drained (burst draining accounts whole
    # batches per on_drained call, so the count lives on the checkpoint)
    real = SweepCheckpoint.on_drained

    def dying(self, *a, **k):
        real(self, *a, **k)
        if self._drained >= 4:
            raise KeyboardInterrupt("simulated SIGKILL")

    monkeypatch.setattr(SweepCheckpoint, "on_drained", dying)
    with pytest.raises(KeyboardInterrupt):
        sweep_flat(filterbank.FilterbankFile(fn), dms, nsub=16,
                   group_size=8, chunk_payload=2048,
                   checkpoint_path=ckpt, checkpoint_every=1)
    monkeypatch.setattr(SweepCheckpoint, "on_drained", real)
    assert os.path.exists(ckpt)
    with np.load(ckpt) as z:
        saved_cursor = int(z["cursor"])
    assert saved_cursor >= 4 * 2048  # the crash point's drained coverage

    # resume: the re-rooted source must start AT the cursor, not 0
    seeks = []
    real_reroot = staged_mod._reroot_source

    def spying(src, start_raw):
        seeks.append(start_raw)
        return real_reroot(src, start_raw)

    monkeypatch.setattr(staged_mod, "_reroot_source", spying)
    resumed = sweep_flat(filterbank.FilterbankFile(fn), dms, nsub=16,
                         group_size=8, chunk_payload=2048,
                         checkpoint_path=ckpt,
                         checkpoint_every=1).steps[0].result
    assert seeks == [saved_cursor]
    np.testing.assert_array_equal(resumed.snr, whole.snr)
    np.testing.assert_array_equal(resumed.peak_sample, whole.peak_sample)
    np.testing.assert_array_equal(resumed.mean, whole.mean)
    assert not os.path.exists(ckpt)  # cleaned up on completion
