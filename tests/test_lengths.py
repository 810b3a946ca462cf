"""Block and chunk lengths planned from the observation and the device's
memory (plan/lengths.py): the planner as a pure function, the sweep and
the survey chain under a small stated memory size, the mask stage at two
block lengths."""

import json
import os

import numpy as np
import pytest

from pypulsar_tpu.io import filterbank
from pypulsar_tpu.ops import numpy_ref
from pypulsar_tpu.plan import lengths

V5E = 16e9  # one v5e chip: 16 GB HBM
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gbncc():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "gbncc-350.json")) as f:
        return json.load(f)


# (channels, subbands, largest delay, trials, interval samples) of the
# benchmark's 1024-channel deployments: every one keeps the old constants
_OLD = {
    "htru-hilat": (1024, 64, 1300, 32, 15625),
    "htru-hilat-host4": (1024, 64, 9000, 256, 15625),
    "htru-hilat-fleet4": (1024, 64, 2500, 64, 15625),
    "apertif-rt": (1024, 64, 4000, 1024, 0),
}


@pytest.mark.parametrize("name", sorted(_OLD))
def test_1024_channels_keep_the_old_lengths(name):
    nchan, nsub, delay, trials, pts = _OLD[name]
    got = lengths.plan_lengths(nchan, nsub, delay, trials, V5E,
                               interval_samples=pts)
    assert (got.chunk, got.chunk_bound) == (1 << 18, "default")
    assert (got.mask_intervals, got.mask_bound) == (16, "default")
    assert not got.cut
    # and with room: a gigabyte and a half already resident changes nothing
    again = lengths.plan_lengths(nchan, nsub, delay, trials, V5E, 1.5e9,
                                 interval_samples=pts)
    assert (again.chunk, again.mask_intervals) == (1 << 18, 16)


def test_gbncc_on_a_16_gb_chip_is_what_its_configuration_states():
    """4096 channels: the 2^18 chunk and the 16-interval block do not fit
    (PR 33's parent: 16.00 G of 15.75 G before the first transform); the
    planner cuts both, and the configuration's ``chunk`` (the payload the
    benchmark's reference streams with) is the planner's answer."""
    from pypulsar_tpu.parallel.sweep import (choose_group_size,
                                             make_sweep_plan,
                                             planned_payload)

    cfg = _gbncc()
    freqs = cfg["fch1"] - cfg["bw"] / cfg["nchan"] * np.arange(cfg["nchan"])
    dms = cfg["dm_lo"] + cfg["dm_step"] * np.arange(cfg["dm_trials"])
    pts = int(round(cfg["mask_time"] / cfg["tsamp"]))
    payloads = []
    for widths in (tuple(cfg["widths"]), (1,)):  # detection, series
        g = choose_group_size(dms, freqs, cfg["tsamp"], cfg["nsub"])
        plan = make_sweep_plan(dms, freqs, cfg["tsamp"], nsub=cfg["nsub"],
                               group_size=g, widths=widths)
        got = lengths.plan_lengths(cfg["nchan"], cfg["nsub"],
                                   plan.min_overlap, plan.n_trials, V5E,
                                   interval_samples=pts)
        assert (got.chunk, got.chunk_bound, got.cut) == (1 << 16, "memory",
                                                         True)
        assert (got.mask_intervals, got.mask_bound) == (8, "memory")
        assert got.chunk_need <= got.budget and got.mask_need <= got.budget
        payloads.append(planned_payload(plan, got))
        assert payloads[-1] + plan.min_overlap <= got.chunk
    # one payload for both passes of an observation, and the file's
    assert payloads == [cfg["chunk"], cfg["chunk"]]


@pytest.mark.parametrize("case", ["no_memory_reported", "operator",
                                  "delay_grows", "delay_grows_operator",
                                  "cut_then_grown", "growth_over_budget",
                                  "floor"])
def test_planner_cases(case):
    plan = lengths.plan_lengths
    if case == "no_memory_reported":  # the CPU: no bound, the defaults
        got = plan(65536, 64, 100, 4096, None, interval_samples=10 ** 6)
        assert (got.chunk, got.mask_intervals, got.budget) == (1 << 18, 16,
                                                               None)
    elif case == "operator":  # an explicit chunk is not the planner's
        got = plan(4096, 64, 7453, 32, V5E, chunk=1 << 18)
        assert (got.chunk, got.chunk_bound, got.cut) == (1 << 18,
                                                         "operator", False)
    elif case == "delay_grows":  # a delay over half the chunk doubles it
        got = plan(256, 32, (1 << 17) + 5, 32, V5E)
        assert (got.chunk, got.chunk_bound) == (1 << 19, "overlap")
    elif case == "delay_grows_operator":
        got = plan(256, 32, 5000, 32, V5E, chunk=1 << 13)
        assert (got.chunk, got.chunk_bound) == (1 << 14, "overlap")
    elif case == "cut_then_grown":  # cut for memory, grown for the delay,
        # still under the default: the chunk is a cut one
        got = plan(4096, 64, 40000, 32, 32e9)
        assert got.chunk == 1 << 17 and got.cut
    elif case == "growth_over_budget":
        with pytest.raises(lengths.LengthPlanError) as e:
            plan(4096, 64, 40000, 32, V5E)
        msg = str(e.value)
        assert "4096 channels" in msg and "40000 samples" in msg
        assert str(lengths.chunk_bytes(4096, 64, 32, 1 << 17)) in msg
    elif case == "floor":  # never below 2^12, whatever the memory
        got = plan(4096, 64, 100, 32, 1e6, interval_samples=12207)
        assert got.chunk == lengths.MIN_CHUNK and got.mask_intervals == 1


def test_plan_chunk_names_the_dm(monkeypatch):
    """The sweep's own wrapper adds what the pure function cannot know:
    the top DM, the sample time and the band."""
    from pypulsar_tpu.parallel.sweep import make_sweep_plan, plan_chunk

    freqs = 400.0 - (100.0 / 256) * np.arange(256)
    plan = make_sweep_plan([0.0, 600.0], freqs, 8.192e-5, nsub=32,
                           group_size=2)
    monkeypatch.setattr(lengths, "device_memory", lambda: 3e9)
    with pytest.raises(lengths.LengthPlanError, match="top DM 600.00"):
        plan_chunk(plan)


# -- the sweep and the survey chain under a small stated memory ------------


def _wide_fil(tmp_path, C=256, T=32768, dt=1e-3, dm=40.0, period=512):
    """A 256-channel 8-bit file with a periodic dispersed pulse."""
    rng = np.random.RandomState(7)
    freqs = 1500.0 - (300.0 / C) * np.arange(C)
    data = rng.randint(0, 200, size=(T, C)).astype(np.float32)
    bins = numpy_ref.bin_delays(dm, freqs, dt)
    for c in range(C):
        data[(np.arange(0, T, period) + bins[c]) % T, c] += 40.0
    fn = str(tmp_path / "wide.fil")
    hdr = dict(filterbank.DEFAULT_HEADER)
    hdr.update(nchans=C, fch1=freqs[0], foff=freqs[1] - freqs[0], tsamp=dt,
               nbits=8)
    filterbank.write_filterbank(fn, hdr, data)
    return fn, freqs, data


def _small_memory(monkeypatch, nchan, nsub, trials, chunk):
    """State a device memory under which ``chunk`` is the longest that
    fits: the planner's own count, and a third more. Returns it."""
    need = lengths.chunk_bytes(nchan, nsub, trials, chunk)
    limit = int(need * 4 / 3 / lengths.MEMORY_SHARE)
    monkeypatch.setattr(lengths, "device_memory", lambda: limit)
    return limit


def _events(path, name):
    out = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == "event" and rec.get("name") == name:
                out.append(rec.get("attrs", {}))
    return out


def test_sweep_under_small_memory(tmp_path, monkeypatch):
    """`sweep --write-dats` with the chunk the planner cuts for a small
    memory: the series match the NumPy reference and are byte-equal to
    the run at the default chunk (on this engine a series does not
    depend on the chunk; the `.cands` rows are per-chunk statistics and
    do, so they are only asked to hold the pulse)."""
    from pypulsar_tpu.cli import sweep as sweep_cli

    fn, freqs, data = _wide_fil(tmp_path)
    argv = [fn, "--lodm", "30", "--dmstep", "5", "--numdms", "4", "-s",
            "16", "--group-size", "1", "--threshold", "8", "--write-dats"]
    monkeypatch.setenv("PYPULSAR_TPU_DATS_RESIDENT_LIMIT", "0")  # stream
    whole = str(tmp_path / "whole")
    assert sweep_cli.main(argv + ["-o", whole]) == 0
    _small_memory(monkeypatch, 256, 16, 4, 8192)
    cut = str(tmp_path / "cut")
    tlm = str(tmp_path / "cut.jsonl")
    assert sweep_cli.main(argv + ["-o", cut, "--telemetry", tlm]) == 0
    (ev,) = _events(tlm, "sweep.chunk_plan")
    assert (ev["nchan"], ev["chunk"], ev["bound"]) == (256, 8192, "memory")
    assert ev["payload"] + ev["overlap"] <= 8192 < ev["budget_bytes"]
    assert ev["need_bytes"] == lengths.chunk_bytes(256, 16, 4, 8192)
    # the two-stage scheme in NumPy: channels aligned inside each subband
    # at the trial's DM (a group of one), then the subbands
    sub, _ = numpy_ref.subband(data.T, freqs, 1e-3, 16, subdm=40.0)
    hif = freqs[np.arange(16) * 16]
    want = numpy_ref.shift_channels(
        sub, numpy_ref.bin_delays(40.0, hif, 1e-3, ref_freq=freqs.max()))
    for dm in (30.0, 35.0, 40.0, 45.0):
        a = np.fromfile(f"{whole}_DM{dm:.2f}.dat", "<f4")
        b = np.fromfile(f"{cut}_DM{dm:.2f}.dat", "<f4")
        assert a.tobytes() == b.tobytes(), dm
    got = np.fromfile(f"{cut}_DM40.00.dat", "<f4")
    valid = len(got) - int(numpy_ref.bin_delays(40.0, freqs, 1e-3).max())
    np.testing.assert_allclose(got[:valid], want.sum(axis=0)[:valid],
                               rtol=1e-6)
    for out in (whole, cut):
        rows = [ln.split() for ln in open(out + ".cands")
                if not ln.startswith("#")]
        assert any(float(r[0]) == 40.0 for r in rows)
    with open(tlm) as f:
        final = [json.loads(ln) for ln in f if '"counters"' in ln][-1]
    c = final["counters"]
    assert c["sweep.payload_samples"] == 32768
    chunks = -(-32768 // ev["payload"])
    assert c["sweep.chunks"] == chunks
    assert c["sweep.chunk_samples"] == chunks * (ev["payload"]
                                                 + ev["overlap"])


def test_survey_under_small_memory(tmp_path, monkeypatch):
    """The whole chain through ``survey`` with every length cut: the mask
    stage's block and the sweep's chunk are planned, the products are
    there, and each `.dat` is byte-equal to the run with no bound."""
    from pypulsar_tpu.cli import survey as survey_cli

    fn, _freqs, _data = _wide_fil(tmp_path)
    argv = [fn, "--devices", "1", "--lodm", "30", "--dmstep", "5",
            "--numdms", "4", "-s", "16", "--mask-time", "2.0",
            "--accel-zmax", "4", "--accel-numharm", "2", "--fold-npart",
            "8"]
    outs = {}
    for name in ("whole", "cut"):
        if name == "cut":
            limit = _small_memory(monkeypatch, 256, 16, 4, 8192)
            assert lengths.mask_block_bytes(256, 2000, 8) \
                <= lengths.MEMORY_SHARE * limit \
                < lengths.mask_block_bytes(256, 2000, 16)
        outs[name] = str(tmp_path / name)
        rc = survey_cli.main(argv + ["-o", outs[name], "--telemetry-dir",
                                     os.path.join(outs[name], "tlm")])
        assert rc == 0
    tlm = os.path.join(outs["cut"], "tlm", "fleet.jsonl")
    (mask,) = _events(tlm, "rfifind.block_plan")
    assert (mask["nchan"], mask["intervals"], mask["bound"]) == (256, 8,
                                                                 "memory")
    (chunk,) = _events(tlm, "sweep.chunk_plan")
    assert (chunk["chunk"], chunk["bound"]) == (8192, "memory")
    (free,) = _events(os.path.join(outs["whole"], "tlm", "fleet.jsonl"),
                      "rfifind.block_plan")
    assert (free["intervals"], free["bound"], free["budget_bytes"]) == (
        16, "default", -1)
    for suffix in ("_rfifind.mask", "_rfifind.stats.npz", ".accelcands",
                   "_snr.json"):
        assert os.path.exists(os.path.join(outs["cut"], "wide" + suffix))
    for dm in (30.0, 35.0, 40.0, 45.0):
        a, b = (open(os.path.join(outs[k], f"wide_DM{dm:.2f}.dat"),
                     "rb").read() for k in ("whole", "cut"))
        assert a == b and len(a) == 4 * 32768, dm
    a, b = (open(os.path.join(outs[k], "wide_rfifind.mask"), "rb").read()
            for k in ("whole", "cut"))
    assert a == b


@pytest.mark.parametrize("raw", [True, False], ids=["raw", "host"])
def test_mask_stage_at_two_block_lengths(tmp_path, monkeypatch, raw):
    """The statistics are per (interval, channel): 3 intervals a block or
    16, on the raw path and the host path alike, the statistics are
    equal bit for bit and the `.mask` byte for byte."""
    from pypulsar_tpu.io.filterbank import FilterbankFile
    from pypulsar_tpu.ops.rfifind import rfifind

    fn, _freqs, _data = _wide_fil(tmp_path, C=32, T=20 * 512 + 300)
    got = {}
    with FilterbankFile(fn) as reader:
        if not raw:
            monkeypatch.setattr(reader, "BLOCK_ITER_ARRAYS", False,
                                raising=False)
        for ints in (16, 3):
            got[ints] = rfifind(reader, time=0.512, ints_per_read=ints,
                                outbase=str(tmp_path / f"b{ints}"))
    (s16, f16, m16), (s3, f3, m3) = got[16], got[3]
    assert s16.nint == 21  # 20 whole intervals and the padded tail
    for name in ("mean", "std", "maxpow"):
        assert getattr(s16, name).tobytes() == getattr(s3, name).tobytes()
    assert np.array_equal(f16, f3)
    with open(m16, "rb") as a, open(m3, "rb") as b:
        assert a.read() == b.read()
