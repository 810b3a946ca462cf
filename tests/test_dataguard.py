"""Data-integrity layer tests (round 13): salvaging readers, the
validity scrub through the device chain, finite-output gates, and the
corruption/fuzz tooling.

The contract under test, end to end: garbage input bytes mean
"flagged, salvaged, and reported" — never "crash, hang, or silently
wrong candidates". Every reader, fed arbitrary corrupted bytes, parses
(possibly salvaging a prefix) or raises a located ``DataFormatError``;
a NaN born mid-chunk is zero-filled ON DEVICE and counted in ``data.*``
telemetry; and no non-finite value can reach a .cands/.cand/.txtcand
row. The checked-in corpus in ``tests/fixtures/corrupt/`` pins the
reader half (regenerate with ``make_corpus.py`` — every fixture comes
from the ONE shared corruption code path, never hand-hexed bytes)."""

import glob
import io as _io
import json
import os
import warnings

import numpy as np
import pytest

from pypulsar_tpu.io import sigproc
from pypulsar_tpu.io.errors import DataFormatError, read_exact
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.resilience import dataguard, faultinject

from tests.test_accel_pipeline import SWEEP_ARGS, _pulsar_fil

CORPUS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures", "corrupt")


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.reset()
    yield
    faultinject.reset()


# ---------------------------------------------------------------------------
# read_exact + header parsing: located errors, never bare struct.error
# ---------------------------------------------------------------------------


def test_read_exact_short_read_is_located():
    f = _io.BytesIO(b"\x01\x02\x03")
    f.read(1)
    with pytest.raises(DataFormatError) as ei:
        read_exact(f, 8, "/data/x.fil", "value of 'tsamp'")
    assert ei.value.path == "/data/x.fil"
    assert ei.value.offset == 1
    assert "wanted 8 bytes, got 2" in str(ei.value)


def test_dataformaterror_is_valueerror():
    """Existing broad ``except ValueError`` reader handlers keep
    classifying the new error hierarchy."""
    assert issubclass(DataFormatError, ValueError)


def test_read_header_empty_file_located():
    with pytest.raises(DataFormatError) as ei:
        sigproc.read_header(_io.BytesIO(b""), path="empty.fil")
    assert "empty.fil" in str(ei.value)


def test_read_header_truncated_mid_keyword():
    """A header cut mid-field names the file and the byte offset."""
    buf = sigproc.addto_hdr("HEADER_START", None)[:8]
    with pytest.raises(DataFormatError) as ei:
        sigproc.read_header(_io.BytesIO(buf), path="cut.fil")
    assert ei.value.offset is not None


def test_read_header_runaway_stream_terminates():
    """A stream that keeps yielding decodable keywords without
    HEADER_END must terminate with a clean error, not walk megabytes
    of payload as 'header'."""
    buf = sigproc.addto_hdr("HEADER_START", None)
    buf += sigproc.addto_hdr("nifs", 1) * (sigproc.MAX_HEADER_KEYS + 8)
    with pytest.raises(DataFormatError, match="runaway header"):
        sigproc.read_header(_io.BytesIO(buf), path="runaway.fil")


@pytest.mark.parametrize("patch, field", [
    (dict(nbits=7), "nbits"),
    (dict(nbits=0), "nbits"),
    (dict(nchans=0), "nchans"),
    (dict(nchans=1 << 30), "nchans"),
    (dict(tsamp=float("nan")), "tsamp"),
    (dict(tsamp=-1e-3), "tsamp"),
    (dict(fch1=float("inf")), "fch1"),
    (dict(nifs=0), "nifs"),
])
def test_validate_header_rejects_insane_fields(patch, field):
    hdr = dict(nchans=16, tsamp=1e-3, fch1=1500.0, foff=-1.0, nbits=32,
               nifs=1)
    hdr.update(patch)
    with pytest.raises(DataFormatError, match=field):
        sigproc.validate_header(hdr, "x.fil")


def test_validate_header_accepts_sane():
    sigproc.validate_header(dict(nchans=16, tsamp=1e-3, fch1=1500.0,
                                 foff=-1.0, nbits=8, nifs=1), "x.fil")


# ---------------------------------------------------------------------------
# the checked-in corrupted-fixture corpus, against every reader
# ---------------------------------------------------------------------------


def _corpus_files():
    fns = [fn for fn in sorted(glob.glob(os.path.join(CORPUS, "*")))
           if not fn.endswith((".py", ".md", ".inf"))]
    assert len(fns) >= 12, f"corpus missing — regenerate: {fns}"
    return fns


def _open_and_read(fn):
    """Open fixture ``fn`` with its format's reader and actually READ
    from it; returns the salvage report (None = whole)."""
    if fn.endswith(".fil"):
        from pypulsar_tpu.io.filterbank import FilterbankFile

        fb = FilterbankFile(fn)
        try:
            n = min(int(fb.number_of_samples), 8)
            if n > 0:
                fb.get_samples(0, n)
            return fb.salvage
        finally:
            fb.close()
    if fn.endswith(".fits"):
        from pypulsar_tpu.io.psrfits import PsrfitsFile

        pf = PsrfitsFile(fn)
        try:
            n = min(int(pf.nspec), 4)
            if n > 0:
                pf.get_spectra(0, n)
            return None
        finally:
            pf.close()
    from pypulsar_tpu.io.datfile import Datfile

    d = Datfile(fn)
    try:
        d.read_all()
        return d.salvage
    finally:
        d.close()


@pytest.mark.parametrize(
    "fn", _corpus_files(),
    ids=[os.path.basename(f) for f in _corpus_files()])
def test_corrupted_fixture_corpus(fn):
    """Every corpus file produces the outcome its name prefix declares:
    ``err_`` a located DataFormatError, ``salv_`` a successful open
    with a salvage report, ``ok_`` a clean parse — NEVER an unhandled
    raw exception (struct.error, IndexError, UnicodeDecodeError...)."""
    want = os.path.basename(fn).split("_")[0]
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            salvage = _open_and_read(fn)
    except DataFormatError as e:
        assert want == "err", f"{fn}: unexpected DataFormatError {e}"
        assert os.path.basename(fn) in str(e), (
            f"error not located: {e}")
        return
    if want == "salv":
        assert salvage is not None, f"{fn}: expected a salvage report"
        assert salvage["missing_samples"] > 0 \
            or salvage["partial_tail_bytes"] > 0
    else:
        assert want == "ok", f"{fn}: expected DataFormatError, parsed"


# ---------------------------------------------------------------------------
# truncated-tail salvage: the valid prefix reads back exactly
# ---------------------------------------------------------------------------


def test_filterbank_salvage_reads_valid_prefix(tmp_path):
    """Truncating a .fil mid-spectrum: the reader opens, reports the
    missing span, and the surviving whole samples read back
    bit-identical to the pristine file's prefix."""
    from pypulsar_tpu.io.filterbank import FilterbankFile

    fil = _pulsar_fil(tmp_path, T=2048)
    with FilterbankFile(fil) as fb:
        whole = fb.get_samples(0, 2048)
        hsize = fb.header_size
        bps = fb.bytes_per_spectrum
    cut = str(tmp_path / "cut.fil")
    with open(fil, "rb") as f:
        img = f.read()
    keep = 1200
    with open(cut, "wb") as f:
        f.write(img[: hsize + keep * bps + 3])  # +3: mid-spectrum
    with pytest.warns(UserWarning, match="salvaged"):
        fb = FilterbankFile(cut)
    try:
        assert fb.number_of_samples == keep
        assert fb.salvage == {
            "read_samples": keep, "expected_samples": 2048,
            "missing_samples": 2048 - keep, "partial_tail_bytes": 3}
        np.testing.assert_array_equal(fb.get_samples(0, keep),
                                      whole[:keep])
    finally:
        fb.close()


def test_datfile_salvage_clamps_inf_N(tmp_path):
    from pypulsar_tpu.io.datfile import Datfile, write_dat
    from pypulsar_tpu.io.infodata import InfoData

    inf = InfoData()
    inf.epoch = 55000.0
    inf.dt = 1e-3
    inf.DM = 0.0
    series = np.arange(501, dtype=np.float32)  # odd size on purpose
    base = str(tmp_path / "t")
    write_dat(base, series, inf)
    os.truncate(base + ".dat", 300 * 4 + 2)  # mid-sample cut
    with pytest.warns(UserWarning, match="salvaged"):
        d = Datfile(base + ".dat")
    try:
        assert d.infdata.N == 300
        assert d.salvage["missing_samples"] == 201
        assert d.salvage["partial_tail_bytes"] == 2
        np.testing.assert_array_equal(d.read_all(), series[:300])
    finally:
        d.close()


def test_write_filterbank_stamps_nsamples(tmp_path):
    """The writer records the sample count so readers can cross-check
    the file size (what turns truncation into a REPORTED salvage)."""
    from pypulsar_tpu.io.filterbank import FilterbankFile, \
        write_filterbank

    fn = str(tmp_path / "n.fil")
    write_filterbank(fn, dict(nchans=4, tsamp=1e-3, fch1=1500.0,
                              foff=-1.0, nbits=32),
                     np.zeros((37, 4), np.float32))
    with FilterbankFile(fn) as fb:
        assert fb.header["nsamples"] == 37
        assert fb.salvage is None


# ---------------------------------------------------------------------------
# deterministic corruption + the structure-aware reader fuzz
# ---------------------------------------------------------------------------


def test_corrupt_file_deterministic(tmp_path):
    """Same (kind, seed) -> byte-identical corruption; different seeds
    differ. The determinism bench/tests leans on to replay a fault."""
    imgs = {}
    for tag, seed in (("a", 5), ("b", 5), ("c", 6)):
        sub = tmp_path / tag
        sub.mkdir()
        fn = _pulsar_fil(sub, T=1024)  # same basename: seed decides
        dataguard.corrupt_file(fn, "bitflip", seed=seed)
        with open(fn, "rb") as f:
            imgs[tag] = f.read()
    assert imgs["a"] == imgs["b"]
    assert imgs["a"] != imgs["c"]


def test_corrupt_file_kinds_and_bad_kind(tmp_path):
    fil = _pulsar_fil(tmp_path, T=1024)
    with open(fil, "rb") as f:
        pristine = f.read()
    for kind in dataguard.CORRUPT_KINDS:
        fn = str(tmp_path / f"{kind}.fil")
        with open(fn, "wb") as f:
            f.write(pristine)
        desc = dataguard.corrupt_file(fn, kind, seed=3)
        assert desc["kind"] == kind
        with open(fn, "rb") as f:
            assert f.read() != pristine, f"{kind} was a no-op"
    with pytest.raises(ValueError, match="unknown corruption kind"):
        dataguard.corrupt_file(fil, "gamma_ray")


def test_fuzz_mutate_deterministic():
    base = bytes(range(256)) * 8
    a = dataguard.fuzz_mutate(base, dataguard._rng(1, "t"))
    b = dataguard.fuzz_mutate(base, dataguard._rng(1, "t"))
    c = dataguard.fuzz_mutate(base, dataguard._rng(2, "t"))
    assert a == b
    assert a != c or len(a) != len(c)


@pytest.mark.parametrize("fmt", ["filterbank", "psrfits", "dat"])
def test_reader_fuzz_quick(fmt, tmp_path):
    """Tier-1 fuzz slice: 60 seeded mutations per format, zero contract
    violations (the 500-per-format acceptance run is the slow twin
    below + the committed CORRUPT_r01.json receipt)."""
    counts, failures = dataguard.run_reader_fuzz(
        fmt, 60, 11, str(tmp_path / fmt))
    assert not failures, f"contract violations: {failures[:5]}"
    assert sum(counts.values()) == 60


@pytest.mark.slow
@pytest.mark.parametrize("fmt", ["filterbank", "psrfits", "dat"])
def test_reader_fuzz_full(fmt, tmp_path):
    """The acceptance-scale fuzz (N=500 per format), opted into by
    ``make test-corruption``."""
    counts, failures = dataguard.run_reader_fuzz(
        fmt, 500, 1, str(tmp_path / fmt))
    assert not failures, f"contract violations: {failures[:5]}"
    assert sum(counts.values()) == 500


# ---------------------------------------------------------------------------
# the stream scrub: non-finite cells zero-filled + counted, on device
# ---------------------------------------------------------------------------


def _nan_spectra(C=4, T=512, n_bad=37):
    from pypulsar_tpu.core.spectra import Spectra

    rng = np.random.default_rng(3)
    data = rng.standard_normal((C, T)).astype(np.float32)
    flat = data.reshape(-1)
    flat[rng.choice(flat.size, size=n_bad, replace=False)] = np.nan
    flat[0] = np.inf
    return Spectra(1500.0 - np.arange(float(C)), 1e-3, data)


def test_guarded_source_scrubs_and_accounts():
    from pypulsar_tpu.parallel.staged import _SpectraSource

    sp = _nan_spectra()
    src = dataguard.guard_source(_SpectraSource(sp))
    assert isinstance(src, dataguard.GuardedSource)
    with telemetry.session() as tlm:
        blocks = [np.asarray(b) for _, b in
                  src.chan_major_blocks(256, 0)]
        for b in blocks:
            assert np.isfinite(b).all()
        totals = tlm.counter_totals()
    assert src.stats.nonfinite_cells == 38  # 37 NaN + 1 inf
    assert totals["data.nonfinite_cells"] == 38
    assert tlm.event_counts.get("data.nonfinite_scrubbed", 0) >= 1
    assert src.stats.fraction_bad() == pytest.approx(38 / (4 * 512))


def test_guard_disabled_by_env(monkeypatch):
    from pypulsar_tpu.parallel.staged import _SpectraSource

    monkeypatch.setenv(dataguard.ENV_GUARD, "0")
    src = dataguard.guard_source(_SpectraSource(_nan_spectra()))
    assert not isinstance(src, dataguard.GuardedSource)


def test_guard_skips_integer_sources(tmp_path):
    """uint filterbanks cannot hold a NaN: the hot 8-bit path stays
    unwrapped (and untouched) unless a data fault needs a landing."""
    from pypulsar_tpu.io.filterbank import FilterbankFile, \
        write_filterbank
    from pypulsar_tpu.parallel.staged import _ReaderSource

    fn = str(tmp_path / "u8.fil")
    write_filterbank(fn, dict(nchans=4, tsamp=1e-3, fch1=1500.0,
                              foff=-1.0, nbits=8),
                     np.zeros((64, 4), np.uint8))
    with FilterbankFile(fn) as fb:
        src = _ReaderSource(fb, 0, None)
        assert not isinstance(dataguard.guard_source(src),
                              dataguard.GuardedSource)
        faultinject.configure("nanburst:data.block:1")
        assert isinstance(dataguard.guard_source(src),
                          dataguard.GuardedSource)


def test_sweep_through_nan_input_stays_finite(tmp_path):
    """End-to-end through the DEVICE chain: a .fil with a NaN burst in
    its payload sweeps to finite SNRs (the scrub zero-fills before
    dedispersion), with the masked cells reported in telemetry."""
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.parallel.staged import sweep_flat

    fil = _pulsar_fil(tmp_path, T=4096)
    dataguard.corrupt_file(fil, "nanburst", seed=9)
    with telemetry.session() as tlm:
        res = sweep_flat(filterbank.FilterbankFile(fil),
                         np.arange(8) * 10.0, nsub=8, group_size=4,
                         chunk_payload=2048).steps[0].result
        totals = tlm.counter_totals()
    assert np.isfinite(np.asarray(res.snr)).all()
    assert totals["data.nonfinite_cells"] > 0
    assert totals["data.cells"] > 0


# ---------------------------------------------------------------------------
# data-fault injection at read time (faultinject DATA kinds)
# ---------------------------------------------------------------------------


def test_trip_data_fires_once_deterministically():
    a = np.zeros(400, np.float32)
    faultinject.configure("nanburst:data.block:2")
    out1 = faultinject.trip_data("data.block", a)
    assert np.isfinite(out1).all()  # hit 1: untouched
    out2 = faultinject.trip_data("data.block", a)
    assert np.isnan(out2).sum() > 0
    out3 = faultinject.trip_data("data.block", a)
    assert np.isfinite(out3).all()  # disarmed after firing
    # replaying the same (kind, point, hit) corrupts identical bytes
    faultinject.configure("nanburst:data.block:2")
    faultinject.trip_data("data.block", a)
    replay = faultinject.trip_data("data.block", a)
    np.testing.assert_array_equal(
        np.isnan(out2), np.isnan(replay))


def test_corrupt_array_kinds():
    rng = dataguard._rng(4, "t")
    base = np.ones((8, 64), np.float32)
    nan = faultinject.corrupt_array(base, "nanburst", rng)
    assert np.isnan(nan).sum() > 0 and np.isinf(nan).sum() == 1
    drop = faultinject.corrupt_array(base, "dropblock", rng)
    assert (drop == 0).sum() > 0
    dc = faultinject.corrupt_array(base, "dcjump", rng)
    assert dc.max() > 1e3
    u8 = faultinject.corrupt_array(np.ones(256, np.uint8), "dcjump",
                                   rng)
    assert u8.dtype == np.uint8 and u8.max() > 1
    trunc = faultinject.corrupt_array(base, "truncate", rng)
    assert (trunc.reshape(-1)[-10:] == 0).all()


def test_nanburst_gate_acceptance(tmp_path):
    """THE acceptance gate test: inject a NaN burst mid-chunk into a
    clean sweep, and assert (a) the published .cands table is 100%
    finite, (b) the masked fraction is reported in telemetry, (c) the
    injection is recorded — garbage degraded the run, visibly, and
    nothing non-finite reached a row."""
    from pypulsar_tpu.cli import sweep as cli_sweep

    fil = _pulsar_fil(tmp_path, T=8192)
    olddir = os.getcwd()
    os.chdir(tmp_path)
    try:
        with telemetry.session() as tlm:
            assert cli_sweep.main(
                [fil, "-o", "gate", *SWEEP_ARGS, "--chunk", "2048",
                 "--fault-inject", "nanburst:data.block:2"]) == 0
            totals = tlm.counter_totals()
            events = dict(tlm.event_counts)
        rows = np.atleast_2d(np.loadtxt("gate.cands"))
        if rows.size:
            assert np.isfinite(rows).all()
        assert totals["data.nonfinite_cells"] > 0, (
            "masked fraction unreported")
        assert totals["data.cells"] > 0
        assert events.get("resilience.fault_injected", 0) == 1
    finally:
        os.chdir(olddir)


# ---------------------------------------------------------------------------
# finite-output gates
# ---------------------------------------------------------------------------


def test_finite_rows_gate_counts_drops(capsys):
    rows = [{"dm": 1.0, "snr": 9.0, "time_sec": 0.5},
            {"dm": 2.0, "snr": float("nan"), "time_sec": 0.5},
            {"dm": 3.0, "snr": 8.0, "time_sec": float("inf")}]
    with telemetry.session() as tlm:
        good = dataguard.finite_rows(rows, ("dm", "snr", "time_sec"))
        totals = tlm.counter_totals()
    assert good == rows[:1]
    assert totals["data.nonfinite_cands_dropped"] == 2
    assert "dropped 2 non-finite" in capsys.readouterr().out


def test_finite_cands_gate(capsys):
    from pypulsar_tpu.fourier.accelsearch import AccelCandidate

    good = AccelCandidate(r=100.0, z=0.0, power=40.0, sigma=9.0,
                          numharm=2)
    nan_sig = AccelCandidate(r=100.0, z=0.0, power=40.0,
                             sigma=float("nan"), numharm=2)
    r_zero = AccelCandidate(r=0.0, z=0.0, power=40.0, sigma=9.0,
                            numharm=2)
    with telemetry.session() as tlm:
        out = dataguard.finite_cands([good, nan_sig, r_zero], T=100.0)
        totals = tlm.counter_totals()
    assert out == [good]
    assert totals["data.nonfinite_cands_dropped"] == 2


def test_write_candfiles_gates_nonfinite(tmp_path):
    """No non-finite value reaches a .cand/.txtcand pair — the gate
    sits in the shared writer every accel path funnels through."""
    from pypulsar_tpu.fourier.accelsearch import AccelCandidate
    from pypulsar_tpu.io.prestocand import read_rzwcands
    from pypulsar_tpu.parallel.accelpipe import write_candfiles

    cands = [AccelCandidate(r=100.0, z=0.0, power=40.0, sigma=9.0,
                            numharm=2),
             AccelCandidate(r=200.0, z=float("nan"), power=40.0,
                            sigma=8.0, numharm=2)]
    candfn = str(tmp_path / "g_ACCEL_20.cand")
    txtfn = str(tmp_path / "g_ACCEL_20.txtcand")
    write_candfiles(candfn, txtfn, cands, T=100.0)
    assert len(read_rzwcands(candfn)) == 1
    body = open(txtfn).read()
    assert "nan" not in body.lower() and "inf" not in body.lower()


# ---------------------------------------------------------------------------
# ingest validation + survey degrade-vs-quarantine policy
# ---------------------------------------------------------------------------


def test_validate_input_reports(tmp_path):
    fil = _pulsar_fil(tmp_path, T=1024)
    rep = dataguard.validate_input(fil)
    assert rep["format"] == "filterbank"
    assert rep["bad_frac"] == 0.0 and rep["salvage"] is None
    # truncated: recognized, salvaged, bad_frac = missing fraction
    dataguard.corrupt_file(fil, "truncate", seed=1)
    rep = dataguard.validate_input(fil)
    assert 0.3 < rep["bad_frac"] < 0.5
    assert rep["salvage"]["missing_samples"] > 0
    # garbage header after a positive sniff: a DATA error
    dataguard.corrupt_file(fil, "header", seed=1)
    with pytest.raises(DataFormatError):
        dataguard.validate_input(fil)
    # unrecognized or missing: None (the stage itself will complain)
    other = tmp_path / "notes.txt"
    other.write_text("hello")
    assert dataguard.validate_input(str(other)) is None
    assert dataguard.validate_input(str(tmp_path / "gone.fil")) is None


def test_max_bad_frac_env(monkeypatch):
    assert dataguard.max_bad_frac_default() == 0.5
    monkeypatch.setenv(dataguard.ENV_MAX_BAD_FRAC, "0.25")
    assert dataguard.max_bad_frac_default() == 0.25
    monkeypatch.setenv(dataguard.ENV_MAX_BAD_FRAC, "bogus")
    assert dataguard.max_bad_frac_default() == 0.5


def test_survey_data_quarantine_vs_degrade(tmp_path):
    """The fleet policy end to end: a garbage-header input is DATA-
    quarantined at ingest (zero stages burned, reason 'data' distinct
    from runtime quarantine), a salvageable truncated input below the
    --max-bad-frac bar completes DEGRADED with its salvage story in
    the manifest, and --status renders both verdicts."""
    from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import (Observation, format_status,
                                           status_rows)

    from tests.test_survey import CFG_KW, OBS

    fil_bad = _pulsar_fil(tmp_path, name="bad.fil", **OBS)
    fil_cut = _pulsar_fil(tmp_path, name="cut.fil", **OBS)
    dataguard.corrupt_file(fil_bad, "header", seed=2)
    dataguard.corrupt_file(fil_cut, "truncate", seed=2)
    out = tmp_path / "out"
    os.makedirs(out)
    obs = [Observation("bad", fil_bad, str(out / "bad")),
           Observation("cut", fil_cut, str(out / "cut"))]
    cfg = SurveyConfig(**CFG_KW)
    result = FleetScheduler(obs, cfg, max_host_workers=2).run()
    assert set(result.quarantined) == {"bad"}
    q = result.quarantined["bad"]
    assert q["reason"] == "data" and q["stage"] == "ingest"
    # the degraded obs ran its WHOLE chain on the salvaged prefix
    assert len(result.ran) == len(build_dag(cfg))
    rows = status_rows([o.manifest for o in obs])
    by = {r["obs"]: r for r in rows}
    dq = by["cut"]["data_quality"]
    assert dq["salvage"]["missing_samples"] > 0
    assert 0.3 < dq["bad_frac"] < 0.5
    assert by["bad"]["quarantine"]["reason"] == "data"
    rendered = format_status(rows)
    assert "DATA-QUARANTINED" in rendered
    assert "salvaged" in rendered


def test_survey_max_bad_frac_zero_quarantines_salvage(tmp_path):
    """Tightening --max-bad-frac below the salvaged fraction flips the
    SAME input from degrade to data-quarantine — without burning a
    single stage (ingest happens before any lease is taken)."""
    from pypulsar_tpu.survey.dag import SurveyConfig
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    from tests.test_survey import CFG_KW, OBS

    fil = _pulsar_fil(tmp_path, **OBS)
    dataguard.corrupt_file(fil, "truncate", seed=2)
    obs = [Observation("a", fil, str(tmp_path / "a"))]
    result = FleetScheduler(obs, SurveyConfig(**CFG_KW),
                            max_bad_frac=0.1).run()
    assert set(result.quarantined) == {"a"}
    assert result.quarantined["a"]["reason"] == "data"
    assert len(result.ran) == 0


# ---------------------------------------------------------------------------
# satellites: py2 integer-division regressions + --corrupt tooling
# ---------------------------------------------------------------------------


def test_ra_dec_string_fields_stay_in_range():
    """The py2-era ``int(v / 10000)`` field splits truncated through a
    float quotient; the floor-division port must keep every field in
    range at the odd boundary values that used to wobble."""
    vals = [0.0, 1.5, 95959.9999, 123456.789, 235959.9999,
            85959.99999999999, -123456.789]
    for v in vals:
        for fn, lim in ((sigproc.ra_to_hms_string, 24),
                        (sigproc.dec_to_dms_string, 90)):
            s = fn(v)
            neg = s.startswith("-")
            hh, mm, ss = s.lstrip("-").split(":")
            assert 0 <= int(mm) < 60, f"{fn.__name__}({v}) = {s}"
            assert 0.0 <= float(ss) < 100.0
            rebuilt = (int(hh) * 10000 + int(mm) * 100 + float(ss))
            assert rebuilt == pytest.approx(abs(v), abs=1e-3)
            assert neg == (v < 0)


def test_psrfits_data_size_exact_int(tmp_path):
    """PsrfitsData.data_size is an exact integer byte count even at odd
    sample counts (the py2 float ``/ 8.0`` leaked fractional floats
    into count fields)."""
    from pypulsar_tpu.io.datafile import PsrfitsData
    from pypulsar_tpu.io.psrfits import write_psrfits

    fn = str(tmp_path / "odd.fits")
    rng = np.random.default_rng(5)
    write_psrfits(fn, rng.integers(0, 40, (8, 48)).astype(np.float32),
                  1500.0 - np.arange(8.0), 1e-3, nsamp_per_subint=16,
                  nbits=8)
    d = PsrfitsData([fn])
    assert isinstance(d.data_size, int)
    assert d.data_size == d.num_samples * 8 * d.num_channels_per_record \
        // 8


def test_filterbank_odd_sizes_exact(tmp_path):
    """Sample counts stay exact at odd sizes and sub-byte widths."""
    from pypulsar_tpu.io.filterbank import FilterbankFile, \
        write_filterbank

    fn = str(tmp_path / "odd.fil")
    write_filterbank(fn, dict(nchans=6, tsamp=1e-3, fch1=1500.0,
                              foff=-1.0, nbits=32),
                     np.zeros((101, 6), np.float32))
    with FilterbankFile(fn) as fb:
        assert fb.number_of_samples == 101
        assert isinstance(fb.number_of_samples, int)


def test_make_synthetic_fil_corrupt_flag(tmp_path):
    """--corrupt KIND[:SEED] corrupts through the ONE shared code path;
    float-only kinds are rejected for the uint payload."""
    from pypulsar_tpu.io.filterbank import FilterbankFile

    from tests.test_survey import _load_make_synthetic_fil

    mod = _load_make_synthetic_fil()
    common = ["--nchan", "8", "--duration", "0.5", "--tsamp", "1e-3",
              "--period-samples", "128", "--width", "2"]
    fn = str(tmp_path / "cut.fil")
    mod.main(["--out", fn, *common, "--corrupt", "truncate:3"])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with FilterbankFile(fn) as fb:
            assert fb.salvage is not None
            assert fb.salvage["missing_samples"] > 0
    with pytest.raises(SystemExit, match="f32 payload"):
        mod.main(["--out", str(tmp_path / "x.fil"), *common,
                  "--corrupt", "nanburst"])


def test_pfd_snr_gates_nonfinite_row(monkeypatch):
    """A pathological archive (non-finite SNR from a corrupted stats
    block) lands as an ERROR row in the JSON summary, never as a NaN."""
    import argparse

    from pypulsar_tpu.cli import pfd_snr as mod
    from pypulsar_tpu.fold import profile_snr

    class _FakePfd:
        candnm = "FAKE"
        bestdm = 10.0
        curr_p1 = 0.1

    monkeypatch.setattr(mod, "effective_sefd", lambda args, pfd: None)
    monkeypatch.setattr(profile_snr, "pfd_snr",
                        lambda pfd, **kw: {"snr": float("nan"),
                                           "weq": 1.0, "smean": None})
    args = argparse.Namespace(interactive=False, on_pulse=None,
                              model_file=None, gauss_file=None,
                              json="x.json")
    rows = []
    with telemetry.session() as tlm:
        mod._append_archive_row(args, _FakePfd(), "fake.pfd", rows)
        totals = tlm.counter_totals()
    assert rows == [{"pfd": "fake.pfd", "name": "FAKE",
                     "best_dm": 10.0, "period": 0.1, "snr": None,
                     "weq_bins": None, "smean_mjy": None,
                     "ra": None, "dec": None,
                     "error": "non-finite SNR"}]
    assert totals["data.nonfinite_cands_dropped"] == 1
    assert json.dumps(rows)  # the summary stays serializable


def test_pfd_corrupt_string_length_is_located(tmp_path):
    """A corrupt negative/huge header string length in a .pfd must
    raise a located DataFormatError instead of slurping the file."""
    import struct as _struct

    from pypulsar_tpu.io.prestopfd import PfdFile

    for bad_len in (-5, 1 << 30):
        fn = tmp_path / f"bad_{bad_len & 0xffffffff}.pfd"
        fn.write_bytes(_struct.pack("<12i", *([4] * 12))
                       + _struct.pack("<i", bad_len) + b"x" * 8)
        with pytest.raises(DataFormatError) as ei:
            PfdFile(str(fn))
        assert "implausible" in str(ei.value) and str(fn) in str(ei.value)


def test_pfd_and_mask_corrupt_counts_are_located(tmp_path):
    """Corrupt negative/huge array counts in .pfd/.mask headers must
    raise located DataFormatErrors — np.fromfile would otherwise slurp
    the file (negative) or silently short-read and misalign (huge)."""
    import struct as _struct

    from pypulsar_tpu.io.prestopfd import PfdFile
    from pypulsar_tpu.io.rfimask import RfifindMask

    # .pfd: numdms = -1 with an otherwise readable fixed header
    fn = tmp_path / "negdms.pfd"
    fn.write_bytes(_struct.pack("<12i", -1, *([1] * 11)) + b"\x00" * 240)
    with pytest.raises(DataFormatError) as ei:
        PfdFile(str(fn))
    assert "implausible dms count" in str(ei.value)

    # .mask: zap-channel count corrupted negative
    mf = tmp_path / "neg.mask"
    mf.write_bytes(b"\x00" * 48 + _struct.pack("<3i", 4, 2, 10)
                   + _struct.pack("<i", -7))
    with pytest.raises(DataFormatError) as ei:
        RfifindMask(str(mf))
    assert "implausible zap channels count" in str(ei.value)
