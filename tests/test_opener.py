"""How a raw-data file's format is decided at open (io/opener.py).

The answer sits in a file's first 16 bytes: a SIGPROC file never
reaches a FITS codec, and the in-tree codec refuses a non-FITS file at
its first card. The expectations below are what the tree before the
opener gave for the same files (reader chosen, or error raised)."""

import io
import json
import types

import numpy as np
import pytest

from pypulsar_tpu.io import fitsio, open_reader, psrfits
from pypulsar_tpu.io.errors import DataFormatError
from pypulsar_tpu.io.filterbank import FilterbankFile, write_filterbank
from pypulsar_tpu.io.opener import SNIFF_LEN
from pypulsar_tpu.io.psrfits import PsrfitsFile, write_psrfits
from pypulsar_tpu.obs import telemetry

NCHAN = 16
BIG_NSAMP = 1 << 19  # x 16 channels x 8 bit = 8 MiB of samples
NOT_SIGPROC = "invalid SIGPROC header string length"


def _write_fil(fn, nsamp, seed=25):
    rng = np.random.RandomState(seed)
    data = rng.randint(0, 255, size=(nsamp, NCHAN)).astype(np.uint8)
    write_filterbank(fn, dict(fch1=1500.0, foff=-4.0, nchans=NCHAN,
                              tsamp=1e-3, nbits=8), data)


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """One file of every kind the opener has to tell apart."""
    d = tmp_path_factory.mktemp("opener")
    out = {k: str(d / k) for k in (
        "small.fil", "big.fil", "ok.fits", "plain.fits", "empty.fil",
        "short.fil", "short_simple.fil", "cut_primary.fits",
        "cut_ext.fits")}
    _write_fil(out["small.fil"], 256)
    _write_fil(out["big.fil"], BIG_NSAMP)
    rng = np.random.RandomState(25)
    write_psrfits(out["ok.fits"],
                  rng.randint(0, 40, size=(8, 64)).astype(np.float32),
                  1500.0 - np.arange(8.0), 1e-3, nsamp_per_subint=16,
                  nbits=8)
    # FITS, but neither FITSTYPE = PSRFITS nor a SUBINT extension
    cols = fitsio.ColDefs([fitsio.Column(
        "X", "1J", array=np.arange(4, dtype=np.int32))])
    fitsio.HDUList([
        fitsio.PrimaryHDU(),
        fitsio.BinTableHDU.from_columns(cols, name="OTHER"),
    ]).writeto(out["plain.fits"])
    with open(out["empty.fil"], "wb"):
        pass
    with open(out["short.fil"], "wb") as f:
        f.write(b"\x0c\x00\x00\x00HEADER")  # shorter than the magic
    with open(out["short_simple.fil"], "wb") as f:
        f.write(b"SIMPLE  =    T")  # shorter than one 80-byte card
    with open(out["ok.fits"], "rb") as f:
        raw = f.read()
    end = raw.index(b"END" + b" " * 77)
    ext = (end // fitsio.BLOCK + 1) * fitsio.BLOCK
    assert raw[ext:ext + 9] == b"XTENSION="
    with open(out["cut_primary.fits"], "wb") as f:
        f.write(raw[:end - 80])  # inside the primary header
    with open(out["cut_ext.fits"], "wb") as f:
        f.write(raw[:ext + fitsio.BLOCK + 100])  # inside SUBINT's header
    return out


def _sniffed(tlm_path):
    """(io.open span records, final counters) of one session's JSONL."""
    with open(tlm_path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    spans = [r for r in recs if r["type"] == "span"
             and r["name"] == "io.open"]
    counters = [r for r in recs if r["type"] == "counters"][-1]["counters"]
    return spans, counters


# (file, is_PSRFITS, reader class or (error class, fragment of its text))
CASES = [
    ("small.fil", False, FilterbankFile),
    ("big.fil", False, FilterbankFile),
    ("ok.fits", True, PsrfitsFile),
    ("plain.fits", False, (DataFormatError, NOT_SIGPROC)),
    ("empty.fil", False, (DataFormatError, "wanted 4 bytes, got 0")),
    ("short.fil", False, (DataFormatError, "truncated while reading")),
    ("short_simple.fil", False, (DataFormatError, NOT_SIGPROC)),
    ("cut_primary.fits", False, (DataFormatError, NOT_SIGPROC)),
    ("cut_ext.fits", False, (DataFormatError, NOT_SIGPROC)),
    ("missing.fil", False, (ValueError, "File does not exist")),
]


@pytest.mark.parametrize("name,is_fits,expect", CASES,
                         ids=[c[0] for c in CASES])
def test_open_reader_picks_what_the_file_says(files, tmp_path, name,
                                              is_fits, expect):
    fn = files.get(name, str(tmp_path / name))
    assert psrfits.is_PSRFITS(fn) is is_fits
    tlm = str(tmp_path / "open.jsonl")
    with telemetry.session(tlm):
        if isinstance(expect, tuple):
            with pytest.raises(expect[0], match=expect[1]) as err:
                open_reader(fn)
            if expect[0] is DataFormatError:  # located: file and offset
                assert err.value.path == fn
                assert err.value.offset is not None
        else:
            reader = open_reader(fn)
            try:
                assert type(reader) is expect
            finally:
                reader.close()
    spans, counters = _sniffed(tlm)
    assert len(spans) == 1  # the opener's own, failed opens included
    nread = counters["io.sniff_bytes"]
    assert spans[0]["attrs"]["sniff_bytes"] == nread
    if expect is FilterbankFile:
        # the magic alone, whatever the file's size: never one FITS block
        assert nread == SNIFF_LEN <= fitsio.BLOCK
        assert spans[0]["attrs"]["format"] == "sigproc"
    elif expect is PsrfitsFile:
        # the magic + both headers (primary, SUBINT), no data
        assert SNIFF_LEN < nread <= SNIFF_LEN + 8 * fitsio.BLOCK
        assert spans[0]["attrs"]["format"] == "fits"
    else:
        assert nread <= SNIFF_LEN + 8 * fitsio.BLOCK
        assert spans[0]["attrs"]["format"] == "unknown"


class _CountingFile(io.FileIO):
    nread = 0

    def read(self, n=-1):
        data = super().read(n)
        self.nread += len(data)
        return data


@pytest.mark.parametrize("name,message", [
    ("big.fil", "not a FITS header"),
    ("cut_primary.fits", "truncated FITS header"),
    ("cut_ext.fits", "truncated FITS header"),
])
def test_fitsio_refuses_a_file_from_its_first_card(files, monkeypatch,
                                                   name, message):
    """A non-FITS file costs one block, not a card-by-card scan of all
    8 MiB for an END that is not there; a FITS file cut inside a header
    still says so."""
    opened = []

    def counting_open(fn, mode="r"):
        assert mode == "rb"
        opened.append(_CountingFile(fn, "r"))
        return opened[-1]

    monkeypatch.setattr(fitsio, "builtins",
                        types.SimpleNamespace(open=counting_open))
    with pytest.raises(ValueError, match=message):
        fitsio.open(files[name])
    (f,) = opened
    assert f.closed  # a refused file is not left open
    if name == "big.fil":
        assert f.nread <= fitsio.BLOCK
    assert psrfits.sniff_PSRFITS(files[name]) == (False, 0)


def test_psrfits_through_the_rfifind_cli_by_content(tmp_path):
    """The opener goes by what the file holds, not by its name: a PSRFITS
    pointing named like a filterbank file opens as PSRFITS through the
    mask tool (in-tree codec), and the mask finds its loud channel."""
    from pypulsar_tpu.cli import rfifind as cli_rfifind
    from pypulsar_tpu.io.rfimask import RfifindMask

    nchan, nsamp = 16, 8 * 256
    rng = np.random.RandomState(4)
    data = rng.randn(nchan, nsamp).astype(np.float32) * 2.0 + 10.0
    data[3] *= 25.0  # loud channel, file order = mask channel 3
    fn = str(tmp_path / "pointing.fil")
    write_psrfits(fn, data, 1400.0 + 1.0 * np.arange(nchan), tsamp=1e-3,
                  nsamp_per_subint=256, nbits=32)
    tlm = str(tmp_path / "mask.jsonl")
    outbase = str(tmp_path / "pointing")
    assert cli_rfifind.main([fn, "-o", outbase, "-t", "0.256",
                             "--telemetry", tlm]) == 0
    (span,), _ = _sniffed(tlm)
    assert span["attrs"]["format"] == "fits"
    assert span["attrs"]["sniff_bytes"] < 8 * fitsio.BLOCK
    mask = RfifindMask(outbase + "_rfifind.mask")
    assert mask.nchan == nchan and mask.nint == 8
    assert 3 in set(mask.mask_zap_chans)
