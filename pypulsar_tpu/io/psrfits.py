"""PSRFITS search-mode reader (+ synthetic writer for tests).

Behavioral parity target: reference formats/psrfits.py (PsrfitsFile
:54-183, SpectraInfo :186-560, is_PSRFITS :577-591, DATEOBS_to_MJD
:563-574), itself an emulation of PRESTO's psrfits.c.  Differences by
design:

- astropy.io.fits only (no pyfits fallback), memmapped.
- No slalib: ``DATEOBS_to_MJD`` uses our own Gregorian calendar math
  (pypulsar_tpu.astro.calendar).
- Sub-byte samples (4/2/1 bit) are unpacked vectorized on host; the
  scale/offset/weight application ``(data*scales + offsets)*weights``
  (reference :107) is a single float32 broadcast.
- ``get_spectra(startsamp, N)`` returns our immutable Spectra pytree with
  the band flipped to high-frequency-first (reference :162-181) — the
  orientation every downstream kernel assumes.
- A writer (``write_psrfits``) exists for synthetic-injection tests
  (SURVEY.md §4); the reference has no writer.
"""

from __future__ import annotations

import math
import os
import re
import struct
import warnings
from typing import Dict, List, Optional, Sequence

import numpy as np

from pypulsar_tpu.astro import calendar, protractor
from pypulsar_tpu.core import psrmath
from pypulsar_tpu.core.spectra import Spectra
from pypulsar_tpu.io.errors import DataFormatError

date_obs_re = re.compile(
    r"^(?P<year>[0-9]{4})-(?P<month>[0-9]{2})-(?P<day>[0-9]{2})T"
    r"(?P<hour>[0-9]{2}):(?P<min>[0-9]{2}):(?P<sec>[0-9]{2}(?:\.[0-9]+)?)$"
)


def _fits():
    """astropy when available; otherwise our self-contained FITS codec
    (pypulsar_tpu.io.fitsio), which implements the same API subset."""
    try:
        from astropy.io import fits as pyfits
    except ImportError:
        from pypulsar_tpu.io import fitsio as pyfits
    return pyfits


# ---------------------------------------------------------------------------
# bit unpacking (reference formats/psrfits.py:37-50 — 4-bit only; PRESTO's
# psrfits.c also handles 2- and 1-bit, which we support for completeness)
# ---------------------------------------------------------------------------

def unpack_4bit(data: np.ndarray) -> np.ndarray:
    """Unpack bytes holding two unsigned 4-bit samples each (low nibble
    first, matching reference :48-50)."""
    data = np.asarray(data, dtype=np.uint8)
    out = np.empty(data.size * 2, dtype=np.uint8)
    out[0::2] = data & 15
    out[1::2] = data >> 4
    return out


def unpack_2bit(data: np.ndarray) -> np.ndarray:
    data = np.asarray(data, dtype=np.uint8)
    out = np.empty(data.size * 4, dtype=np.uint8)
    for i in range(4):
        out[i::4] = (data >> (2 * i)) & 3
    return out


def unpack_1bit(data: np.ndarray) -> np.ndarray:
    data = np.asarray(data, dtype=np.uint8)
    out = np.empty(data.size * 8, dtype=np.uint8)
    for i in range(8):
        out[i::8] = (data >> i) & 1
    return out


_UNPACKERS = {4: unpack_4bit, 2: unpack_2bit, 1: unpack_1bit}


# ---------------------------------------------------------------------------
# sniffing / date parsing
# ---------------------------------------------------------------------------

def is_PSRFITS(fn: str) -> bool:
    """True if the file looks like PSRFITS: FITSTYPE == PSRFITS or a
    SUBINT extension present (reference :577-591)."""
    return sniff_PSRFITS(fn)[0]


def sniff_PSRFITS(fn: str):
    """``(is_PSRFITS(fn), header bytes read to say so)``. Headers only:
    both codecs refuse a file that does not begin ``SIMPLE  =`` from its
    first block and skip every HDU's data by seek. The byte count is the
    in-tree codec's (``fitsio.HDUList.header_bytes``); astropy reports
    none, and a file refused mid-walk counts 0."""
    if not os.path.isfile(fn):
        return False, 0
    try:
        with _fits().open(fn, mode="readonly", memmap=True) as hdus:
            nread = getattr(hdus, "header_bytes", 0)
            primary = hdus[0].header
            if str(primary.get("FITSTYPE", "")).upper().startswith("PSRFITS"):
                return True, nread
            return any(h.name == "SUBINT" for h in hdus), nread
    except Exception:
        return False, 0


def DATEOBS_to_MJD(dateobs: str):
    """DATE-OBS card ('YYYY-MM-DDThh:mm:ss.sss') -> (int MJD, frac day)
    (reference :563-574, slalib-free)."""
    m = date_obs_re.match(dateobs)
    if m is None:
        warnings.warn(f"DATE-OBS card is not in the expected format: {dateobs!r}")
        return 0, 0.0
    mjd_day = calendar.gregorian_to_MJD(
        int(m.group("year")), int(m.group("month")), int(m.group("day"))
    )
    fmjd = (
        float(m.group("sec")) / 3600.0
        + int(m.group("min")) / 60.0
        + int(m.group("hour"))
    ) / 24.0
    return int(mjd_day), fmjd


# ---------------------------------------------------------------------------
# SpectraInfo — multi-file header aggregation (reference :186-560)
# ---------------------------------------------------------------------------

class SpectraInfo:
    """Aggregate search-mode metadata over one or more PSRFITS files.

    Carries the same attribute surface the reference exposes (telescope,
    source, fctr, lo_freq/hi_freq/df/BW, start_MJD[], num_subint[],
    start_spec[], num_spec[], num_pad[], N, T, need_scale/offset/weight/
    flipband, summed_polns, ...).  Files must be time-ordered; gaps
    between files become padding (num_pad), as in reference :425-432.
    """

    def __init__(self, filenames: Sequence[str]):
        try:
            self._init(filenames)
        except DataFormatError:
            raise
        except Exception as e:  # noqa: BLE001 - see below
            # the FITS codecs (astropy or our fitsio) surface truncation
            # and garbage as a zoo of exception types (ValueError,
            # KeyError, struct.error, even AttributeError from a
            # column-less table stub); the reader-fuzz contract is ONE
            # located error hierarchy — the original type survives in the
            # detail and the chained __cause__
            raise DataFormatError(
                filenames[0] if filenames else "<none>",
                f"malformed PSRFITS ({type(e).__name__}: {e})") from e

    def _init(self, filenames: Sequence[str]):
        self.filenames = list(filenames)
        self.num_files = len(self.filenames)
        self.N = 0
        self.user_poln = 0
        self.default_poln = 0

        self.start_MJD = np.empty(self.num_files)
        self.num_subint = np.empty(self.num_files, dtype=np.int64)
        self.start_subint = np.empty(self.num_files, dtype=np.int64)
        self.start_spec = np.empty(self.num_files, dtype=np.int64)
        self.num_pad = np.empty(self.num_files, dtype=np.int64)
        self.num_spec = np.empty(self.num_files, dtype=np.int64)

        self.need_scale = False
        self.need_offset = False
        self.need_weight = False
        self.need_flipband = False

        pyfits = _fits()
        for ii, fn in enumerate(self.filenames):
            if not is_PSRFITS(fn):
                raise ValueError(f"File '{fn}' does not appear to be PSRFITS!")
            with pyfits.open(fn, mode="readonly", memmap=True) as hdus:
                self._read_one(ii, hdus)

        # position strings -> degrees (reference :437-439)
        self.ra2000 = protractor.convert(self.ra_str, "hmsstr", "deg")
        self.dec2000 = protractor.convert(self.dec_str, "dmsstr", "deg")

        self.summed_polns = self.poln_order in ("AA+BB", "INTEN")

        self.T = self.N * self.dt
        self.orig_df /= float(self.orig_num_chan)
        self.samples_per_spectra = self.num_polns * self.num_channels
        self.bytes_per_spectra = (
            self.bits_per_sample * self.samples_per_spectra
        ) // 8
        self.samples_per_subint = self.samples_per_spectra * self.spectra_per_subint
        self.bytes_per_subint = self.bytes_per_spectra * self.spectra_per_subint

        if self.hi_freq < self.lo_freq:  # flip band (reference :458-464)
            self.hi_freq, self.lo_freq = self.lo_freq, self.hi_freq
            self.df *= -1.0
            self.need_flipband = True
        self.BW = self.num_channels * self.df
        self.mjd = int(self.start_MJD[0])
        self.secs = (self.start_MJD[0] % 1) * psrmath.SECPERDAY

    def _read_one(self, ii: int, hdus):
        if ii == 0:
            self.hdu_names = [hdu.name for hdu in hdus]
        primary = hdus[0].header

        telescope = str(primary.get("TELESCOP", ""))
        if telescope == "ARECIBO 305m":  # MockSpec quirk (reference :288-290)
            telescope = "Arecibo"
        if ii == 0:
            self.telescope = telescope
        elif telescope != self.telescope:
            warnings.warn(f"'TELESCOP' values don't match for files 0 and {ii}!")

        self.observer = primary.get("OBSERVER", "")
        self.source = primary.get("SRC_NAME", "")
        self.frontend = primary.get("FRONTEND", "")
        self.backend = primary.get("BACKEND", "")
        self.project_id = primary.get("PROJID", "")
        self.date_obs = primary.get("DATE-OBS", "")
        self.poln_type = primary.get("FD_POLN", "")
        self.ra_str = primary.get("RA", "00:00:00")
        self.dec_str = primary.get("DEC", "00:00:00")
        self.fctr = primary.get("OBSFREQ", 0.0)
        self.orig_num_chan = primary.get("OBSNCHAN", 1)
        self.orig_df = primary.get("OBSBW", 0.0)
        self.beam_FWHM = primary.get("BMIN", 0.0)
        self.chan_dm = primary.get("CHAN_DM", 0.0)
        self.start_lst = primary.get("STT_LST", 0.0)
        ibeam = primary.get("IBEAM")
        self.beam_id = None if ibeam in (None, "") else int(ibeam)

        self.start_MJD[ii] = primary.get("STT_IMJD", 0) + (
            primary.get("STT_SMJD", 0) + primary.get("STT_OFFS", 0.0)
        ) / psrmath.SECPERDAY

        track = primary.get("TRK_MODE", "TRACK") == "TRACK"
        if ii == 0:
            self.tracking = track
        elif track != self.tracking:
            warnings.warn(f"'TRK_MODE' values don't match for files 0 and {ii}")

        subint = hdus["SUBINT"].header
        self.dt = subint["TBIN"]
        self.num_channels = subint["NCHAN"]
        self.num_polns = subint["NPOL"]
        self._validate_subint(ii, subint)

        # PSRFITS_POLN env override (reference :275-282)
        envval = os.getenv("PSRFITS_POLN")
        if envval is not None:
            ival = int(envval)
            if -1 < ival < self.num_polns:
                self.default_poln = ival
                self.user_poln = 1

        self.poln_order = subint["POL_TYPE"]
        self.num_ifs = subint.get("NUMIFS", 1)  # Mock spectrometer extension
        if subint.get("NCHNOFFS", 0) > 0:
            warnings.warn(f"first freq channel is not 0 in file {ii}")
        self.spectra_per_subint = subint["NSBLK"]
        self.bits_per_sample = subint["NBITS"]
        self.num_subint[ii] = subint["NAXIS2"]
        self.start_subint[ii] = subint.get("NSUBOFFS", 0)
        self.time_per_subint = self.dt * self.spectra_per_subint

        # MJD offset from the starting subint number (reference :296-300)
        self.start_MJD[ii] += (
            self.time_per_subint * self.start_subint[ii]
        ) / psrmath.SECPERDAY

        MJDf = self.start_MJD[ii] - self.start_MJD[0]
        if MJDf < 0.0:
            raise ValueError(f"File {ii} seems to be from before file 0!")
        self.start_spec[ii] = int(MJDf * psrmath.SECPERDAY / self.dt + 0.5)

        subint_hdu = hdus["SUBINT"]
        colnames = subint_hdu.columns.names
        for col, attr in (("OFFS_SUB", "offs_sub_col"), ("DATA", "data_col")):
            if col not in colnames:
                warnings.warn(f"Can't find the '{col}' column!")
            else:
                colnum = colnames.index(col)
                if ii == 0:
                    setattr(self, attr, colnum)
                elif getattr(self, attr) != colnum:
                    warnings.warn(
                        f"'{col}' column changes between files 0 and {ii}!"
                    )
        if hasattr(self, "data_col"):
            self.FITS_typecode = subint_hdu.columns[self.data_col].format[-1]

        row0 = subint_hdu.data[0]
        self.azimuth = float(row0["TEL_AZ"]) if "TEL_AZ" in colnames else 0.0
        self.zenith_ang = float(row0["TEL_ZEN"]) if "TEL_ZEN" in colnames else 0.0

        if "DAT_FREQ" not in colnames:
            warnings.warn("Can't find the channel freq column, 'DAT_FREQ'!")
        else:
            freqs = np.atleast_1d(np.asarray(row0["DAT_FREQ"], dtype=np.float64))
            if ii == 0:
                self.df = freqs[1] - freqs[0] if freqs.size > 1 else self.orig_df
                self.lo_freq = freqs[0]
                self.hi_freq = freqs[-1]
                if freqs.size > 1 and np.any(np.abs(np.diff(freqs) - self.df) > 1e-7):
                    warnings.warn(f"Channel spacing changes in file {ii}!")
            else:
                if freqs.size > 1 and abs(self.df - (freqs[1] - freqs[0])) > 1e-7:
                    warnings.warn(f"Channel spacing between files 0 and {ii}!")
                if abs(self.lo_freq - freqs[0]) > 1e-7:
                    warnings.warn(f"Low channel changes between files 0 and {ii}!")
                if abs(self.hi_freq - freqs[-1]) > 1e-7:
                    warnings.warn(f"High channel changes between files 0 and {ii}!")

        for col, flag, bad in (
            ("DAT_WTS", "need_weight", 1.0),
            ("DAT_OFFS", "need_offset", 0.0),
            ("DAT_SCL", "need_scale", 1.0),
        ):
            if col not in colnames:
                warnings.warn(f"Can't find the channel column, '{col}'!")
            elif np.any(np.asarray(row0[col]) != bad):
                setattr(self, flag, True)

        # samples per file + padding owed by the previous file (reference
        # :425-432)
        self.num_pad[ii] = 0
        self.num_spec[ii] = self.spectra_per_subint * self.num_subint[ii]
        if ii > 0 and self.start_spec[ii] > self.N:
            self.num_pad[ii - 1] = self.start_spec[ii] - self.N
            self.N += self.num_pad[ii - 1]
        self.N += self.num_spec[ii]

    def _validate_subint(self, ii: int, subint) -> None:
        """Sanity-bound the SUBINT geometry before any derived math
        trusts it: a bit-flipped NBITS of 0 divides by zero in
        bytes_per_spectra, a garbage NCHAN of 2**30 allocates gigabyte
        tables, a non-finite TBIN poisons every timestamp."""
        path = self.filenames[ii]

        def bad(detail):
            raise DataFormatError(path, f"insane SUBINT header: {detail}")

        try:
            dt = float(self.dt)
            nchan = int(self.num_channels)
            npol = int(self.num_polns)
            nsblk = int(subint["NSBLK"])
            nbits = int(subint["NBITS"])
            nrows = int(subint["NAXIS2"])
        except (TypeError, ValueError) as e:
            bad(f"non-numeric geometry field ({e})")
        if not (math.isfinite(dt) and dt > 0):
            bad(f"TBIN={self.dt!r} not a positive finite float")
        if not 1 <= nchan <= (1 << 20):
            bad(f"NCHAN={nchan} outside [1, 2**20]")
        if not 1 <= npol <= 8:
            bad(f"NPOL={npol} outside [1, 8]")
        if not 1 <= nsblk <= (1 << 24):
            bad(f"NSBLK={nsblk} outside [1, 2**24]")
        if nbits not in (1, 2, 4, 8, 16, 32):
            bad(f"NBITS={nbits} not one of (1, 2, 4, 8, 16, 32)")
        if nrows < 0:
            bad(f"NAXIS2={nrows} negative")

    def __getitem__(self, key):
        return getattr(self, key)

    def __str__(self):
        lines = [
            f"From the PSRFITS file '{self.filenames[0]}':",
            f"                       HDUs = {', '.join(self.hdu_names)}",
            f"                  Telescope = {self.telescope}",
            f"                   Observer = {self.observer}",
            f"                Source Name = {self.source}",
            f"            Obs Date String = {self.date_obs}",
            f"     MJD start time (STT_*) = {self.start_MJD[0]:19.14f}",
            f"                   RA J2000 = {self.ra_str}",
            f"                  Dec J2000 = {self.dec_str}",
            f"           Sample time (us) = {self.dt * 1e6:-17.15g}",
            f"         Central freq (MHz) = {self.fctr:-17.15g}",
            f"          Low channel (MHz) = {self.lo_freq:-17.15g}",
            f"         High channel (MHz) = {self.hi_freq:-17.15g}",
            f"        Channel width (MHz) = {self.df:-17.15g}",
            f"         Number of channels = {self.num_channels}",
            f"      Total Bandwidth (MHz) = {self.BW:-17.15g}",
            f"         Spectra per subint = {self.spectra_per_subint}",
            f"           Subints per file = {self.num_subint[0]}",
            f"           Spectra per file = {self.num_spec[0]}",
            f"              Need scaling? = {self.need_scale}",
            f"              Need offsets? = {self.need_offset}",
            f"              Need weights? = {self.need_weight}",
            f"        Need band inverted? = {self.need_flipband}",
        ]
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# PsrfitsFile — single-file random access (reference :54-183)
# ---------------------------------------------------------------------------

class PsrfitsFile:
    """Random-access search-mode PSRFITS reader with the reference's
    surface: ``read_subint``, ``get_weights/scales/offsets``, and the
    loader boundary ``get_spectra(startsamp, N) -> Spectra``."""

    def __init__(self, psrfitsfn: str):
        if not os.path.isfile(psrfitsfn):
            raise ValueError(f"ERROR: File does not exist!\n\t({psrfitsfn})")
        self.filename = psrfitsfn
        try:
            self._open(psrfitsfn)
        except DataFormatError:
            raise
        except Exception as e:  # noqa: BLE001 - one error hierarchy (see
            # SpectraInfo.__init__)
            raise DataFormatError(
                psrfitsfn,
                f"malformed PSRFITS ({type(e).__name__}: {e})") from e

    def _open(self, psrfitsfn: str):
        self.fits = _fits().open(psrfitsfn, mode="readonly", memmap=True)
        self.specinfo = SpectraInfo([psrfitsfn])
        self.header = self.fits[0].header
        self.nbits = self.specinfo.bits_per_sample
        self.nchan = self.specinfo.num_channels
        self.npoln = self.specinfo.num_polns
        self.nsamp_per_subint = self.specinfo.spectra_per_subint
        self.nsubints = int(self.specinfo.num_subint[0])
        self.dat_freqs = np.atleast_1d(
            np.asarray(self.fits["SUBINT"].data[0]["DAT_FREQ"], dtype=np.float64)
        )
        # the public frequency table matches get_spectra's delivered
        # channel order (high-frequency-first unless the file is already
        # inverted) — a low-first table paired with flipped data sent
        # dedispersion delays to the wrong channels
        if not self.specinfo.need_flipband:
            self.freqs = self.dat_freqs[::-1].copy()
        else:
            self.freqs = self.dat_freqs
        self.frequencies = self.freqs
        self.tsamp = self.specinfo.dt
        self.nspec = int(self.nsamp_per_subint) * self.nsubints

    def close(self):
        self.fits.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def read_subint(
        self,
        isub: int,
        apply_weights: bool = True,
        apply_scales: bool = True,
        apply_offsets: bool = True,
    ) -> np.ndarray:
        """One subint as float32 [nsamp_per_subint, nchan] with
        ``(data*scales + offsets)*weights`` applied per channel
        (reference :70-108).  Multi-poln data keeps poln
        ``specinfo.default_poln`` (PRESTO-style; summed polns pass
        through)."""
        from pypulsar_tpu import native

        subintdata = np.asarray(self.fits["SUBINT"].data[isub]["DATA"])
        if self.nbits in _UNPACKERS:
            if native.available():
                data = native.unpack_bits(subintdata.ravel(), self.nbits)
            else:
                data = _UNPACKERS[self.nbits](
                    subintdata.ravel()).astype(np.float32)
        else:
            data = subintdata.astype(np.float32).ravel()
        offsets = self.get_offsets(isub) if apply_offsets else 0
        scales = self.get_scales(isub) if apply_scales else 1
        weights = self.get_weights(isub) if apply_weights else 1
        if self.npoln > 1:
            data = data.reshape((self.nsamp_per_subint, self.npoln, self.nchan))
            poln = self.specinfo.default_poln
            data = data[:, poln, :]
            # DAT_SCL/DAT_OFFS hold npol consecutive nchan blocks
            sl = slice(poln * self.nchan, (poln + 1) * self.nchan)
            scales = np.asarray(scales).reshape(-1)[sl]
            offsets = np.asarray(offsets).reshape(-1)[sl]
        else:
            data = data.reshape((self.nsamp_per_subint, self.nchan))
        if (native.available()
                and all(np.ndim(a) and np.asarray(a).size == self.nchan
                        for a in (scales, offsets, weights))):
            return native.scale_offset_weight(
                np.ascontiguousarray(data), scales, offsets, weights)
        return ((data * scales) + offsets) * weights

    def get_weights(self, isub: int) -> np.ndarray:
        return np.asarray(self.fits["SUBINT"].data[isub]["DAT_WTS"])

    def get_scales(self, isub: int) -> np.ndarray:
        return np.asarray(self.fits["SUBINT"].data[isub]["DAT_SCL"])

    def get_offsets(self, isub: int) -> np.ndarray:
        return np.asarray(self.fits["SUBINT"].data[isub]["DAT_OFFS"])

    def get_spectra(self, startsamp: int, N: int) -> Spectra:
        """[chan, time] Spectra spanning subints, truncated to exactly N
        samples, flipped to high-frequency-first (reference :143-183).
        Garbage payload bytes (a DATA cell whose length no longer
        matches the declared geometry) surface as a located
        :class:`DataFormatError`, not a reshape ValueError."""
        startsamp = int(startsamp)
        N = int(N)
        # range check OUTSIDE the wrapper: a caller bug, not bad data
        if startsamp < 0 or startsamp + N > self.nspec:
            raise ValueError(
                f"requested samples [{startsamp}, {startsamp + N}) outside "
                f"file range [0, {self.nspec})"
            )
        try:
            return self._get_spectra(startsamp, N)
        except DataFormatError:
            raise
        except Exception as e:  # noqa: BLE001 - one error hierarchy (see
            # SpectraInfo.__init__)
            raise DataFormatError(
                self.filename,
                f"malformed SUBINT payload ({type(e).__name__}: "
                f"{e})") from e

    def _get_spectra(self, startsamp: int, N: int) -> Spectra:
        startsub = startsamp // self.nsamp_per_subint
        skip = startsamp - startsub * self.nsamp_per_subint
        endsub = (startsamp + N - 1) // self.nsamp_per_subint
        blocks = [self.read_subint(isub) for isub in range(startsub, endsub + 1)]
        data = np.concatenate(blocks) if len(blocks) > 1 else blocks[0]
        data = data.T[:, skip : skip + N]
        if not self.specinfo.need_flipband:
            # file stores low->high; Spectra wants high-frequency first
            # (self.freqs is already in the delivered order)
            data = data[::-1, :]
        return Spectra(
            self.freqs,
            self.tsamp,
            np.ascontiguousarray(data, dtype=np.float32),
            starttime=self.tsamp * startsamp,
            dm=self.specinfo.chan_dm,
        )


# ---------------------------------------------------------------------------
# writer — synthetic search-mode PSRFITS for tests & tooling
# ---------------------------------------------------------------------------

def write_psrfits(
    fn: str,
    data: np.ndarray,
    freqs: np.ndarray,
    tsamp: float,
    nsamp_per_subint: int = 64,
    nbits: int = 8,
    start_mjd: float = 56000.0,
    src_name: str = "FAKE_PSR",
    telescope: str = "FAKE",
    ra_str: str = "00:00:00.0",
    dec_str: str = "00:00:00.0",
    scales: Optional[np.ndarray] = None,
    offsets: Optional[np.ndarray] = None,
    weights: Optional[np.ndarray] = None,
    nsuboffs: int = 0,
    extra_primary: Optional[Dict[str, object]] = None,
) -> str:
    """Write ``data`` [chan, time] (channel 0 = freqs[0]; stored on disk
    low-frequency-first as real PSRFITS search files are) to a minimal
    but conformant search-mode PSRFITS file.

    nbits 8 stores uint8 (values clipped), nbits 4 packs two samples per
    byte, nbits 32 stores float32 verbatim.  Per-channel scales/offsets/
    weights default to identity.
    """
    pyfits = _fits()
    freqs = np.asarray(freqs, dtype=np.float64)
    data = np.asarray(data)
    nchan, nspec = data.shape
    if freqs.size > 1 and freqs[0] > freqs[-1]:
        # store low->high on disk
        freqs = freqs[::-1]
        data = data[::-1, :]
    nsub = -(-nspec // nsamp_per_subint)
    padded = np.zeros((nchan, nsub * nsamp_per_subint), dtype=np.float32)
    padded[:, :nspec] = data
    tdata = padded.T  # [time, chan]

    scales = np.ones(nchan, np.float32) if scales is None else np.asarray(scales, np.float32)
    offsets = np.zeros(nchan, np.float32) if offsets is None else np.asarray(offsets, np.float32)
    weights = np.ones(nchan, np.float32) if weights is None else np.asarray(weights, np.float32)

    imjd = int(start_mjd)
    fsec = (start_mjd - imjd) * psrmath.SECPERDAY
    smjd = int(fsec)
    offs = fsec - smjd

    primary = pyfits.PrimaryHDU()
    ph = primary.header
    ph["FITSTYPE"] = "PSRFITS"
    ph["OBS_MODE"] = "SEARCH"
    ph["TELESCOP"] = telescope
    ph["OBSERVER"] = "pypulsar_tpu"
    ph["SRC_NAME"] = src_name
    ph["FRONTEND"] = "FAKE"
    ph["BACKEND"] = "FAKE"
    ph["PROJID"] = "TEST"
    ph["DATE-OBS"] = calendar.MJD_to_datetime(start_mjd).strftime(
        "%Y-%m-%dT%H:%M:%S"
    )
    ph["FD_POLN"] = "LIN"
    ph["RA"] = ra_str
    ph["DEC"] = dec_str
    ph["OBSFREQ"] = float(freqs.mean())
    ph["OBSNCHAN"] = nchan
    ph["OBSBW"] = float(abs(freqs[-1] - freqs[0]) + abs(freqs[1] - freqs[0])) if nchan > 1 else 1.0
    ph["BMIN"] = 0.1
    ph["CHAN_DM"] = 0.0
    ph["TRK_MODE"] = "TRACK"
    ph["STT_IMJD"] = imjd
    ph["STT_SMJD"] = smjd
    ph["STT_OFFS"] = offs
    ph["STT_LST"] = 0.0
    for key, val in (extra_primary or {}).items():
        ph[key] = val

    nrows = nsub
    if nbits == 32:
        stored = tdata.reshape(nrows, nsamp_per_subint, 1, nchan).astype(np.float32)
        data_col = pyfits.Column(
            name="DATA",
            format=f"{nsamp_per_subint * nchan}E",
            dim=f"({nchan},1,{nsamp_per_subint})",
            array=stored.reshape(nrows, -1),
        )
    elif nbits == 8:
        stored = np.clip(np.round(tdata), 0, 255).astype(np.uint8)
        stored = stored.reshape(nrows, nsamp_per_subint, 1, nchan)
        data_col = pyfits.Column(
            name="DATA",
            format=f"{nsamp_per_subint * nchan}B",
            dim=f"({nchan},1,{nsamp_per_subint})",
            array=stored.reshape(nrows, -1),
        )
    elif nbits == 4:
        vals = np.clip(np.round(tdata), 0, 15).astype(np.uint8)
        flat = vals.reshape(nrows, -1)
        if flat.shape[1] % 2:
            raise ValueError("4-bit data needs an even samples*chan per row")
        packed = (flat[:, 0::2] & 15) | (flat[:, 1::2] << 4)
        data_col = pyfits.Column(
            name="DATA",
            format=f"{packed.shape[1]}B",
            dim=f"({nchan // 2},1,{nsamp_per_subint})" if nchan % 2 == 0 else None,
            array=packed,
        )
    else:
        raise ValueError(f"unsupported nbits={nbits}")

    tsub = nsamp_per_subint * tsamp
    cols = pyfits.ColDefs(
        [
            pyfits.Column(name="TSUBINT", format="1D", unit="s",
                          array=np.full(nrows, tsub)),
            pyfits.Column(name="OFFS_SUB", format="1D", unit="s",
                          array=(np.arange(nrows) + 0.5) * tsub),
            pyfits.Column(name="TEL_AZ", format="1D", unit="deg",
                          array=np.zeros(nrows)),
            pyfits.Column(name="TEL_ZEN", format="1D", unit="deg",
                          array=np.full(nrows, 5.0)),
            pyfits.Column(name="DAT_FREQ", format=f"{nchan}D", unit="MHz",
                          array=np.tile(freqs, (nrows, 1))),
            pyfits.Column(name="DAT_WTS", format=f"{nchan}E",
                          array=np.tile(weights, (nrows, 1))),
            pyfits.Column(name="DAT_OFFS", format=f"{nchan}E",
                          array=np.tile(offsets, (nrows, 1))),
            pyfits.Column(name="DAT_SCL", format=f"{nchan}E",
                          array=np.tile(scales, (nrows, 1))),
            data_col,
        ]
    )
    subint = pyfits.BinTableHDU.from_columns(cols, name="SUBINT")
    sh = subint.header
    sh["TBIN"] = tsamp
    sh["NCHAN"] = nchan
    sh["NPOL"] = 1
    sh["POL_TYPE"] = "AA+BB"
    sh["NCHNOFFS"] = 0
    sh["NSBLK"] = nsamp_per_subint
    sh["NBITS"] = nbits
    sh["NSUBOFFS"] = nsuboffs
    sh["CHAN_BW"] = float(freqs[1] - freqs[0]) if nchan > 1 else 1.0

    pyfits.HDUList([primary, subint]).writeto(fn, overwrite=True)
    return fn
