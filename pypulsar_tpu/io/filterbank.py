"""SIGPROC filterbank file reader/writer.

Replaces reference formats/filterbank.py (and its external sigproc dep) with
our own codec. The loader boundary is ``get_spectra(startsamp, N) -> Spectra``
(reference formats/filterbank.py:143-157): data arrives on host as
[time, chan], is transposed to [chan, time] and wrapped in a Spectra.

Also provides a writer (the reference has none beyond header copies in
bin/zero_dm_filter.py:21-27) — needed for synthetic-injection tests
(SURVEY.md §4 strategy 2) and for CLI tools that rewrite .fil files.
"""

from __future__ import annotations

import os
import warnings
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from pypulsar_tpu.core.spectra import Spectra
from pypulsar_tpu.io import sigproc
from pypulsar_tpu.io.errors import DataFormatError


class FilterbankFile:
    """Random-access SIGPROC filterbank reader.

    Attributes mirror the reference reader: ``header`` dict, ``frequencies``
    (per-channel MHz, in file channel order), ``nspec`` total samples,
    ``is_hifreq_first`` (foff < 0).
    """

    # iter_blocks yields (startsamp, [time, chan] ndarray) blocks stepping
    # by block_size — the contract the raw streaming paths require
    # (parallel/staged._ReaderSource, ops.rfifind.rfifind: blocks ship as
    # the file holds them and the device unpacks; fbobs.iter_blocks has
    # different semantics and no marker, PsrfitsFile no iter_blocks: both
    # unpack on the host)
    BLOCK_ITER_ARRAYS = True

    def __init__(self, filfn: str):
        self.filename = filfn
        if not os.path.isfile(filfn):
            raise ValueError(f"File does not exist: {filfn}")
        self.filfile = open(filfn, "rb")
        self.header, self.header_params, self.header_size = sigproc.read_header(
            self.filfile, path=filfn
        )
        sigproc.validate_header(self.header, filfn)
        nbits = int(self.header["nbits"])
        if nbits == 32:
            self.dtype = np.dtype("float32")
        elif nbits in (8, 16):
            self.dtype = np.dtype(f"uint{nbits}")
        else:
            # sub-byte: 8//nbits channels per byte, low bits = lower
            # channel index (the PSRFITS convention, io/psrfits.py:55-81;
            # reference formats/psrfits.py:48-50). Raw blocks stay PACKED
            # so a 4-bit file ships half an 8-bit file's bytes over the
            # host->device wire (the streamed sweep's measured
            # bottleneck); unpack happens on device (ops/ingest.
            # _ingest_tc: the sweep's block source and the mask stage)
            # or on host in get_samples / get_spectra.
            # (validate_header already rejected anything outside
            # {1, 2, 4, 8, 16, 32})
            if self.nchans % (8 // nbits):
                raise DataFormatError(
                    filfn, f"nbits={nbits} requires nchans divisible by "
                           f"{8 // nbits}; got {self.nchans}")
            self.dtype = np.dtype("uint8")
        self.nbits = nbits
        self.bytes_per_spectrum = self.nchans * nbits // 8
        self.data_size = os.stat(filfn).st_size - self.header_size
        self.number_of_samples = self.data_size // self.bytes_per_spectrum
        # truncated-tail salvage: the whole valid prefix is readable and
        # the missing span is REPORTED (reader.salvage feeds the survey's
        # per-obs data-quality report) — a dropped network copy or a
        # recorder kill must degrade, not crash
        partial_tail = self.data_size % self.bytes_per_spectrum
        expected = int(self.header.get("nsamples", 0) or 0)
        missing = (max(expected - self.number_of_samples, 0)
                   if expected > 0 else 0)
        self.salvage = None
        if partial_tail or missing:
            self.salvage = {
                "read_samples": int(self.number_of_samples),
                "expected_samples": int(expected) or None,
                "missing_samples": int(missing),
                "partial_tail_bytes": int(partial_tail),
            }
            warnings.warn(
                f"{filfn}: truncated tail salvaged — reading "
                f"{self.number_of_samples} whole samples"
                + (f" of {expected} expected" if expected else "")
                + (f" ({partial_tail} partial-spectrum bytes dropped)"
                   if partial_tail else ""))
        self.frequencies = self.fch1 + self.foff * np.arange(self.nchans)
        self.freqs = self.frequencies
        self.is_hifreq_first = self.foff < 0

    # header fields as attributes (reference filterbank.py:36)
    def __getattr__(self, name):
        try:
            return self.__dict__["header"][name]
        except KeyError:
            raise AttributeError(name)

    @property
    def nspec(self) -> int:
        return self.number_of_samples

    @property
    def obs_duration(self) -> float:
        return self.number_of_samples * self.tsamp

    def close(self):
        self.filfile.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def seek_to_sample(self, sampnum: int):
        self.filfile.seek(self.header_size + self.bytes_per_spectrum * sampnum)

    def read_Nsamples(self, N: int) -> np.ndarray:
        count = N * self.bytes_per_spectrum // self.dtype.itemsize
        return np.fromfile(self.filfile, dtype=self.dtype, count=count)

    def read_all_samples(self) -> np.ndarray:
        self.seek_to_sample(0)
        data = np.fromfile(self.filfile, dtype=self.dtype)
        if self.nbits < 8:
            from pypulsar_tpu.io.psrfits import _UNPACKERS

            data = _UNPACKERS[self.nbits](data)
        return data

    def _read_raw_block(self, startsamp: int, N: int) -> np.ndarray:
        """Validated seek+read of N samples in the file's native dtype
        (flat array of N*nchans values)."""
        startsamp, N = int(startsamp), int(N)
        if startsamp < 0 or startsamp + N > self.number_of_samples:
            raise ValueError(
                f"requested samples [{startsamp}, {startsamp + N}) outside "
                f"file range [0, {self.number_of_samples})"
            )
        self.seek_to_sample(startsamp)
        return self.read_Nsamples(N)

    def get_samples(self, startsamp: int, N: int) -> np.ndarray:
        """Raw [time, chan] block as float32 (no Spectra wrapper);
        sub-byte files are unpacked on host here, and every sample
        widens to 4 bytes on the host (16x a 2-bit file's bytes). The
        random-access path: get_spectra, waterfaller, the tests' host
        reference. The streaming readers of a whole file (sweep, mask
        stage) take ``iter_blocks(raw=True)`` and unpack on the device."""
        data = self._read_raw_block(startsamp, N)
        if self.nbits < 8:
            from pypulsar_tpu.io.psrfits import _UNPACKERS

            data = _UNPACKERS[self.nbits](data)
        data.shape = (int(N), self.nchans)
        return data.astype(np.float32)

    def get_spectra(self, startsamp: int, N: int) -> Spectra:
        """The loader boundary: [chan, time] Spectra of N samples.  Uses
        the native fused widen+transpose when available."""
        from pypulsar_tpu import native

        if native.available() and self.nbits >= 8:
            raw = self._read_raw_block(startsamp, N)
            data = native.transpose_to_chan_major(raw, int(N), self.nchans)
        else:
            data = self.get_samples(startsamp, N).T
        return Spectra(
            self.frequencies,
            self.tsamp,
            data,
            starttime=self.tsamp * int(startsamp),
            dm=0.0,
        )

    def iter_blocks(
        self, block_size: int, overlap: int = 0, start: int = 0,
        end: Optional[int] = None, prefetch: bool = True, raw: bool = False,
    ) -> Iterator[Tuple[int, np.ndarray]]:
        """Stream [time, chan] blocks with ``overlap`` samples of lookahead
        beyond each block (overlap-save for chunked dedispersion; the TPU
        analogue of the reference's file streaming, SURVEY.md §2.4 row 3).

        With ``prefetch`` (default) blocks load on a native background
        thread a few blocks ahead of the consumer
        (pypulsar_tpu.native.PrefetchReader, prefetch.cpp), so disk reads
        overlap device compute; falls back to synchronous reads when the
        native library is unavailable.

        ``raw`` yields blocks in the file's native dtype instead of
        float32: an 8-bit file then ships 1 byte/sample to the device,
        where the f32 cast is exact and fused — a quarter of the bytes
        on the host->device link.
        Sub-byte files yield PACKED [time, nchans*nbits//8] uint8 blocks
        when ``raw`` (device-side unpack in ops/ingest._ingest_tc, for
        the sweep's block source and for ops.rfifind's mask stage alike:
        a 4-bit file ships HALF the 8-bit bytes, VERDICT r4 item 2) and
        host-unpacked float32 [time, chan] otherwise.

        Yields (startsamp, block[time, chan]) with block length
        block_size + overlap except possibly at the tail.
        """
        if start < 0:
            raise ValueError(f"iter_blocks start must be >= 0; got {start}")
        end = self.number_of_samples if end is None else min(end, self.number_of_samples)
        row_len = (self.bytes_per_spectrum // self.dtype.itemsize
                   if self.nbits < 8 else self.nchans)
        if prefetch and start < end:
            from pypulsar_tpu import native

            reader = native.PrefetchReader(
                self.filename,
                self.header_size + start * self.bytes_per_spectrum,
                self.bytes_per_spectrum,
                end - start, payload=block_size, overlap=overlap)
            for pos, rawbuf in reader:
                block = np.frombuffer(rawbuf, dtype=self.dtype).reshape(
                    -1, row_len)
                yield pos + start, (block if raw
                                    else self._widen_block(block))
            return
        pos = start
        while pos < end:
            n = min(block_size + overlap, end - pos)
            if raw:
                block = self._read_raw_block(pos, n).reshape(-1, row_len)
            else:
                block = self.get_samples(pos, n)
            yield pos, block
            pos += block_size

    def _widen_block(self, packed: np.ndarray) -> np.ndarray:
        """[time, row_len] native-dtype block -> [time, chan] float32
        (host-side unpack for sub-byte files)."""
        if self.nbits >= 8:
            return packed.astype(np.float32)
        from pypulsar_tpu.io.psrfits import _UNPACKERS

        return _UNPACKERS[self.nbits](packed.ravel()).reshape(
            -1, self.nchans).astype(np.float32)


DEFAULT_HEADER = {
    "telescope_id": 0,
    "machine_id": 0,
    "data_type": 1,  # filterbank
    "source_name": "synthetic",
    "barycentric": 0,
    "src_raj": 0.0,
    "src_dej": 0.0,
    "az_start": 0.0,
    "za_start": 0.0,
    "nbits": 32,
    "nifs": 1,
    "tstart": 60000.0,
}


def pack_subbyte(values: np.ndarray, nbits: int) -> np.ndarray:
    """Pack uint samples (< 2**nbits after clipping) into bytes, low bits
    = lower index — the inverse of io.psrfits unpack_{4,2,1}bit. The
    LAST axis is packed and must be divisible by 8//nbits."""
    spb = 8 // nbits
    v = np.asarray(values)
    if v.shape[-1] % spb:
        raise ValueError(f"last axis {v.shape[-1]} not divisible by {spb}")
    v = np.clip(v, 0, (1 << nbits) - 1).astype(np.uint8)
    v = v.reshape(v.shape[:-1] + (v.shape[-1] // spb, spb))
    out = np.zeros(v.shape[:-1], dtype=np.uint8)
    for i in range(spb):
        out |= v[..., i] << (nbits * i)
    return out


def write_filterbank(filfn: str, header: Dict[str, object], data: np.ndarray):
    """Write a filterbank file.

    ``data`` is [time, chan] (file sample order). Required header keys:
    fch1, foff, nchans, tsamp; everything else defaults sensibly.
    Sub-byte nbits (4/2/1) packs the channel axis low-bits-first
    (pack_subbyte); values are clipped to the representable range.
    """
    hdr = dict(DEFAULT_HEADER)
    hdr.update(header)
    for key in ("fch1", "foff", "nchans", "tsamp"):
        if key not in hdr:
            raise ValueError(f"header missing required key {key!r}")
    # stamp the sample count: readers cross-check it against the actual
    # file size, which is what turns a truncated copy into a REPORTED
    # salvaged span instead of a silently shorter observation
    hdr.setdefault("nsamples", int(np.asarray(data).shape[0]))
    nbits = int(hdr["nbits"])
    if nbits == 32:
        dtype = np.dtype("float32")
    elif nbits in (8, 16):
        dtype = np.dtype(f"uint{nbits}")
    elif nbits in (4, 2, 1):
        dtype = None  # packed below
    else:
        raise ValueError(f"unsupported nbits={nbits}")
    data = np.asarray(data)
    if data.ndim != 2 or data.shape[1] != int(hdr["nchans"]):
        raise ValueError(
            f"data must be [time, nchans={hdr['nchans']}]; got {data.shape}"
        )
    with open(filfn, "wb") as f:
        f.write(sigproc.pack_header(hdr))
        if dtype is None:
            pack_subbyte(data, nbits).tofile(f)
        else:
            data.astype(dtype).tofile(f)
