"""SIGPROC filterbank files, read and written without the program's codec.

Header: ``HEADER_START`` .. ``HEADER_END``, each keyword a length-prefixed
ASCII string followed by its little-endian value. Payload: time-major
samples, ``nchans`` per spectrum, ``nbits`` each; sub-byte samples are packed
low bits first (the lower channel index in the lower bits).
"""

from __future__ import annotations

import os
import struct

import numpy as np

_TYPES = {
    "telescope_id": "i", "machine_id": "i", "data_type": "i",
    "rawdatafile": "str", "source_name": "str", "barycentric": "i",
    "pulsarcentric": "i", "az_start": "d", "za_start": "d", "src_raj": "d",
    "src_dej": "d", "tstart": "d", "tsamp": "d", "nbits": "i",
    "nsamples": "i", "fch1": "d", "foff": "d", "fchannel": "d",
    "nchans": "i", "nifs": "i", "refdm": "d", "period": "d", "nbeams": "i",
    "ibeam": "i", "signed": "b",
}


def _string(s: str) -> bytes:
    raw = s.encode("ascii")
    return struct.pack("<i", len(raw)) + raw


def pack_header(hdr: dict) -> bytes:
    out = [_string("HEADER_START")]
    for key, val in hdr.items():
        kind = _TYPES[key]
        out.append(_string(key))
        out.append(_string(str(val)) if kind == "str"
                   else struct.pack("<" + kind, val))
    out.append(_string("HEADER_END"))
    return b"".join(out)


def read_header(path: str):
    """(header dict, payload byte offset)."""
    hdr = {}
    with open(path, "rb") as f:
        def string():
            (n,) = struct.unpack("<i", f.read(4))
            if not 0 < n < 256:
                raise ValueError(f"{path}: bad header string length {n}")
            return f.read(n).decode("ascii")

        if string() != "HEADER_START":
            raise ValueError(f"{path}: not a SIGPROC file")
        while True:
            key = string()
            if key == "HEADER_END":
                return hdr, f.tell()
            kind = _TYPES[key]
            if kind == "str":
                hdr[key] = string()
            else:
                size = struct.calcsize("<" + kind)
                (hdr[key],) = struct.unpack("<" + kind, f.read(size))


def pack_subbyte(values: np.ndarray, nbits: int) -> np.ndarray:
    """uint8 samples (< 2**nbits) packed along the last axis, low bits
    first."""
    spb = 8 // nbits
    v = values.reshape(values.shape[:-1] + (values.shape[-1] // spb, spb))
    out = np.zeros(v.shape[:-1], dtype=np.uint8)
    for i in range(spb):
        out |= (v[..., i] << (nbits * i)).astype(np.uint8)
    return out


def unpack_subbyte(raw: np.ndarray, nbits: int) -> np.ndarray:
    """The inverse of :func:`pack_subbyte`, through a 256-row table."""
    spb = 8 // nbits
    table = np.array([[(v >> (nbits * i)) & ((1 << nbits) - 1)
                       for i in range(spb)] for v in range(256)],
                     dtype=np.uint8)
    return table[raw].reshape(raw.shape[:-1] + (raw.shape[-1] * spb,))


class Filterbank:
    """Header geometry plus block reads as [chan, time] arrays with the
    highest frequency in row 0 (the order the dedispersion tables use)."""

    def __init__(self, path: str):
        self.path = path
        self.header, self._offset = read_header(path)
        h = self.header
        self.nchan = int(h["nchans"])
        self.nbits = int(h["nbits"])
        self.tsamp = float(h["tsamp"])
        if self.nbits not in (2, 4, 8):
            raise ValueError(f"{path}: {self.nbits}-bit samples unsupported")
        self._row_bytes = self.nchan * self.nbits // 8
        self.nsamp = (os.path.getsize(path) - self._offset) // self._row_bytes
        freqs = float(h["fch1"]) + float(h["foff"]) * np.arange(self.nchan)
        self._flip = self.nchan > 1 and freqs[0] < freqs[-1]
        self.freqs = freqs[::-1].copy() if self._flip else freqs

    def read(self, start: int, n: int, dtype=np.float32,
             ascending: bool = False) -> np.ndarray:
        """Samples [start, start+n) clipped to the file, as [chan, time],
        highest frequency first (``ascending``: lowest first)."""
        n = max(0, min(n, self.nsamp - start))
        raw = np.fromfile(self.path, dtype=np.uint8,
                          count=n * self._row_bytes,
                          offset=self._offset + start * self._row_bytes)
        raw = raw.reshape(n, self._row_bytes)
        if self.nbits < 8:
            raw = unpack_subbyte(raw, self.nbits)
        if self._flip != ascending:
            raw = raw[:, ::-1]
        # transposed in strips: one strided pass over the whole block is
        # several times slower than strips that stay in cache
        out = np.empty((self.nchan, n), dtype=dtype)
        strip = 512
        for i in range(0, n, strip):
            out[:, i:i + strip] = raw[i:i + strip].T
        return out
