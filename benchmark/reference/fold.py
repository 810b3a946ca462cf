"""Folding in float64, and the ``.pfd`` archives the program writes.

A fold at constant period: sample i of the series, at time i*dt, falls in
phase bin floor(i*dt/period*nbins) mod nbins; each of ``npart`` equal time
partitions keeps its own profile of summed samples.
"""

from __future__ import annotations

import struct

import numpy as np


def fold_parts(series, dt: float, period: float, nbins: int, npart: int):
    series = np.asarray(series, dtype=np.float64)
    T = len(series)
    t = np.arange(T, dtype=np.float64) * dt
    f0 = 1.0 / period
    phase = t * (f0 + t * (0.0 / 2.0 + t * 0.0 / 6.0))
    bins = np.floor(phase * nbins).astype(np.int64) % nbins
    part_len = T // npart
    profs = np.empty((npart, nbins))
    for i in range(npart):
        sl = slice(i * part_len, (i + 1) * part_len)
        profs[i] = np.bincount(bins[sl], weights=series[sl],
                               minlength=nbins)
    return profs


def read_pfd(path: str) -> dict:
    """Geometry, fold period, DM and the [npart, nsub, proflen] profiles
    of a PRESTO-layout ``.pfd``."""
    with open(path, "rb") as f:
        (numdms, numperiods, numpdots, nsub, npart, proflen, numchan,
         _ps, _pds, _dms, _ndf, _npf) = struct.unpack("<12i", f.read(48))
        for _ in range(4):
            (n,) = struct.unpack("<i", f.read(4))
            f.read(n)
        test = f.read(16)
        if b":" in test:
            f.read(16)
        else:
            f.seek(-16, 1)
        (dt, _st, _et, _te, _be, _v, _lo, _cw, bestdm) = struct.unpack(
            "<9d", f.read(72))
        periods = {}
        for pre in ("topo", "bary", "fold"):
            f.read(8)
            periods[pre] = struct.unpack("<3d", f.read(24))
        f.read(56)
        np.fromfile(f, "<f8", numdms + numperiods + numpdots)
        profs = np.fromfile(f, "<f8", npart * nsub * proflen).reshape(
            npart, nsub, proflen)
    return {"dt": dt, "dm": bestdm, "period": periods["fold"][0],
            "npart": npart, "nsub": nsub, "nbins": proflen, "profs": profs}
