"""The acceleration search's arithmetic at one candidate, in float64.

A candidate row says: at fundamental bin ``r`` and drift ``z`` the sum over
``H`` harmonics of the matched-filter power is ``pow``. This module makes
the spectrum again (real FFT of the mean-removed series, red-noise
normalisation by running block medians) and evaluates that sum directly:
each subharmonic b/H correlated, without any FFT, against the analytic
response of a drifting sinusoid, windowed and normalised to unit energy as
the search's template banks are.
"""

from __future__ import annotations

import math

import numpy as np
from scipy.special import fresnel

CAND_DTYPE = np.dtype([
    ("r", "<f8"), ("rerr", "<f4"), ("_pad1", "<f4"),
    ("z", "<f8"), ("zerr", "<f4"), ("_pad2", "<f4"),
    ("w", "<f8"), ("werr", "<f4"), ("pow", "<f4"), ("powerr", "<f4"),
    ("sig", "<f4"), ("rawpow", "<f4"), ("phs", "<f4"), ("phserr", "<f4"),
    ("cen", "<f4"), ("cenerr", "<f4"), ("pur", "<f4"), ("purerr", "<f4"),
    ("locpow", "<f4"),
])


def read_cands(path: str):
    """Records of a PRESTO ``.cand`` file (88-byte fourierprops); the
    program stores the number of harmonics summed in ``locpow``."""
    return np.fromfile(path, dtype=CAND_DTYPE)


def deredden(fft, initialbuflen=6, maxbuflen=200):
    """PRESTO-style red-noise normalisation: each block of bins is scaled
    by the inverse root of a line through the medians of neighbouring
    blocks, block lengths growing with the logarithm of the offset."""
    powers = np.abs(fft) ** 2
    out = np.array(fft, dtype=np.complex128)
    out[0] = 1 + 0j
    newoffset = fixedoffset = 1
    mean_old = np.median(powers[newoffset:newoffset + initialbuflen]) \
        / np.log(2.0)
    newoffset += initialbuflen
    lastbuflen = initialbuflen
    newbuflen = int(initialbuflen * np.log(newoffset))
    if newoffset > maxbuflen:
        newbuflen = maxbuflen
    scaleval = np.ones(1)
    while newoffset + newbuflen < len(out):
        mean_new = np.median(powers[newoffset:newoffset + newbuflen]) \
            / np.log(2.0)
        slope = (mean_new - mean_old) / (newbuflen + lastbuflen)
        ioffs = np.arange(lastbuflen)
        lineval = mean_old + slope * (0.5 * (newbuflen + lastbuflen) - ioffs)
        scaleval = 1.0 / np.sqrt(lineval)
        out[fixedoffset + ioffs] *= scaleval
        fixedoffset += lastbuflen
        lastbuflen = newbuflen
        mean_old = mean_new
        newoffset += lastbuflen
        newbuflen = min(int(initialbuflen * np.log(newoffset)), maxbuflen)
    out[fixedoffset:] *= scaleval[-1]
    return out


def spectrum(series: np.ndarray) -> np.ndarray:
    series = np.asarray(series, dtype=np.float64)
    return deredden(np.fft.rfft(series - series.mean()))


def z_response(z: float, offsets) -> np.ndarray:
    """Response of a sinusoid drifting by ``z`` bins, at bin offsets from
    its start frequency (continuum limit; sinc for no drift)."""
    q = -np.asarray(offsets, dtype=np.float64)
    if abs(z) < 1e-4:
        return np.exp(1j * np.pi * q) * np.sinc(q)
    if z < 0:
        return np.conj(z_response(-z, -np.asarray(offsets, np.float64)))
    y0 = q * np.sqrt(2.0 / z)
    y1 = (1.0 + q / z) * np.sqrt(2.0 * z)
    s0, c0 = fresnel(y0)
    s1, c1 = fresnel(y1)
    return np.exp(-1j * np.pi * q * q / z) \
        * ((c1 - c0) + 1j * (s1 - s0)) / np.sqrt(2.0 * z)


def halfwidth(z: float, min_halfwidth: int = 24) -> int:
    return int(np.ceil(abs(z) / 2.0)) + min_halfwidth


def summed_power(fft, r: float, z: float, H: int, zmax: float, dz: float,
                 min_halfwidth: int = 24) -> float:
    """Harmonic-summed matched power of the candidate (fundamental bin
    ``r``, drift ``z``, ``H`` harmonics): the grid cell it names is the top
    harmonic's half-bin ``round(2 r H)/2`` and drift ``round(z H / dz) dz``."""
    zs = -zmax + dz * np.arange(int(np.floor(2 * zmax / dz)) + 1)
    r_top = round(2.0 * r * H) / 2.0
    z_top = round(z * H / dz) * dz
    front = max(halfwidth(zz, min_halfwidth) for zz in zs) + 1
    ext = np.concatenate([np.conj(fft[1:front + 1][::-1]), fft])
    total = 0.0
    for b in range(1, H + 1):
        rho = b / H
        half = int(math.floor(2.0 * rho * r_top + 0.5))
        r_int, frac = half // 2, 0.5 * (half % 2)
        z_b = z_top * rho
        hw = max(halfwidth(zz * rho, min_halfwidth) for zz in zs)
        k = np.arange(-hw, hw, dtype=np.float64)
        resp = z_response(z_b, k - frac + z_b / 2.0)
        row = np.conj(resp) / math.sqrt(np.sum(np.abs(resp) ** 2))
        lo = front + r_int - hw
        total += abs(np.sum(ext[lo:lo + 2 * hw] * row)) ** 2
    return total
