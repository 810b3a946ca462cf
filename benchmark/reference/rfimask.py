"""The RFI mask stage in float64: block statistics, clipping, products.

Per (interval, channel) block: mean, standard deviation about that mean,
and the largest power of the block's spectrum (padded to a power of two)
over the spectrum's own mean. A block is flagged when that power passes the
exponential-null threshold, or when its mean or deviation is an outlier of
its channel's timeline or its interval's bandpass against a median and an
interquartile sigma, iterated. Channels flagged in over ``chanfrac`` of the
intervals, and intervals flagged in over ``intfrac`` of the channels, are
zapped whole. Channel 0 of every table here is the LOWEST frequency (the
.mask convention).
"""

from __future__ import annotations

import math
import struct
import warnings

import numpy as np


def _pow2(n: int) -> int:
    m = 1
    while m < n:
        m <<= 1
    return m


def block_stats(data: np.ndarray, pts: int):
    """data[chan, nint*pts] -> mean, std, maxpow, each [nint, chan]."""
    C = data.shape[0]
    nint = data.shape[1] // pts
    blocks = np.asarray(data[:, :nint * pts], np.float64).reshape(
        C, nint, pts)
    mean = blocks.mean(axis=2)
    std = np.empty_like(mean)
    maxpow = np.empty_like(mean)
    for c in range(C):  # channel by channel: the spectra stay in cache
        centred = blocks[c] - mean[c][:, None]
        std[c] = np.sqrt((centred * centred).mean(axis=1))
        spec = np.fft.rfft(centred, n=_pow2(pts), axis=1)
        power = (spec.real ** 2 + spec.imag ** 2)[:, 1:]
        norm = np.maximum(power.mean(axis=1, keepdims=True), 1e-30)
        maxpow[c] = (power / norm).max(axis=1)
    return mean.T, std.T, maxpow.T


def file_stats(fil, time_s: float = 1.0, ints_per_read: int = 16):
    """Block statistics of a whole file, lowest frequency first. A tail of
    half an interval or more is padded with its last sample into a whole
    interval; a shorter one is dropped."""
    pts = max(int(round(time_s / fil.tsamp)), 2)
    out = [[], [], []]
    pos = 0
    while pos < fil.nsamp:
        n = min(pts * ints_per_read, fil.nsamp - pos)
        buf = fil.read(pos, n, dtype=np.float64, ascending=True)
        tail = n % pts
        if pos + n >= fil.nsamp and tail >= pts // 2 and tail:
            buf = np.concatenate(
                [buf, np.repeat(buf[:, -1:], pts - tail, axis=1)], axis=1)
        if buf.shape[1] >= pts:
            for acc, part in zip(out, block_stats(buf, pts)):
                acc.append(part)
        pos += n
    return tuple(np.concatenate(a) for a in out), pts


def _centre_scale(x, good, axis):
    masked = np.where(good, x, np.nan)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        med = np.nanmedian(masked, axis=axis, keepdims=True)
        q75 = np.nanpercentile(masked, 75, axis=axis, keepdims=True)
        q25 = np.nanpercentile(masked, 25, axis=axis, keepdims=True)
    med = np.where(np.isnan(med), 0.0, med)
    sigma = (q75 - q25) / 1.349
    sigma = np.where(np.isnan(sigma) | (sigma <= 0), np.inf, sigma)
    return med, sigma


def clip(mean, std, maxpow, pts, time_sigma=10.0, freq_sigma=4.0,
         max_iter=10):
    """Flag table [nint, chan] and, for every cell, how far (in units of
    its own threshold) the nearest test is from flipping it."""
    B = _pow2(pts) // 2
    q = 0.5 * math.erfc(freq_sigma / math.sqrt(2.0))
    thresh = math.log(B / max(q, 1e-300))
    flags = maxpow > thresh
    margin = np.abs(maxpow - thresh) / thresh
    for _ in range(max_iter):
        good = ~flags
        new = flags.copy()
        for x in (mean, std):
            for axis in (0, 1):
                med, sigma = _centre_scale(x, good, axis)
                dev = np.abs(x - med)
                new |= dev > time_sigma * sigma
                with np.errstate(invalid="ignore", divide="ignore"):
                    m = np.abs(dev / (time_sigma * sigma) - 1.0)
                margin = np.minimum(margin, np.where(np.isfinite(m), m, 1.0))
        if np.array_equal(new, flags):
            break
        flags = new
    return flags, margin


def zap_table(flags, chanfrac=0.7, intfrac=0.3):
    """The mask's coverage [nint, chan]: flagged cells, whole channels
    flagged in over ``chanfrac`` of intervals, whole intervals flagged in
    over ``intfrac`` of channels."""
    table = flags.copy()
    table[:, flags.mean(axis=0) > chanfrac] = True
    table[flags.mean(axis=1) > intfrac, :] = True
    return table


def read_mask(path: str):
    """(zap table [nint, chan] lowest frequency first, ptsperint) of a
    PRESTO-layout ``.mask`` file."""
    with open(path, "rb") as f:
        f.read(48)  # time_sigma, freq_sigma, MJD, dtint, lofreq, df
        nchan, nint, pts = struct.unpack("<3i", f.read(12))

        def ints(n):
            return np.frombuffer(f.read(4 * n), dtype="<i4")

        zap_chans = ints(int(ints(1)[0]))
        zap_ints = ints(int(ints(1)[0]))
        counts = ints(nint)
        table = np.zeros((nint, nchan), dtype=bool)
        for i, n in enumerate(counts):
            table[i, ints(int(n))] = True
    table[:, zap_chans] = True
    table[zap_ints, :] = True
    return table, pts
