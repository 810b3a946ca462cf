"""Two-stage subband dedispersion and boxcar detection, float64 NumPy.

The program's DM sweep states its semantics as integer shift tables: each
group of neighbouring trials first aligns the channels inside every subband
at the group's mean DM (stage 1), then aligns the subbands at the trial's
own DM (stage 2). This module derives those tables again from the
frequencies and the DM grid (the cold-plasma law with the constant
1/2.41e-4) and sums the shifted channels in a plain loop.

Streaming semantics kept, because results depend on them:
- the file is consumed in blocks of ``payload + overlap`` samples;
- an RFI mask is applied per block: a zapped cell takes its channel's
  median of the block's middle 80% (sorted), before anything else;
- the detection path subtracts one per-channel baseline (the mean of the
  first block) and pads the end of data with zeros after that;
- a trial's mean and standard deviation are over all samples of the file,
  its window sums may run past the end into the padding.
"""

from __future__ import annotations

import math

import numpy as np

DM_CONST_INV = 2.41e-4
DEFAULT_WIDTHS = (1, 2, 4, 8, 16, 32)
DEFAULT_FFT_LEN = 1 << 18


def delay_from_dm(dm, freqs):
    f = np.asarray(freqs, dtype=np.float64)
    return dm / (DM_CONST_INV * f * f)


def bin_delays(dm, freqs, dt):
    f = np.asarray(freqs, dtype=np.float64)
    rel = delay_from_dm(dm, f) - delay_from_dm(dm, f.max())
    return np.round(rel / dt).astype(np.int64)


def dm_smear(dm, bw, f_centre):
    return dm * bw / (0.0001205 * f_centre ** 3.0)


def choose_group_size(dms, freqs, dt, nsub=64, max_smear_bins=1.0,
                      max_group=128):
    """Largest power-of-two group whose edge trial smears the lowest
    subband by at most ``max_smear_bins`` samples."""
    dms = np.asarray(dms, dtype=np.float64)
    if len(dms) < 2:
        return 1
    ddm = float(np.max(np.abs(np.diff(dms))))
    f_low = float(np.min(freqs))
    bw_sub = float(abs(np.max(freqs) - np.min(freqs))) / nsub
    g = 1
    while g * 2 <= max_group:
        if dm_smear(g * ddm, bw_sub, f_low) > max_smear_bins * dt:
            break
        g *= 2
    return g


class Plan:
    """Per-trial channel shifts (samples) of the two-stage scheme, for
    channels in descending frequency order."""

    def __init__(self, dms, freqs, dt, nsub=64, group_size=0,
                 widths=DEFAULT_WIDTHS, chunk=None):
        self.chunk = chunk
        self.dms = np.asarray(dms, dtype=np.float64)
        self.freqs = np.asarray(freqs, dtype=np.float64)
        if np.any(np.diff(self.freqs) > 0):
            raise ValueError("channels must be in descending frequency")
        self.dt = float(dt)
        self.nsub = nsub
        self.widths = tuple(widths)
        self.group_size = group_size if group_size > 0 else \
            choose_group_size(self.dms, self.freqs, dt, nsub)
        C = len(self.freqs)
        self.per = C // nsub
        self.sub_hif = self.freqs[np.arange(nsub) * self.per]
        n = len(self.dms)
        G = -(-n // self.group_size)
        padded = np.concatenate(
            [self.dms, np.repeat(self.dms[-1], G * self.group_size - n)])
        self.subdms = padded.reshape(G, self.group_size).mean(axis=1)
        # table maxima bound the overlap every block carries
        s1 = np.stack([self._stage1(sd) for sd in self.subdms])
        s2 = np.stack([self._stage2(dm) for dm in padded])
        self.max_shift1 = int(s1.max(initial=0))
        self.max_shift2 = int(s2.max(initial=0))
        self.max_total_shift = self.max_shift1 + self.max_shift2
        self.min_overlap = self.max_total_shift + max(self.widths)

    def _stage1(self, subdm):
        d_chan = delay_from_dm(subdm, self.freqs)
        d_ref = np.repeat(delay_from_dm(subdm, self.sub_hif), self.per)
        return np.round((d_chan - d_ref) / self.dt).astype(np.int64)

    def _stage2(self, dm):
        d_sub = delay_from_dm(dm, self.sub_hif)
        d0 = delay_from_dm(dm, self.freqs.max())
        return np.round((d_sub - d0) / self.dt).astype(np.int64)

    def shifts(self, trial: int) -> np.ndarray:
        """Total shift of every channel for one trial."""
        g = trial // self.group_size
        return self._stage1(self.subdms[g]) + np.repeat(
            self._stage2(self.dms[trial]), self.per)

    def payload(self, nsamp: int) -> int:
        """Streaming payload: the configuration's ``chunk`` where it sets
        one, else the default FFT chunk (doubled until the overlap fits in
        half of it) less the overlap; at most the file."""
        if self.chunk:
            payload = min(int(self.chunk), nsamp)
        else:
            n = DEFAULT_FFT_LEN
            while self.min_overlap >= n // 2:
                n <<= 1
            payload = min(n - self.min_overlap, nsamp)
        if payload <= self.min_overlap:
            payload = min(nsamp, 2 * self.min_overlap + 1)
        return payload


def mask_fill(block: np.ndarray, cells: np.ndarray) -> np.ndarray:
    """Zapped cells (``cells`` True, [chan, time]) take the channel's
    median of the sorted block's middle 80%."""
    C, L = block.shape
    n = int(np.round(0.1 * L))
    out = block.copy()
    for c in np.nonzero(cells.any(axis=1))[0]:
        srt = np.sort(block[c])
        out[c, cells[c]] = np.median(srt[n:L - n] if n else srt)
    return out


def stream_blocks(fil, plan: Plan, payload: int, zap_table=None,
                  ptsperint: int = 0):
    """(pos, block[chan, L]) in file order, mask-filled. ``zap_table``
    is [nint, chan] with channels in descending frequency order."""
    overlap = plan.min_overlap
    pos = 0
    while pos < fil.nsamp:
        L = min(payload + overlap, fil.nsamp - pos)
        block = fil.read(pos, L, dtype=np.float64)
        if zap_table is not None:
            nint = zap_table.shape[0]
            iv = np.minimum((pos + np.arange(L)) // ptsperint, nint - 1)
            if zap_table[iv[0]:iv[-1] + 1].any():
                block = mask_fill(block, zap_table[iv].T)
        yield pos, block
        pos += payload


def shifted_sum(block: np.ndarray, shifts: np.ndarray,
                out_len: int) -> np.ndarray:
    """sum_c block[c, s_c : s_c + out_len], accumulated channel by channel
    in the block's own dtype (zeros past the block's end)."""
    C, L = block.shape
    acc = np.zeros(out_len, dtype=block.dtype)
    for c in range(C):
        s = int(shifts[c])
        n = max(0, min(out_len, L - s))
        if n:
            np.add(acc[:n], block[c, s:s + n], out=acc[:n])
    return acc


def _over_trials(fn, n: int) -> list:
    """``[fn(0), .., fn(n-1)]`` on a few threads (NumPy's adds release the
    interpreter lock; the trials share the block read-only)."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    workers = max(1, min(8, n, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(fn, range(n)))


def series(fil, plan: Plan, trials, zap_table=None, ptsperint: int = 0,
           dtype=np.float64) -> np.ndarray:
    """Dedispersed series [len(trials), nsamp] as the program's series
    writer defines them: raw sums, no baseline, zeros past the end."""
    payload = plan.payload(fil.nsamp)
    out = np.zeros((len(trials), fil.nsamp), dtype=np.float64)
    shifts = [plan.shifts(t) for t in trials]
    for pos, block in stream_blocks(fil, plan, payload, zap_table,
                                    ptsperint):
        valid = min(payload, fil.nsamp - pos)
        if dtype != np.float64:
            block = block.astype(dtype)
        rows = _over_trials(
            lambda i: shifted_sum(block, shifts[i], valid), len(trials))
        for i, row in enumerate(rows):
            out[i, pos:pos + valid] = row
    return out


def detect(fil, plan: Plan, trials, zap_table=None, ptsperint: int = 0,
           dtype=np.float64):
    """Boxcar detection of the sampled trials.

    Returns a :class:`Detection`. ``dtype`` is the precision of the baseline-removed blocks, the
    channel sums and the window sums; the moments and the SNR formula stay
    float64 as on the host."""
    payload = plan.payload(fil.nsamp)
    W = max(plan.widths)
    T = fil.nsamp
    need = payload + plan.min_overlap
    shifts = [plan.shifts(t) for t in trials]
    full = np.zeros((len(trials), T + W), dtype=np.float64)
    baseline = None
    for pos, block in stream_blocks(fil, plan, payload, zap_table,
                                    ptsperint):
        if baseline is None:
            baseline = block.mean(axis=1, keepdims=True)
        L = block.shape[1]
        block -= baseline
        data = block if dtype == np.float64 else block.astype(dtype)
        if L < need:
            data = np.pad(data, ((0, 0), (0, need - L)))
        stat_len = min(payload, L)
        rows = _over_trials(
            lambda i: shifted_sum(data, shifts[i], stat_len + W),
            len(trials))
        for i, row in enumerate(rows):
            full[i, pos:pos + stat_len + W] = row
    return Detection(plan.widths, T, full, dtype)


class Detection:
    """Boxcar SNRs of a few dedispersed series: ``snr[trial, width]`` of
    the best window, its start sample ``best``, and ``at`` for any other
    window. Keeps one cumulative sum per trial, not every window."""

    def __init__(self, widths, T: int, full: np.ndarray, dtype):
        self.widths, self.T = tuple(widths), T
        self.cs, self.mean, self.std = [], [], []
        n = len(full)
        self.snr = np.zeros((n, len(self.widths)))
        self.best = np.zeros((n, len(self.widths)), dtype=np.int64)
        for i, ts in enumerate(full):
            std = ts[:T].std()
            self.mean.append(ts[:T].mean())
            self.std.append(std if std > 0 else 1.0)
            self.cs.append(np.concatenate(
                [[0.0], np.cumsum(ts.astype(dtype), dtype=dtype)]
            ).astype(np.float64))
            for wi, w in enumerate(self.widths):
                box = self._windows(i, w)
                self.best[i, wi] = int(box.argmax())
                self.snr[i, wi] = box[self.best[i, wi]]

    def _windows(self, i: int, w: int, lo: int = 0, hi=None):
        hi = self.T if hi is None else hi
        cs = self.cs[i]
        return (cs[lo + w:hi + w] - cs[lo:hi] - w * self.mean[i]) \
            / (math.sqrt(w) * self.std[i])

    def at(self, i: int, wi: int, sample: int) -> float:
        """SNR of the window of width index ``wi`` starting at ``sample``."""
        return float(self._windows(i, self.widths[wi], sample, sample + 1)[0])
