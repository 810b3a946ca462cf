"""TPU-native RFI mask generation — a PRESTO ``rfifind`` equivalent.

The reference *consumes* rfifind ``.mask`` files (bin/waterfaller.py:21,
28-48; ``rfifind`` imported 3x per SURVEY.md §2.5) but the mask generator
itself is PRESTO's external C program — one of the L0 native dependencies
SURVEY.md says must be replaced. This module closes that gap so a user can
go raw file -> mask -> masked pipeline without PRESTO installed:

  1. device pass (jit): per-(interval, channel) block statistics — mean,
     standard deviation, and the maximum normalized Fourier power of the
     block (periodic-interference detector);
  2. host pass: iterative sigma clipping of the small [nint, nchan] stat
     tables along both axes (each channel's timeline and each interval's
     bandpass), PRESTO-style;
  3. reduction to the mask products: whole channels / whole intervals are
     zapped when more than ``chanfrac`` / ``intfrac`` of their blocks are
     flagged, the remainder becomes the per-interval zap lists; written in
     the reference binary layout by io.rfimask.write_mask.

The Fourier detector pads each block to a power of two before the rfft —
non-power-of-two FFTs lower to a dense O(L^2) DFT matmul on this TPU
toolchain (BENCHNOTES.md). Padding only dilutes a tone's power by the duty
factor, which the significance threshold absorbs.

Statistics are flagged against a robust center/scale (median and
interquartile-range-derived sigma) so that the estimate itself is immune
to the outliers being hunted; the max-power test uses the exponential null
distribution of normalized powers: P(max over B bins > p) ~ B*exp(-p),
thresholded at the single-sided Gaussian tail probability of
``freq_sigma``.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.ops import transfer
from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len
from pypulsar_tpu.ops.ingest import _ingest_tc, _timed_reads, ingest_nbits
from pypulsar_tpu.plan import lengths

__all__ = [
    "RfiStats",
    "block_stats",
    "block_stats_numpy",
    "clip_stats",
    "mask_products",
    "rfifind",
]


@partial(jax.jit, static_argnames=("pts", "n_fft"))
@jax.named_scope("rfifind.block_stats")
def _block_stats_impl(data, pts: int, n_fft: int):
    """data[C, nint*pts] -> (mean[nint, C], std[nint, C], maxpow[nint, C]).

    maxpow is the largest normalized power over the block's positive-
    frequency bins: powers / (their own mean), so a flat (white) block
    scores ~ln(B) and a coherent tone scores its SNR^2-scale power —
    interval-to-interval gain drifts cancel out.
    """
    C = data.shape[0]
    nint = data.shape[1] // pts
    blocks = data[:, : nint * pts].reshape(C, nint, pts)
    mean = jnp.mean(blocks, axis=2)
    # f32 two-pass variance: centered sum of squares (one-pass sum/sumsq
    # catastrophically cancels for offset-dominated 8-bit data)
    centered = blocks - mean[:, :, None]
    var = jnp.mean(centered * centered, axis=2)
    std = jnp.sqrt(var)
    spec = jnp.fft.rfft(centered, n=n_fft, axis=2)
    pow_ = spec.real * spec.real + spec.imag * spec.imag
    pow_ = pow_[:, :, 1:]  # DC removed by centering; drop it anyway
    norm = jnp.mean(pow_, axis=2, keepdims=True)
    maxpow = jnp.max(pow_ / jnp.maximum(norm, 1e-30), axis=2)
    return mean.T, std.T, maxpow.T


def block_stats(data, pts: int):
    """Device per-block stats of ``data[C, T]`` (whole intervals only)."""
    n_fft = fourier_chunk_len(pts)
    return _block_stats_impl(transfer.ship(data, jnp.float32), pts, n_fft)


def block_stats_numpy(data: np.ndarray, pts: int):
    """float64 NumPy twin of block_stats (parity tests)."""
    C = data.shape[0]
    nint = data.shape[1] // pts
    blocks = data[:, : nint * pts].reshape(C, nint, pts).astype(np.float64)
    mean = blocks.mean(axis=2)
    centered = blocks - mean[:, :, None]
    std = np.sqrt((centered * centered).mean(axis=2))
    spec = np.fft.rfft(centered, n=fourier_chunk_len(pts), axis=2)
    pow_ = (spec.real**2 + spec.imag**2)[:, :, 1:]
    norm = np.maximum(pow_.mean(axis=2, keepdims=True), 1e-30)
    maxpow = (pow_ / norm).max(axis=2)
    return mean.T, std.T, maxpow.T


@dataclasses.dataclass
class RfiStats:
    """Per-(interval, channel) statistics of an observation, in *file*
    channel order (the .mask convention; io/rfimask.py docstring)."""

    mean: np.ndarray  # [nint, nchan]
    std: np.ndarray
    maxpow: np.ndarray
    ptsperint: int
    dtint: float
    lofreq: float
    df: float
    mjd: float = 0.0
    # set by rfifind(): fraction of (interval, channel) cells the final
    # mask products zap (None until products are computed)
    mask_coverage: Optional[float] = None

    @property
    def nint(self) -> int:
        return self.mean.shape[0]

    @property
    def nchan(self) -> int:
        return self.mean.shape[1]

    def save(self, fn: str) -> str:
        """Sidecar stats file (our own npz schema — PRESTO's .stats binary
        carries the same tables; kept separate so the .mask stays
        reference-layout)."""
        np.savez(fn, mean=self.mean, std=self.std, maxpow=self.maxpow,
                 ptsperint=self.ptsperint, dtint=self.dtint,
                 lofreq=self.lofreq, df=self.df, mjd=self.mjd,
                 mask_coverage=(np.nan if self.mask_coverage is None
                                else self.mask_coverage))
        return fn

    @classmethod
    def load(cls, fn: str) -> "RfiStats":
        with np.load(fn) as z:
            cov = float(z["mask_coverage"]) if "mask_coverage" in z else np.nan
            return cls(mean=z["mean"], std=z["std"], maxpow=z["maxpow"],
                       ptsperint=int(z["ptsperint"]), dtint=float(z["dtint"]),
                       lofreq=float(z["lofreq"]), df=float(z["df"]),
                       mjd=float(z["mjd"]),
                       mask_coverage=None if np.isnan(cov) else cov)


def _robust_center_scale(x: np.ndarray, good: np.ndarray, axis: int):
    """(median, sigma) along ``axis`` using only ``good`` cells; sigma from
    the 25-75 interquartile range (IQR/1.349 estimates a Gaussian sigma
    robustly). Cells where everything is flagged get sigma=inf (no new
    flags can arise from them).

    Every line's median and quartiles come from ONE sort of the table
    along ``axis`` (NaN, which stands for a flagged cell, sorts last), and
    are, bit for bit, ``np.nanmedian`` and ``np.nanpercentile(..., 25 /
    75)`` of the unflagged cells: NumPy computes those with one Python
    call a line, which was 97% of the clip's time
    (``tests/test_rfifind.py`` holds this function to them)."""
    masked = np.where(good, x, np.nan)
    dtype = masked.dtype
    srt = np.sort(masked, axis=axis)
    # the cells of a line that count: its first n after the sort
    n = np.count_nonzero(~np.isnan(srt), axis=axis, keepdims=True)
    top = n - 1  # -1 where none counts: the line's last cell, a NaN

    def at(i):
        return np.take_along_axis(srt, i, axis=axis)

    def percentile(q):
        # NumPy's "linear" method (lib/_function_base_impl.py, _quantile
        # and _lerp): the virtual index (n - 1) * q and its fraction in the
        # table's dtype, the neighbour above clipped to the last element
        virtual = top.astype(dtype) * (dtype.type(q) / dtype.type(100))
        below = np.floor(virtual)
        t = virtual - below
        below = below.astype(np.intp)
        a, b = at(below), at(np.minimum(below + 1, top))
        diff = b - a
        return np.where(t >= 0.5, b - diff * (1 - t), a + diff * t)

    with np.errstate(invalid="ignore", over="ignore"):  # as NumPy's are
        # np.median: the mean of the two middle elements, in the dtype
        med = (at(top // 2) + at(n // 2)) / 2
        q75, q25 = percentile(75), percentile(25)
    med = np.where(np.isnan(med), 0.0, med)
    sigma = (q75 - q25) / 1.349
    sigma = np.where(np.isnan(sigma) | (sigma <= 0), np.inf, sigma)
    return med, sigma


def clip_stats(
    stats: RfiStats,
    time_sigma: float = 10.0,
    freq_sigma: float = 4.0,
    max_iter: int = 10,
) -> np.ndarray:
    """Boolean flag table [nint, nchan] (True = bad block).

    Time-domain test: a block's mean or std is an outlier at
    ``time_sigma`` against its channel's timeline (axis 0) or its
    interval's bandpass (axis 1). Fourier test: the block's max normalized
    power exceeds the exponential-null threshold at the ``freq_sigma``
    Gaussian-equivalent tail probability. Clipping iterates so that loud
    blocks do not inflate the scale estimate that judges the others.
    """
    return _clip_passes(stats, time_sigma, freq_sigma, max_iter)[0]


def _clip_passes(stats: RfiStats, time_sigma: float, freq_sigma: float,
                 max_iter: int = 10):
    """:func:`clip_stats` with the work it took: (flags, passes of the
    clipping loop run, line statistics computed over tables, axes and
    passes)."""
    mean, std, maxpow = stats.mean, stats.std, stats.maxpow
    # exponential null for the max of B normalized powers (mean power = 1):
    # P(max > p) ~ B * exp(-p)  ->  p_thresh = ln(B / q)
    B = fourier_chunk_len(stats.ptsperint) // 2
    q = 0.5 * math.erfc(freq_sigma / math.sqrt(2.0))
    power_thresh = math.log(B / max(q, 1e-300))
    flags = maxpow > power_thresh

    # flags accumulate monotonically: a fully-flagged row/column has no
    # good cells left to estimate a scale from (sigma=inf), so re-deriving
    # flags from scratch each pass would silently unflag it
    scales: dict = {}  # (table, axis) -> [median, sigma] of every line
    changed = None  # the cells the last pass flagged; None: judge all
    passes = lines = 0
    for passes in range(1, max_iter + 1):
        good = ~flags
        new = flags.copy()
        for k, x in enumerate((mean, std)):
            for axis in (0, 1):
                med, sigma, redone = _center_scale_of_changed(
                    scales, (k, axis), x, good, axis, changed)
                lines += redone
                new |= np.abs(x - med) > time_sigma * sigma
        if np.array_equal(new, flags):
            break
        changed = new & good
        flags = new
    return flags, passes, lines


def _center_scale_of_changed(scales: dict, key, x, good, axis: int,
                             changed):
    """:func:`_robust_center_scale` of every line of ``x`` along ``axis``,
    computed once and afterwards only for the lines that hold a cell the
    last pass flagged: a line's median and quartiles depend on its own
    good cells alone, so the others' stand. Returns (median, sigma, lines
    computed in this call)."""
    if changed is None:
        scales[key] = list(_robust_center_scale(x, good, axis))
        return (*scales[key], x.shape[1 - axis])
    med, sigma = scales[key]
    lines = np.nonzero(changed.any(axis=axis))[0]
    if len(lines):
        sel = (slice(None), lines) if axis == 0 else (lines, slice(None))
        med[sel], sigma[sel] = _robust_center_scale(x[sel], good[sel], axis)
    return med, sigma, len(lines)


def mask_products(
    flags: np.ndarray,
    chanfrac: float = 0.7,
    intfrac: float = 0.3,
    extra_zap_chans: Sequence[int] = (),
    extra_zap_ints: Sequence[int] = (),
) -> Tuple[List[int], List[int], List[List[int]]]:
    """Reduce the flag table to (zap_chans, zap_ints, zap_chans_per_int).

    A channel flagged in more than ``chanfrac`` of intervals is zapped
    outright (likewise intervals at ``intfrac``) — PRESTO's -chanfrac /
    -intfrac semantics; remaining flags become per-interval lists. The
    per-interval lists exclude globally zapped channels (the reader
    re-unions them), keeping the file small.
    """
    nint, nchan = flags.shape
    for c in extra_zap_chans:
        if not 0 <= int(c) < nchan:
            raise ValueError(
                f"zap channel {c} outside [0, {nchan}) — indices are in "
                f"mask channel order (channel 0 = lowest frequency)")
    for i in extra_zap_ints:
        if not 0 <= int(i) < nint:
            raise ValueError(f"zap interval {i} outside [0, {nint})")
    chan_bad = flags.mean(axis=0)
    int_bad = flags.mean(axis=1)
    zap_chans = set(np.nonzero(chan_bad > chanfrac)[0].tolist())
    zap_chans.update(int(c) for c in extra_zap_chans)
    zap_ints = set(np.nonzero(int_bad > intfrac)[0].tolist())
    zap_ints.update(int(i) for i in extra_zap_ints)
    per_int: List[List[int]] = []
    for i in range(nint):
        if i in zap_ints:
            per_int.append([])
            continue
        chans = np.nonzero(flags[i])[0]
        per_int.append([int(c) for c in chans if int(c) not in zap_chans])
    return sorted(zap_chans), sorted(zap_ints), per_int


def _iter_file_blocks(reader, samples_per_read: int):
    """Yield [nchan, n] LOW-frequency-first HOST blocks from a PSRFITS /
    multi-file (fbobs) / filterbank reader — the .mask channel convention
    (PRESTO reorders every band ascending on read, so mask channel 0 is
    always the lowest frequency regardless of on-disk order;
    io/rfimask.py docstring). Everything here is unpacked and widened to
    float32 by the reader, on the host: ``get_samples`` (filterbank) and
    ``get_sample_interval`` (fbobs) return on-disk order, flipped here
    when the band is descending; the ``get_spectra`` fallback (PSRFITS)
    delivers high-frequency-first Spectra, always flipped.

    ``rfifind()`` takes this path for readers WITHOUT the
    ``BLOCK_ITER_ARRAYS`` marker (PsrfitsFile, FilterbankObs). A reader
    with it (FilterbankFile: every SIGPROC input) never comes here: its
    blocks ship as the file holds them and the device unpacks
    (ops/ingest.py). The filterbank branch stays as the host reference
    the parity tests hold that path to."""
    total = int(getattr(reader, "nspec", None)
                or reader.number_of_samples)
    get_samples = getattr(reader, "get_samples", None)
    get_interval = getattr(reader, "get_sample_interval", None)
    raw = get_samples is not None or get_interval is not None
    if raw:
        f = np.asarray(reader.frequencies, dtype=float)  # on-disk order
        flip = len(f) > 1 and f[0] > f[-1]
    else:
        flip = True
    # on-disk bytes of one spectrum, for io.bytes_read
    per_spec = getattr(reader, "bytes_per_spectrum", None)
    pos = 0
    while pos < total:
        n = min(samples_per_read, total - pos)
        # read + host unpack (sub-byte files widen to float32 here)
        with telemetry.span("io.read", aggregate=False, samples=int(n)):
            if get_samples is not None:
                d = get_samples(pos, n).T
            elif get_interval is not None:
                d = get_interval(pos, pos + n).T
            else:
                d = np.asarray(reader.get_spectra(pos, n).data)
        telemetry.counter("io.bytes_read", int(
            n * per_spec if per_spec else d.nbytes))
        yield d[::-1] if flip else d
        pos += n


def rfifind(
    source,
    *,
    time: float = 1.0,
    dt: Optional[float] = None,
    time_sigma: float = 10.0,
    freq_sigma: float = 4.0,
    chanfrac: float = 0.7,
    intfrac: float = 0.3,
    zap_chans: Sequence[int] = (),
    zap_ints: Sequence[int] = (),
    outbase: Optional[str] = None,
    lofreq: float = 0.0,
    df: float = 0.0,
    mjd: float = 0.0,
    ints_per_read: Optional[int] = None,
    hifreq_first: bool = True,
):
    """End-to-end mask generation.

    ``source`` is a reader (FilterbankFile / PsrfitsFile: dt, nspec/fch1
    discovered) or a raw [nchan, T] array (then ``dt`` is required and
    lofreq/df/mjd may be given; rows are taken high-frequency-first — the
    framework's Spectra convention — unless ``hifreq_first=False``).
    Returns (RfiStats, flags, maskfn-or-None), all in the .mask channel
    convention (channel 0 = lowest frequency); pass ``outbase`` to write
    ``{outbase}_rfifind.mask`` (+ ``.stats.npz``).

    The interval length is ``time`` seconds rounded to whole samples; a
    trailing partial interval shorter than half an interval is dropped
    (it has too few samples for stable statistics), otherwise it is
    padded by repeating its last sample into a full interval.

    Where the samples are unpacked: a reader with the
    ``BLOCK_ITER_ARRAYS`` marker (FilterbankFile: every SIGPROC input,
    any bit depth) is read through ``iter_blocks(raw=True)``; its blocks
    go to the device as the file holds them and ``ops/ingest._ingest_tc``
    unpacks, transposes, widens and flips them there (counter
    ``rfifind.raw_blocks``). Readers without it (PsrfitsFile,
    FilterbankObs) and array input are staged as float32 on the host
    (``_iter_file_blocks``). Both hand ``_block_stats_impl`` the same
    float32 block, so the statistics agree bit for bit.

    How long a block is: ``ints_per_read`` intervals, by default what
    ``plan/lengths.py`` plans from the channel count, the interval's
    length and the device's memory (16 at 1024 channels on a 16 GB
    chip; event ``rfifind.block_plan``), the same for both paths. The
    statistics are per (interval, channel), so the products do not
    depend on it.
    """
    if isinstance(source, np.ndarray) or hasattr(source, "ndim"):
        if dt is None:
            raise ValueError("dt is required for array input")
        data = np.asarray(source)
        if hifreq_first:
            data = data[::-1]
        nchan = data.shape[0]
        blocks = [data]
    else:
        dt = float(getattr(source, "dt", None) or source.tsamp)
        nchan = int(getattr(source, "nchans", None)
                    or getattr(source, "nchan"))
        f = np.asarray(source.frequencies, dtype=float)
        lofreq = float(f.min())
        df = float(abs(f[1] - f[0])) if len(f) > 1 else 0.0
        mjd = 0.0
        try:
            mjd = float(source.tstart)  # SIGPROC header
        except (AttributeError, TypeError):
            pass
        if not mjd and hasattr(source, "specinfo"):  # PSRFITS
            try:
                mjd = float(np.atleast_1d(source.specinfo.start_MJD)[0])
            except (AttributeError, TypeError, IndexError):
                pass
        if not mjd and hasattr(source, "startmjds"):  # fbobs multi-file
            mjd = float(np.atleast_1d(source.startmjds)[0])
        blocks = None

    pts = max(int(round(time / dt)), 2)
    if ints_per_read is None and blocks is None:  # an array is one block
        planned = lengths.plan_lengths(nchan, 1, 0, 1,
                                       lengths.device_memory(),
                                       interval_samples=pts)
        ints_per_read = planned.mask_intervals
        telemetry.event(
            "rfifind.block_plan", nchan=int(nchan), pts=int(pts),
            intervals=int(ints_per_read), bound=planned.mask_bound,
            need_bytes=planned.mask_need,
            budget_bytes=-1 if planned.budget is None else planned.budget)
    means, stds, maxpows = [], [], []
    # a reader with the marker hands out its blocks as the file holds them
    # ([time, row] in the file's dtype, sub-byte samples packed): they are
    # staged and shipped so, and unpacked, transposed, widened and flipped
    # on the device. Everything else is staged as [chan, time] float32.
    raw = blocks is None and getattr(source, "BLOCK_ITER_ARRAYS", False)
    taxis = 0 if raw else 1  # the time axis of a staged block
    if raw:
        flip = len(f) > 1 and f[0] > f[-1]  # .mask: channel 0 = lowest
        nbits = ingest_nbits(source)
    carry = None  # samples past the last whole interval, once a block came

    def consume(chunk, final=False):
        nonlocal carry
        # the block as it ships: carry + chunk along the time axis, then
        # the tail pad. Host path: the float32 cast and the concatenate
        # materialize the reader's transposed / flipped view. Raw path: a
        # row-append of the file's own bytes, and with nothing carried
        # (every read but the last) the reader's block itself, uncopied.
        with telemetry.span("rfifind.stage_block") as sp:
            if not raw:
                chunk = np.asarray(chunk, np.float32)
            if raw and (carry is None or not len(carry)):
                buf = chunk
            else:
                buf = np.concatenate(
                    [chunk] if carry is None else [carry, chunk], axis=taxis)
            nint = buf.shape[taxis] // pts
            if final:
                tail = buf.shape[taxis] - nint * pts
                if tail >= pts // 2:
                    last = np.take(buf, [-1], axis=taxis)
                    pad = np.repeat(last, pts - tail, axis=taxis)
                    buf = np.concatenate([buf, pad], axis=taxis)
                    nint += 1
            if sp is not None:
                sp.set(bytes=int(buf.nbytes))
        head, carry = np.split(buf, [nint * pts], axis=taxis)
        if nint:
            telemetry.counter("rfifind.intervals", int(nint))
            if raw:
                dev = transfer.ship(head)
                with telemetry.span("rfifind.ingest", nint=int(nint)):
                    block = _ingest_tc(dev, flip, nbits)
            else:
                block = transfer.ship(head, jnp.float32)
            # program + ONE batched pull (3 device->host syncs otherwise)
            with telemetry.span("rfifind_block_stats", nint=int(nint)):
                m, s, p = transfer.pull_host(*block_stats(block, pts))
            means.append(m)
            stds.append(s)
            maxpows.append(p)

    if raw:
        for _pos, b in _timed_reads(
                source.iter_blocks(pts * ints_per_read, 0, raw=True)):
            telemetry.counter("rfifind.raw_blocks")
            consume(b)
    elif blocks is not None:
        for b in blocks:
            consume(b)
    else:
        for b in _iter_file_blocks(source, pts * ints_per_read):
            consume(b)
    if carry is not None:
        consume(np.take(carry, [], axis=taxis), final=True)

    if not means:
        raise ValueError("no complete intervals: data shorter than time/2")
    stats = RfiStats(
        mean=np.concatenate(means), std=np.concatenate(stds),
        maxpow=np.concatenate(maxpows), ptsperint=pts, dtint=pts * dt,
        lofreq=lofreq, df=df, mjd=mjd,
    )
    with telemetry.span("rfifind.clip") as sp:
        flags, passes, lines = _clip_passes(stats, time_sigma, freq_sigma)
        if sp is not None:
            sp.set(passes=passes, lines=lines, cells=int(flags.size))
        zc, zi, per_int = mask_products(
            flags, chanfrac=chanfrac, intfrac=intfrac,
            extra_zap_chans=zap_chans, extra_zap_ints=zap_ints)
    # effective mask coverage (union of whole-channel, whole-interval and
    # per-interval zaps, via the reader's own table builder). A BRIGHT
    # PULSAR trips the Fourier max-power detector in every (interval,
    # channel) exactly like periodic RFI would — a known failure mode of
    # this class of detector (PRESTO's rfifind shares it); masking most
    # of the band deletes the signal the downstream search is looking
    # for, so shout.
    from pypulsar_tpu.io.rfimask import build_zap_table

    coverage = float(build_zap_table(stats.nint, stats.nchan, zc, zi,
                                     per_int).mean())
    stats.mask_coverage = coverage
    if coverage > 0.5:
        warnings.warn(
            f"mask covers {coverage * 100:.0f}% of the data — either RFI "
            f"is pervasive or a bright periodic source is being flagged "
            f"as interference; consider raising freq_sigma/time_sigma "
            f"or zapping known-bad channels explicitly", stacklevel=2)
    maskfn = None
    if outbase is not None:
        from pypulsar_tpu.io.rfimask import write_mask

        with telemetry.span("rfifind.write"):
            maskfn = write_mask(
                outbase + "_rfifind.mask", time_sigma=time_sigma,
                freq_sigma=freq_sigma, mjd=stats.mjd, dtint=stats.dtint,
                lofreq=stats.lofreq, df=stats.df, nchan=stats.nchan,
                nint=stats.nint, ptsperint=pts, zap_chans=zc, zap_ints=zi,
                zap_chans_per_int=per_int,
            )
            stats.save(outbase + "_rfifind.stats.npz")
    return stats, flags, maskfn
