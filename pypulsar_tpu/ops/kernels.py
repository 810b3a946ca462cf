"""Pure-JAX kernels for the pulsar data plane.

Each kernel is a pure function on ``data[nchan, nspec]`` arrays mirroring the
behavior of a reference Spectra method (reference formats/spectra.py) or
preprocessing script, redesigned for XLA:

- per-channel variable shifts are index-gathers with static shapes (instead of
  the reference's Python loop of psr_utils.rotate at formats/spectra.py:76-94),
  so they vmap over DM trials and shard over a device mesh;
- integer bin delays may be passed in precomputed (host f64, exactly matching
  the reference's NumPy delay math) or computed on device from a traced DM;
- shape-changing ops (trim / downsample) take static Python ints.

NumPy golden twins live in ``pypulsar_tpu.ops.numpy_ref``; parity is enforced
in tests/test_kernels.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from pypulsar_tpu.core.psrmath import DM_CONST_INV
from pypulsar_tpu.tune import knobs


def delay_from_DM(dm, freqs):
    """Dispersion delay (s) at freqs (MHz). Device version of
    core.psrmath.delay_from_DM; 0 for non-positive frequencies."""
    freqs = jnp.asarray(freqs)
    return jnp.where(freqs > 0.0, dm / (DM_CONST_INV * freqs * freqs), 0.0)


def bin_delays(dm, freqs, dt, ref_freq=None):
    """Integer relative bin delays for dedispersion at ``dm`` (traced OK).

    Matches reference formats/spectra.py:247-250: delays relative to the
    highest frequency, rounded half-even (np.round semantics).
    """
    if ref_freq is None:
        ref_freq = jnp.max(freqs)
    rel = delay_from_DM(dm, freqs) - delay_from_DM(dm, ref_freq)
    return jnp.round(rel / dt).astype(jnp.int32)


def rotate_rows(data, bins):
    """Left-rotate each row of ``data[C, T]`` by ``bins[C]`` places (circular).

    Gather formulation of the reference's per-channel psr_utils.rotate loop
    (formats/spectra.py:76-80); works under vmap/jit with traced bins.
    """
    T = data.shape[-1]
    idx = (jnp.arange(T, dtype=jnp.int32)[None, :] + bins[:, None].astype(jnp.int32)) % T
    return jnp.take_along_axis(data, idx, axis=-1)


def shift_channels(data, bins, padval=0, backend="auto", n_fft=None):
    """Shift each channel left by bins[c]; pad vacated cells.

    padval: numeric, 'mean', 'median' (of the rotated channel — the reference
    computes pad stats after rotation, formats/spectra.py:81-94; a circular
    rotation permutes the row, so these equal the stats of the ORIGINAL
    row), or 'rotate' (pure circular shift).

    backend: 'gather' (take_along_axis; bit-exact reference formulation),
    'fourier' (pad to a power of two, integer phase multiply, irfft —
    values agree to FFT f32 rounding), or 'auto': fourier on TPU, where
    the generic row gather measures only ~70M elem/s (~670 ms for one
    [256, 156k] dedispersion) while the FFT path runs at HBM speed
    (BENCHNOTES round 5); gather elsewhere. 'rotate' padval always takes
    the gather path (the FFT formulation is a LINEAR shift — circular
    wrap-around of real data has period T, which is generally not a
    power of two and would lower to a dense DFT matmul on this
    platform).

    n_fft: static power-of-two FFT length for the fourier path. Callers
    with host-known bins can pass ``fourier_chunk_len(T + max|bins|)``
    (Spectra does) to halve the default 2T padding; must satisfy
    ``n_fft - T >= max|bins|`` or the wrap region overlaps real data."""
    if backend == "auto":
        backend = _resolve_shift_backend(padval, jnp.asarray(data).dtype)
    if backend == "fourier" and padval != "rotate":
        return _shift_channels_fourier(data, bins, padval, n_fft)
    return _shift_channels_gather(data, bins, padval)


def _resolve_shift_backend(padval, dtype) -> str:
    """'auto' policy, resolved at CALL time (PYPULSAR_TPU_SHIFT_BACKEND
    env override; else fourier on TPU for float data with a fillable
    padval, gather everywhere else). Callers that jit around
    shift_channels pass the resolved value as a static arg so the env
    override lands in their jit key instead of being frozen into the
    first-compiled executable."""
    import os

    return knobs.env_str("PYPULSAR_TPU_SHIFT_BACKEND") or (
        "fourier" if padval != "rotate"
        and jnp.issubdtype(dtype, jnp.floating)
        and jax.default_backend() == "tpu" else "gather")


def _vacated_fill(shifted, stats_src, bins, padval):
    """Overwrite the cells a left-shift by ``bins`` vacated with the pad
    value. 'mean'/'median' stats come from ``stats_src`` — the gather
    path passes the rotated row, the fourier path the original row; a
    circular rotation permutes the row so the two are identical."""
    if padval == "mean":
        pad = jnp.mean(stats_src, axis=-1, keepdims=True)
    elif padval == "median":
        pad = jnp.median(stats_src, axis=-1, keepdims=True)
    else:
        pad = jnp.full((shifted.shape[0], 1), padval, dtype=shifted.dtype)
    T = shifted.shape[-1]
    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    b = bins[:, None].astype(jnp.int32)
    vacated = jnp.where(b > 0, t >= T - b, t < -b)
    return jnp.where(vacated, pad.astype(shifted.dtype), shifted)


@partial(jax.jit, static_argnames=("padval",))
def _shift_channels_gather(data, bins, padval=0):
    shifted = rotate_rows(data, bins)
    if padval == "rotate":
        return shifted
    return _vacated_fill(shifted, shifted, bins, padval)


@partial(jax.jit, static_argnames=("padval", "n_fft"))
def _shift_channels_fourier(data, bins, padval=0, n_fft=None):
    """Linear per-channel shift as a Fourier phase multiply.

    Rows are zero-padded to ``n = 2^ceil(log2(2T))`` and rotated by the
    exact integer phase ``W^(k*s)`` (index mod n via int32 wraparound —
    ops/fourier_dedisperse._phase); with ``|s| <= n - T`` the wrap region
    is all zeros, so ``out[:T]`` is the linear shift and the vacated-fill
    logic is identical to the gather path. Rows with ``|s| >= T`` are
    fully vacated and end up all-padval either way. Kept values carry FFT
    f32 rounding (~1e-6 relative; inside the documented 2e-6 SNR parity
    contract at detection level)."""
    from pypulsar_tpu.ops.fourier_dedisperse import _phase, fourier_chunk_len

    C, T = data.shape
    n = n_fft if n_fft is not None else fourier_chunk_len(2 * T)
    F = n // 2 + 1
    k = jnp.arange(F, dtype=jnp.int32)
    X = jnp.fft.rfft(data, n=n, axis=-1)
    ph = _phase(bins.astype(jnp.int32), k, n)  # [C, F]
    shifted = jnp.fft.irfft(X * ph, n=n, axis=-1)[:, :T].astype(data.dtype)
    return _vacated_fill(shifted, data, bins, padval)


def dedisperse(data, freqs, dt, dm, in_dm=0.0, padval=0):
    """Dedisperse at ``dm`` given current dm ``in_dm`` (reference
    formats/spectra.py:229-254, with the :37 dm-discard bug fixed).
    Shift values follow the shift_channels backend contract: bit-exact
    on CPU (gather); FFT f32 rounding on TPU unless
    PYPULSAR_TPU_SHIFT_BACKEND=gather (resolved per call; inside a
    user's enclosing jit it freezes at their trace time)."""
    backend = _resolve_shift_backend(padval, jnp.asarray(data).dtype)
    return _dedisperse_jit(data, freqs, dt, dm, in_dm, padval, backend)


@partial(jax.jit, static_argnames=("padval", "backend"))
def _dedisperse_jit(data, freqs, dt, dm, in_dm, padval, backend):
    bins = bin_delays(dm - in_dm, freqs, dt)
    return shift_channels(data, bins, padval, backend=backend)


def dedisperse_with_bins(data, bins, padval=0, n_fft=None):
    """Dedisperse with host-precomputed integer bin delays: the BIN MATH
    is the exact f64 reference path; shifted values follow the
    shift_channels backend contract (bit-exact gather on CPU, FFT f32
    rounding on TPU unless PYPULSAR_TPU_SHIFT_BACKEND=gather, resolved
    per call)."""
    return shift_channels(data, bins, padval, n_fft=n_fft)


def subband(data, freqs, dt, nsub, subdm=None, in_dm=0.0, padval=0):
    """Sum channel groups into ``nsub`` subbands, optionally dedispersing
    within each subband at ``subdm`` first (reference formats/spectra.py:96-138).

    Returns (subbanded_data[nsub, T], subband_center_freqs[nsub]).
    ``subdm``/``in_dm`` are traced (no per-DM recompile); only nsub/padval and
    the presence of subdm are static.
    """
    if subdm is None:
        return _subband_nodm(data, freqs, nsub)
    backend = _resolve_shift_backend(padval, jnp.asarray(data).dtype)
    return _subband_dm(data, freqs, dt, nsub, subdm, in_dm, padval, backend)


@partial(jax.jit, static_argnames=("nsub",))
def _subband_nodm(data, freqs, nsub):
    C, T = data.shape
    assert C % nsub == 0
    per = C // nsub
    hif = freqs[::per]
    lof = freqs[per - 1 :: per]
    ctr = 0.5 * (hif + lof)
    return data.reshape(nsub, per, T).sum(axis=1), ctr


@partial(jax.jit, static_argnames=("nsub", "padval", "backend"))
def _subband_dm(data, freqs, dt, nsub, subdm, in_dm, padval, backend):
    C, T = data.shape
    assert C % nsub == 0
    per = C // nsub
    hif = freqs[:: per]
    lof = freqs[per - 1 :: per]
    ctr = 0.5 * (hif + lof)
    ref = delay_from_DM(subdm - in_dm, hif)
    delays = delay_from_DM(subdm - in_dm, freqs)
    rel = delays - jnp.repeat(ref, per)
    bins = jnp.round(rel / dt).astype(jnp.int32)
    data = shift_channels(data, bins, padval, backend=backend)
    out = data.reshape(nsub, per, T).sum(axis=1)
    return out, ctr


@partial(jax.jit, static_argnames=("factor",))
def downsample(data, factor):
    """Co-add ``factor`` adjacent time bins; excess trimmed off the end
    (reference formats/spectra.py:329-351). ``factor`` static."""
    if factor <= 1:
        return data
    C, T = data.shape
    T2 = T // factor
    return data[:, : T2 * factor].reshape(C, T2, factor).sum(axis=-1)


@partial(jax.jit, static_argnames=("width", "padval"))
def smooth(data, width, padval=0):
    """RMS-preserving boxcar smooth of each channel: convolve with
    ones(width)/sqrt(width), 'same' alignment after padding ``width`` samples
    on both sides per ``padval`` mode (reference formats/spectra.py:262-303,
    itself from PRESTO single_pulse_search). ``width`` static."""
    if width <= 1:
        return data
    C, T = data.shape
    kernel = (jnp.ones(width, dtype=jnp.float32) / jnp.sqrt(float(width))).astype(data.dtype)
    if padval == "wrap":
        left, right = data[:, -width:], data[:, :width]
    elif padval == "mean":
        m = jnp.mean(data, axis=-1, keepdims=True)
        left = right = jnp.broadcast_to(m, (C, width))
    elif padval == "median":
        m = jnp.median(data, axis=-1, keepdims=True)
        left = right = jnp.broadcast_to(m, (C, width))
    else:
        left = right = jnp.full((C, width), padval, dtype=data.dtype)
    tosmooth = jnp.concatenate([left, data, right], axis=-1)
    # full f32 accumulation: XLA's default conv precision is bf16 on TPU
    sm = jax.vmap(
        lambda row: jnp.convolve(row, kernel, mode="same", precision=jax.lax.Precision.HIGHEST)
    )(tosmooth)
    return sm[:, width:-width]


@partial(jax.jit, static_argnames=("indep",))
def scaled(data, indep=False):
    """Subtract per-channel median; divide by global (or per-channel) std of
    the ORIGINAL data (reference formats/spectra.py:140-163)."""
    med = jnp.median(data, axis=-1, keepdims=True)
    std = jnp.std(data, axis=-1, keepdims=True) if indep else jnp.std(data)
    return (data - med) / std


@partial(jax.jit, static_argnames=("indep",))
def scaled2(data, indep=False):
    """Subtract per-channel min; divide by global (or per-channel) max of the
    ORIGINAL data (reference formats/spectra.py:165-188)."""
    mn = jnp.min(data, axis=-1, keepdims=True)
    mx = jnp.max(data, axis=-1, keepdims=True) if indep else jnp.max(data)
    return (data - mn) / mx


def _flip_negative(i):
    """Swap float32 bit patterns (as int32) with their order keys: the
    int32 whose signed order is the floats' (IEEE totalOrder: -NaN < -inf
    < ... < -0.0 < +0.0 < ... < +inf < NaN). A non-negative float's bits
    already order; a negative one's order backwards, so its magnitude
    bits flip. The map is its own inverse."""
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def _f32_order_key(x):
    return _flip_negative(jax.lax.bitcast_convert_type(x, jnp.int32))


def _select_middle_f32(data):
    """(sorted[(T-1)//2], sorted[T//2]) of each float32 row of data[C, T],
    with no sort: an exact selection on the rows' order keys.

    The k-th smallest key is built from its top bit down: with a prefix
    decided, the next bit is set iff at most k keys lie under prefix|bit,
    and that count is one fused compare-and-count reduction over the
    block (the keys are recomputed from ``data`` inside it, never
    stored): 32 passes that each read the block once, at the memory's
    pace. The upper middle of an even row comes from the lower by one
    more pass: the lower again if its ties reach past k, else the least
    key above it."""
    C, T = data.shape
    k = (T - 1) // 2
    sign = jnp.uint32(0x80000000)

    def as_signed(u):  # unsigned rank order -> the keys' signed order
        return jax.lax.bitcast_convert_type(u ^ sign, jnp.int32)

    def decide(i, prefix):
        trial = prefix | (sign >> i.astype(jnp.uint32))
        below = (_f32_order_key(data) < as_signed(trial)[:, None]).sum(
            axis=-1, dtype=jnp.int32)
        return jnp.where(below <= k, trial, prefix)

    lo = as_signed(jax.lax.fori_loop(0, 32, decide,
                                     jnp.zeros((C,), jnp.uint32)))
    if T % 2:
        hi = lo
    else:
        key = _f32_order_key(data)
        at_most = (key <= lo[:, None]).sum(axis=-1, dtype=jnp.int32)
        above = jnp.where(key > lo[:, None], key,
                          jnp.iinfo(jnp.int32).max).min(axis=-1)
        hi = jnp.where(at_most > k + 1, lo, above)
    return tuple(jax.lax.bitcast_convert_type(_flip_negative(key), jnp.float32)
                 for key in (lo, hi))


def row_median(data):
    """Median of each row of data[C, T], value for value ``np.median``:
    the middle order statistic, or ``lo*0.5 + hi*0.5`` of the two middle
    ones as ``jnp.median`` combines them, and NaN for a row that holds
    one. (``jnp.median`` weighs an odd row's middle as ``v*1 + v*0`` and
    so reads NaN where the median is infinite; this reads the infinity,
    as NumPy does.) A zero median reads +0.0 whichever zeros the row
    holds.

    float32 rows are selected from, not sorted (:func:`_select_middle_f32`):
    of a sorted row the median uses one or two elements, and a block of
    the sweep is 1024 rows of 2^18. Any other dtype takes ONE sort and
    indexes its middle. The choice reads the dtype alone."""
    T = data.shape[-1]
    if data.dtype == jnp.float32:
        lo, hi = _select_middle_f32(data)
        has_nan = jnp.isnan(data).any(axis=-1)
    else:
        if not jnp.issubdtype(data.dtype, jnp.inexact):
            data = data.astype(jnp.float32)
        srt = jnp.sort(data, axis=-1)  # NaNs sort last
        lo, hi = srt[:, (T - 1) // 2], srt[:, T // 2]
        has_nan = jnp.isnan(srt[:, -1])
    med = lo if T % 2 else lo * 0.5 + hi * 0.5
    med = jnp.where(med == 0, jnp.zeros_like(med), med)
    return jnp.where(has_nan, jnp.asarray(jnp.nan, med.dtype), med)


def channel_maskvals(data, maskval="median-mid80"):
    """Per-channel fill value for masking (reference formats/spectra.py:211-224).

    'median-mid80': median of the channel with top & bottom 10% of sorted
    samples removed (n = round(0.1*T)). The trim is symmetric, so the
    middle of sorted[n:T-n] is the middle of sorted[0:T]: elements
    (T-1)//2 and T//2 of the same sorted row either way. The trimmed
    median IS the plain median, and both are :func:`row_median` (a row
    that holds a NaN reads NaN under either name; the sweep's dataguard
    lets none in).
    """
    if maskval == "mean":
        return jnp.mean(data, axis=-1)
    if maskval in ("median", "median-mid80"):
        with jax.named_scope("mask.select_median"):
            return row_median(data)
    return jnp.full(data.shape[:1], maskval, dtype=data.dtype)


@partial(jax.jit, static_argnames=("maskval",))
def masked(data, mask, maskval="median-mid80"):
    """Replace masked cells (mask True) with per-channel fill values
    (reference formats/spectra.py:190-227)."""
    vals = channel_maskvals(data, maskval)
    with jax.named_scope("mask.fill"):
        return jnp.where(mask, vals[:, None].astype(data.dtype), data)


@jax.jit
def zero_dm(data):
    """Zero-DM RFI filter: subtract the cross-channel mean from every time
    sample (reference bin/zero_dm_filter.py:30-39)."""
    return data - jnp.mean(data, axis=0, keepdims=True)


def trim(data, bins):
    """Drop ``bins`` spectra from the end (or start if negative); static.

    Parity exception: the reference's negative branch (formats/spectra.py:324-327)
    slices ``data[:, bins:]`` which KEEPS only the last |bins| samples and
    grows numspectra — contradicting its own docstring. We implement the
    documented intent: drop |bins| samples from the beginning.
    """
    if bins == 0:
        return data
    if bins > 0:
        return data[:, :-bins]
    return data[:, -bins:]


# ---------------------------------------------------------------------------
# detection / reduction kernels used by the sweep engine
# ---------------------------------------------------------------------------


@jax.jit
def dedispersed_timeseries(data, bins):
    """Fold channels into a dedispersed time series: sum over channels after
    per-channel circular left-shift. The hot kernel of the DM sweep."""
    return rotate_rows(data, bins).sum(axis=0)


@partial(jax.jit, static_argnames=("widths",))
def boxcar_snr(ts, widths):
    """Matched-filter boxcar SNRs of a 1-D time series.

    Normalizes ts to zero median / unit std, then for each width w convolves
    with ones(w)/sqrt(w) (the RMS-preserving kernel of reference
    formats/spectra.py:283 / formats/pulse.py smooth) and takes the max.
    Returns (best_snr_per_width[len(widths)], argmax_per_width[len(widths)]).
    ``widths`` is a static tuple.
    """
    med = jnp.median(ts)
    std = jnp.std(ts)
    norm = (ts - med) / jnp.where(std == 0, 1.0, std)
    cs = jnp.concatenate([jnp.zeros(1, norm.dtype), jnp.cumsum(norm)])
    snrs = []
    idxs = []
    n = norm.shape[0]
    for w in widths:
        sums = (cs[w:] - cs[:-w]) / jnp.sqrt(float(w))
        snrs.append(jnp.max(sums))
        idxs.append(jnp.argmax(sums))
    return jnp.stack(snrs), jnp.stack(idxs)
