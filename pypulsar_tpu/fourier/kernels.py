"""JAX kernels for the Fourier-domain search layer.

Design notes (TPU-first re-design of reference formats/prestofft.py):

- ``fourier_interpolate`` evaluates the FFT at fractional bins via the exact
  finite-window interpolation sum; the window gather is batched (vmap-free
  advanced indexing) so all target bins evaluate in one fused XLA op.
  PARITY EXCEPTION: the reference (prestofft.py:93-94) passes ``np.pi*x`` to
  ``np.sinc`` which already includes the pi factor, so its interpolant does
  not reproduce the FFT values at integer bins. We use the correct
  ``sinc(r-k)`` kernel (PRESTO's Fourier interpolation).

- ``deredden`` (PRESTO-style red-noise normalization, prestofft.py:151-195)
  looks sequential, but its log-growing block schedule depends only on N —
  not on the data — so the whole pass vectorizes: host precomputes block
  boundaries (``deredden_schedule``), the device computes one masked median
  per block and one gathered linear-interp scale per element. The NumPy twin
  in fourier.numpy_ref follows the reference loop exactly; parity is enforced
  in tests.

- ``spectrogram`` is a reshape + batched rfft (bin/spectrogram.py:17-37), the
  canonical MXU/VPU-friendly formulation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from pypulsar_tpu.compile import plane_jit
from pypulsar_tpu.ops.transfer import (join_planes, split_complex,
                                        to_host_complex)


def _interpolate_body(fft, r, m):
    """Traceable interpolation core (complex in/out — call only inside
    jit; complex cannot cross executable boundaries, ops/transfer.py)."""
    if m % 2 != 0:
        raise ValueError("Input 'm' must be an even integer: %s" % str(m))
    nn = fft.shape[0]
    r = jnp.asarray(r)
    round_r = jnp.round(r).astype(jnp.int32)
    k = round_r[:, None] + jnp.arange(-m // 2, m // 2 + 1, dtype=jnp.int32)
    valid = (k >= 0) & (k < nn)
    coefs = jnp.where(valid, fft[jnp.clip(k, 0, nn - 1)], 0.0)
    x = r[:, None] - k
    expterm = jnp.exp(-1.0j * jnp.pi * x)
    sincterm = jnp.sinc(x)  # sin(pi x)/(pi x): exact at integer bins
    return jnp.sum(coefs * expterm * sincterm, axis=1)


@plane_jit(static_argnames=("m",), stage="accel")
def _fourier_interpolate_jit(re, im, r, m=32):
    out = _interpolate_body(join_planes(re, im), r, m)
    return out.real, out.imag


def fourier_interpolate(fft, r, m=32) -> np.ndarray:
    """Interpolate complex FFT coefficients at real bin indices ``r`` using
    the ``m+1`` nearest bins. Out-of-range window bins contribute zero.

    Returns HOST complex64: complex64 lives only inside jit (the
    ops/transfer.py convention), so the complex FFT enters as float
    planes and the result recombines host-side."""
    re, im = split_complex(fft)
    our, oui = _fourier_interpolate_jit(jnp.asarray(re), jnp.asarray(im),
                                        jnp.asarray(r), m)
    return to_host_complex(our, oui)


@plane_jit(static_argnames=("nharm",), stage="accel")
def harmonic_sum(powers, nharm=8):
    """Decimated harmonic sum: out[i] = sum_{h=1..nharm} powers[i*h]
    (reference prestofft.py:98-113). Output length N//nharm."""
    nn = powers.shape[0]
    out_len = nn // nharm
    out = powers[:out_len]
    for nh in range(2, nharm + 1):
        out = out + powers[:: nh][:out_len]
    return out


@plane_jit(static_argnames=("nharm", "m"), stage="accel")
def _incoherent_harmonic_sum_jit(re, im, powers, nharm=8, m=2):
    fft = join_planes(re, im)
    nn = fft.shape[0]
    out = powers
    for nh in range(2, nharm + 1):
        r = jnp.arange(nn) / float(nh)
        out = out + jnp.abs(_interpolate_body(fft, r, m)) ** 2
    return out


@plane_jit(static_argnames=("nharm", "m"), stage="accel")
def _coherent_harmonic_sum_jit(re, im, nharm=8, m=2):
    fft = join_planes(re, im)
    nn = fft.shape[0]
    out = fft
    for nh in range(2, nharm + 1):
        r = jnp.arange(nn) / float(nh)
        out = out + _interpolate_body(fft, r, m)
    return jnp.abs(out) ** 2


def incoherent_harmonic_sum(fft, powers, nharm=8, m=2):
    """Sum |FFT interpolated at r/nh|^2 over harmonics onto each bin
    (reference prestofft.py:115-131). Returns powers array of full length;
    bin i corresponds to frequency freqs[i]/nharm."""
    re, im = split_complex(fft)
    return _incoherent_harmonic_sum_jit(jnp.asarray(re), jnp.asarray(im),
                                        jnp.asarray(powers), nharm, m)


def coherent_harmonic_sum(fft, nharm=8, m=2):
    """Sum complex FFT interpolated at r/nh over harmonics, then square
    (reference prestofft.py:133-149)."""
    re, im = split_complex(fft)
    return _coherent_harmonic_sum_jit(jnp.asarray(re), jnp.asarray(im),
                                      nharm, m)


class DereddenSchedule(NamedTuple):
    """Host-precomputed geometry of the PRESTO deredden pass for length N.

    blocks ``0..B-1`` start at ``starts`` with lengths ``lens`` (block 0
    begins at element 1; the DC bin is handled separately). Corrections are
    applied to blocks ``0..B-2``; elements past the last corrected block
    (the tail) reuse the final correction's last scale value.
    """

    starts: np.ndarray  # (B,) int32
    lens: np.ndarray  # (B,) int32
    elem_block: np.ndarray  # (N,) int32: correction block id per element
    elem_off: np.ndarray  # (N,) int32: offset within that block
    maxlen: int
    n: int


@functools.lru_cache(maxsize=16)
def deredden_schedule(n, initialbuflen=6, maxbuflen=200) -> DereddenSchedule:
    """Reproduce the reference's block-length recurrence
    (prestofft.py:157-195): buflen grows as int(initialbuflen*log(offset)),
    capped at maxbuflen. Cached: the schedule depends only on the length,
    and batch searches deredden many same-length spectra."""
    starts, lens = [1], [initialbuflen]
    newoffset = 1 + initialbuflen
    newbuflen = int(initialbuflen * np.log(newoffset))
    if newoffset > maxbuflen:  # reference quirk: first cap tests the OFFSET
        newbuflen = maxbuflen
    while (newoffset + newbuflen) < n:
        starts.append(newoffset)
        lens.append(newbuflen)
        newoffset += newbuflen
        newbuflen = int(initialbuflen * np.log(newoffset))
        if newbuflen > maxbuflen:
            newbuflen = maxbuflen
    starts = np.asarray(starts, dtype=np.int32)
    lens = np.asarray(lens, dtype=np.int32)
    B = len(starts)

    # element -> (correction block, offset) map; corrections exist for blocks
    # 0..B-2. Tail elements (beyond the last corrected block) map to the last
    # correction's final element, matching `dered[fixedoffset:] *= scaleval[-1]`.
    elem_block = np.zeros(n, dtype=np.int32)
    elem_off = np.zeros(n, dtype=np.int32)
    for c in range(max(B - 1, 1)):
        s, l = starts[c], lens[c]
        elem_block[s : s + l] = c
        elem_off[s : s + l] = np.arange(l)
    tail_start = starts[B - 1] if B > 1 else starts[0] + lens[0]
    elem_block[tail_start:] = max(B - 2, 0)
    elem_off[tail_start:] = lens[max(B - 2, 0)] - 1
    return DereddenSchedule(
        starts, lens, elem_block, elem_off, int(lens.max()), n
    )


def _masked_block_stat(values, starts, lens, maxlen, stat):
    """Gather each block's values into rows of (B, maxlen) and compute a
    masked statistic per row. ``stat`` in {'median', 'std'}."""
    B = starts.shape[0]
    idx = starts[:, None] + jnp.arange(maxlen, dtype=jnp.int32)[None, :]
    n = values.shape[0]
    valid = (jnp.arange(maxlen, dtype=jnp.int32)[None, :] < lens[:, None]) & (idx < n)
    rows = jnp.where(valid, values[jnp.clip(idx, 0, n - 1)], jnp.inf)
    if stat == "median":
        srt = jnp.sort(rows, axis=1)
        L = lens
        lo = jnp.take_along_axis(srt, ((L - 1) // 2)[:, None], axis=1)[:, 0]
        hi = jnp.take_along_axis(srt, (L // 2)[:, None], axis=1)[:, 0]
        return 0.5 * (lo + hi)
    elif stat == "std":
        cnt = lens.astype(values.dtype)
        vals = jnp.where(valid, rows, 0.0)
        mean = vals.sum(axis=1) / cnt
        mean2 = (vals * vals).sum(axis=1) / cnt
        return jnp.sqrt(jnp.maximum(mean2 - mean * mean, 0.0))
    raise ValueError(stat)


def _deredden_body(re, im, powers, starts, lens, elem_block, elem_off,
                   maxlen):
    fft = join_planes(re, im)
    LN2 = float(np.log(2.0))
    med = _masked_block_stat(powers, starts, lens, maxlen, "median") / LN2
    B = starts.shape[0]
    # correction c (blocks 0..B-2) interpolates between med[c] and med[c+1]
    m_old = med[:-1] if B > 1 else med
    m_new = med[1:] if B > 1 else med
    len_old = lens[:-1] if B > 1 else lens
    len_new = lens[1:] if B > 1 else lens
    denom = (len_new + len_old).astype(powers.dtype)
    slope = (m_new - m_old) / denom
    lineoffset = 0.5 * denom

    c = elem_block
    j = elem_off.astype(powers.dtype)
    lineval = m_old[c] + slope[c] * (lineoffset[c] - j)
    scale = 1.0 / jnp.sqrt(lineval)
    out = fft * scale.astype(fft.real.dtype)
    out = out.at[0].set(1.0 + 0.0j)
    return out.real, out.imag


_deredden_apply = plane_jit(_deredden_body, static_argnames=("maxlen",),
                            stage="accel")


@plane_jit(static_argnames=("maxlen",), stage="accel")
def _prep_spectra_kernel(series, starts, lens, elem_block, elem_off, maxlen):
    # subtract the per-series mean before the f32 rfft: deredden overwrites
    # bin 0 anyway, so this changes nothing in exact arithmetic, but a
    # large DC offset (8-bit data sits ~100x sigma above zero) otherwise
    # leaks into the low bins through f32 rounding of the butterflies —
    # the same fluctuation-scale argument as the sweep's baseline
    # subtraction (ADVICE r5)
    with jax.named_scope("prep.rfft"):
        s32 = series.astype(jnp.float32)
        s32 = s32 - jnp.mean(s32, axis=1, keepdims=True)
        fft = jnp.fft.rfft(s32, axis=1)
        re = fft.real.astype(jnp.float32)
        im = fft.imag.astype(jnp.float32)
    with jax.named_scope("prep.deredden"):
        powers = re * re + im * im
        return jax.vmap(
            _deredden_body, in_axes=(0, 0, 0, None, None, None, None, None)
        )(re, im, powers, starts, lens, elem_block, elem_off, maxlen)


@plane_jit(static_argnames=("maxlen",), stage="accel")
def _prep_transformed_kernel(re, im, starts, lens, elem_block, elem_off,
                             maxlen):
    """Deredden-only half of :func:`_prep_spectra_kernel` for input that
    is ALREADY in the Fourier domain — the prep of the spectral-fusion
    path (round 15), which hands over per-trial spectra with no time
    series to rfft. Mean subtraction is re-expressed spectrally: the
    series mean lives entirely in bin 0, which ``_deredden_body``
    overwrites with 1+0j, so nothing remains to subtract."""
    with jax.named_scope("prep.deredden"):
        powers = re * re + im * im
        return jax.vmap(
            _deredden_body, in_axes=(0, 0, 0, None, None, None, None, None)
        )(re, im, powers, starts, lens, elem_block, elem_off, maxlen)


def prep_spectra_batch(series=None, schedule: DereddenSchedule | None = None,
                       mesh=None, spectra=None):
    """rfft + deredden a batch of time series in ONE device program.

    ``series`` is [B, n] float; returns device-resident ``(re, im)``
    plane arrays of the normalized [B, n//2+1] spectra, consumable
    directly by ``accel_search_batch`` (which skips its host conversion
    for plane tuples). This replaces the batched CLI's per-spectrum
    host path — np.fft.rfft on one core plus a deredden device round
    trip — with a single fused dispatch whose output never leaves the
    device. Host-prep parity: the host path rffts in float64; this one
    is float32 end-to-end, so candidate sigmas agree to ~1e-6 relative
    (inside the documented 2e-6 SNR contract), not bitwise.

    ``spectra`` (exclusive with ``series``) is a ``(re, im)`` tuple of
    real [B, F] planes that are ALREADY the one-sided transforms — the
    spectral-fusion handoff (parallel/specfuse.py), whose sweep kernel
    never leaves the Fourier domain. Only the red-noise normalization
    runs (``_prep_transformed_kernel``); the per-series mean
    subtraction is spectrally a bin-0 edit that deredden's DC overwrite
    subsumes, so the elided rfft is the ONLY difference from the
    series path.

    ``mesh`` shards the batch axis over its 'dm' devices (B must be a
    multiple of the 'dm' size): each device rffts + dereddens only its
    local spectra — every op
    is per-row, so the sharded planes are value-identical to the
    unsharded dispatch and stay resident for the equally-sharded
    ``accel_search_batch`` (the multi-chip handoff's prep half).
    """
    if (series is None) == (spectra is None):
        raise ValueError("give exactly one of series= or spectra=")
    if spectra is not None:
        re, im = (jnp.asarray(spectra[0]), jnp.asarray(spectra[1]))
        if re.ndim != 2 or re.shape != im.shape:
            raise ValueError(f"spectra planes must be two [B, F] arrays; "
                             f"got {re.shape} / {im.shape}")
        if schedule is None:
            schedule = deredden_schedule(re.shape[1])
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            ndm = int(mesh.shape["dm"])
            if re.shape[0] % ndm:
                raise ValueError(f"batch {re.shape[0]} must be a multiple "
                                 f"of the mesh 'dm' axis {ndm}")
            spec = NamedSharding(mesh, P("dm"))
            re = jax.device_put(re, spec)
            im = jax.device_put(im, spec)
        return _prep_transformed_kernel(
            re, im,
            jnp.asarray(schedule.starts), jnp.asarray(schedule.lens),
            jnp.asarray(schedule.elem_block),
            jnp.asarray(schedule.elem_off),
            maxlen=schedule.maxlen,
        )
    series = jnp.asarray(series)
    if series.ndim != 2:
        raise ValueError(f"series must be [B, n]; got {series.shape}")
    if schedule is None:
        schedule = deredden_schedule(series.shape[1] // 2 + 1)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        ndm = int(mesh.shape["dm"])
        if series.shape[0] % ndm:
            raise ValueError(f"batch {series.shape[0]} must be a multiple "
                             f"of the mesh 'dm' axis {ndm}")
        series = jax.device_put(series, NamedSharding(mesh, P("dm")))
    return _prep_spectra_kernel(
        series,
        jnp.asarray(schedule.starts), jnp.asarray(schedule.lens),
        jnp.asarray(schedule.elem_block), jnp.asarray(schedule.elem_off),
        maxlen=schedule.maxlen,
    )


def deredden(fft, powers=None, initialbuflen=6, maxbuflen=200,
             schedule: DereddenSchedule | None = None):
    """PRESTO-style red-noise normalization of a complex FFT.

    Divides by sqrt of a piecewise-linear fit to log-growing block medians of
    the power spectrum (reference prestofft.py:151-195, vectorized — see
    module docstring). Pass ``schedule`` to reuse the host geometry across
    many same-length FFTs.
    """
    re, im = split_complex(fft)
    if powers is None:
        powers = re * re + im * im
    if schedule is None:
        schedule = deredden_schedule(re.shape[0], initialbuflen, maxbuflen)
    our, oui = _deredden_apply(
        jnp.asarray(re), jnp.asarray(im), jnp.asarray(powers),
        jnp.asarray(schedule.starts), jnp.asarray(schedule.lens),
        jnp.asarray(schedule.elem_block), jnp.asarray(schedule.elem_off),
        maxlen=schedule.maxlen,
    )
    return to_host_complex(our, oui)


@plane_jit(static_argnames=("maxlen",), stage="accel")
def _errors_apply(powers, starts, lens, elem_block, elem_off, maxlen):
    rms = _masked_block_stat(powers, starts, lens, maxlen, "std")
    B = starts.shape[0]
    m_old = rms[:-1] if B > 1 else rms
    m_new = rms[1:] if B > 1 else rms
    len_old = lens[:-1] if B > 1 else lens
    len_new = lens[1:] if B > 1 else lens
    denom = (len_new + len_old).astype(powers.dtype)
    slope = (m_new - m_old) / denom
    lineoffset = 0.5 * denom
    c = elem_block
    j = elem_off.astype(powers.dtype)
    errs = m_old[c] + slope[c] * (lineoffset[c] - j)
    return errs.at[0].set(0.0)


def estimate_power_errors(powers, initialbuflen=6, maxbuflen=200,
                          schedule: DereddenSchedule | None = None):
    """Per-bin power uncertainties: piecewise-linear interpolation of block
    RMS values (reference prestofft.py:197-236, vectorized)."""
    powers = jnp.asarray(powers)
    if schedule is None:
        schedule = deredden_schedule(powers.shape[0], initialbuflen, maxbuflen)
    return _errors_apply(
        powers,
        jnp.asarray(schedule.starts), jnp.asarray(schedule.lens),
        jnp.asarray(schedule.elem_block), jnp.asarray(schedule.elem_off),
        maxlen=schedule.maxlen,
    )


@plane_jit(static_argnames=("samp_per_block",), stage="accel")
def spectrogram(timeseries, samp_per_block):
    """Block power spectra: reshape to (numspec, samp_per_block), batched
    rfft, |.|^2 (reference bin/spectrogram.py:17-37). Returns
    spectra[numspec, samp_per_block//2+1]."""
    n = timeseries.shape[0]
    numspec = n // samp_per_block
    blocks = timeseries[: numspec * samp_per_block].reshape(numspec, samp_per_block)
    return jnp.abs(jnp.fft.rfft(blocks, axis=1)) ** 2
