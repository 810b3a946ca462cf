"""Fourier-domain two-stage dedispersion: the TPU fast path of the sweep.

Why: the time-domain formulation of the sweep's hot loop — per-row
``dynamic_slice`` gathers (parallel/sweep.py ``_slice_rows``) — lowers to a
generic XLA gather that measured **26 GB/s effective on v5e** (3% of the
819 GB/s HBM roofline; see BENCHNOTES.md for the recorded A/B). This
module removes the gather entirely: a
circular shift by ``s`` bins is multiplication by ``exp(2i*pi*k*s/n)`` in
the Fourier domain, so the whole two-stage shift-and-sum becomes

    X = rfft(chunk)                                    # once per chunk
    stage 1 (per group):  Xsub[s] = sum_{c in s} X[c] * W^(k*s1[g,c])
    stage 2 (per trial):  Xts    = sum_s  Xsub[s] * W^(k*s2[d,s])
    ts = irfft(Xts)[:, :out_len]

— batched power-of-two FFTs plus *elementwise multiply-reduce* streams,
the access pattern XLA fuses to full bandwidth on TPU. The phase is
applied factored over the frequency-bin axis
(k = M*hi + lo) so the per-shift phase costs ~2*sqrt(F) transcendentals
instead of F — the round-3 profile showed the stages were
phase-generation-bound at ~92G cos-sin/s, and this lifted the measured
chunk time from 323 ms to 146 ms on v5e (BENCHNOTES.md round-4 A/B). Phases compose
additively, so the total integer shift per channel is EXACTLY the same
``s1 + s2`` the time-domain path applies: results agree to FFT f32
rounding, inside the sweep's SNR parity contract of <=2e-6 relative SNR
(measured worst case 5e-7; README "Golden parity"; enforced in
tests/test_sweep.py::test_fourier_engine_snr_tolerance).

Exactness of the phase table: with ``n`` a power of two, the index
``(k * s) mod n`` needs only the low ``log2(n)`` bits of the product, which
int32 wraparound multiplication preserves — no int64, no float64, no
accumulated phase error at large ``k*s``.

Zero-padding to ``n >= chunk_len + max_total_shift`` guarantees circular
shifts never wrap data into the valid window (the pad region is what wraps,
and it is zero — matching the time-domain path's zero end-padding).

Reference treatment: nonexistent (the reference dedisperses with per-channel
Python rolls, formats/spectra.py:54-94, one trial at a time on one core).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from pypulsar_tpu.ops.pallas_kernels import boxcar_stats

__all__ = ["sweep_chunk_spectra", "fourier_chunk_len"]


def fourier_chunk_len(min_len: int) -> int:
    """Smallest power-of-two FFT length >= min_len. TPU XLA lowers only
    power-of-two FFTs efficiently (other sizes fall back to a dense DFT
    matmul that allocates O(L^2) — observed 77 GB for L=139194)."""
    n = 1
    while n < min_len:
        n <<= 1
    return n


def _phase(shifts, k, n_fft: int):
    """exp(2i*pi*k*shifts/n) for integer shifts[...] and bins k[F]:
    shift-LEFT by s in time is multiplication by W^(+k*s) in frequency.
    Index math wraps mod n via int32 overflow (exact for power-of-two n)."""
    idx = (k * shifts[..., None]) & jnp.int32(n_fft - 1)
    ang = (2.0 * jnp.pi / n_fft) * idx.astype(jnp.float32)
    return jax.lax.complex(jnp.cos(ang), jnp.sin(ang))


def _fact_split(F: int) -> int:
    """Power-of-two M minimizing ceil(F/M) + M — the per-shift
    transcendental count of the bin-axis factorization below."""
    best, best_cost = 1, F + 1
    m = 1
    while m <= F:
        cost = -(-F // m) + m
        if cost < best_cost:
            best, best_cost = m, cost
        m <<= 1
    return best


def _factored_view(X):
    """The spectrum X[C, F] viewed as Xp[C, Fh, M] (zero-padded to Fh*M
    bins) for the bin-axis factorization k = M*hi + lo, with the two bin
    ranges ``k_hi[Fh]``, ``k_lo[M]``."""
    C, F = X.shape
    M = _fact_split(F)
    Fh = -(-F // M)
    k_hi = jnp.arange(Fh, dtype=jnp.int32)
    k_lo = jnp.arange(M, dtype=jnp.int32)
    Xp = jnp.pad(X, ((0, 0), (0, Fh * M - F))).reshape(C, Fh, M)
    return Xp, k_hi, k_lo


def _two_stage_factored(Xp, k_hi, k_lo, s1, s2, nsub: int, n_fft: int):
    """One trial group's two-stage shift-and-sum in the Fourier domain:
    Xp[C, Fh, M], s1[C], s2[g, S] -> per-trial spectra [g, Fh*M].

    W^(s*k) = W^((s*M)*hi) * W^(s*lo), so each shift costs Fh + M
    ~ 2*sqrt(F) cos/sin pairs instead of F, applied as two rank-3
    broadcast complex multiplies — hi along axis 1, lo along axis 2 —
    gather-free, no F-length phase row ever materialized."""
    C, Fh, M = Xp.shape
    per = C // nsub
    with jax.named_scope("dedisp.stage1"):
        hi1 = _phase(s1 * jnp.int32(M), k_hi, n_fft)  # [C, Fh]
        lo1 = _phase(s1, k_lo, n_fft)                 # [C, M]
        xsub = (Xp * hi1[:, :, None] * lo1[:, None, :]) \
            .reshape(nsub, per, Fh, M).sum(axis=1)     # [S, Fh, M]
    with jax.named_scope("dedisp.stage2"):
        hi2 = _phase(s2 * jnp.int32(M), k_hi, n_fft)  # [g, S, Fh]
        lo2 = _phase(s2, k_lo, n_fft)                 # [g, S, M]
        xts = (xsub[None] * hi2[..., None] * lo2[..., None, :]) \
            .sum(axis=1)                               # [g, Fh, M]
        return xts.reshape(-1, Fh * M)


def sweep_chunk_fourier_impl(
    data,
    stage1_bins,
    stage2_bins,
    nsub: int,
    out_len: int,
    widths: Tuple[int, ...],
    stat_len: int,
    n_fft: int,
):
    """Fourier-path equivalent of parallel.sweep._sweep_chunk_impl.

    data[C, L] (L <= n_fft; n_fft >= out_len + max total shift so shifts
    cannot wrap); stage1_bins[G, C]; stage2_bins[G, g, S].
    Returns per-trial (sum[D], sumsq[D], maxbox[D, W], argbox[D, W]) with
    window starts confined to the first ``stat_len`` samples.

    The phase is applied factored over the BIN axis
    (:func:`_two_stage_factored`): the stages were phase-generation-bound
    with cos/sin per element (323 ms a 1024-trial chunk on v5e against
    146 ms factored, BENCHNOTES.md round-4 A/B). The factorization costs
    one extra f32 complex multiply (~3e-7 relative), inside the sweep's
    SNR parity budget.
    """
    G, g, S = stage2_bins.shape
    with jax.named_scope("dedisp.rfft"):
        X = jnp.fft.rfft(data, n=n_fft, axis=1)  # [C, F]
    F = X.shape[1]
    Xp, k_hi, k_lo = _factored_view(X)

    def per_group(carry, xs):
        s1, s2 = xs  # [C], [g, S]
        xts = _two_stage_factored(Xp, k_hi, k_lo, s1, s2, nsub, n_fft)
        with jax.named_scope("dedisp.irfft"):
            ts = jnp.fft.irfft(xts[:, :F], n=n_fft, axis=1)[:, :out_len]
        return carry, boxcar_stats(ts, widths, stat_len)

    _, (s, ss, mb, ab) = jax.lax.scan(per_group, 0,
                                      (stage1_bins, stage2_bins))
    D = G * g
    return (
        s.reshape(D),
        ss.reshape(D),
        mb.reshape(D, len(widths)),
        ab.reshape(D, len(widths)),
    )


def dedisperse_series_fourier_impl(
    data,
    stage1_bins,
    stage2_bins,
    nsub: int,
    out_len: int,
    n_fft: int,
):
    """Two-stage subband dedispersed SERIES for every trial: the same
    phase math as :func:`sweep_chunk_fourier_impl` with the fused boxcar
    detection swapped for the raw [D, out_len] time series — the chunk
    kernel of the streamed .dat writer (cli sweep --write-dats on files
    too large for a device-resident Spectra; PRESTO-prepsubband
    semantics: subband dedispersion, not per-channel-exact)."""
    G, g, S = stage2_bins.shape
    with jax.named_scope("dedisp.rfft"):
        X = jnp.fft.rfft(data, n=n_fft, axis=1)  # [C, F]
    F = X.shape[1]
    Xp, k_hi, k_lo = _factored_view(X)

    def body(carry, xs):
        s1, s2 = xs
        xts = _two_stage_factored(Xp, k_hi, k_lo, s1, s2, nsub, n_fft)
        with jax.named_scope("dedisp.irfft"):
            return carry, jnp.fft.irfft(
                xts[:, :F], n=n_fft, axis=1)[:, :out_len]

    _, ts = jax.lax.scan(body, 0, (stage1_bins, stage2_bins))
    return ts.reshape(G * g, out_len)


def sweep_chunk_spectra_impl(
    data,
    stage1_bins,
    stage2_bins,
    nsub: int,
    n_fft: int,
    dec_stride: int,
    dec_len: int,
    mean_len: int,
):
    """Per-trial dedispersed SPECTRA, pre-irfft — the spectral-fusion
    kernel (round 15). Same two-stage phase math as
    :func:`dedisperse_series_fourier_impl` with the final irfft DELETED:
    the per-trial ``Xts`` is kept in the Fourier domain and DECIMATED
    onto the accel stage's T-point grid (``dec_stride = n_fft // T``,
    ``dec_len = T//2 + 1``, ``mean_len = T``; requires ``n_fft % T ==
    0`` and data support confined to ``[0, T)``). Returns ``(re, im)``
    float32 planes ``[D, dec_len]`` (complex never crosses the jit
    boundary, ops/transfer.py).

    Boundary semantics — read before trusting parity: decimating by
    ``n_fft/T`` in frequency is alias-folding the implied frame to
    period T in time, so the result is EXACTLY the spectrum of the
    **circularly** dedispersed series ``ts[u] = sum_c x_c[(u + s_c) mod
    T]`` — the Fourier-domain-dedispersion convention (PAPERS.md
    2110.03482 applies the chirp to the full-observation spectrum the
    same way). The framework's time-domain engines use PRESTO's
    zero-padded LINEAR shifts instead; the two agree everywhere except
    the final ``max_total_shift`` samples, where linear has partial
    sums (channels read past the data end into zeros) and circular
    wraps in each channel's first ``s_c`` samples. No phase trick can
    reconcile them: every channel's full T samples are present in any
    phase-shifted frame, and the fold must put the ``s_c`` head samples
    — which the linear window never reads — SOMEWHERE in the period.
    This was measured, not guessed (BENCHNOTES round 10): the candidate
    tables differ at toy scale, which is why parallel/specfuse.py ships
    this kernel as the opt-in ``decimate`` regime and defaults to the
    bit-exact stitched regime.

    ``mean_len`` (= T): per-channel means over the real samples are
    subtracted first, masked so the zero pad stays zero. Each channel's
    subtracted boxcar spans exactly one fold period, which aliases to a
    CONSTANT — spectrally a pure bin-0 term, exactly like
    ``prep_spectra_batch``'s series-mean subtraction (also a bin-0
    edit), and deredden overwrites bin 0 anyway. Numerically it keeps
    the f32 butterflies at fluctuation scale instead of the ~100x-sigma
    DC of 8-bit data.
    """
    G, g, S = stage2_bins.shape
    col = jnp.arange(data.shape[1], dtype=jnp.int32)
    live = (col < mean_len).astype(data.dtype)[None, :]
    mu = (data * live).sum(axis=1, keepdims=True) / jnp.float32(mean_len)
    data = data - mu * live
    with jax.named_scope("dedisp.rfft"):
        X = jnp.fft.rfft(data, n=n_fft, axis=1)  # [C, F]
    didx = jnp.arange(dec_len, dtype=jnp.int32) * jnp.int32(dec_stride)
    Xp, k_hi, k_lo = _factored_view(X)

    def body(carry, xs):
        s1, s2 = xs
        xts = _two_stage_factored(Xp, k_hi, k_lo, s1, s2, nsub, n_fft)
        xts = jnp.take(xts, didx, axis=1)
        return carry, (xts.real.astype(jnp.float32),
                       xts.imag.astype(jnp.float32))

    _, (re, im) = jax.lax.scan(body, 0, (stage1_bins, stage2_bins))
    return re.reshape(G * g, dec_len), im.reshape(G * g, dec_len)


sweep_chunk_spectra = jax.jit(
    sweep_chunk_spectra_impl,
    static_argnames=("nsub", "n_fft", "dec_stride", "dec_len", "mean_len"),
)
