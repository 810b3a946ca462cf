"""Device fold engine: scatter-add time samples into pulse-phase bins.

The reference folds on the host, one rotation at a time, by cutting the
time series at polyco-predicted period boundaries (formats/datfile.py:231-275
driving bin/dissect.py) — O(pulses) Python iterations.  The TPU-native
design evaluates the phase polynomial for a whole block of samples at once
(float64, host) and folds the block on device with a single segment-sum:

    profile[b] = sum data[i] where floor(phase_i * nbins) % nbins == b

Note the binning convention: bin b collects phases [b/nbins, (b+1)/nbins),
so its representative phase is the bin *center* (b+0.5)/nbins — TOA code
comparing a folded profile against a template sampled at b/nbins must
account for the half-bin offset (as PRESTO's fold does).

1-D series fold with ``jax.ops.segment_sum``; 2-D [chan, time] folds (the
.pfd-style chan x phase archive) as a one-hot matmul on the MXU at
HIGHEST precision — the TPU-native scatter formulation (see fold_bins).
NumPy golden twins live alongside for parity tests (SURVEY.md §4
strategy 1).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pypulsar_tpu.compile import bucket_rows, plane_jit, register_warmer
from pypulsar_tpu.core.psrmath import SECPERDAY
from pypulsar_tpu.obs import telemetry


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fold_bins_impl(data, bin_idx, nbins: int):
    data = jnp.asarray(data)
    bin_idx = jnp.asarray(bin_idx, jnp.int32)
    counts = jax.ops.segment_sum(
        jnp.ones(bin_idx.shape, jnp.int32), bin_idx, num_segments=nbins
    )
    if data.ndim == 1:
        prof = jax.ops.segment_sum(data, bin_idx, num_segments=nbins)
    else:
        prof, _ = _onehot_fold_2d(data, bin_idx, nbins)
    return prof, counts


_fold_bins_jit = plane_jit(_fold_bins_impl, static_argnames=("nbins",),
                           stage="fold")


def fold_bins(data, bin_idx, nbins: int):
    """Scatter-add ``data`` (1-D [time] or 2-D [chan, time]) into ``nbins``
    phase bins given per-sample bin indices.  Returns (profile, counts).

    The 2-D path is formulated as ``data @ one_hot(bin_idx)`` — a phase-
    bin scatter is a matmul with a 0/1 selection matrix, which runs on
    the MXU instead of XLA's serialized scatter-add (the vmapped
    segment_sum formulation measured ~7 s for a 1024x2^20 fold on v5e;
    the matmul is bandwidth-bound). Counts stay integer (float32 would
    saturate at 2^24 samples/bin)."""
    if telemetry.is_active():
        telemetry.counter("fold.samples", int(np.size(data)))
    with telemetry.span("fold_bins", nbins=nbins):
        return _fold_bins_jit(data, bin_idx, nbins)


_FOLD_BLOCK = 1 << 17  # bounds the live one-hot to ~64 MB at 128 bins


@jax.named_scope("fold.matmul")
def _onehot_fold_2d(data, bin_idx, nbins: int):
    """``data[C, T] @ one_hot(bin_idx)`` accumulated over time blocks so
    the selection matrix never exceeds _FOLD_BLOCK x nbins (a monolithic
    one-hot is T*nbins*4 bytes — 64 GB for a 2^27-sample fold). The tail
    pads with index ``nbins``, which one_hot maps to an all-zero row.

    Returns (prof[C, nbins], counts_f32[nbins]) — counts are column sums
    of the same one-hot matrices: exact in f32 per block (0/1 sums up to
    _FOLD_BLOCK << 2^24) and across the f32 block accumulation until
    ~2^24 samples/bin. Callers needing exact counts beyond that
    (fold_bins' whole-series totals) use an integer segment_sum instead.
    HIGHEST precision throughout: the default TPU matmul rounds inputs
    to bf16, which visibly degrades fold sums (caught by the bench
    parity check)."""
    C, T = data.shape
    if T <= _FOLD_BLOCK:
        onehot = jax.nn.one_hot(bin_idx, nbins, dtype=data.dtype)
        prof = jnp.dot(data, onehot, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
        return prof, onehot.sum(axis=0)
    nblk = -(-T // _FOLD_BLOCK)
    pad = nblk * _FOLD_BLOCK - T
    d = jnp.pad(data, ((0, 0), (0, pad)))
    b = jnp.pad(bin_idx, (0, pad), constant_values=nbins)
    d = d.reshape(C, nblk, _FOLD_BLOCK).transpose(1, 0, 2)
    b = b.reshape(nblk, _FOLD_BLOCK)

    def body(acc, xs):
        dblk, bblk = xs
        acc_p, acc_c = acc
        onehot = jax.nn.one_hot(bblk, nbins, dtype=dblk.dtype)
        prof = jnp.dot(dblk, onehot, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
        return (acc_p + prof, acc_c + onehot.sum(axis=0)), None

    (prof, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((C, nbins), jnp.float32),
               jnp.zeros((nbins,), jnp.float32)), (d, b))
    return prof, cnt


def _fold_parts_impl(data, bin_idx, nbins: int, npart: int):
    """Traceable body of :func:`fold_parts` (shared with the fused
    :func:`fold_stats` program, which inlines it in its own trace)."""
    data = jnp.asarray(data)
    bin_idx = jnp.asarray(bin_idx, jnp.int32)
    C, T = data.shape
    part_len = T // npart
    if part_len >= 1 << 24:
        raise ValueError(
            f"part_len={part_len} >= 2^24: f32 one-hot counts would lose "
            f"exactness; use more partitions")
    b = bin_idx[: npart * part_len].reshape(npart, part_len)

    def body(carry, ci):
        dpart = jax.lax.dynamic_slice(
            data, (0, ci * part_len), (C, part_len))
        prof, cnt = _onehot_fold_2d(dpart, b[ci], nbins)
        return carry, (prof, cnt.astype(jnp.int32))

    _, (profs, counts) = jax.lax.scan(body, 0, jnp.arange(npart))
    return profs, counts


_fold_parts_jit = plane_jit(_fold_parts_impl,
                            static_argnames=("nbins", "npart"), stage="fold")


def fold_parts(data, bin_idx, nbins: int, npart: int):
    """Fold into a ``[npart, nchan, nbins]`` sub-integration archive cube
    (the .pfd product) in ONE compiled program.

    ``data[C, T]`` is cut into ``npart`` equal partitions (a trailing
    remainder is dropped, as the reference's whole-rotation cuts drop the
    tail); a lax.scan folds each via the one-hot matmul, holding only one
    partition's selection matrix live. One dispatch for the whole cube,
    not one per partition.

    Two measured costs are engineered out (v5e A/B, BENCHNOTES): the
    per-partition ``segment_sum`` count scatters (counts come from
    column sums of the SAME one-hot matrix — exact in f32 while
    part_len < 2^24, asserted host-side) and a whole-array pre-transpose
    (partitions slice out of the original layout inside the scan).
    Returns (profiles[npart, C, nbins], counts[npart, nbins])."""
    if telemetry.is_active():
        telemetry.counter("fold.samples", int(np.size(data)))
    with telemetry.span("fold_parts", nbins=nbins, npart=npart):
        return _fold_parts_jit(data, bin_idx, nbins, npart)


@plane_jit(static_argnames=("nbins", "npart"), stage="fold")
def _fold_stats_jit(data, bin_idx, nbins: int, npart: int, dp_offsets):
    """One-dispatch fold + ON-DEVICE profile statistics (VERDICT r3
    item 4): everything pfd_snr-style analysis needs leaves the device as
    KILOBYTES instead of the [npart, C, nbins] archive cube (33 MB at
    bench shapes).

    Computed inside the one program, on top of the fold_parts cube:
      - ``part_profs[npart, nbins]``: channel-summed sub-integration
        profiles (the .pfd time-phase plot),
      - ``chan_profs[C, nbins]``: partition-summed channel-phase archive
        (the frequency-phase plot / subband view),
      - ``counts[npart, nbins]``,
      - ``dsum, dsumsq``: folded-data moments for the off-pulse std
        (profile_snr.profile_std / L&K eq. 7.1, reference
        bin/pfd_snr.py:674-718),
      - ``dp_profs[J, nbins]``: bestprof-style period refinement — trial
        ``j`` rotates partition ``i`` by ``dp_offsets[j, i]`` cycles
        (Fourier rotation, exact for band-limited profiles) and sums;
        the host picks the chi2-max trial (reference surface:
        prepfold's .bestprof via bin/pfd_snr.py:151-156
        ``adjust_period``).

    ``dp_offsets[J, npart]`` float32 cycles. The cube itself never
    leaves the device and is freed with the program.
    """
    profs, counts = _fold_parts_impl(data, bin_idx, nbins, npart)
    part_profs = profs.sum(axis=1)  # [npart, nbins]
    chan_profs = profs.sum(axis=0)  # [C, nbins]
    C, T = data.shape
    part_len = T // npart
    used = data[:, : npart * part_len]
    dsum = jnp.sum(used, dtype=jnp.float32)
    dsumsq = jnp.sum(used * used, dtype=jnp.float32)
    # Fourier rotation: shifting a profile by x cycles multiplies rfft
    # bin k by exp(-2i*pi*k*x)
    pf = jnp.fft.rfft(part_profs, axis=1)  # [npart, K]
    k = jnp.arange(pf.shape[1], dtype=jnp.float32)
    ang = -2.0 * jnp.pi * dp_offsets[:, :, None] * k[None, None, :]
    rot = jax.lax.complex(jnp.cos(ang), jnp.sin(ang))  # [J, npart, K]
    # HIGHEST: the default TPU matmul rounds f32 inputs to bf16 (~2e-3
    # relative — the same trap _onehot_fold_2d documents), which would
    # swamp the 2e-4 twin-parity tolerance and noise the chi2 argmax
    dp_f = jnp.einsum("ik,jik->jk", pf, rot,
                      precision=jax.lax.Precision.HIGHEST)
    dp_profs = jnp.fft.irfft(dp_f, n=nbins, axis=1)  # [J, nbins]
    return part_profs, chan_profs, counts, dsum, dsumsq, dp_profs


def fold_stats(data, bin_idx, nbins: int, npart: int, dp_offsets):
    """See :func:`_fold_stats_jit` — this wrapper only adds telemetry
    (folded-sample counter + dispatch span) around the one-dispatch
    program, behind the inactive-is-one-branch check."""
    if telemetry.is_active():
        telemetry.counter("fold.samples", int(np.size(data)))
    with telemetry.span("fold_stats", nbins=nbins, npart=npart):
        return _fold_stats_jit(data, bin_idx, nbins, npart, dp_offsets)


def fold_stats_numpy(data, bin_idx, nbins: int, npart: int, dp_offsets):
    """Golden float64 twin of :func:`fold_stats`."""
    data = np.asarray(data, np.float64)
    C, T = data.shape
    part_len = T // npart
    profs = []
    counts = []
    for i in range(npart):
        p, c = fold_numpy(data[:, i * part_len:(i + 1) * part_len],
                          bin_idx[i * part_len:(i + 1) * part_len], nbins)
        profs.append(p)
        counts.append(c)
    profs = np.stack(profs)  # [npart, C, nbins]
    counts = np.stack(counts)
    part_profs = profs.sum(axis=1)
    chan_profs = profs.sum(axis=0)
    used = data[:, : npart * part_len]
    dsum = used.sum()
    dsumsq = (used * used).sum()
    pf = np.fft.rfft(part_profs, axis=1)
    k = np.arange(pf.shape[1])
    rot = np.exp(-2j * np.pi * np.asarray(dp_offsets)[:, :, None]
                 * k[None, None, :])
    dp_profs = np.fft.irfft(np.einsum("ik,jik->jk", pf, rot), n=nbins,
                            axis=1)
    return part_profs, chan_profs, counts, dsum, dsumsq, dp_profs


def bestprof_offsets(npart: int, T_sec: float, period: float,
                     ntrial: int = 65, max_drift_cycles: float = 2.0):
    """(dp_trials[J] seconds, dp_offsets[J, npart] cycles) for the
    fold_stats period refinement: a fold at period ``P`` of a signal with
    true period ``P + dp`` drifts by ``t * dp / P**2`` cycles at time t;
    trial j rotates partition i (mid-time t_i) by the OPPOSITE so the
    matching trial re-aligns the summed profile. ``max_drift_cycles`` is
    the drift across the whole observation at the largest trial."""
    dp_max = max_drift_cycles * period * period / max(T_sec, 1e-12)
    dps = np.linspace(-dp_max, dp_max, ntrial)
    t_mid = (np.arange(npart) + 0.5) * (T_sec / npart)
    off = -t_mid[None, :] * dps[:, None] / (period * period)
    return dps, off.astype(np.float32)


def fold_snr_stats(data, bin_idx, nbins: int, npart: int, dt: float,
                   period: float, ntrial: int = 65):
    """Device fold + fused statistics, then the host-side (float64, tiny)
    finishing math: off-pulse std from the data moments, L&K eq. 7.1 SNR
    of the summed profile with an auto on-pulse region, and the refined
    period from the chi2-max dp trial. One device dispatch; ~100 KB
    pulled (vs the 33 MB cube).

    Returns a dict with ``snr``, ``best_period``, ``chi2`` [J],
    ``dp_trials`` [J], ``profile`` [nbins], ``part_profs``,
    ``chan_profs``, ``counts``.
    """
    import jax.numpy as jnp

    from pypulsar_tpu.fold.profile_snr import (
        OnPulseError,
        calc_snr,
        onpulse_auto,
        profile_std,
    )

    C, T = np.shape(data)
    part_len = T // npart
    T_sec = npart * part_len * dt
    dps, off = bestprof_offsets(npart, T_sec, period, ntrial=ntrial)
    out = fold_stats(jnp.asarray(data), jnp.asarray(bin_idx), nbins, npart,
                     jnp.asarray(off))
    # one batched pull, then f64 on host: six per-array np.asarray pulls
    # would be six device->host syncs (ops/transfer.pull_host)
    from pypulsar_tpu.ops.transfer import pull_host

    part_profs, chan_profs, counts, dsum, dsumsq, dp_profs = \
        (np.asarray(x, dtype=np.float64) for x in pull_host(*out))
    n_used = C * npart * part_len
    data_var = dsumsq / n_used - (dsum / n_used) ** 2
    std = profile_std(max(data_var, 0.0), n_used, nbins, 1.0)
    prof = part_profs.sum(axis=0)
    try:
        snr = calc_snr(prof, onpulse_auto(prof), std)[0]
    except OnPulseError:
        snr = 0.0
    chi2 = ((dp_profs - dp_profs.mean(axis=1, keepdims=True)) ** 2).sum(axis=1)
    j = int(np.argmax(chi2))
    return dict(snr=float(snr), best_period=float(period + dps[j]),
                dp_trials=dps, chi2=chi2, profile=prof,
                part_profs=part_profs, chan_profs=chan_profs,
                counts=counts)


# ---------------------------------------------------------------------------
# batched candidate folding (the fold-pipeline kernels)
# ---------------------------------------------------------------------------

@jax.named_scope("fold.matmul")
def _onehot_fold_1d_batch(data, bin_idx, nbins: int):
    """``[K]``-candidate fold of ONE shared 1-D block: each candidate k
    scatters the same ``data[T]`` into its own bins via
    ``einsum('t,ktb->kb', data, one_hot(bin_idx[k]))`` — the per-candidate
    contraction is the identical length-T f32 gemv the serial 2-D path
    (:func:`_onehot_fold_2d` at C=1) performs, batched on the candidate
    axis. Time blocking at the same ``_FOLD_BLOCK`` seams as the serial
    path, so the f32 accumulation splits match it; the LIVE one-hot is K
    times the serial path's (the candidate axis is the halving_dispatch
    axis on OOM — parallel/foldpipe). Byte-identity with the serial path
    is PINNED on the CPU backend (tests + BENCH_r07_fold.json); on other
    backends XLA may tile the batched contraction differently, where the
    guaranteed contract is the f32/SNR tolerance of the golden twins.
    Returns (prof[K, nbins] f32, counts[K, nbins] f32 — exact while
    block counts < 2^24, the _onehot_fold_2d argument)."""
    K, T = bin_idx.shape
    if T <= _FOLD_BLOCK:
        onehot = jax.nn.one_hot(bin_idx, nbins, dtype=data.dtype)
        prof = jnp.einsum("t,ktb->kb", data, onehot,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
        return prof, onehot.sum(axis=1)
    nblk = -(-T // _FOLD_BLOCK)
    pad = nblk * _FOLD_BLOCK - T
    d = jnp.pad(data, (0, pad)).reshape(nblk, _FOLD_BLOCK)
    b = jnp.pad(bin_idx, ((0, 0), (0, pad)), constant_values=nbins)
    b = b.reshape(K, nblk, _FOLD_BLOCK).transpose(1, 0, 2)

    def body(acc, xs):
        dblk, bblk = xs
        acc_p, acc_c = acc
        onehot = jax.nn.one_hot(bblk, nbins, dtype=dblk.dtype)
        prof = jnp.einsum("t,ktb->kb", dblk, onehot,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
        return (acc_p + prof, acc_c + onehot.sum(axis=1)), None

    (prof, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((K, nbins), jnp.float32),
               jnp.zeros((K, nbins), jnp.float32)), (d, b))
    return prof, cnt


def _fold_parts_batch_impl(series, bin_idx, nbins: int, npart: int):
    series = jnp.asarray(series)
    bin_idx = jnp.asarray(bin_idx, jnp.int32)
    K, T = bin_idx.shape
    part_len = T // npart
    if part_len >= 1 << 24:
        raise ValueError(
            f"part_len={part_len} >= 2^24: f32 one-hot counts would lose "
            f"exactness; use more partitions")
    d = series[: npart * part_len].reshape(npart, part_len)
    b = bin_idx[:, : npart * part_len].reshape(
        K, npart, part_len).transpose(1, 0, 2)

    def body(carry, xs):
        dpart, bpart = xs
        prof, cnt = _onehot_fold_1d_batch(dpart, bpart, nbins)
        return carry, (prof, cnt.astype(jnp.int32))

    _, (profs, counts) = jax.lax.scan(body, 0, (d, b))
    return profs.transpose(1, 0, 2), counts.transpose(1, 0, 2)


_fold_parts_batch_jit = plane_jit(_fold_parts_batch_impl,
                                  static_argnames=("nbins", "npart"),
                                  stage="fold")


def fold_parts_batch(series, bin_idx, nbins: int, npart: int):
    """Fold ONE shared dedispersed series at ``K`` candidates' phase
    models in one compiled program: ``series[T]`` float32 is cut into
    ``npart`` partitions (trailing remainder dropped, as
    :func:`fold_parts`) and each partition is folded per candidate via
    the batched one-hot contraction — the fold-pipeline core (candidates
    sharing a DM share the data pass; only the per-candidate bin indices
    differ). Returns (profiles[K, npart, nbins] f32,
    counts[K, npart, nbins] int32)."""
    if telemetry.is_active():
        telemetry.counter("fold.samples",
                          int(np.shape(bin_idx)[0]) * int(np.size(series)))
    with telemetry.span("fold_parts_batch", nbins=nbins, npart=npart,
                        n_cands=int(np.shape(bin_idx)[0])):
        return _fold_parts_batch_jit(series, bin_idx, nbins, npart)


@jax.named_scope("fold.matmul")
def _onehot_fold_1d_multi(data, bin_idx, nbins: int):
    """Multi-series twin of :func:`_onehot_fold_1d_batch`: candidate k
    folds its OWN ``data[k]`` row (``einsum('kt,ktb->kb')``) instead of
    one shared series. Per candidate the contraction is the identical
    length-T f32 gemv — same ``_FOLD_BLOCK`` seams, same HIGHEST
    precision — so on the CPU backend each row is bit-identical to the
    shared-series kernel fed that row's series (the batch-broker fusion
    contract, pinned by tests/test_broker.py)."""
    K, T = bin_idx.shape
    if T <= _FOLD_BLOCK:
        onehot = jax.nn.one_hot(bin_idx, nbins, dtype=data.dtype)
        prof = jnp.einsum("kt,ktb->kb", data, onehot,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
        return prof, onehot.sum(axis=1)
    nblk = -(-T // _FOLD_BLOCK)
    pad = nblk * _FOLD_BLOCK - T
    d = jnp.pad(data, ((0, 0), (0, pad))).reshape(
        K, nblk, _FOLD_BLOCK).transpose(1, 0, 2)
    b = jnp.pad(bin_idx, ((0, 0), (0, pad)), constant_values=nbins)
    b = b.reshape(K, nblk, _FOLD_BLOCK).transpose(1, 0, 2)

    def body(acc, xs):
        dblk, bblk = xs
        acc_p, acc_c = acc
        onehot = jax.nn.one_hot(bblk, nbins, dtype=dblk.dtype)
        prof = jnp.einsum("kt,ktb->kb", dblk, onehot,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
        return (acc_p + prof, acc_c + onehot.sum(axis=1)), None

    (prof, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((K, nbins), jnp.float32),
               jnp.zeros((K, nbins), jnp.float32)), (d, b))
    return prof, cnt


def _fold_parts_multi_impl(stack, series_idx, bin_idx, nbins: int,
                           npart: int):
    stack = jnp.asarray(stack)
    series_idx = jnp.asarray(series_idx, jnp.int32)
    bin_idx = jnp.asarray(bin_idx, jnp.int32)
    K, T = bin_idx.shape
    part_len = T // npart
    if part_len >= 1 << 24:
        raise ValueError(
            f"part_len={part_len} >= 2^24: f32 one-hot counts would lose "
            f"exactness; use more partitions")
    # gather each candidate's series row, then mirror
    # _fold_parts_batch_impl exactly (same partition cut, same scan)
    d = stack[series_idx, : npart * part_len].reshape(
        K, npart, part_len).transpose(1, 0, 2)
    b = bin_idx[:, : npart * part_len].reshape(
        K, npart, part_len).transpose(1, 0, 2)

    def body(carry, xs):
        dpart, bpart = xs
        prof, cnt = _onehot_fold_1d_multi(dpart, bpart, nbins)
        return carry, (prof, cnt.astype(jnp.int32))

    _, (profs, counts) = jax.lax.scan(body, 0, (d, b))
    return profs.transpose(1, 0, 2), counts.transpose(1, 0, 2)


_fold_parts_multi_jit = plane_jit(_fold_parts_multi_impl,
                                  static_argnames=("nbins", "npart"),
                                  stage="fold")


def fold_parts_multi(stack, series_idx, bin_idx, nbins: int, npart: int):
    """Fold ``K`` candidates against ``G`` DIFFERENT equal-length
    series in one compiled program: candidate k folds
    ``stack[series_idx[k]]`` at its own phase model. This is the batch
    broker's fused fold kernel (round 24) — candidates from several
    observations, each with its own dedispersed series, fuse into ONE
    device dispatch. Row k is bit-identical (CPU backend) to
    ``fold_parts_batch(stack[series_idx[k]], bin_idx[k:k+1], ...)``.
    Returns (profiles[K, npart, nbins] f32, counts[K, npart, nbins]
    int32)."""
    if telemetry.is_active():
        telemetry.counter("fold.samples",
                          int(np.shape(bin_idx)[0])
                          * int(np.shape(stack)[-1]))
    with telemetry.span("fold_parts_multi", nbins=nbins, npart=npart,
                        n_cands=int(np.shape(bin_idx)[0]),
                        n_series=int(np.shape(stack)[0])):
        return _fold_parts_multi_jit(stack, series_idx, bin_idx, nbins,
                                     npart)


def fold_parts_batch_numpy(series, bin_idx, nbins: int, npart: int):
    """Golden float64 twin of :func:`fold_parts_batch`: per candidate,
    per partition, the EXACT per-candidate :func:`fold_numpy` bincount —
    bit-identical to folding each candidate alone (the parity contract
    of the batched pipeline)."""
    series = np.asarray(series, np.float64)
    bin_idx = np.asarray(bin_idx)
    K, T = bin_idx.shape
    part_len = T // npart
    profs = np.empty((K, npart, nbins), np.float64)
    counts = np.empty((K, npart, nbins), np.int64)
    for k in range(K):
        for i in range(npart):
            sl = slice(i * part_len, (i + 1) * part_len)
            p, c = fold_numpy(series[sl], bin_idx[k, sl], nbins)
            profs[k, i] = p
            counts[k, i] = c.astype(np.int64)
    return profs, counts


@plane_jit(stage="fold")
def _refine_chi2_jit(part_profs, offsets):
    """chi2[K, J] of every candidate x drift-trial combination: trial j
    rotates candidate k's partition i by ``offsets[j, i]`` cycles
    (Fourier phase ramp — exact for band-limited profiles, the
    fold_stats dp machinery generalized to a shared 2-D (p, pdot) drift
    grid), sums the re-aligned partitions and scores the summed profile
    by its variance about the mean (the chi2-max trial is the
    best-aligned one). ZERO refolds: the data never re-enters — only the
    [npart, nbins] sub-profiles rotate."""
    nbins = part_profs.shape[-1]
    pf = jnp.fft.rfft(part_profs, axis=-1)  # [K, npart, F]
    k = jnp.arange(pf.shape[-1], dtype=jnp.float32)
    ang = -2.0 * jnp.pi * offsets[:, :, None] * k[None, None, :]
    rot = jax.lax.complex(jnp.cos(ang), jnp.sin(ang))  # [J, npart, F]
    # HIGHEST: same bf16-rounding trap _fold_stats_jit documents
    dp_f = jnp.einsum("inf,jnf->ijf", pf, rot,
                      precision=jax.lax.Precision.HIGHEST)  # [K, J, F]
    profs = jnp.fft.irfft(dp_f, n=nbins, axis=-1)  # [K, J, nbins]
    return ((profs - profs.mean(axis=-1, keepdims=True)) ** 2).sum(axis=-1)


def refine_chi2(part_profs, offsets):
    """See :func:`_refine_chi2_jit`; this wrapper adds the dispatch span."""
    with telemetry.span("fold_refine", n_cands=int(np.shape(part_profs)[0]),
                        n_trials=int(np.shape(offsets)[0])):
        return _refine_chi2_jit(jnp.asarray(part_profs),
                                jnp.asarray(offsets, jnp.float32))


def refine_chi2_numpy(part_profs, offsets):
    """Golden float64 twin of :func:`refine_chi2`."""
    part_profs = np.asarray(part_profs, np.float64)
    off = np.asarray(offsets, np.float64)
    pf = np.fft.rfft(part_profs, axis=-1)
    k = np.arange(pf.shape[-1])
    rot = np.exp(-2j * np.pi * off[:, :, None] * k[None, None, :])
    profs = np.fft.irfft(np.einsum("inf,jnf->ijf", pf, rot),
                         n=part_profs.shape[-1], axis=-1)
    return ((profs - profs.mean(axis=-1, keepdims=True)) ** 2).sum(axis=-1)


def refine_drift_grid(ntrial_p: int = 33, ntrial_pd: int = 17,
                      max_drift_cycles: float = 2.0):
    """The candidate-INDEPENDENT (p, pdot) refinement trial grid,
    parametrized in whole-observation drift cycles so one grid (and one
    device rotation tensor) serves every candidate in a batch regardless
    of its period:

    - ``dl``: linear drift over the observation, cycles. A fold at P of
      a signal at P + dp is re-aligned by the trial with
      ``dl = dp * T / P**2`` (the bestprof_offsets relation,
      ``off = -t * dp / P**2`` with u = t/T normalized);
    - ``dq``: quadratic drift, cycles. A pdot error dpd is re-aligned by
      ``dq = dpd * T**2 / (2 P**2)``.

    Returns (dl[J], dq[J]) flattened over the ``ntrial_p x ntrial_pd``
    grid (``ntrial_pd=1`` collapses to the pure-period bestprof grid);
    :func:`drift_offsets` turns them into per-partition rotation offsets
    and :func:`drift_to_p_pd` maps a winning trial back to a candidate's
    (p, pdot)."""
    # a single-trial axis collapses to ZERO drift (np.linspace(-m, m, 1)
    # would return [-m], biasing every refined value by a full -m drift)
    dls = (np.linspace(-max_drift_cycles, max_drift_cycles, ntrial_p)
           if ntrial_p > 1 else np.array([0.0]))
    dqs = (np.linspace(-max_drift_cycles, max_drift_cycles, ntrial_pd)
           if ntrial_pd > 1 else np.array([0.0]))
    DL, DQ = np.meshgrid(dls, dqs, indexing="ij")
    return DL.ravel(), DQ.ravel()


def drift_offsets(dl: np.ndarray, dq: np.ndarray, npart: int) -> np.ndarray:
    """offsets[J, npart] float32 rotation cycles for the drift grid:
    partition i (normalized mid-time u_i) of trial j re-aligns by the
    drift the trial hypothesizes at u_i (the bestprof_offsets sign
    convention, which the fold_stats chi2-argmax machinery pins down)."""
    u = (np.arange(npart) + 0.5) / npart
    off = -(dl[:, None] * u[None, :] + dq[:, None] * u[None, :] ** 2)
    return off.astype(np.float32)


def drift_to_p_pd(dl: float, dq: float, period: float, pdot: float,
                  T_sec: float):
    """Map a winning drift trial back to this candidate's refined
    (p, pdot): inverse of the :func:`refine_drift_grid` relations."""
    dp = dl * period * period / max(T_sec, 1e-12)
    dpd = 2.0 * dq * period * period / max(T_sec * T_sec, 1e-24)
    return period + dp, pdot + dpd


def phase_to_bins(phases: np.ndarray, nbins: int) -> np.ndarray:
    """Fractional rotation counts -> phase bin indices (host, float64)."""
    return (np.floor(np.asarray(phases, np.float64) * nbins).astype(np.int64)
            % nbins).astype(np.int32)


def fold_numpy(data: np.ndarray, bin_idx: np.ndarray, nbins: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Golden twin of fold_bins."""
    data = np.asarray(data)
    bin_idx = np.asarray(bin_idx)
    counts = np.bincount(bin_idx, minlength=nbins).astype(np.float32)
    if data.ndim == 1:
        prof = np.bincount(bin_idx, weights=data, minlength=nbins)
    else:
        prof = np.stack(
            [np.bincount(bin_idx, weights=row, minlength=nbins) for row in data]
        )
    return prof.astype(np.float64), counts


# ---------------------------------------------------------------------------
# phase models
# ---------------------------------------------------------------------------

def phases_constant_period(n: int, dt: float, period: float,
                           start_phase: float = 0.0) -> np.ndarray:
    """Sample phases for a constant period (bin/dissect.py's '-p' mode)."""
    return start_phase + np.arange(n, dtype=np.float64) * (dt / period)


def phases_from_polycos(pcs, mjdstart: float, n: int, dt: float) -> np.ndarray:
    """Absolute rotation counts for n samples starting at mjdstart, from a
    Polycos container.  Evaluated blockwise per valid polyco so each block
    uses one polynomial (float64; the per-sample Horner loop of the
    reference collapses to vectorized polyval)."""
    mjdi = int(mjdstart)
    mjdf0 = mjdstart - mjdi
    tsamp_days = dt / SECPERDAY
    out = np.empty(n, dtype=np.float64)
    i = 0
    while i < n:
        mjdf = mjdf0 + i * tsamp_days
        block_poly = pcs.polycos[pcs.select_polyco(mjdi, mjdf)]
        # samples still covered by this block
        t_end = block_poly.TMID + pcs.validrange
        remaining = int(
            min(n - i, max(1, np.floor((t_end - (mjdi + mjdf)) / tsamp_days)))
        )
        idx = np.arange(i, i + remaining, dtype=np.float64)
        out[i : i + remaining] = block_poly.rotation_batch(
            mjdi, mjdf0 + idx * tsamp_days
        )
        i += remaining
    return out


# ---------------------------------------------------------------------------
# high-level folds
# ---------------------------------------------------------------------------

def _fold_any(data, dt, nbins, n, period, polycos, mjdstart, normalize):
    if period is not None:
        phases = phases_constant_period(n, dt, period)
    elif polycos is not None and mjdstart is not None:
        phases = phases_from_polycos(polycos, mjdstart, n, dt)
    else:
        raise ValueError("need period or (polycos, mjdstart)")
    bin_idx = phase_to_bins(phases, nbins)
    prof, counts = fold_bins(jnp.asarray(np.asarray(data, np.float32)),
                             bin_idx, nbins)
    prof = np.asarray(prof, dtype=np.float64)
    counts = np.asarray(counts, dtype=np.float64)
    if normalize:
        prof = np.where(counts > 0, prof / np.maximum(counts, 1), 0.0)
    return prof, counts


def fold_timeseries(
    data: np.ndarray,
    dt: float,
    nbins: int,
    *,
    period: Optional[float] = None,
    polycos=None,
    mjdstart: Optional[float] = None,
    normalize: bool = False,
):
    """Fold a 1-D time series into an ``nbins`` profile.

    Give either a constant ``period`` or (``polycos``, ``mjdstart``).
    Returns (profile, counts) as numpy arrays; with ``normalize`` the
    profile is divided by per-bin counts (empty bins -> 0).
    """
    return _fold_any(data, dt, nbins, len(data), period, polycos, mjdstart,
                     normalize)


def fold_spectra(
    data: np.ndarray,
    dt: float,
    nbins: int,
    *,
    period: Optional[float] = None,
    polycos=None,
    mjdstart: Optional[float] = None,
    normalize: bool = False,
):
    """Fold 2-D [chan, time] data into a [chan, nbins] archive (the
    .pfd-style product)."""
    return _fold_any(data, dt, nbins, data.shape[1], period, polycos,
                     mjdstart, normalize)


# ---------------------------------------------------------------------------
# warm-pool precompile (round 22)

def _warm_fold(*, n_samples=None, downsamp=1, fold_nbins=64,
               fold_npart=32, fold_batch=32, **_ignored) -> int:
    """Warm-pool planner for the fold stage: AOT-lower the batched
    partition fold at the geometry the fold pipeline will dispatch —
    the downsampled series length and the candidate batch padded to the
    compile plane's bucket ladder (exactly what foldpipe's dispatch
    pads to). Abstract arrays only; nothing is read or dispatched."""
    T = int(n_samples or 0) // max(1, int(downsamp))
    if T <= 0:
        return 0
    K = bucket_rows(max(1, int(fold_batch)))
    series = jax.ShapeDtypeStruct((T,), np.float32)
    bins = jax.ShapeDtypeStruct((K, T), np.int32)
    return int(_fold_parts_batch_jit.warm(series, bins, int(fold_nbins),
                                          int(fold_npart)))


register_warmer("fold", _warm_fold)
