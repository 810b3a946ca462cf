"""The DM-trial sweep engine — the framework's headline workload.

Executes a brute-force (or DDplan-driven) dedispersion sweep: for every DM
trial, form the channel-summed dedispersed time series and reduce it to
matched-filter boxcar detection statistics, streaming the time axis in
overlap-save chunks and sharding the DM axis across a device mesh.

Reference treatment: nonexistent — the reference generates the trial list
(utils/DDplan2b.py:253-268) and defers execution to PRESTO, one CPU core,
one trial at a time. This module is the TPU-native design the north star
names: vmapped per-channel shifts over trials, shard_map over the ICI mesh,
lax.top_k candidate reduction.

Algorithm: two-stage subband dedispersion, the same structure DDplan
prescribes with its numsub/dsubDM machinery (reference utils/DDplan2b.py:
132-150) and Spectra.subband implements per-group (formats/spectra.py:96-138):

  stage 1 (per trial-group): shift channels to a group ``subdm`` and sum into
     ``nsub`` subbands — amortizes the full-channel pass over a group of
     nearby trials;
  stage 2 (per trial): shift + sum the nsub subbands at the trial DM.

Cost per chunk: O(G*C*T + D*S*T) HBM traffic instead of O(D*C*T) for direct
per-trial shifts — the reuse factor that makes the sweep bandwidth-feasible.
All shifts are integer bins precomputed host-side in float64 (bit-compatible
with the NumPy twin in tests/test_sweep.py); on device they are static-length
lax.dynamic_slice starts, so everything jits with fixed shapes.

Boundary handling: chunks carry ``overlap`` extra samples (>= max total delay
+ max boxcar width), the overlap-save analogue of ring-attention halo
exchange; in the time-sharded multi-device path the halo comes from the
ICI neighbor via lax.ppermute instead of the host stream.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from functools import partial
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pypulsar_tpu.compile import (
    bucket_rows,
    note_bucket_pad,
    plane_jit,
    register_warmer,
)
from pypulsar_tpu.core import psrmath
from pypulsar_tpu.ops import transfer
from pypulsar_tpu.ops.pallas_kernels import boxcar_stats
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.plan import lengths
from pypulsar_tpu.tune import knobs

DEFAULT_WIDTHS = (1, 2, 4, 8, 16, 32)

ENGINES = ("gather", "fourier")


def resolve_engine(engine: str = "auto") -> str:
    """Pick the chunk-kernel formulation, once, where a public entry
    takes ``engine="auto"``; the chunk kernels below take the resolved
    name.

    'fourier' (ops/fourier_dedisperse.py) is what a TPU runs: the
    recorded v5e A/B (BENCHNOTES.md) measured the gather path at ~26 GB/s
    effective (3% of HBM roofline) while the Fourier phase-multiply path
    streams at bandwidth. 'gather' is the bit-parity reference
    formulation the tests compare against, and what runs off-TPU (CPU
    XLA handles the vmapped dynamic_slice fine).
    """
    if engine != "auto":
        if engine not in ENGINES:
            raise ValueError(f"unknown sweep engine {engine!r}; "
                             f"expected one of {ENGINES + ('auto',)}")
        return engine
    # resolve through the gang-lease registry (PL002): under a lease the
    # engine choice must reflect the leased chip, not whatever backend
    # device 0 happens to be. A backend that cannot be asked is an error,
    # never a quiet CPU choice.
    from pypulsar_tpu.parallel.mesh import lease_devices

    platform = lease_devices()[0].platform
    return "fourier" if platform == "tpu" else "gather"


def _check_resolved(engine: str) -> None:
    """The chunk kernels take a resolved engine: 'auto' is decided by
    :func:`resolve_engine` at the public entry, never inside a trace."""
    if engine not in ENGINES:
        raise ValueError(f"chunk kernels take a resolved engine, one of "
                         f"{ENGINES}; got {engine!r}")


def choose_group_size(
    dms,
    freqs,
    dt: float,
    nsub: int = 64,
    max_extra_smear_bins: float = 1.0,
    max_group: int = 128,
) -> int:
    """Largest power-of-two stage-1 group size whose extra subband
    smearing stays under ``max_extra_smear_bins`` samples.

    Stage 1 dedisperses each subband at the GROUP's mean DM; a trial at
    the group edge is off by ``(g/2) * dDM``, smearing the worst (lowest)
    subband by ``dm_smear(dDM_off, BW_sub, f_low)``. Larger groups
    amortize the expensive full-channel stage-1 pass over more trials —
    the measured v5e geometry grid (BENCHNOTES.md) has (nsub=64, g=64)
    25% faster than g=32 — and at dense trial spacing (the 4096-trial
    north-star grid has dDM ~ 0.12) the smearing cost of g=64-128 is a
    fraction of a sample. This chooser makes that tradeoff explicit:
    DDplan's own numsub/dsubDM machinery, applied to the engine geometry
    (reference utils/DDplan2b.py:132-150 is the same bound for its
    subband steps)."""
    dms = np.asarray(dms, dtype=np.float64)
    if len(dms) < 2:
        return 1
    ddm = float(np.max(np.abs(np.diff(dms))))
    freqs = np.asarray(freqs, dtype=np.float64)
    f_low = float(freqs.min())
    bw_sub = float(abs(freqs.max() - freqs.min())) / nsub
    g = 1
    while g * 2 <= max_group:  # honors non-power-of-two caps too
        off = g * ddm  # next candidate's worst-case offset = (2g/2)*ddm
        if psrmath.dm_smear(off, bw_sub, f_low) > max_extra_smear_bins * dt:
            break
        g *= 2
    return g


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """Host-side precomputed geometry of a sweep.

    stage1_bins[G, C]   int32  per-group per-channel shifts (to group subdm)
    stage2_bins[G, g, S] int32 per-trial per-subband shifts (trial dm)
    dms[G*g] float64 trial DMs (padded trials replicated from last real one)
    """

    dms: np.ndarray
    freqs: np.ndarray
    dt: float
    nsub: int
    group_size: int
    stage1_bins: np.ndarray
    stage2_bins: np.ndarray
    subdms: np.ndarray
    n_real_trials: int
    widths: Tuple[int, ...] = DEFAULT_WIDTHS

    @property
    def n_groups(self) -> int:
        return self.stage1_bins.shape[0]

    @property
    def n_trials(self) -> int:
        return self.n_groups * self.group_size

    @property
    def max_shift1(self) -> int:
        return int(self.stage1_bins.max(initial=0))

    @property
    def max_shift2(self) -> int:
        return int(self.stage2_bins.max(initial=0))

    @property
    def max_total_shift(self) -> int:
        return self.max_shift1 + self.max_shift2

    @property
    def min_overlap(self) -> int:
        return self.max_total_shift + max(self.widths)


def make_sweep_plan(
    dms: Sequence[float],
    freqs: np.ndarray,
    dt: float,
    nsub: int = 64,
    group_size: int = 32,
    widths: Tuple[int, ...] = DEFAULT_WIDTHS,
    pad_groups_to: Optional[int] = None,
) -> SweepPlan:
    """Precompute integer shift tables (float64 host math).

    Channels are assumed high-frequency-first (SIGPROC foff<0 order); the
    reference's get_spectra delivers them that way (formats/psrfits.py:175
    flips the band to guarantee it).
    """
    dms = np.asarray(dms, dtype=np.float64)
    freqs = np.asarray(freqs, dtype=np.float64)
    if group_size <= 0:  # auto: largest group within the smearing bound
        group_size = choose_group_size(dms, freqs, dt, nsub)
    C = len(freqs)
    if C > 1 and not np.all(np.diff(freqs) <= 0):
        raise ValueError(
            "make_sweep_plan needs monotonically descending (high-"
            "frequency-first) channels: flip/sort the data and frequency "
            "axes first (the staged block sources flip ascending tables "
            "automatically)")
    if C % nsub:
        raise ValueError(f"nsub={nsub} must divide nchan={C}")
    per = C // nsub
    n_real = len(dms)
    G = -(-n_real // group_size)
    if pad_groups_to is not None:
        if pad_groups_to < G:
            raise ValueError("pad_groups_to smaller than required groups")
        G = pad_groups_to
    padded = np.concatenate([dms, np.repeat(dms[-1], G * group_size - n_real)])

    sub_hif = freqs[np.arange(nsub) * per]  # top freq of each subband
    f_ref = freqs.max()

    stage1 = np.zeros((G, C), dtype=np.int32)
    stage2 = np.zeros((G, group_size, nsub), dtype=np.int32)
    subdms = np.zeros(G, dtype=np.float64)
    for gi in range(G):
        block = padded[gi * group_size : (gi + 1) * group_size]
        subdm = float(np.mean(block))
        subdms[gi] = subdm
        # stage 1: intra-subband shifts at subdm, relative to subband top freq
        d_chan = psrmath.delay_from_DM(subdm, freqs)
        d_ref = np.repeat(psrmath.delay_from_DM(subdm, sub_hif), per)
        stage1[gi] = np.round((d_chan - d_ref) / dt).astype(np.int32)
        # stage 2: per-trial subband shifts, relative to global top freq
        for ti, dm in enumerate(block):
            d_sub = psrmath.delay_from_DM(dm, sub_hif)
            d0 = psrmath.delay_from_DM(dm, f_ref)
            stage2[gi, ti] = np.round((d_sub - d0) / dt).astype(np.int32)

    return SweepPlan(
        dms=padded,
        freqs=freqs,
        dt=float(dt),
        nsub=nsub,
        group_size=group_size,
        stage1_bins=stage1,
        stage2_bins=stage2,
        subdms=subdms,
        n_real_trials=n_real,
        widths=tuple(widths),
    )


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------


DEFAULT_CHUNK_FFT_LEN = lengths.DEFAULT_CHUNK
# The registry default of the PYPULSAR_TPU_SWEEP_CHUNK knob (round 17)
# and the most plan/lengths.py plans for memory's sake (its comment has
# the v5e A/B behind 2^18). Anywhere a chunk length is not explicitly
# given, :func:`plan_chunk` resolves env > tuned cache > the length
# planned from the channel count and the device's memory, which at 1024
# channels on a 16 GB chip is this constant.

_CHUNK_KNOB = "PYPULSAR_TPU_SWEEP_CHUNK"


def chunk_fft_len(tuned: bool = True) -> int:
    """The ``PYPULSAR_TPU_SWEEP_CHUNK`` knob rounded up to a power of
    two (the FFT/doubling machinery in :func:`plan_chunk` and the
    checkpoint fingerprints both assume pow2), floored at 2^12 so a typo
    cannot degenerate the stream to sample-sized dispatches.

    ``tuned=False`` resolves env > default only, skipping the
    auto-tuning overlays: the single-pulse DETECTION sweep's chunk is
    part of its results (per-chunk statistics, one event per chunk —
    the documented streaming semantics ``--chunk`` fingerprints), so
    the tuner may move the chunk for the byte-invariant series/handoff
    paths but never for the detector. An env var or ``--chunk`` remains
    an explicit operator choice either way."""
    n = int(knobs.env_int(_CHUNK_KNOB, overlays=tuned))
    n = max(lengths.MIN_CHUNK, n)
    if n & (n - 1):
        n = 1 << n.bit_length()
    return n


def _operator_chunk(tuned: bool) -> Optional[int]:
    """:func:`chunk_fft_len` where the environment or a tuning overlay
    set it; None where the declared default stands, which is the
    planner's to bound by memory."""
    n = chunk_fft_len(tuned)
    if knobs.env_raw(_CHUNK_KNOB) is None \
            and n == knobs.knob(_CHUNK_KNOB).default:
        return None
    return n


def planned_lengths(nchan: int, nsub: int, max_delay: int, trials: int,
                    tuned: bool = True) -> lengths.Lengths:
    """``plan/lengths.plan_lengths`` for this thread's device, with the
    operator's or the tuner's chunk where one is set."""
    return lengths.plan_lengths(nchan, nsub, max_delay, trials,
                                lengths.device_memory(),
                                chunk=_operator_chunk(tuned))


def plan_chunk(plan: "SweepPlan", tuned: bool = True,
               ndm: int = 1) -> lengths.Lengths:
    """The streaming FFT chunk for ``plan`` on this thread's device
    (``plan/lengths.py``): the operator's or the tuner's length where one
    is set, else 2^18 bounded by the device's memory at the plan's
    channel count, grown by doubling until the dedispersion overlap fits
    in half of it. ``ndm`` devices share the trials of a dispatch.
    Raises :class:`lengths.LengthPlanError`, naming the plan's top DM,
    where no chunk both holds the overlap and fits."""
    try:
        return planned_lengths(
            len(plan.freqs), plan.nsub, plan.min_overlap,
            -(-plan.n_trials // max(1, int(ndm))), tuned)
    except lengths.LengthPlanError as e:
        raise lengths.LengthPlanError(
            f"{e} (top DM {float(np.max(plan.dms)):.2f} at "
            f"{plan.dt * 1e6:.2f} us over {float(np.min(plan.freqs)):.1f}"
            f"-{float(np.max(plan.freqs)):.1f} MHz)") from None


def planned_payload(plan: "SweepPlan", planned: lengths.Lengths) -> int:
    """The streaming payload of ``plan`` at a planned FFT chunk. At the
    default length, or an operator's, it is the chunk less the plan's own
    overlap: what every 1024-channel stream has run, and its bytes depend
    on it. A chunk cut for memory leaves room for the delay and the
    widest default boxcar whichever pass asks, so that the detection
    pass and the series pass of one observation (whose plans differ in
    their widths alone) stream the same blocks, and a configuration can
    state the one payload (as ``--chunk`` would)."""
    if planned.cut:
        return planned.chunk - plan.max_total_shift - max(
            max(plan.widths), max(DEFAULT_WIDTHS))
    return planned.chunk - plan.min_overlap


def default_chunk_payload(plan: "SweepPlan", tuned: bool = True,
                          ndm: int = 1) -> int:
    """Default streaming chunk payload: :func:`planned_payload` at
    :func:`plan_chunk`'s FFT length."""
    return planned_payload(plan, plan_chunk(plan, tuned, ndm))


def note_chunk_plan(span, plan: "SweepPlan", payload: int,
                    planned: Optional[lengths.Lengths] = None) -> None:
    """What was planned for one observation's stream, on the
    ``sweep.plan`` span (``span`` may be None) and once as the
    ``sweep.chunk_plan`` event, which also says which bound decided
    (``operator``: ``--chunk`` gave the payload)."""
    if not telemetry.is_active():
        return
    from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len

    rec = dict(nchan=len(plan.freqs), overlap=int(plan.min_overlap),
               payload=int(payload),
               chunk=fourier_chunk_len(int(payload) + plan.min_overlap))
    if planned is not None:
        rec.update(need_bytes=planned.chunk_need,
                   budget_bytes=-1 if planned.budget is None
                   else planned.budget)
    if span is not None:
        span.set(**rec)
    telemetry.event(
        "sweep.chunk_plan", nsub=int(plan.nsub),
        trials=int(plan.n_trials),
        bound="operator" if planned is None else planned.chunk_bound,
        **rec)


def _slice_rows(rows, starts, length):
    """rows[N, L] -> [N, length], row i starting at starts[i] (static length)."""
    return jax.vmap(lambda r, s: jax.lax.dynamic_slice(r, (s,), (length,)))(
        rows, starts.astype(jnp.int32)
    )


def _sweep_chunk_impl(
    data,
    stage1_bins,
    stage2_bins,
    nsub: int,
    out_len: int,
    slack2: int,
    widths: Tuple[int, ...],
    stat_len: int,
    engine: str = "gather",
):
    """Process one chunk for all trial groups.

    data[C, L] with L >= out_len + slack2 + max(stage1) ; out_len = chunk
    payload + max boxcar width so boxcars can start anywhere in the payload.
    stat_len <= out_len is the number of samples whose statistics (sum/sumsq)
    belong to this chunk (the payload), so streamed chunks don't double-count
    overlap samples.

    ``engine`` (resolved, see :func:`resolve_engine`): 'gather' (vmapped
    dynamic_slice, the reference) or 'fourier' (phase-multiply fast
    path, ops/fourier_dedisperse.py). They agree to f32 rounding
    (tests/test_sweep.py).

    Returns per-trial (sum[D], sumsq[D], maxbox[D, W], argbox[D, W]).
    """
    _check_resolved(engine)
    if engine == "fourier":
        from pypulsar_tpu.ops.fourier_dedisperse import (
            fourier_chunk_len,
            sweep_chunk_fourier_impl,
        )

        return sweep_chunk_fourier_impl(
            data, stage1_bins, stage2_bins, nsub, out_len, widths,
            stat_len, fourier_chunk_len(data.shape[1]))
    C, L = data.shape
    G, g, S = stage2_bins.shape
    per = C // nsub
    L1 = out_len + slack2

    def per_group(carry, xs):
        shift1, shift2 = xs
        with jax.named_scope("dedisp.stage1"):
            sliced = _slice_rows(data, shift1, L1)  # [C, L1]
            sub = sliced.reshape(nsub, per, L1).sum(axis=1)  # [S, L1]
        with jax.named_scope("dedisp.stage2"):
            ts = jax.vmap(
                lambda sh: _slice_rows(sub, sh, out_len).sum(axis=0))(
                shift2
            )  # [g, out_len]
        # fused detection stats: Pallas kernel on TPU, lax elsewhere
        # (windows start within the payload region)
        s, ss, mb_g, ab_g = boxcar_stats(ts, widths, stat_len)
        return carry, (s, ss, mb_g, ab_g)

    _, (s, ss, mb, ab) = jax.lax.scan(per_group, 0, (stage1_bins, stage2_bins))
    D = G * g
    return (
        s.reshape(D),
        ss.reshape(D),
        mb.reshape(D, len(widths)),
        ab.reshape(D, len(widths)),
    )


# the single-device chunk program (see _sweep_chunk_impl). Its name stays
# jit__sweep_chunk_jit: traces, PERF.md and the ledger's breakdown read it
sweep_chunk = plane_jit(
    _sweep_chunk_impl,
    static_argnames=("nsub", "out_len", "slack2", "widths", "stat_len",
                     "engine"),
    stage="sweep", name="_sweep_chunk_jit")


def _dedisperse_series_impl(data, stage1_bins, stage2_bins, nsub,
                            out_len: int, slack2: int, engine="gather"):
    """Two-stage subband dedispersed SERIES [D, out_len] for one chunk —
    :func:`_sweep_chunk_impl` with the fused detection swapped for the
    raw per-trial time series. The chunk kernel of the streamed .dat
    writer (staged.write_dats_streamed): PRESTO-prepsubband semantics
    (subband dedispersion with the sweep's own stage bins), so the
    written series is exactly what the sweep's detections saw. Shared by
    the single-device program (:func:`dedisperse_series_chunk`) and the
    mesh-sharded factory."""
    _check_resolved(engine)
    if engine == "fourier":
        from pypulsar_tpu.ops.fourier_dedisperse import (
            dedisperse_series_fourier_impl,
            fourier_chunk_len,
        )

        return dedisperse_series_fourier_impl(
            data, stage1_bins, stage2_bins, nsub, out_len,
            fourier_chunk_len(data.shape[1]))
    C, L = data.shape
    G, g, S = stage2_bins.shape
    per = C // nsub
    L1 = out_len + slack2

    def per_group(carry, xs):
        shift1, shift2 = xs
        with jax.named_scope("dedisp.stage1"):
            sliced = _slice_rows(data, shift1, L1)
            sub = sliced.reshape(nsub, per, L1).sum(axis=1)
        with jax.named_scope("dedisp.stage2"):
            ts = jax.vmap(
                lambda sh: _slice_rows(sub, sh, out_len).sum(axis=0))(
                shift2)
        return carry, ts

    _, ts = jax.lax.scan(per_group, 0, (stage1_bins, stage2_bins))
    return ts.reshape(G * g, out_len)


# named jit__dedisperse_series_jit for the same readers
dedisperse_series_chunk = plane_jit(
    _dedisperse_series_impl,
    static_argnames=("nsub", "out_len", "slack2", "engine"),
    stage="sweep", name="_dedisperse_series_jit")


def make_sharded_sweep_chunk(mesh: Mesh, nsub, out_len, slack2, widths,
                             stat_len, engine="gather"):
    """Chunk sweep with trial groups sharded over the mesh 'dm' axis.

    The chunk is replicated to every device; each device scans only its local
    trial groups (shard_map), so there is NO inter-device communication in the
    hot loop — candidates are reduced host-side after streaming. The group
    count must divide the 'dm' axis size (use make_sweep_plan(pad_groups_to=...)).
    """
    return _sharded_sweep_chunk(mesh, nsub, out_len, slack2, tuple(widths),
                                stat_len, engine)


@functools.lru_cache(maxsize=64)
def _sharded_sweep_chunk(mesh, nsub, out_len, slack2, widths, stat_len,
                         engine):
    """One plane wrapper per (mesh, chunk geometry): the wrapper owns the
    AOT executables of its mesh, so a second observation on the same
    gang finds them (``compile.cache_hit``) and a gang on other chips
    holds its own."""

    # a closure, not a partial: the plane binds the call against the
    # signature, and only the three arrays are arguments
    def impl(data, stage1_bins, stage2_bins):
        return _sweep_chunk_impl(data, stage1_bins, stage2_bins, nsub,
                                 out_len, slack2, widths, stat_len,
                                 engine=engine)

    fn = jax.shard_map(
        impl,
        mesh=mesh,
        in_specs=(P(), P("dm"), P("dm")),
        out_specs=P("dm"),
    )
    return plane_jit(fn, stage="sweep", name="sweep_sharded_chunk")


def make_sharded_series_chunk(mesh: Mesh, nsub, out_len, slack2,
                              engine="gather"):
    """:func:`dedisperse_series_chunk` with trial groups sharded over the
    mesh 'dm' axis — the chunk engine of the DM-sharded sweep->accel
    handoff (parallel.accelpipe). The chunk replicates to every device;
    each device dedisperses only its local trial groups and the [D, out]
    series concatenates in group order (out_specs P('dm')), so the rows a
    consumer sees are BIT-identical to the unsharded kernel's — per-group
    math is device-count independent. The group count must divide the
    'dm' axis size (make_sweep_plan(pad_groups_to=...))."""
    return _sharded_series_chunk(mesh, nsub, out_len, slack2, engine)


@functools.lru_cache(maxsize=64)
def _sharded_series_chunk(mesh, nsub, out_len, slack2, engine):
    """Memoised like :func:`_sharded_sweep_chunk`."""
    def impl(data, stage1_bins, stage2_bins):
        return _dedisperse_series_impl(data, stage1_bins, stage2_bins,
                                       nsub, out_len, slack2, engine)

    fn = jax.shard_map(
        impl,
        mesh=mesh,
        in_specs=(P(), P("dm"), P("dm")),
        out_specs=P("dm"),
    )
    return plane_jit(fn, stage="sweep", name="series_sharded_chunk")


def make_sharded_sweep_chunk_2d(
    mesh: Mesh, nsub, local_payload, overlap, slack2, widths, engine="gather"
):
    """Chunk sweep sharded over BOTH mesh axes: trial groups over 'dm' and the
    time axis over 'time' (the long-context axis, SURVEY.md §5).

    Each time shard holds [C, local_payload + overlap] after receiving an
    ``overlap``-sample halo from its right neighbor over ICI (lax.ppermute —
    the overlap-save seam exchange; the final shard pads with zeros, matching
    the host-streamed tail). Per-shard boxcar stats are then combined with
    psum (moments) and all_gather+argmax (peaks) along 'time'.

    Input: data[C, T] sharded as P(None, 'time'); stage tables sharded P('dm').
    T must equal local_payload * mesh.shape['time'].
    """
    W = max(widths)
    out_len = local_payload + W
    nt = mesh.shape["time"]

    def local_fn(data_local, s1_local, s2_local):
        # halo: leading `overlap` samples of the RIGHT neighbor (shard i+1 -> i)
        lead = data_local[:, :overlap]
        halo = jax.lax.ppermute(
            lead, "time", [(i, i - 1) for i in range(1, nt)]
        )
        data_ext = jnp.concatenate([data_local, halo], axis=1)
        s, ss, mb, ab = _sweep_chunk_impl(
            data_ext, s1_local, s2_local, nsub, out_len, slack2, widths,
            stat_len=local_payload, engine=engine,
        )
        # moments: payload regions partition the time axis exactly
        s = jax.lax.psum(s, "time")
        ss = jax.lax.psum(ss, "time")
        # peaks: shift to global sample indices, reduce by max over shards
        ti = jax.lax.axis_index("time")
        ab = ab + ti * local_payload
        mb_all = jax.lax.all_gather(mb, "time")  # [nt, Dl, W]
        ab_all = jax.lax.all_gather(ab, "time")
        k = mb_all.argmax(axis=0)
        mb = jnp.take_along_axis(mb_all, k[None], axis=0)[0]
        ab = jnp.take_along_axis(ab_all, k[None], axis=0)[0]
        return s, ss, mb, ab

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, "time"), P("dm"), P("dm")),
        out_specs=(P("dm"), P("dm"), P("dm"), P("dm")),
        check_vma=False,  # outputs are replicated over 'time' by construction
    )
    return plane_jit(fn, stage="sweep", name="sweep_sharded_chunk_2d",
                     aot=False)


@dataclasses.dataclass
class SweepResult:
    """Accumulated sweep output. ``snr[d, w]`` is the matched-filter SNR of
    the best boxcar of width widths[w] for trial dms[d]:
    (max_w_sum - w*mean) / (sqrt(w)*std) with mean/std over the full series
    (streaming mean/std normalization; the single-block path in
    ops.kernels.boxcar_snr uses the reference's median/std convention and is
    parity-tested against it)."""

    dms: np.ndarray
    widths: Tuple[int, ...]
    snr: np.ndarray  # [D, W]
    peak_sample: np.ndarray  # [D, W] global sample index of best box start
    mean: np.ndarray
    std: np.ndarray
    # with keep_chunk_peaks: per-chunk peak SNRs/samples [nchunks, D, W]
    chunk_snr: Optional[np.ndarray] = None
    chunk_sample: Optional[np.ndarray] = None

    def events(self, threshold: float):
        """Every per-chunk peak above ``threshold`` SNR, as (dm, width,
        snr, sample) records — one event per (chunk, trial, width) cell,
        so a trial can report many pulses across the observation (the
        single-best ``snr``/``peak_sample`` fields keep only the global
        max). Requires the sweep to have run with ``keep_chunk_peaks``;
        raises otherwise."""
        if self.chunk_snr is None:
            raise ValueError(
                "per-chunk peaks were not recorded: run the sweep with "
                "keep_chunk_peaks=True (cli: --all-events)")
        out = []
        nch, D, W = self.chunk_snr.shape
        for ci in range(nch):
            hits = np.argwhere(self.chunk_snr[ci] >= threshold)
            for di, wi in hits:
                out.append(dict(
                    dm=float(self.dms[di]),
                    width=int(self.widths[wi]),
                    snr=float(self.chunk_snr[ci, di, wi]),
                    sample=int(self.chunk_sample[ci, di, wi]),
                ))
        out.sort(key=lambda e: (e["dm"], e["sample"]))
        return out

    def best(self, k: int = 10):
        """Top-k (dm, width, snr, sample) candidates over all trials."""
        flat = self.snr.reshape(-1)
        order = np.argsort(flat)[::-1][:k]
        d, w = np.unravel_index(order, self.snr.shape)
        return [
            dict(
                dm=float(self.dms[di]),
                width=int(self.widths[wi]),
                snr=float(self.snr[di, wi]),
                sample=int(self.peak_sample[di, wi]),
            )
            for di, wi in zip(d, w)
        ]


class AccumParts(NamedTuple):
    """Raw sweep accumulator state (``sweep_stream(finalize=False)``):
    everything :func:`finalize_sweep` needs, in mergeable form. ``mb``
    carries f32 window-sum maxima and ``ab`` their global sample
    positions; ``s``/``ss`` are host-f64 moment sums over ``n`` payload
    samples; ``baseline_sum`` restores original units. ``chunk_mb``/
    ``chunk_ab`` (with ``keep_chunk_peaks``) are the per-chunk peak
    records in stream order — window-local slices of the sequential
    sweep's chunk sequence, so cross-window merging is concatenation."""

    n: int
    s: np.ndarray
    ss: np.ndarray
    mb: np.ndarray
    ab: np.ndarray
    baseline_sum: float
    chunk_mb: tuple = ()
    chunk_ab: tuple = ()


def merge_accum_parts(parts: Sequence["AccumParts"]) -> "AccumParts":
    """Merge per-window accumulators IN ORDER (earliest window first).

    Addition order of the f64 moment sums is then deterministic, and max
    tie-breaking keeps the earliest window's peak — the same choice the
    sequential chunk loop makes (``_Accum.update`` keeps the incumbent on
    ties), so a time-sharded sweep merges to the sequential result up to
    f64 re-association of the moment sums (mb/ab exactly equal). Chunk
    peak records concatenate in window order (= the sequential chunk
    order)."""
    if not parts:
        raise ValueError("no accumulator parts to merge")
    n = parts[0].n
    s = np.array(parts[0].s, dtype=np.float64)
    ss = np.array(parts[0].ss, dtype=np.float64)
    mb = np.array(parts[0].mb)
    ab = np.array(parts[0].ab, dtype=np.int64)
    chunk_mb = tuple(parts[0].chunk_mb)
    chunk_ab = tuple(parts[0].chunk_ab)
    for p in parts[1:]:
        n += p.n
        s += p.s
        ss += p.ss
        better = p.mb > mb
        mb = np.where(better, p.mb, mb)
        ab = np.where(better, p.ab, ab)
        chunk_mb += tuple(p.chunk_mb)
        chunk_ab += tuple(p.chunk_ab)
    return AccumParts(n, s, ss, mb, ab, parts[0].baseline_sum,
                      chunk_mb, chunk_ab)


def _repad_rows(a: np.ndarray, pad: int) -> np.ndarray:
    """Extend the trial axis by ``pad`` copies of the last real row —
    exactly what padded trials (replicated last DM) would have
    accumulated, so a checkpoint saved at one padded width resumes at
    another bit-for-bit."""
    a = np.asarray(a)
    if pad <= 0:
        return a
    return np.concatenate([a, np.repeat(a[-1:], pad, axis=0)], axis=0)


class _Accum:
    def __init__(self, D, W, keep_chunk_peaks: bool = False,
                 n_real: Optional[int] = None):
        self.n = 0
        self.s = np.zeros(D)
        self.ss = np.zeros(D)
        self.mb = np.full((D, W), -np.inf)
        self.ab = np.zeros((D, W), dtype=np.int64)
        # optional per-chunk peak record: one (maxbox, argbox) pair per
        # (chunk, trial, width), stored f32 and sliced to the real trials
        # — ~n_chunks * D * W * 12 bytes (e.g. ~90 MB for a 2000-trial,
        # 2700-chunk survey sweep)
        self.keep_chunk_peaks = keep_chunk_peaks
        self.n_real = D if n_real is None else n_real
        self.chunk_mb: list = []
        self.chunk_ab: list = []

    def update(self, start, stat_len, s, ss, mb, ab):
        self.n += stat_len
        self.s += np.asarray(s, dtype=np.float64)
        self.ss += np.asarray(ss, dtype=np.float64)
        mb = np.asarray(mb)
        ab = np.asarray(ab, dtype=np.int64) + start
        if self.keep_chunk_peaks:
            self.chunk_mb.append(mb[: self.n_real].astype(np.float32))
            self.chunk_ab.append(ab[: self.n_real].copy())
        better = mb > self.mb
        self.mb = np.where(better, mb, self.mb)
        self.ab = np.where(better, ab, self.ab)


class SweepCheckpoint:
    """In-sweep checkpointing for long streams (SURVEY.md §5: the reference
    pipeline is file-granular; a multi-hour 4096-trial sweep needs a
    restart point finer than whole files).

    Persists the host-side accumulator (`_Accum`), the resume cursor (first
    unprocessed payload sample) and the per-channel baseline every ``every``
    drained chunks, written atomically (tmp + rename). Chunk accumulation
    happens in stream order on resume exactly as it would uninterrupted, so
    a killed-and-resumed sweep reproduces the uninterrupted result
    bit-for-bit (tested in tests/test_sweep.py).

    A fingerprint of the plan geometry guards against resuming with
    different parameters: mismatch starts from scratch.
    """

    def __init__(self, path: str, every: int = 16, cleanup: bool = True):
        self.path = path
        self.every = max(1, int(every))
        self.cleanup = cleanup
        self._drained = 0

    @staticmethod
    def _fingerprint(plan: SweepPlan, chunk_payload: int,
                     context: str = "") -> str:
        """``context`` carries everything outside the plan that affects the
        numerics — the resolved engine and the mesh layout — so a
        checkpoint can only resume under the exact configuration that
        wrote it (the bit-identity contract; engines agree only to
        ~1e-4). Only the *real* trials are hashed: padded trials
        replicate the last real DM, so the padded group count (mesh
        divisibility, compile-plane bucket ladder) is an execution
        detail a resume may legally change (round 22)."""
        import hashlib

        h = hashlib.sha256()
        nr = plan.n_real_trials
        for part in (plan.dms[:nr].tobytes(), plan.freqs.tobytes(),
                     np.float64(plan.dt).tobytes(),
                     np.int64([plan.nsub, plan.group_size,
                               plan.n_real_trials, chunk_payload]).tobytes(),
                     np.int64(plan.widths).tobytes(),
                     context.encode()):
            h.update(part)
        return h.hexdigest()

    def load(self, plan: SweepPlan, chunk_payload: int, context: str = "",
             keep_chunk_peaks: bool = False):
        """(acc, cursor, baseline) from a matching checkpoint, else None.
        ``keep_chunk_peaks`` must match the value the checkpoint was
        written with (it is part of the fingerprinted state: a resume
        without the per-chunk record would silently drop events)."""
        if not os.path.exists(self.path):
            return None
        try:
            with np.load(self.path, allow_pickle=False) as z:
                if str(z["fingerprint"]) != self._fingerprint(
                        plan, chunk_payload, context):
                    return None
                has_peaks = "chunk_mb" in z
                if has_peaks != keep_chunk_peaks:
                    return None
                acc = _Accum(plan.n_trials, len(plan.widths),
                             keep_chunk_peaks=keep_chunk_peaks,
                             n_real=plan.n_real_trials)
                acc.n = int(z["n"])
                # checkpoints persist the real rows only; padded trials
                # replicate the last real DM, so their accumulator state
                # is bit-identical to the last real row — rebuild it by
                # replication at whatever padded width THIS run uses
                # (the bucket ladder may have moved between runs)
                pad = plan.n_trials - plan.n_real_trials
                acc.s = _repad_rows(z["s"], pad)
                acc.ss = _repad_rows(z["ss"], pad)
                acc.mb = _repad_rows(z["mb"], pad)
                acc.ab = _repad_rows(z["ab"], pad)
                if keep_chunk_peaks:
                    acc.chunk_mb = list(z["chunk_mb"])
                    acc.chunk_ab = list(z["chunk_ab"])
                return acc, int(z["cursor"]), z["baseline"]
        except Exception:  # noqa: BLE001 - a corrupt checkpoint restarts
            return None

    def save(self, plan: SweepPlan, chunk_payload: int, acc: "_Accum",
             cursor: int, baseline, context: str = "") -> None:
        tmp = self.path + ".tmp.npz"  # .npz suffix: savez must not append
        extra = {}
        if acc.keep_chunk_peaks:
            # every entry is [n_real, W]; the key must exist even before
            # the first drain so load() can tell peak checkpoints apart
            W = acc.mb.shape[1]
            extra["chunk_mb"] = (np.stack(acc.chunk_mb) if acc.chunk_mb
                                 else np.zeros((0, acc.n_real, W),
                                               np.float32))
            extra["chunk_ab"] = (np.stack(acc.chunk_ab) if acc.chunk_ab
                                 else np.zeros((0, acc.n_real, W),
                                               np.int64))
        nr = plan.n_real_trials  # real rows only: see load()
        np.savez(tmp,
                 fingerprint=self._fingerprint(plan, chunk_payload, context),
                 n=acc.n, s=acc.s[:nr], ss=acc.ss[:nr], mb=acc.mb[:nr],
                 ab=acc.ab[:nr],
                 cursor=cursor,
                 baseline=np.asarray(baseline, dtype=np.float32),
                 **extra)
        os.replace(tmp, self.path)

    def on_drained(self, plan, chunk_payload, acc, cursor, baseline,
                   context: str = "", n: int = 1) -> None:
        """Account ``n`` newly drained chunks; save when the count crosses
        an ``every`` boundary. Burst draining accounts a whole batch in
        one call with the batch-end (acc, cursor) — the only state pair
        that is consistent (acc already holds every drained chunk, so a
        mid-batch cursor would double-accumulate on resume)."""
        fire = (self._drained + n) // self.every > self._drained // self.every
        self._drained += n
        if fire:
            telemetry.counter("sweep.checkpoint_saves")
            with telemetry.span("checkpoint_save"):
                self.save(plan, chunk_payload, acc, cursor, baseline,
                          context)

    def finish(self) -> None:
        if self.cleanup and os.path.exists(self.path):
            os.remove(self.path)


def sweep_stream(
    plan: SweepPlan,
    blocks,
    chunk_payload: int,
    mesh: Optional[Mesh] = None,
    chan_major: bool = False,
    baseline=None,
    engine: str = "auto",
    max_pending: Optional[int] = None,
    checkpoint: Optional[SweepCheckpoint] = None,
    keep_chunk_peaks: bool = False,
    block_factory=None,
    checkpoint_context: str = "",
    finalize: bool = True,
) -> SweepResult:
    """Run the sweep over a stream of (startsamp, block) chunks.
    ``checkpoint_context`` is appended to the checkpoint fingerprint
    context for result-affecting state the plan cannot see (e.g. the
    rfifind mask applied by the block source).

    Blocks are [time, chan] host arrays (e.g. FilterbankFile.iter_blocks with
    overlap >= plan.min_overlap) or, with ``chan_major=True``, [chan, time]
    arrays that may already live on device (device-resident datasets slice
    with no host round-trip).

    When ``mesh`` is given, trial groups are sharded over its 'dm' axis via
    shard_map — zero cross-device communication until the final (host-side)
    top-k, the layout the north star prescribes.

    SNR accumulation-order contract (the "bit-exact SNR" policy, BASELINE.md):

    1. A single per-channel baseline — ``baseline`` if given (sweep_spectra
       passes the whole-series per-channel mean so results are independent
       of chunking), else the f32 per-channel mean of the first streamed
       block — is subtracted from every block before dedispersion.
       The SNR is exactly invariant under per-channel constant shifts (every
       window sum of trial d loses ``w * B`` and the series mean loses ``B``
       where ``B = sum_c baseline_c``), so this changes no result in exact
       arithmetic; numerically it removes the DC term so all f32 rounding is
       relative to the *fluctuation* scale, not the offset (8-bit PSRFITS
       data has offsets ~100x sigma, which otherwise costs ~3 decimal digits
       of SNR through catastrophic cancellation in ``maxbox - w*mean``).
    2. On device (f32): stage-1 channel-group sums and stage-2 subband sums
       in XLA reduction order; per-chunk payload sum/sumsq; per-width window
       sums (cumsum-difference in the lax path, dyadic doubling in the
       Pallas kernel) and their running max.
    3. On host (f64): cross-chunk accumulation of the moments, the
       cross-chunk max of the f32 window sums, and the final SNR formula
       ``(maxbox - w*mean) / (sqrt(w)*std)``.

    Guaranteed (and tested, tests/test_sweep.py) bound vs the float64 NumPy
    twin: |dSNR| <= 1e-4 absolute with relative error at f32-ulp scale
    (measured ~1e-6), independent of per-channel DC offsets. End-of-data is
    zero-padded *after* baseline subtraction, i.e. padded samples sit at the
    channel baseline level in original units.
    """
    engine = resolve_engine(engine)
    W = max(plan.widths)
    out_len = chunk_payload + W
    slack2 = plan.max_shift2
    D = plan.n_trials
    acc = _Accum(D, len(plan.widths), keep_chunk_peaks=keep_chunk_peaks,
                 n_real=plan.n_real_trials)
    cursor = 0  # first payload sample not yet accumulated
    ckpt_context = "engine=%s/meshdm=%s%s" % (
        engine, 0 if mesh is None else mesh.shape.get("dm", 0),
        checkpoint_context)
    if checkpoint is not None:
        state = checkpoint.load(plan, chunk_payload, ckpt_context,
                                keep_chunk_peaks=keep_chunk_peaks)
        if state is not None:
            acc, cursor, ckpt_baseline = state
            if baseline is None:
                baseline = ckpt_baseline  # bit-identical resume needs it
            if cursor > 0 and block_factory is not None:
                # seek-resume (round 5): without this, a resumed sweep
                # re-produces (reads AND ships) every pre-cursor block
                # only for the `start < cursor` guard below to drop it —
                # a resume at 65% of the 28.8 GB north star replayed the
                # whole wire. The factory re-roots the stream at the
                # cursor; the guard stays as the correctness backstop.
                blocks = block_factory(cursor)

    s1 = jnp.asarray(plan.stage1_bins)
    s2 = jnp.asarray(plan.stage2_bins)
    if mesh is not None:
        if plan.n_groups % mesh.shape["dm"]:
            raise ValueError(
                f"group count {plan.n_groups} must divide mesh 'dm' axis "
                f"{mesh.shape['dm']}; use make_sweep_plan(pad_groups_to=...)"
            )
        spec = NamedSharding(mesh, P("dm"))
        s1 = jax.device_put(s1, spec)
        s2 = jax.device_put(s2, spec)

    sharded_fns = {}  # stat_len -> compiled sharded chunk fn

    def run_chunk(data, stat_len):
        """Dispatch one chunk over the trial groups; returns a LIST of
        output 4-tuples in group order (normally one entry covering every
        group). A device RESOURCE_EXHAUSTED halves the group axis with
        bounded backoff and re-dispatches the halves
        (resilience.retry.halving_dispatch) — per-group scans share no
        state, so host-side concatenation of the halves is bit-identical
        to the whole dispatch. OOM only surfaces here at dispatch time;
        an async-surfaced OOM at the drain pull stays fatal."""
        from pypulsar_tpu.resilience import faultinject
        from pypulsar_tpu.resilience.retry import halving_dispatch

        ndm = 1 if mesh is None else mesh.shape["dm"]
        n_groups = plan.n_groups

        def dispatch(lo, hi):
            faultinject.trip("sweep.chunk_dispatch")
            whole = (lo, hi) == (0, n_groups)
            s1_sl, s2_sl = (s1, s2) if whole else (s1[lo:hi], s2[lo:hi])
            if mesh is None:
                return sweep_chunk(
                    data, s1_sl, s2_sl, plan.nsub, out_len, slack2,
                    plan.widths, stat_len, engine=engine
                )
            if not whole:  # re-lay the sliced tables on the mesh
                spec_sl = NamedSharding(mesh, P("dm"))
                s1_sl = jax.device_put(s1_sl, spec_sl)
                s2_sl = jax.device_put(s2_sl, spec_sl)
            if stat_len not in sharded_fns:
                sharded_fns[stat_len] = make_sharded_sweep_chunk(
                    mesh, plan.nsub, out_len, slack2, plan.widths,
                    stat_len, engine=engine
                )
            return sharded_fns[stat_len](data, s1_sl, s2_sl)

        return [outs for _, _, outs in halving_dispatch(
            dispatch, n_groups, min_size=ndm, what="sweep.chunk")]

    # Dispatch a few chunks ahead of the host-side accumulate so transfers
    # overlap compute, but bound the depth so queued input buffers (one chunk
    # of HBM each) can be freed. Callers with an HBM budget (bench.py) pass
    # ``max_pending`` explicitly; each pending chunk holds one input buffer.
    MAX_PENDING = 4 if max_pending is None else max(1, int(max_pending))
    DRAIN_BATCH = min(4, MAX_PENDING)
    pending = []  # (start, stat_len, [device output 4-tuples, group order])

    def drain(limit):
        nonlocal cursor
        if len(pending) <= limit:
            return
        # pull EVERY due chunk's outputs in ONE device_get, then
        # accumulate host-side in stream order (bit-identical to
        # per-chunk pulls). Each pull is a synchronous device->host
        # round trip that also waits behind whatever transfer is in
        # flight, so batching divides that per-chunk toll by the batch
        # size. Outputs are KBs per chunk; the batch adds no meaningful
        # HBM.
        due = []
        while len(pending) > limit:
            due.append(pending.pop(0))
        with telemetry.span("device_wait+accumulate"):
            flat = transfer.pull_host(
                *(arr for _, _, parts in due for outs in parts
                  for arr in outs))
            k = 0
            for start, stat_len, parts in due:
                got = flat[k:k + 4 * len(parts)]
                k += 4 * len(parts)
                if len(parts) == 1:
                    s, ss, mb, ab = got
                else:
                    # OOM-halved chunk: concatenate the group-axis
                    # slices back to the full trial axis (group order
                    # was preserved, so this is the whole dispatch)
                    s, ss, mb, ab = (
                        np.concatenate(got[j::4]) for j in range(4))
                acc.update(start, stat_len, s, ss, mb, ab)
                cursor = start + stat_len
        # outside the span: checkpoint_save has its own aggregated span
        # and nested aggregated spans both record wall time, so saving
        # inside would double-count in the overlap accounting
        if checkpoint is not None:
            checkpoint.on_drained(plan, chunk_payload, acc, cursor,
                                  baseline, ckpt_context, n=len(due))

    need = out_len + slack2 + plan.max_shift1
    chunk_len = _transform_len(need, engine)
    transformed = 0  # samples the chunk programs took in, overlap and pad too

    def process(start, data, L):
        nonlocal transformed
        if L < need:  # end-of-data: pad with zeros (reference pads padval=0)
            data = jnp.pad(data, ((0, 0), (0, need - L)))
        stat_len = min(chunk_payload, L)
        with telemetry.span("dispatch_sweep_chunk"):
            pending.append((start, stat_len, run_chunk(data, stat_len)))
        transformed += chunk_len
        if telemetry.is_active():
            # one record per streamed chunk: position, payload and the
            # dispatch-pipeline depth at this moment (how far device work
            # ran ahead of the host accumulate)
            telemetry.counter("sweep.chunks")
            telemetry.gauge("sweep.pending_depth", len(pending))
            telemetry.event("sweep.chunk", start=int(start),
                            stat_len=int(stat_len), pending=len(pending))

    # A short block is only legal at end-of-data: hold one block back so we
    # can tell whether the stream continues past its end. A block that is
    # short while later data exists would silently zero-pad real samples and
    # depress every seam SNR — raise instead.
    prev = None
    if baseline is not None:
        baseline = jnp.asarray(baseline, dtype=jnp.float32).reshape(-1, 1)
    # explicit iteration so the time spent PRODUCING each block (disk read
    # wait + host->device ship in the source generator) is attributed to
    # its own span (block_source) — the streamed-bench overlap accounting
    # needs transfer separated from device wait (BENCHNOTES.md round 4)
    _block_iter = iter(blocks)
    while True:
        with telemetry.span("block_source"):
            nxt = next(_block_iter, None)
        if nxt is None:
            break
        start, block = nxt
        if start < cursor:  # chunk already accumulated (checkpoint resume)
            continue
        with telemetry.span("host_to_device"):
            if not chan_major:
                block = np.ascontiguousarray(block.T)
            data = transfer.ship(block, jnp.float32)
        if baseline is None:
            # per-channel baseline from the first block (see the SNR
            # accumulation-order contract in the docstring)
            baseline = jnp.mean(data, axis=1, keepdims=True)
        data = data - baseline
        L = data.shape[1]
        if prev is not None:
            pstart, pdata, pL = prev
            if pL < need and pstart + pL < start + L:
                raise ValueError(
                    f"interior block at sample {pstart} has {pL} samples but "
                    f"data continues to sample {start + L}; the sweep needs "
                    f"{need} per block (payload {chunk_payload} + overlap >= "
                    f"plan.min_overlap = {plan.min_overlap}); stream blocks "
                    f"with block_size={chunk_payload} and overlap >= "
                    f"plan.min_overlap"
                )
            process(pstart, pdata, pL)
            # burst drain: let MAX_PENDING chunks queue, then pull them
            # all in one roundtrip (see drain) — a per-block drain would
            # pay the trapped-pull toll once per chunk
            if len(pending) > MAX_PENDING:
                drain(max(MAX_PENDING - DRAIN_BATCH, 0))
        prev = (start, data, L)
    if prev is not None:
        process(*prev)
    drain(0)
    if checkpoint is not None:
        checkpoint.finish()
    if telemetry.is_active():
        telemetry.counter("sweep.trials_completed", plan.n_real_trials)
        telemetry.counter("sweep.payload_samples", int(acc.n))
        telemetry.counter("sweep.chunk_samples", int(transformed))
        telemetry.device_snapshot(tag="sweep_stream_end")

    B = float(np.asarray(baseline, dtype=np.float64).sum()) if baseline is not None else 0.0
    if not finalize:
        # raw accumulator parts, for callers that merge across hosts
        # before the (single) finalize — parallel.distributed.
        # time_sharded_sweep merges windows in time order so the f64
        # accumulation grouping is deterministic
        return AccumParts(acc.n, acc.s, acc.ss, acc.mb, acc.ab, B,
                          tuple(acc.chunk_mb), tuple(acc.chunk_ab))
    with telemetry.span("sweep.finalize", rows=int(plan.n_real_trials)):
        return finalize_sweep(plan, acc.n, acc.s, acc.ss, acc.mb, acc.ab, B,
                              chunk_mb=acc.chunk_mb, chunk_ab=acc.chunk_ab)


def _transform_len(need: int, engine: str) -> int:
    """Samples a chunk program takes in for ``need`` samples of block:
    the Fourier engine's power-of-two transform length, the block itself
    for the gather engine."""
    if engine != "fourier":
        return int(need)
    from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len

    return fourier_chunk_len(int(need))


def finalize_sweep(plan: SweepPlan, n: int, s, ss, mb, ab,
                   baseline_sum: float = 0.0,
                   chunk_mb=None, chunk_ab=None) -> SweepResult:
    """Host-side (float64) SNR formula over accumulated moments + window
    maxima — step 3 of the accumulation-order contract. ``baseline_sum``
    restores the reported mean to original (pre-baseline-subtraction)
    units; snr and std are invariant under the per-channel shift.
    ``chunk_mb``/``chunk_ab`` (lists of per-chunk [D, W] peaks) populate
    the multi-event fields using the same whole-series moments."""
    s = np.asarray(s, dtype=np.float64)
    ss = np.asarray(ss, dtype=np.float64)
    mb = np.asarray(mb, dtype=np.float64)
    ab = np.asarray(ab, dtype=np.int64)
    mean = s / max(n, 1)
    var = np.maximum(ss / max(n, 1) - mean * mean, 0.0)
    std = np.sqrt(var)
    ws = np.array(plan.widths, dtype=np.float64)
    denom = np.sqrt(ws)[None, :] * np.where(std > 0, std, 1.0)[:, None]

    def to_snr(maxbox):
        return (maxbox - ws[None, :] * mean[:, None]) / denom

    snr = to_snr(mb)
    nr = plan.n_real_trials
    chunk_snr = chunk_sample = None
    if chunk_mb:
        # entries are already [:nr] — slice the moments to match (trials
        # can be padded to a group multiple, so nr < D is the norm)
        mean_r = mean[:nr]
        denom_r = denom[:nr]
        chunk_snr = np.stack([
            ((np.asarray(m, dtype=np.float64)[:nr]
              - ws[None, :] * mean_r[:, None]) / denom_r)
            .astype(np.float32)
            for m in chunk_mb])
        chunk_sample = np.stack([np.asarray(a, dtype=np.int64)[:nr]
                                 for a in chunk_ab])
    return SweepResult(
        dms=plan.dms[:nr],
        widths=plan.widths,
        snr=snr[:nr],
        peak_sample=ab[:nr],
        mean=mean[:nr] + baseline_sum,
        std=std[:nr],
        chunk_snr=chunk_snr,
        chunk_sample=chunk_sample,
    )


def padded_group_count(n_groups: int, ndm: int = 1) -> int:
    """Canonical padded trial-group count (round 22): the real group
    count rounded so groups divide the mesh 'dm' axis (``ndm``) and,
    when ``PYPULSAR_TPU_COMPILE_BUCKETS`` is on, up the compile plane's
    bucket ladder. Padded groups replicate the last real trial — the
    real rows are bit-exact regardless of padding — so bucketing trades
    a few redundant trials for executable reuse across nearby DM
    counts. The bucket choice never reaches a checkpoint/journal
    fingerprint (those hash real trials only), so resumes cross
    bucket-ladder changes byte-identically."""
    G = int(n_groups)
    ndm = max(1, int(ndm))
    base = -(-G // ndm) * ndm  # mesh-divisibility floor (pre-round-22)
    padded = bucket_rows(G, multiple=ndm)
    if padded > base:
        note_bucket_pad(base, padded)
    return padded


def _mesh_pad_groups(n_dms: int, group_size: int, mesh) -> Optional[int]:
    """Group padding so trial groups divide the mesh 'dm' axis and land
    on the compile plane's bucket ladder (padded_group_count)."""
    G = -(-n_dms // group_size)
    ndm = 1 if mesh is None else mesh.shape["dm"]
    padded = padded_group_count(G, ndm)
    if mesh is None and padded == G:
        return None  # nothing pads: keep the plan's natural shape
    return padded


def _series_baseline(data):
    """Whole-series per-channel baseline per the SNR contract: host arrays
    get a float64 host mean (cast to f32), device arrays a device mean —
    identical across the streamed and resident paths."""
    if isinstance(data, np.ndarray):
        return np.mean(data, axis=1, keepdims=True,
                       dtype=np.float64).astype(np.float32)
    return jnp.mean(data.astype(jnp.float32), axis=1, keepdims=True)


def sweep_spectra(spectra, dms, nsub=64, group_size=32, widths=DEFAULT_WIDTHS,
                  chunk_payload=None, mesh=None, pad_groups_to=None,
                  engine="auto", max_pending=None) -> SweepResult:
    """Convenience: sweep an in-memory (possibly device-resident) Spectra
    over ``dms``; chunks are device-side slices, no host round-trips."""
    freqs = np.asarray(spectra.freqs, dtype=np.float64)
    if group_size <= 0:
        group_size = choose_group_size(dms, freqs, spectra.dt, nsub)
    if pad_groups_to is None:
        pad_groups_to = _mesh_pad_groups(len(dms), group_size, mesh)
    plan = make_sweep_plan(dms, freqs, spectra.dt, nsub=nsub, group_size=group_size,
                           widths=widths, pad_groups_to=pad_groups_to)
    T = spectra.numspectra
    if chunk_payload is None:
        chunk_payload = T
    data = spectra.data

    def blocks():
        ov = plan.min_overlap
        pos = 0
        while pos < T:
            n = min(chunk_payload + ov, T - pos)
            yield pos, data[:, pos : pos + n]
            pos += chunk_payload

    # whole-series per-channel baseline: makes the result (incl. the padded
    # end-of-data windows) independent of chunk_payload — see the contract.
    # Host arrays stay on host for this (a device round-trip of the full
    # series would defeat chunked streaming's memory bound).
    baseline = _series_baseline(data)
    return sweep_stream(plan, blocks(), chunk_payload, mesh=mesh, chan_major=True,
                        baseline=baseline, engine=engine, max_pending=max_pending)


def sweep_resident(spectra, dms, nsub=64, group_size=32, widths=DEFAULT_WIDTHS,
                   chunk_payload=None, engine="auto",
                   pad_groups_to=None, mesh=None) -> SweepResult:
    """Whole sweep of a device-resident Spectra as ONE compiled program.

    ``sweep_spectra`` dispatches per chunk and pulls per-chunk statistics
    to the host accumulator — the right structure for streamed files, but
    every dispatch and pull pays host<->device latency. Here the chunk loop
    is a ``lax.scan`` over device-side slices of the resident dataset: per-chunk
    statistics stack on device and ship in a single transfer, and the host
    combines them in stream order — the SAME f64 cross-chunk accumulation
    the streamed path performs, so results are bit-identical to
    ``sweep_spectra`` with the same chunking (tested).

    The time axis is truncated to a whole number of chunks (bench data is
    sized accordingly; file pipelines should use the streamed path, which
    handles ragged tails). With ``mesh``, trial groups shard over its 'dm'
    axis inside the same single program.
    """
    engine = resolve_engine(engine)
    freqs = np.asarray(spectra.freqs, dtype=np.float64)
    if group_size <= 0:
        group_size = choose_group_size(dms, freqs, spectra.dt, nsub)
    if pad_groups_to is None:
        pad_groups_to = _mesh_pad_groups(len(dms), group_size, mesh)
    plan = make_sweep_plan(dms, freqs, spectra.dt, nsub=nsub,
                           group_size=group_size, widths=tuple(widths),
                           pad_groups_to=pad_groups_to)
    T = spectra.numspectra
    payload = T if chunk_payload is None else min(chunk_payload, T)
    n_chunks = max(T // payload, 1)
    T_used = n_chunks * payload
    W = max(plan.widths)
    out_len = payload + W
    slack2 = plan.max_shift2
    need = out_len + slack2 + plan.max_shift1

    data = jnp.asarray(spectra.data, dtype=jnp.float32)[:, :T_used]
    s1 = jnp.asarray(plan.stage1_bins)
    s2 = jnp.asarray(plan.stage2_bins)
    if mesh is not None:
        if plan.n_groups % mesh.shape["dm"]:
            raise ValueError("group count must divide the mesh 'dm' axis")
        spec_sh = NamedSharding(mesh, P("dm"))
        s1 = jax.device_put(s1, spec_sh)
        s2 = jax.device_put(s2, spec_sh)

    run = _make_resident_runner(plan.nsub, out_len, slack2, plan.widths,
                                payload, need, engine, mesh)
    # baseline parity with sweep_spectra: host f64 mean for host arrays
    # (the docstring's bit-identity contract includes the baseline)
    baseline = jnp.asarray(
        _series_baseline(np.asarray(spectra.data)[:, :T_used]
                         if isinstance(spectra.data, np.ndarray)
                         else data))
    with telemetry.span("sweep_resident_run", n_chunks=n_chunks,
                        payload=int(payload)):
        s, ss, mb, ab = transfer.pull_host(
            *run(data, s1, s2, baseline, n_chunks))
    if telemetry.is_active():
        telemetry.counter("sweep.chunks", n_chunks)
        telemetry.counter("sweep.trials_completed", plan.n_real_trials)
        telemetry.counter("sweep.payload_samples", int(n_chunks * payload))
        telemetry.counter("sweep.chunk_samples",
                          int(n_chunks * _transform_len(need, engine)))
        telemetry.device_snapshot(tag="sweep_resident_end")
    s = np.asarray(s, dtype=np.float64)
    ss = np.asarray(ss, dtype=np.float64)
    mb = np.asarray(mb)
    ab = np.asarray(ab, dtype=np.int64)
    acc = _Accum(plan.n_trials, len(plan.widths))
    for ci in range(n_chunks):
        acc.update(ci * payload, payload, s[ci], ss[ci], mb[ci], ab[ci])
    B = float(np.asarray(baseline, dtype=np.float64).sum())
    return finalize_sweep(plan, acc.n, acc.s, acc.ss, acc.mb, acc.ab, B)


@functools.lru_cache(maxsize=32)
def _make_resident_runner(nsub, out_len, slack2, widths, payload, need,
                          engine, mesh):
    """Compiled whole-sweep scan program, cached across calls (a fresh
    jit closure per sweep would recompile every invocation)."""
    impl = partial(_sweep_chunk_impl, nsub=nsub, out_len=out_len,
                   slack2=slack2, widths=widths, stat_len=payload,
                   engine=engine)
    if mesh is not None:
        impl = jax.shard_map(impl, mesh=mesh,
                                in_specs=(P(), P("dm"), P("dm")),
                                out_specs=P("dm"))

    # NOT donated: a full-size slice of the caller's Spectra shares its
    # buffer (verified), so donation would invalidate the caller's data on
    # backends that honor it; bench budgeting charges the padded working
    # copy instead
    @plane_jit(static_argnames=("n_chunks",), stage="sweep",
               aot=(mesh is None))
    def run(data, s1, s2, baseline, n_chunks):
        data = data - baseline
        # zero tail pad so the final chunk's overlap reads data-shaped zeros
        padded = jnp.pad(data, ((0, 0), (0, need)))

        def body(carry, ci):
            chunk = jax.lax.dynamic_slice(
                padded, (0, ci * payload), (padded.shape[0], need))
            return carry, impl(chunk, s1, s2)

        _, ys = jax.lax.scan(body, 0, jnp.arange(n_chunks))
        return ys

    return run


# ---------------------------------------------------------------------------
# warm-pool precompile (round 22)

def _warm_sweep(*, dms, freqs, dt, nsub=64, group_size=0,
                widths=DEFAULT_WIDTHS, n_samples=None, downsamp=1,
                chunk_payload=None, engine="auto", **_ignored) -> int:
    """Warm-pool planner for the sweep stage: rebuild the geometry the
    streamed sweep will dispatch (plan, bounded chunk payload, padded
    group tables) and AOT-lower the chunk kernel from abstract arrays —
    no data read, nothing dispatched. Extra geometry keys are ignored
    so one scheduler-side dict can feed every stage's warmer."""
    dms = np.asarray(dms, dtype=np.float64)
    # the plan wants high-frequency-first channels (the block sources
    # flip ascending tables; shapes are order-independent anyway)
    freqs = np.sort(np.asarray(freqs, dtype=np.float64))[::-1].copy()
    if dms.size == 0 or freqs.size == 0 or not dt or dt <= 0:
        return 0
    factor = max(1, int(downsamp))
    dt = float(dt) * factor  # ``dt`` is the RAW header sample time
    if group_size <= 0:
        group_size = choose_group_size(dms, freqs, float(dt), nsub)
    plan = make_sweep_plan(
        dms, freqs, float(dt), nsub=nsub, group_size=group_size,
        widths=tuple(widths),
        pad_groups_to=_mesh_pad_groups(len(dms), group_size, None))
    if chunk_payload is None:
        # the staged CLI's bounded default (tuned=False: detection
        # chunks are results, the tuner's overlay must not move them)
        chunk_payload = default_chunk_payload(plan, tuned=False)
    if n_samples:
        n_ds = int(n_samples) // factor
        chunk_payload = min(int(chunk_payload), n_ds)
        if chunk_payload <= plan.min_overlap:
            chunk_payload = min(n_ds, 2 * plan.min_overlap + 1)
        if chunk_payload <= 0:
            return 0
    W = max(plan.widths)
    out_len = int(chunk_payload) + W
    need = out_len + plan.max_shift2 + plan.max_shift1
    data = jax.ShapeDtypeStruct((len(freqs), need), np.float32)
    s1 = jax.ShapeDtypeStruct(plan.stage1_bins.shape,
                              plan.stage1_bins.dtype)
    s2 = jax.ShapeDtypeStruct(plan.stage2_bins.shape,
                              plan.stage2_bins.dtype)
    return int(sweep_chunk.warm(
        data, s1, s2, plan.nsub, out_len, plan.max_shift2,
        tuple(plan.widths), int(chunk_payload),
        engine=resolve_engine(engine)))


register_warmer("sweep", _warm_sweep)
