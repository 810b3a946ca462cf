"""Staged DDplan execution: run each DDstep at its own downsample factor.

The reference's DDplan2b emits a staged plan — per step a (downsample
factor, dDM, numDMs, numsub) block chosen so total smearing stays bounded
while work shrinks as ``numDMs / downsamp`` (reference utils/DDplan2b.py:
202-273) — but defers execution to PRESTO (prepsubband + search, one CPU
core). Here each step becomes its own compiled sharded sweep: separate
static shapes per step (SURVEY.md §7 "DDplan ragged stages: execute
per-step"), with the raw data stream downsampled on device by the step
factor before entering the overlap-save chunk engine.

The per-step work saving the plan encodes is therefore realized on the
TPU: a step at downsamp=f processes T/f samples per trial, so the HBM
traffic of high-DM steps falls geometrically exactly as the reference's
``work_fracts`` predicts.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pypulsar_tpu.compile import plane_jit
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.ops import kernels, transfer
from pypulsar_tpu.ops.ingest import _ingest_tc, _timed_reads, ingest_nbits
from pypulsar_tpu.tune import knobs
from pypulsar_tpu.parallel.sweep import (
    DEFAULT_WIDTHS,
    SweepCheckpoint,
    SweepResult,
    make_sweep_plan,
    sweep_stream,
)


@dataclasses.dataclass
class StepResult:
    """One DDstep's sweep output at its own time resolution."""

    downsamp: int
    dt: float  # effective (downsampled) sampling time, seconds
    result: SweepResult

    def candidates(self) -> List[dict]:
        """All (dm, width, snr, sample) records in physical units."""
        out = []
        res = self.result
        for di, dm in enumerate(res.dms):
            for wi, w in enumerate(res.widths):
                out.append(dict(
                    dm=float(dm),
                    snr=float(res.snr[di, wi]),
                    width_bins=int(w),
                    width_sec=float(w * self.dt),
                    sample=int(res.peak_sample[di, wi]),
                    time_sec=float(res.peak_sample[di, wi] * self.dt),
                    downsamp=self.downsamp,
                ))
        return out


@dataclasses.dataclass
class StagedSweepResult:
    """All DDsteps' results plus global candidate selection."""

    steps: List[StepResult]

    @property
    def n_trials(self) -> int:
        return sum(len(s.result.dms) for s in self.steps)

    def best(self, k: int = 10) -> List[dict]:
        """Global top-k candidates (best width per trial) across steps."""
        cands = []
        for s in self.steps:
            res = s.result
            wi = np.argmax(res.snr, axis=1)  # best width per DM trial
            for di, dm in enumerate(res.dms):
                w = res.widths[wi[di]]
                cands.append(dict(
                    dm=float(dm),
                    snr=float(res.snr[di, wi[di]]),
                    width_bins=int(w),
                    width_sec=float(w * s.dt),
                    sample=int(res.peak_sample[di, wi[di]]),
                    time_sec=float(res.peak_sample[di, wi[di]] * s.dt),
                    downsamp=s.downsamp,
                ))
        cands.sort(key=lambda c: -c["snr"])
        return cands[:k]

    def above_threshold(self, snr: float) -> List[dict]:
        """All per-(trial, width) detections above ``snr``, time-ordered."""
        out = [c for s in self.steps for c in s.candidates() if c["snr"] >= snr]
        out.sort(key=lambda c: (c["dm"], c["time_sec"]))
        return out

    def events(self, snr: float) -> List[dict]:
        """Multi-event single-pulse list: every per-chunk peak above
        ``snr`` across all steps, in physical units (needs the sweep run
        with keep_chunk_peaks)."""
        out = []
        for s in self.steps:
            for e in s.result.events(snr):
                out.append(dict(
                    dm=e["dm"], snr=e["snr"], width_bins=e["width"],
                    width_sec=e["width"] * s.dt,
                    sample=e["sample"], time_sec=e["sample"] * s.dt,
                    downsamp=s.downsamp,
                ))
        out.sort(key=lambda c: (c["dm"], c["time_sec"]))
        return out


def _band_orientation(freqs):
    """(normalized_freqs, flip): high-frequency-first view of a channel
    table (the sweep plan's convention; an ascending table silently sent
    delays to the wrong channels before this normalization)."""
    freqs = np.asarray(freqs, dtype=np.float64)
    flip = len(freqs) > 1 and freqs[0] < freqs[-1]
    return (freqs[::-1].copy() if flip else freqs), flip


class _SpectraSource:
    """Block source over an in-memory (possibly device-resident) Spectra,
    delivered high-frequency-first (see _band_orientation)."""

    def __init__(self, spectra):
        self.frequencies, self._flip = _band_orientation(spectra.freqs)
        self.tsamp = float(spectra.dt)
        self.nsamples = int(spectra.numspectra)
        self._data = spectra.data

    def chan_major_blocks(self, payload: int, overlap: int):
        pos = 0
        while pos < self.nsamples:
            n = min(payload + overlap, self.nsamples - pos)
            block = self._data[:, pos:pos + n]
            # per-block flip: a whole-dataset reversed copy would double
            # device residency for the sweep's lifetime. jnp.flip for
            # device arrays rather than an eager [::-1] strided slice (a
            # workaround kept from an earlier backend; ROADMAP design
            # queue lists it for removal once measured)
            if self._flip:
                block = (jnp.flip(block, axis=0)
                         if isinstance(block, jax.Array) else block[::-1])
            yield pos, block
            pos += payload


class _ReaderSource:
    """Block source over a file reader (FilterbankFile / PsrfitsFile /
    FilterbankObs): anything with ``frequencies``, ``tsamp`` and either
    ``get_samples(start, N) -> [time, chan]`` or ``get_spectra(start, N)``.

    ``start``/``end`` bound the source to a sample window whose blocks
    still read their dedispersion overlap PAST ``end`` (into the
    neighbouring window's data, clamped at the file tail) — the
    overlap-save seam contract that lets time-sharded hosts each sweep a
    window and merge accumulators exactly (parallel.distributed.
    time_sharded_sweep). Positions stay file-absolute."""

    def __init__(self, reader, start: int = 0, end: Optional[int] = None):
        self.reader = reader
        self.frequencies, self._flip = _band_orientation(reader.frequencies)
        self.tsamp = float(reader.tsamp)
        for attr in ("number_of_samples", "nspec", "nsamples"):
            n = getattr(reader, attr, None)
            if n is not None:
                self.total = int(n() if callable(n) else n)
                break
        else:
            raise ValueError(f"cannot determine sample count of {reader!r}")
        self.start = int(start)
        self.end = self.total if end is None else min(int(end), self.total)
        if not 0 <= self.start <= self.end:
            raise ValueError(f"bad window [{start}, {end}) of {self.total}")
        self.nsamples = self.end - self.start

    def chan_major_blocks(self, payload: int, overlap: int):
        # Seam contract: interior windows (end < total) must be whole
        # payload multiples — the last in-window block otherwise extends
        # its full payload past `end` into the neighbour's window and the
        # merged moment sums double-count the seam. time_sharded_sweep
        # constructs aligned windows; fail loudly for anyone else.
        if self.end < self.total and (self.end - self.start) % payload:
            raise ValueError(
                f"windowed source [{self.start}, {self.end}) is not a "
                f"whole multiple of payload={payload}; seam samples "
                f"would be double-counted across window boundaries")
        iter_blocks = getattr(self.reader, "iter_blocks", None)
        if iter_blocks is not None and getattr(
                self.reader, "BLOCK_ITER_ARRAYS", False):
            # reader-provided streaming (filterbank: native background
            # prefetch thread, native/prefetch.cpp) — disk reads overlap
            # device compute. Gated on the marker: fbobs.iter_blocks
            # yields Spectra with different stepping semantics and must
            # take the fallback branches below. Blocks ship in the file's
            # NATIVE dtype and are transposed/widened/flipped on device
            # (_ingest_tc): 4x less link traffic for 8-bit files.
            # read_end extends past the window so in-window blocks keep
            # their full overlap; iteration stops at the window end (the
            # iterator would otherwise yield overhang-only tail blocks).
            read_end = min(self.end + overlap, self.total)
            raw_blocks = iter_blocks(payload, overlap, start=self.start,
                                     end=read_end, raw=True)
            nbits = ingest_nbits(self.reader)
            for pos, dev in _ship_ahead(raw_blocks):
                if pos >= self.end:
                    break
                yield pos, _ingest_tc(dev, self._flip, nbits)
            return
        get_samples = getattr(self.reader, "get_samples", None)
        get_interval = getattr(self.reader, "get_sample_interval", None)
        pos = self.start
        while pos < self.end:
            n = min(payload + overlap, self.total - pos)
            if get_samples is not None:
                block = np.ascontiguousarray(get_samples(pos, n).T)
            elif get_interval is not None:  # fbobs multi-file
                block = np.ascontiguousarray(get_interval(pos, pos + n).T)
            else:
                block = self.reader.get_spectra(pos, n).data
            yield pos, self._orient(block)
            pos += payload

    def _orient(self, block):
        """High-frequency-first channel rows (every yield goes through
        here so a future reader branch cannot forget the flip)."""
        return block[::-1] if self._flip else block


def _ship_ahead(raw_blocks, depth: int = 2):
    """Host->device ship of streamed blocks on a background thread.

    A `jnp.asarray(block)` holds the calling thread for the host->device
    copy, and the main sweep loop also dispatches programs and drains
    results — so with everything on one thread the copy serializes
    against all of it. Transfers can run concurrently with device
    execution, so shipping from a dedicated thread lets block N+1 ride
    the wire while the main thread dispatches and drains block N.
    In-flight device blocks peak at ``depth + 2`` (queue slots + one the
    worker holds while parked on ``q.put`` + the one yielded to the
    consumer) — ~536 MB of HBM at depth=2 for 134 MB north-star blocks;
    size streaming budgets accordingly.

    This is the shared :func:`parallel.prefetch.prefetch` core (ordering
    preserved, worker errors re-raise in the consumer, abandoned
    consumers stop the worker, PYPULSAR_TPU_SHIP_AHEAD=0 runs inline)
    with the ship as the worker-side transform; queue fill lands on the
    ``sweep.ship.pending_depth`` gauge."""
    from pypulsar_tpu.parallel.prefetch import prefetch

    def ship(item):
        pos, block = item
        return pos, transfer.ship(block)

    # retries: a transient wire failure re-ships the (still in hand)
    # host block instead of aborting the whole streamed sweep
    return prefetch(_timed_reads(raw_blocks), depth=depth, name="sweep.ship",
                    transform=ship, thread_name="pypulsar-ship-ahead",
                    retries=2)


class _MaskedSource:
    """Decorates a block source with rfifind mask application: masked
    cells are replaced per block with the channel's median-mid80 fill —
    the reference's waterfaller semantics (bin/waterfaller.py:67-100 via
    formats/spectra.py:190-227) applied at the sweep's streaming boundary.
    The wrapped source delivers high-frequency-first rows; .mask channel
    indices are low-frequency-first, so the table flips on upload.

    The [nint, nchan] zap table ships to the device ONCE (~KBs) and each
    block's [C, L] mask expands from interval indices inside the fill
    program — shipping per-block boolean masks would double the wire
    traffic of an 8-bit streamed sweep (the measured bottleneck,
    BENCHNOTES r4)."""

    def __init__(self, src, rfimask):
        self.frequencies = src.frequencies
        self.tsamp = src.tsamp
        self.nsamples = src.nsamples
        self._src = src
        self._mask = rfimask
        self._pts = int(rfimask.ptsperint)
        self._host_table = np.asarray(rfimask._zap_table, dtype=bool)
        self._table = jnp.asarray(
            np.ascontiguousarray(self._host_table[:, ::-1]))  # hi-first

    def chan_major_blocks(self, payload: int, overlap: int):
        nint = self._host_table.shape[0]
        for pos, block in self._src.chan_major_blocks(payload, overlap):
            L = int(block.shape[1])
            i0 = min(pos // self._pts, nint - 1)
            i1 = min((pos + L - 1) // self._pts, nint - 1)
            if self._host_table[i0:i1 + 1].any():
                # split file-absolute pos into (interval base, remainder)
                # on the host: inside jit the arithmetic is int32 (x64
                # off), so pos + arange(L) would overflow for positions
                # past 2^31 samples; base + (rem + arange(L)) // pts is
                # exact for any file length (rem < pts, base < nint)
                telemetry.counter("mask.fill_blocks")
                block = _masked_block(
                    transfer.ship(block, jnp.float32), self._table,
                    min(pos // self._pts, nint - 1), pos % self._pts,
                    self._pts)
            yield pos, block


@plane_jit(static_argnames=("pts",), stage="sweep")
def _masked_block(data, table, base, rem, pts: int):
    """Expand the device-resident [nint, C] zap table to this block's
    [C, L] mask (interval = sample // pts, clamped like
    io.rfimask.get_sample_mask) and apply the median-mid80 fill.
    ``base``/``rem`` are the host-split interval index and in-interval
    offset of the block start (int32-overflow-proof, ADVICE r4)."""
    L = data.shape[1]
    iv = jnp.minimum(base + (rem + jnp.arange(L)) // pts,
                     table.shape[0] - 1)
    return kernels.masked(data, table[iv].T)


def _make_source(source, rfimask=None):
    from pypulsar_tpu.resilience import dataguard

    src = (_SpectraSource(source) if hasattr(source, "numspectra")
           else _ReaderSource(source))
    # dataguard INSIDE the mask wrapper: the mask fill's channel medians
    # must never see a NaN (it would poison the whole channel's fill)
    src = dataguard.guard_source(src)
    if rfimask is not None:
        src = _MaskedSource(src, rfimask)
    return src


def _mask_tag(rfimask) -> str:
    """Checkpoint-context tag identifying the applied mask: a checkpoint
    written with a different (or no) mask must not resume, and the cheap
    source probe only samples the first ~1k samples — zaps in later
    intervals would slip past it."""
    if rfimask is None:
        return ""
    import hashlib

    h = hashlib.sha256()
    h.update(np.int64([rfimask.nchan, rfimask.nint,
                       rfimask.ptsperint]).tobytes())
    h.update(np.packbits(rfimask._zap_table).tobytes())
    return "/mask=" + h.hexdigest()[:16]


def _downsampled_blocks(src, factor: int, payload_ds: int, overlap_ds: int):
    """Stream chan-major device blocks downsampled by ``factor``.

    Raw blocks are read at ``factor *`` the downsampled geometry so bin
    boundaries align exactly across chunks; a partial trailing bin is
    dropped (the reference's downsample drops the remainder,
    formats/spectra.py:329-351 semantics).

    When the reader is integer-sampled and the factor is large enough
    that the exact integer bin sums are SMALLER on the wire than the
    native samples, downsampling happens on the HOST before the ship
    (_host_downsampled_blocks): a DDplan step at downsamp=8 over an
    8-bit file then ships 2/8 = 1/4 of the native bytes (VERDICT r4
    item 3 — the wire is the streamed sweep's measured ceiling).
    Integer sums are exact in uint16/uint32 and in f32, so both paths
    are bit-identical (tests/test_staged.py)."""
    if factor > 1 and _host_downsample_wins(src, factor):
        yield from _host_downsampled_blocks(src, factor, payload_ds,
                                            overlap_ds)
        return
    for pos, block in src.chan_major_blocks(payload_ds * factor,
                                            overlap_ds * factor):
        data = transfer.ship(block, jnp.float32)
        if factor > 1:
            nbin = data.shape[1] // factor
            if nbin == 0:
                continue  # tail shorter than one output bin
            data = kernels.downsample(data[:, :nbin * factor], factor)
        yield pos // factor, data


def _host_downsample_wins(src, factor: int) -> bool:
    """True when host-side downsampling ships fewer bytes than the native
    samples: integer readers only (exact sums; float sum order would
    differ from the device path's), accumulator 2 B (nbits<=8) or 4 B
    (16-bit) per downsampled sample vs nbits/8 per native sample.
    PYPULSAR_TPU_HOST_DOWNSAMP=0/1 overrides the policy."""
    if not isinstance(src, _ReaderSource):
        return False  # masked sources zap at full rate, Spectra is resident
    r = src.reader
    if not (getattr(r, "BLOCK_ITER_ARRAYS", False)
            and getattr(r, "iter_blocks", None)):
        return False
    nbits = int(getattr(r, "nbits", 32) or 32)
    if nbits > 16:
        return False
    if nbits > 8 and factor > 256:
        return False  # uint32 sums past f32's 2^24 integer exactness
    env = knobs.env_str("PYPULSAR_TPU_HOST_DOWNSAMP")
    if env is not None:
        return env != "0"
    acc_bytes = _host_ds_acc_dtype(nbits, factor)().itemsize
    return acc_bytes / factor < nbits / 8


def _host_ds_acc_dtype(nbits: int, factor: int):
    """Accumulator for exact host bin sums: uint16 only while the worst
    case factor*255 fits (factor <= 257); uint32 beyond (and for 16-bit
    samples), still exact in f32 for any factor the policy admits."""
    return np.uint16 if (nbits <= 8 and factor <= 257) else np.uint32


def _host_downsampled_blocks(rsrc, factor: int, payload_ds: int,
                             overlap_ds: int):
    """Raw full-rate blocks -> host unpack (sub-byte) + exact integer
    downsample -> ship the SMALL accumulator blocks -> device ingest.
    Sums of <=257 uint8 (uint16 acc) or <=257 uint16 (uint32 acc) values
    are exact both in the accumulator and in the f32 cast, so results
    are bit-identical to the device downsample path."""
    reader = rsrc.reader
    nbits = int(getattr(reader, "nbits", 8) or 8)
    acc_dtype = _host_ds_acc_dtype(nbits, factor)
    payload_raw = payload_ds * factor
    # same seam contract as chan_major_blocks: interior windows must be
    # whole (raw) payload multiples or merged statistics double-count
    if rsrc.end < rsrc.total and (rsrc.end - rsrc.start) % payload_raw:
        raise ValueError(
            f"windowed source [{rsrc.start}, {rsrc.end}) is not a whole "
            f"multiple of payload={payload_raw}; seam samples would be "
            f"double-counted across window boundaries")
    read_end = min(rsrc.end + overlap_ds * factor, rsrc.total)
    raw_blocks = reader.iter_blocks(payload_raw, overlap_ds * factor,
                                    start=rsrc.start, end=read_end,
                                    raw=True)
    unpack = None
    if nbits < 8:
        from pypulsar_tpu.io.psrfits import _UNPACKERS

        unpack = _UNPACKERS[nbits]

    def ds_blocks():
        for pos, block in raw_blocks:
            if pos >= rsrc.end:
                break
            if unpack is not None:
                block = unpack(block.ravel()).reshape(block.shape[0], -1)
            nbin = block.shape[0] // factor
            if nbin == 0:
                continue
            acc = block[:nbin * factor].reshape(
                nbin, factor, block.shape[1]).sum(axis=1, dtype=acc_dtype)
            yield pos, acc

    for pos, dev in _ship_ahead(ds_blocks()):
        yield pos // factor, _ingest_tc(dev, rsrc._flip, 8)


def _run_step(src, dms, factor: int, nsub: int, group_size: int,
              widths: Tuple[int, ...], chunk_payload: Optional[int],
              mesh, verbose: bool = False, label: str = "",
              checkpoint: Optional[SweepCheckpoint] = None,
              engine: str = "auto",
              keep_chunk_peaks: bool = False,
              ckpt_extra: str = "") -> Optional[StepResult]:
    """Sweep one DM block over ``src`` downsampled by ``factor``.
    ``group_size`` <= 0 picks the largest group within the default
    smearing bound (parallel.sweep.choose_group_size)."""
    dt_eff = src.tsamp * factor
    n_ds = src.nsamples // factor
    if n_ds == 0:
        return None
    from pypulsar_tpu.parallel.sweep import (
        choose_group_size,
        note_chunk_plan,
        padded_group_count,
        plan_chunk,
        planned_payload,
    )

    with telemetry.span("sweep.plan", n_trials=len(dms)) as plan_span:
        if group_size <= 0:
            group_size = choose_group_size(dms, src.frequencies, dt_eff,
                                           nsub)
        ndm = 1 if mesh is None else mesh.shape["dm"]
        pad_groups_to = padded_group_count(-(-len(dms) // group_size), ndm)
        plan = make_sweep_plan(dms, src.frequencies, dt_eff, nsub=nsub,
                               group_size=group_size, widths=widths,
                               pad_groups_to=pad_groups_to)
        # default payload is BOUNDED (round 5: the previous whole-file
        # default made a --chunk-less CLI sweep of an hour-scale file
        # try to build one 2^26-sample chunk) and PLANNED from the
        # channel count and the device's memory (plan/lengths.py) —
        # small data still runs single-chunk via the min(). tuned=False:
        # the DETECTION sweep's chunk is part of its results (per-chunk
        # stats, one event per chunk), so the auto-tuner's overlay must
        # not reach it — only env/--chunk (explicit, fingerprinted
        # operator choices) move it
        planned = None
        if chunk_payload is None:
            planned = plan_chunk(plan, tuned=False, ndm=ndm)
            chunk_payload = planned_payload(plan, planned)
        payload = min(chunk_payload, n_ds)
        if payload <= plan.min_overlap:
            payload = min(n_ds, 2 * plan.min_overlap + 1)
        note_chunk_plan(plan_span, plan, payload, planned)
    if verbose:
        print(f"# {label}downsamp={factor} dt={dt_eff:.3e}s "
              f"DMs {dms[0]:.2f}..{dms[-1]:.2f} "
              f"({len(dms)} trials) payload={payload}")

    def block_factory(cursor_ds: int):
        """Re-root the block stream at a checkpoint cursor (seek-resume:
        the cursor always sits on a payload boundary, so the re-rooted
        window honors the seam contract). Falls back to the full stream
        (skip-based replay) for sources that cannot seek."""
        seeked = _reroot_source(src, cursor_ds * factor)
        return _downsampled_blocks(seeked if seeked is not None else src,
                                   factor, payload, plan.min_overlap)

    # sink-only span (aggregate=False): it encloses the sweep loop's
    # aggregated stages, which must stay non-overlapping in the flat table
    with telemetry.span("sweep_step", aggregate=False, downsamp=factor,
                        n_trials=len(dms), payload=int(payload)):
        res = sweep_stream(
            plan,
            _downsampled_blocks(src, factor, payload, plan.min_overlap),
            payload,
            mesh=mesh,
            chan_major=True,
            checkpoint=checkpoint,
            engine=engine,
            keep_chunk_peaks=keep_chunk_peaks,
            checkpoint_context=ckpt_extra,
            block_factory=block_factory,
        )
    return StepResult(downsamp=factor, dt=dt_eff, result=res)


def _reroot_source(src, start_raw: int):
    """A view of ``src`` whose blocks begin at raw sample ``start_raw``
    (same end bound), or None when the source cannot seek. Positions stay
    file-absolute, so the resumed stream's chunks carry the same
    coordinates they had in the original run. (One public entry point:
    the wrapper recursion lives in :func:`_reroot_impl`.)"""
    return _reroot_impl(src, start_raw)


def _reroot_impl(src, start_raw: int):
    from pypulsar_tpu.resilience.dataguard import GuardedSource

    if isinstance(src, _MaskedSource):
        inner = _reroot_impl(src._src, start_raw)
        return None if inner is None else _MaskedSource(inner, src._mask)
    if isinstance(src, GuardedSource):
        # rewrap sharing the SAME quality account: the resumed stream's
        # scrub continues the original tally instead of forking it
        inner = _reroot_impl(src._src, start_raw)
        return None if inner is None else GuardedSource(inner,
                                                        stats=src.stats)
    if isinstance(src, _ReaderSource):
        end = src.end if src.end < src.total else None
        return _ReaderSource(src.reader, start_raw, end)
    return None


def sweep_flat(
    source,
    dms,
    downsamp: int = 1,
    nsub: int = 64,
    group_size: int = 32,
    widths: Sequence[int] = DEFAULT_WIDTHS,
    chunk_payload: Optional[int] = None,
    mesh=None,
    verbose: bool = False,
    checkpoint_path: Optional[str] = None,
    checkpoint_every: int = 16,
    engine: str = "auto",
    keep_chunk_peaks: bool = False,
    rfimask=None,
) -> StagedSweepResult:
    """Single-stage sweep of an explicit DM grid over a file reader or
    Spectra (the flat counterpart of :func:`sweep_ddplan`, sharing its
    streaming/downsampling machinery). ``checkpoint_path`` enables in-sweep
    checkpoint/resume (see SweepCheckpoint); ``rfimask`` (an
    io.rfimask.RfifindMask) applies median-mid80 mask fill per block."""
    with telemetry.span("sweep.plan"):  # the block source, with its guards
        src = _make_source(source, rfimask)
        ckpt = (SweepCheckpoint(checkpoint_path, every=checkpoint_every)
                if checkpoint_path else None)
    step = _run_step(src, np.asarray(dms, dtype=np.float64), int(downsamp),
                     nsub, group_size, tuple(widths), chunk_payload, mesh,
                     verbose=verbose, checkpoint=ckpt, engine=engine,
                     keep_chunk_peaks=keep_chunk_peaks,
                     ckpt_extra=_mask_tag(rfimask))
    return StagedSweepResult(steps=[] if step is None else [step])


def sweep_ddplan(
    source,
    ddplan,
    nsub: int = 64,
    group_size: int = 32,
    widths: Sequence[int] = DEFAULT_WIDTHS,
    chunk_payload: Optional[int] = None,
    mesh=None,
    verbose: bool = False,
    checkpoint_path: Optional[str] = None,
    checkpoint_every: int = 16,
    engine: str = "auto",
    rfimask=None,
) -> StagedSweepResult:
    """Execute every DDstep of ``ddplan`` over ``source``.

    source: a Spectra, or a reader (FilterbankFile / PsrfitsFile / fbobs).
    Each step sweeps ``step.DMs`` at sampling time ``dt * step.downsamp``
    with its own jit-compiled shapes; chunk_payload is the *downsampled*
    chunk length (default: the whole downsampled series).

    ``checkpoint_path`` is a base path: step ``i`` streams its in-progress
    accumulator to ``{path}.step{i}.npz`` and, once complete, its full
    result to ``{path}.step{i}.done.npz`` — so a killed run resumes the
    interrupted step mid-stream and loads finished steps from their done
    markers without recompute. All marker files are removed when every
    step has completed; the combined result is bit-identical to an
    uninterrupted run (deterministic accumulation order, see
    SweepCheckpoint).
    """
    from pypulsar_tpu.parallel.sweep import resolve_engine

    src = _make_source(source, rfimask)
    mtag = _mask_tag(rfimask)
    ckpt_context = "engine=%s/meshdm=%s%s" % (
        resolve_engine(engine),
        0 if mesh is None else mesh.shape.get("dm", 0), mtag)
    probe = _source_probe(src) if checkpoint_path else b""
    steps: List[StepResult] = []
    done_fns: List[str] = []
    for si, step in enumerate(ddplan.DDsteps):
        done_fn = (f"{checkpoint_path}.step{si}.done.npz"
                   if checkpoint_path else None)
        fp = (_step_fingerprint(src, step.DMs, int(step.downsamp), nsub,
                                group_size, tuple(widths), chunk_payload,
                                ckpt_context, probe)
              if done_fn else "")
        if done_fn and os.path.exists(done_fn):
            sr = _load_step_result(done_fn, fp)
            if sr is not None:
                if verbose:
                    print(f"# step {si}: resumed from {done_fn}")
                steps.append(sr)
                done_fns.append(done_fn)
                continue
        ckpt = (SweepCheckpoint(f"{checkpoint_path}.step{si}.npz",
                                every=checkpoint_every)
                if checkpoint_path else None)
        sr = _run_step(src, step.DMs, int(step.downsamp), nsub, group_size,
                       tuple(widths), chunk_payload, mesh, verbose=verbose,
                       label=f"step {si}: ", checkpoint=ckpt, engine=engine,
                       ckpt_extra=mtag)
        if sr is None:
            break
        if done_fn:
            _save_step_result(done_fn, sr, fp)
            done_fns.append(done_fn)
        steps.append(sr)
    for fn in done_fns:  # full plan finished: clear the markers
        if os.path.exists(fn):
            os.remove(fn)
    return StagedSweepResult(steps=steps)


def _source_probe(src) -> bytes:
    """A cheap content sample of the input (first ~1k samples of every
    channel): catches the input file being swapped for another of
    identical geometry between checkpoint and resume."""
    try:
        _, block = next(src.chan_major_blocks(min(1024, src.nsamples), 0))
        return np.ascontiguousarray(
            np.asarray(block, dtype=np.float32)).tobytes()
    except Exception:  # noqa: BLE001 - probe is best-effort
        return b""


def _default_fft_len(nchan: int, nsub: int, trials: int) -> int:
    # the DETECTION sweep's effective default for this geometry (env >
    # the length planned from the channel count and the device's memory,
    # overlays excluded — see chunk_fft_len; its growth for the overlap
    # follows from the DMs and the band, which the fingerprint holds):
    # re-setting the env knob, or a device of another memory size, must
    # invalidate default-using checkpoint markers, while auto-tuning
    # (which never reaches the detector) must not
    from pypulsar_tpu.parallel.sweep import planned_lengths

    return planned_lengths(nchan, nsub, 0, trials, tuned=False).chunk


def _step_fingerprint(src, dms, factor, nsub, group_size, widths,
                      chunk_payload, context, probe) -> str:
    """Hash of everything that determines a step's result — a done marker
    from different parameters, a different engine/mesh, or a different
    input must not be resumed (the bit-identity contract; engines agree
    only to ~1e-4)."""
    import hashlib

    h = hashlib.sha256()
    for part in (np.asarray(dms, dtype=np.float64).tobytes(),
                 src.frequencies.tobytes(),
                 np.float64([src.tsamp]).tobytes(),
                 # None resolves through default_chunk_payload, so the
                 # sentinel is the (negated) planned default length:
                 # retuning the library default invalidates only markers
                 # that actually USED the default (fourier chunk rounding
                 # is chunk-length-dependent); explicit --chunk runs are
                 # untouched by the constant and keep their markers
                 np.int64([src.nsamples, factor, nsub, group_size,
                           -_default_fft_len(len(src.frequencies), nsub,
                                             len(dms))
                           if chunk_payload is None
                           else chunk_payload]).tobytes(),
                 np.int64(widths).tobytes(),
                 context.encode(), probe):
        h.update(part)
    return h.hexdigest()


def _save_step_result(path: str, sr: StepResult, fingerprint: str) -> None:
    res = sr.result
    tmp = path + ".tmp.npz"
    np.savez(tmp, fingerprint=fingerprint,
             downsamp=sr.downsamp, dt=sr.dt, dms=res.dms,
             widths=np.asarray(res.widths, dtype=np.int64), snr=res.snr,
             peak_sample=res.peak_sample, mean=res.mean, std=res.std)
    os.replace(tmp, path)


def _load_step_result(path: str, fingerprint: str) -> Optional[StepResult]:
    try:
        with np.load(path, allow_pickle=False) as z:
            if str(z["fingerprint"]) != fingerprint:
                return None
            res = SweepResult(
                dms=z["dms"], widths=tuple(int(w) for w in z["widths"]),
                snr=z["snr"], peak_sample=z["peak_sample"],
                mean=z["mean"], std=z["std"])
            return StepResult(downsamp=int(z["downsamp"]),
                              dt=float(z["dt"]), result=res)
    except Exception:  # noqa: BLE001 - corrupt marker -> recompute the step
        return None


def sweep_ddplan_2d(
    source,
    ddplan,
    mesh,
    nsub: int = 64,
    group_size: int = 8,
    widths: Sequence[int] = DEFAULT_WIDTHS,
    engine: str = "auto",
    max_trials_per_step: Optional[int] = None,
) -> StagedSweepResult:
    """Staged DDplan execution over a 2-D {dm, time} device mesh.

    The 1-D path (:func:`sweep_ddplan`) shards trial groups over 'dm' and
    streams time chunks from the host; here each step instead runs as ONE
    sharded program over the whole (downsampled) series with the time axis
    split across the mesh's 'time' axis — halos travel between neighbours
    over ICI via lax.ppermute instead of through host overlap-save
    (parallel.sweep.make_sharded_sweep_chunk_2d). This is the long-context
    layout of SURVEY.md §5 exercised by the driver's multichip dryrun at
    realistic shapes.

    ``max_trials_per_step`` caps each DDstep's trial count (the dryrun uses
    it to bound virtual-CPU wall time while keeping real channel counts and
    sample lengths).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pypulsar_tpu.parallel.sweep import (
        finalize_sweep,
        make_sharded_sweep_chunk_2d,
        padded_group_count,
        resolve_engine,
    )

    engine = resolve_engine(engine)
    src = _make_source(source)
    nd = mesh.shape["dm"]
    nt = mesh.shape["time"]
    steps: List[StepResult] = []
    for si, step in enumerate(ddplan.DDsteps):
        factor = int(step.downsamp)
        dms = np.asarray(step.DMs, dtype=np.float64)
        if max_trials_per_step is not None:
            dms = dms[:max_trials_per_step]
        dt_eff = src.tsamp * factor
        n_ds = src.nsamples // factor
        if n_ds == 0:
            break
        pad_groups_to = padded_group_count(-(-len(dms) // group_size), nd)
        plan = make_sweep_plan(dms, src.frequencies, dt_eff, nsub=nsub,
                               group_size=group_size, widths=tuple(widths),
                               pad_groups_to=pad_groups_to)
        local_payload = n_ds // nt
        if plan.min_overlap >= local_payload:
            raise ValueError(
                f"step {si}: time shard {local_payload} samples does not "
                f"cover the halo {plan.min_overlap}; fewer 'time' shards "
                f"or more data needed")
        T_used = local_payload * nt
        # whole downsampled series on the mesh (one pass; the per-channel
        # baseline keeps the f32 accumulation at fluctuation scale, as in
        # sweep_stream's contract)
        blocks = list(_downsampled_blocks(src, factor, n_ds, 0))
        data = jnp.concatenate([b for _, b in blocks], axis=1)[:, :T_used]
        base = jnp.mean(data, axis=1, keepdims=True)
        base_sum = float(np.asarray(jnp.sum(base), dtype=np.float64))
        data = data - base
        fn = make_sharded_sweep_chunk_2d(
            mesh, plan.nsub, local_payload, plan.min_overlap,
            plan.max_shift2, tuple(plan.widths), engine=engine)
        darr = jax.device_put(data, NamedSharding(mesh, P(None, "time")))
        s1 = jax.device_put(jnp.asarray(plan.stage1_bins),
                            NamedSharding(mesh, P("dm")))
        s2 = jax.device_put(jnp.asarray(plan.stage2_bins),
                            NamedSharding(mesh, P("dm")))
        s, ss, mb, ab = fn(darr, s1, s2)
        jax.block_until_ready((s, ss, mb, ab))
        # mean reported in original units, matching the 1-D staged path
        res = finalize_sweep(plan, T_used, s, ss, mb, ab,
                             baseline_sum=base_sum)
        steps.append(StepResult(downsamp=factor, dt=dt_eff, result=res))
    return StagedSweepResult(steps=steps)


def write_dats_streamed(
    outbase: str,
    reader,
    dms,
    downsamp: int = 1,
    nsub: int = 64,
    group_size: int = 32,
    rfimask=None,
    engine: str = "auto",
    chunk_payload: Optional[int] = None,
    window: Optional[Tuple[int, int]] = None,
    suffix: str = "",
    write_inf: bool = True,
    verbose: bool = False,
) -> List[str]:
    """Stream the file ONCE and write a dedispersed .dat per DM trial.

    The in-memory writer (cli/sweep._write_dats) loads the whole
    observation as a device-resident Spectra — infeasible past HBM for
    the workloads --write-dats exists for (a 900 s x 1024-chan window is
    57.6 GB as f32). This writer streams overlap-save chunks through the
    sweep's own two-stage engine (sweep.dedisperse_series_chunk), so it
    runs at sweep speed on any file length and the written series is
    exactly what the sweep's detections saw. Semantics = PRESTO
    prepsubband (subband dedispersion; reference defers this entire
    stage to PRESTO, SURVEY.md §2.5): values differ from the exact
    per-channel path by one subband smearing, and the file tail is
    zero-padded (linear shifts) rather than wrapped.

    ``window=(s0, s1)`` (DOWNSAMPLED sample coordinates, whole chunk
    multiples — the time-shard seam contract) writes only that span of
    each series; with ``suffix=f".w{rank}"`` each host of a time-sharded
    sweep writes its own segment files, concatenated in rank order by
    cli/sweep (the .dat byte stream is position-ordered, so
    concatenation of whole-chunk windows reproduces the sequential
    file). Returns the written .dat paths.
    """
    factor = max(1, int(downsamp))
    dms = np.asarray(dms, dtype=np.float64)
    dt_eff = _ReaderSource(reader).tsamp * factor
    _plan, _payload, T = dats_geometry(reader, dms, downsamp=factor,
                                       nsub=nsub, group_size=group_size,
                                       chunk_payload=chunk_payload)
    s0, s1 = window if window is not None else (0, T)

    paths = dat_truncate_paths(outbase, dms, suffix)
    for pos, rows in iter_dedispersed_chunks(
            reader, dms, downsamp=factor, nsub=nsub, group_size=group_size,
            rfimask=rfimask, engine=engine, chunk_payload=chunk_payload,
            window=window, verbose=verbose):
        dat_append_rows(paths, rows)
    dat_finalize_paths(paths)
    if write_inf:
        write_dat_infs(outbase, reader, dms, s1 - s0, dt_eff)
    return paths


def dat_truncate_paths(outbase: str, dms, suffix: str = "") -> List[str]:
    """Create (truncated) the per-DM .dat paths — the ONE definition of
    the .dat byte-emitting side, shared with the accel handoff's
    --write-dats tee so the tee-identical contract has a single writer.

    The byte stream accumulates in ``{path}.tmp`` and lands on the final
    name only at :func:`dat_finalize_paths` (tmp + os.replace, the sweep
    checkpoints' discipline): a killed run leaves tmp debris, never a
    truncated ``.dat`` that a later stage would trust as complete."""
    paths = [f"{outbase}_DM{dm:.2f}{suffix}.dat" for dm in dms]
    # truncate once, then reopen per chunk in append mode: holding one
    # descriptor per DM trial would hit the fd limit at prepsubband-
    # scale grids (review r5: --numdms 2000 vs the common 1024 ulimit)
    for p in paths:
        open(p + ".tmp", "wb").close()
    return paths


def dat_append_rows(paths: List[str], rows) -> None:
    """Append one chunk's [D, valid] float32 rows to the per-DM .dat
    byte streams (other half of :func:`dat_truncate_paths`; bytes go to
    the ``.tmp`` staging name until :func:`dat_finalize_paths`)."""
    from pypulsar_tpu.resilience import faultinject

    faultinject.trip("dats.append")  # kill-point: mid-stream .dat write
    for p, row in zip(paths, rows):
        with open(p + ".tmp", "ab") as f:
            row.tofile(f)


def dat_finalize_paths(paths: List[str]) -> None:
    """Atomically publish completed .dat streams (``.tmp`` ->
    final, os.replace): readers only ever see whole files."""
    for p in paths:
        os.replace(p + ".tmp", p)


def iter_dedispersed_chunks(
    reader,
    dms,
    downsamp: int = 1,
    nsub: int = 64,
    group_size: int = 32,
    rfimask=None,
    engine: str = "auto",
    chunk_payload: Optional[int] = None,
    window: Optional[Tuple[int, int]] = None,
    mesh=None,
    verbose: bool = False,
):
    """Stream the file ONCE and yield ``(pos, rows[D, valid] float32)``
    host chunks of every DM trial's two-stage dedispersed series — the
    chunk engine of :func:`write_dats_streamed`, factored out so the
    sweep->accel handoff (parallel.accelpipe) consumes the IDENTICAL
    values the .dat writer would have put on disk without the write +
    re-read round trip (745.9 s of the round-5 configs[4] chain). ``pos``
    is the file-absolute downsampled sample position of the chunk start
    (``window`` bounds which chunks stream); chunk geometry comes from
    :func:`dats_geometry`, so windows must be whole-payload multiples
    (the seam contract). Every value a consumer sees is the f32 the .dat
    byte stream would contain — the paths are bit-identical by
    construction, which the candidate-table parity test pins down.

    ``mesh`` shards the trial groups over its 'dm' axis
    (sweep.make_sharded_series_chunk): each device dedisperses its local
    groups of the replicated chunk, and because per-group math is
    device-count independent the yielded rows stay bit-identical to the
    unsharded stream (the multi-chip byte-parity contract)."""
    from pypulsar_tpu.ops.transfer import pull_host
    from pypulsar_tpu.parallel.sweep import (
        dedisperse_series_chunk,
        resolve_engine,
    )

    engine = resolve_engine(engine)
    factor = max(1, int(downsamp))
    dms = np.asarray(dms, dtype=np.float64)
    probe = _ReaderSource(reader)
    plan, payload, T = dats_geometry(reader, dms, downsamp=factor,
                                     nsub=nsub, group_size=group_size,
                                     chunk_payload=chunk_payload)
    dev_ids = None
    sharded_fn = None
    from pypulsar_tpu.parallel.sweep import padded_group_count

    ndm = 1 if mesh is None else int(mesh.shape["dm"])
    padded_groups = padded_group_count(plan.n_groups, ndm)
    if padded_groups != plan.n_groups:
        # padded groups replicate the last real trial; group math is
        # independent, so the real rows below are untouched
        plan = make_sweep_plan(dms, probe.frequencies,
                               probe.tsamp * factor, nsub=nsub,
                               group_size=plan.group_size, widths=(1,),
                               pad_groups_to=padded_groups)
    if mesh is not None:
        from pypulsar_tpu.parallel.sweep import make_sharded_series_chunk

        sharded_fn = make_sharded_series_chunk(
            mesh, plan.nsub, payload, plan.max_shift2, engine)
        dev_ids = [int(getattr(d, "id", -1)) for d in mesh.devices.flat]
    s0, s1 = window if window is not None else (0, T)
    if not 0 <= s0 <= s1 <= T:
        raise ValueError(f"bad window [{s0}, {s1}) of {T}")
    src = _ReaderSource(reader, s0 * factor,
                        min(s1 * factor, probe.total) if s1 < T else None)
    from pypulsar_tpu.resilience import dataguard

    src = dataguard.guard_source(src)
    if rfimask is not None:
        src = _MaskedSource(src, rfimask)
    s1b = jnp.asarray(plan.stage1_bins)
    s2b = jnp.asarray(plan.stage2_bins)
    need = payload + plan.min_overlap

    for pos, block in _downsampled_blocks(src, factor, payload,
                                          plan.min_overlap):
        L = int(block.shape[1])
        if L < need:  # tail: zero-pad to the static chunk shape
            block = jnp.pad(block, ((0, 0), (0, need - L)))
        valid = min(payload, s1 - pos)
        attrs = dict(n_trials=len(dms), valid=int(valid))
        if dev_ids is not None:
            attrs["dev"] = dev_ids
        with telemetry.span("dedisperse_chunk", **attrs):
            if sharded_fn is not None:
                series = sharded_fn(block, s1b, s2b)
            else:
                series = dedisperse_series_chunk(
                    block, s1b, s2b, plan.nsub, payload, plan.max_shift2,
                    engine)
            (host,) = pull_host(series[:, :valid].astype(jnp.float32))
        if verbose:
            print(f"# dats chunk at {pos}: {valid} samples "
                  f"x {len(dms)} DMs")
        telemetry.counter("dedisperse.chunks")
        if dev_ids is not None:
            for d in dev_ids:
                telemetry.counter(f"device{d}.dedisperse.chunks")
        # the plan pads trial groups to the group size; only the real
        # trials leave this generator
        yield pos, np.asarray(host)[:len(dms)]


def dats_geometry(reader, dms, downsamp: int = 1, nsub: int = 64,
                  group_size: int = 32, chunk_payload: Optional[int] = None):
    """(plan, payload, T_ds) the streamed .dat writer will use for these
    parameters — time-sharding callers need the identical chunk size to
    construct whole-chunk windows (the seam contract)."""
    factor = max(1, int(downsamp))
    probe = _ReaderSource(reader)
    T = probe.nsamples // factor
    plan = make_sweep_plan(np.asarray(dms, dtype=np.float64),
                           probe.frequencies, probe.tsamp * factor,
                           nsub=nsub, group_size=group_size, widths=(1,))
    if chunk_payload is None:
        from pypulsar_tpu.parallel.sweep import default_chunk_payload

        chunk_payload = default_chunk_payload(plan)
    payload = min(chunk_payload, T)
    if payload <= plan.min_overlap:
        payload = min(T, 2 * plan.min_overlap + 1)
    return plan, payload, T


def write_dat_infs(outbase: str, reader, dms, N: int, dt: float):
    """PRESTO .inf sidecars for a set of written .dat series (metadata
    mirrors cli/sweep's in-memory writer; split out so a time-sharded
    run's rank 0 can stamp the CONCATENATED length once)."""
    probe = _ReaderSource(reader)
    freqs = np.asarray(probe.frequencies)
    for dm in np.asarray(dms, dtype=np.float64):
        base = f"{outbase}_DM{dm:.2f}"
        make_dat_inf(base, reader, float(dm), N, dt, freqs).to_file(
            base + ".inf")


def make_dat_inf(basenm: str, reader, dm: float, N: int, dt: float,
                 freqs: np.ndarray):
    """InfoData for a dedispersed series of this reader — the ONE place
    .dat sidecar metadata is built (the in-memory writer in cli/sweep
    and the streamed writer both use it)."""
    from pypulsar_tpu.io.infodata import InfoData

    inf = InfoData()
    inf.basenm = os.path.basename(basenm)
    inf.telescope = getattr(reader, "telescope", "unknown") or "unknown"
    inf.object = getattr(reader, "source_name", "synthetic") or "synthetic"
    inf.epoch = float(getattr(reader, "tstart", 0.0) or 0.0)
    inf.N = int(N)
    inf.dt = float(dt)
    inf.DM = float(dm)
    inf.numchan = len(freqs)
    inf.lofreq = float(freqs.min())
    inf.BW = float(abs(freqs.max() - freqs.min()))
    inf.chan_width = float(inf.BW / max(inf.numchan - 1, 1))
    inf.bary = 0
    inf.analyzer = "pypulsar_tpu"
    return inf
