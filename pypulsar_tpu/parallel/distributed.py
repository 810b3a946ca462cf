"""Multi-host execution: ``jax.distributed`` init + the file-batch axis.

SURVEY.md §2.4 rows 4-5: the reference processes multi-beam / multi-file
observations with sequential per-file Python loops on one core
(``bin/autozap.py:76``, ``bin/fitkepler.py``); it has no communication
backend at all. The TPU-native scale-out has two layers:

1. **Within a host (ICI)**: the sweep engine's ``mesh`` argument shards DM
   trials / the time axis across local devices (parallel/sweep.py) — no
   code here is involved.
2. **Across hosts (DCN)**: this module. Each host initializes the JAX
   distributed runtime (:func:`initialize`), takes its slice of the file
   list (:func:`shard_files` — the data-parallel batch axis of this
   domain), sweeps its files locally, and merges the per-file candidate
   summaries with a fixed-size all-gather over DCN
   (:func:`allgather_candidates`). Candidate summaries are tiny (top-k
   records per file), so cross-host traffic is bytes, not data — the
   layout that keeps collectives off the raw-data path entirely.

The same entry points are no-ops in a single-process run, so pipelines are
written once: ``initialize()`` returns False and the "all-gather" is the
identity. A two-process CPU integration test exercises the real
``jax.distributed`` path (tests/test_distributed.py).
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence

import numpy as np
from pypulsar_tpu.tune import knobs

__all__ = [
    "initialize",
    "is_distributed",
    "local_rank",
    "local_count",
    "process_index",
    "process_count",
    "shard_files",
    "allgather_candidates",
    "multi_host_sweep",
    "time_sharded_sweep",
]

# environment surface (set by a launcher / scheduler on every host)
ENV_COORD = "PYPULSAR_TPU_COORDINATOR"  # e.g. "10.0.0.1:9021"
ENV_NPROC = "PYPULSAR_TPU_NUM_PROCESSES"
ENV_PID = "PYPULSAR_TPU_PROCESS_ID"

_initialized = False


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join the multi-host runtime; returns True if distributed.

    Arguments default to the ``PYPULSAR_TPU_{COORDINATOR,NUM_PROCESSES,
    PROCESS_ID}`` environment variables. With no coordinator configured
    (the common single-host case) this is a no-op returning False. Safe to
    call more than once.
    """
    global _initialized
    if _initialized:
        return True
    coordinator_address = coordinator_address or knobs.env_str(ENV_COORD)
    if not coordinator_address:
        return False
    if num_processes is None:
        num_processes = int(knobs.env_int(ENV_NPROC))
    if process_id is None:
        process_id = int(knobs.env_int(ENV_PID))
    if num_processes <= 1:
        return False
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    return True


def is_distributed() -> bool:
    return _initialized or process_count() > 1


def process_index() -> int:
    import jax

    return jax.process_index()


def process_count() -> int:
    import jax

    return jax.process_count()


def local_rank() -> int:
    """This process's rank WITHOUT touching jax: the launcher env
    (``PYPULSAR_TPU_PROCESS_ID``) when a grid is declared, else the jax
    grid if the distributed runtime is up, else 0. The survey fleet's
    ``--hosts`` launcher and host-id derivation read this — they must
    work on backends (CPU jaxlib) whose collectives cannot even
    initialize."""
    if int(knobs.env_int(ENV_NPROC)) > 1:
        return int(knobs.env_int(ENV_PID))
    if _initialized:
        return process_index()
    return 0


def local_count() -> int:
    """Declared process-grid size, env-first (see :func:`local_rank`)."""
    n = int(knobs.env_int(ENV_NPROC))
    if n > 1:
        return n
    if _initialized:
        return process_count()
    return 1


def shard_files(files: Sequence[str],
                index: Optional[int] = None,
                count: Optional[int] = None) -> List[str]:
    """This host's slice of the observation file list (round-robin, so
    hosts stay balanced when file sizes are similar — the batch axis over
    DCN).

    Surplus-host contract (round 18): with more processes than files the
    high ranks get an EMPTY slice — deliberately, and validated here so
    a mis-wired launcher fails loudly instead of silently double-
    processing (``index >= count`` would alias another rank's files).
    An idle shard is not an idle host: the survey fleet's claim loop
    turns empty-slice hosts into adopters/host-pool workers (they pick
    up orphaned observations the moment a loaded host dies), which is
    the behavior the multi-host tests pin."""
    if index is None:
        index = process_index()
    if count is None:
        count = process_count()
    count = int(count)
    index = int(index)
    if count < 1:
        raise ValueError(f"shard_files count must be >= 1, got {count}")
    if not 0 <= index < count:
        raise ValueError(
            f"shard_files rank {index} outside the {count}-process grid "
            f"[0, {count}): a wrapped rank would alias another host's "
            f"file share")
    return list(files[index::count])


def allgather_candidates(records: np.ndarray, pad_to: int) -> np.ndarray:
    """All-gather fixed-size candidate records across hosts.

    ``records[n, F]`` float64 rows (n <= pad_to); rows are padded with NaN
    to ``pad_to`` so every host contributes the same static shape (the
    collective compiles once). Returns the concatenated valid rows from
    all hosts, on every host. Identity in a single-process run.
    """
    records = np.asarray(records, dtype=np.float64)
    if records.ndim != 2:
        raise ValueError("records must be [n, fields]")
    n, F = records.shape
    if n > pad_to:
        records = records[:pad_to]
        n = pad_to
    padded = np.full((pad_to, F), np.nan)
    padded[:n] = records
    if process_count() == 1:
        gathered = padded[None]
    else:
        from jax.experimental import multihost_utils

        gathered = np.asarray(multihost_utils.process_allgather(padded))
    flat = gathered.reshape(-1, F)
    return flat[~np.isnan(flat[:, 0])]


def time_sharded_sweep(
    path_or_reader,
    dms,
    nsub: int = 64,
    group_size: int = 32,
    chunk_payload: Optional[int] = None,
    mesh=None,
    widths=None,
    engine: str = "auto",
    rfimask=None,
    rank: Optional[int] = None,
    count: Optional[int] = None,
    checkpoint_base: Optional[str] = None,
    checkpoint_every: int = 16,
    downsamp: int = 1,
    keep_chunk_peaks: bool = False,
):
    """Sweep ONE file with its TIME axis sharded across hosts.

    Where the wire between host and device (or the disk behind it) bounds
    the streamed sweep, DM-sharding cannot help — every host still needs
    every sample.
    Time-sharding does: host ``k`` of ``P`` streams only its contiguous
    window of chunks (1/P of the bytes), windows overlap by the
    dedispersion+boxcar reach exactly as chunks do (overlap-save; the
    windowed `_ReaderSource` reads its seam PAST the window end), and
    what crosses DCN afterwards is one accumulator per host: the f64
    moment sums, f32 window-sum maxima and their positions
    (``sweep.AccumParts``, ~KBs). Merging in window order
    (``merge_accum_parts``) reproduces the sequential sweep exactly up
    to f64 re-association of the moment sums — mb/ab (and therefore
    every peak and its sample position) merge bit-identically, and the
    per-channel baseline comes from the FILE's first block on every host
    so window results share one reference.

    ``rank``/``count`` default to the jax.distributed process grid (and
    may be passed explicitly for in-process testing; see also
    :func:`time_shard_local_accum` for the mergeable per-window piece).
    Every host returns the same finalized ``SweepResult``.
    """
    from pypulsar_tpu.parallel.sweep import finalize_sweep, merge_accum_parts

    if rank is None:
        rank = process_index()
    if count is None:
        count = process_count()
    plan, local = time_shard_local_accum(
        path_or_reader, dms, rank, count, nsub=nsub, group_size=group_size,
        chunk_payload=chunk_payload, mesh=mesh, widths=widths, engine=engine,
        rfimask=rfimask, checkpoint_base=checkpoint_base,
        checkpoint_every=checkpoint_every, downsamp=downsamp,
        keep_chunk_peaks=keep_chunk_peaks)
    parts = _allgather_accums(local, count, with_peaks=keep_chunk_peaks,
                              nr=plan.n_real_trials)
    merged = merge_accum_parts(parts)
    return finalize_sweep(plan, merged.n, merged.s, merged.ss, merged.mb,
                          merged.ab, merged.baseline_sum,
                          chunk_mb=list(merged.chunk_mb) or None,
                          chunk_ab=list(merged.chunk_ab) or None)


def time_shard_local_accum(
    path_or_reader,
    dms,
    rank: int,
    count: int,
    nsub: int = 64,
    group_size: int = 32,
    chunk_payload: Optional[int] = None,
    mesh=None,
    widths=None,
    engine: str = "auto",
    rfimask=None,
    checkpoint_base: Optional[str] = None,
    checkpoint_every: int = 16,
    downsamp: int = 1,
    keep_chunk_peaks: bool = False,
):
    """(plan, AccumParts) for rank's window of the file — the mergeable
    half of :func:`time_sharded_sweep` (windows merge with
    ``sweep.merge_accum_parts`` in rank order). ``downsamp`` sweeps the
    factor-downsampled series (windows align to whole raw bins);
    ``keep_chunk_peaks`` carries per-chunk peak records for multi-event
    single-pulse lists (--all-events)."""
    from pypulsar_tpu.parallel.sweep import DEFAULT_WIDTHS

    if widths is None:
        widths = DEFAULT_WIDTHS
    reader = path_or_reader
    opened = isinstance(path_or_reader, str)
    if opened:
        from pypulsar_tpu.io import filterbank

        reader = filterbank.FilterbankFile(path_or_reader)
    try:
        return _time_shard_local_accum(
            reader, dms, rank, count, nsub, group_size, chunk_payload,
            mesh, widths, engine, rfimask, checkpoint_base,
            checkpoint_every, downsamp=downsamp,
            keep_chunk_peaks=keep_chunk_peaks)
    finally:
        if opened:
            close = getattr(reader, "close", None)
            if close is not None:
                close()


def _time_shard_local_accum(reader, dms, rank, count, nsub, group_size,
                            chunk_payload, mesh, widths, engine, rfimask,
                            checkpoint_base, checkpoint_every, downsamp=1,
                            keep_chunk_peaks=False):
    import jax.numpy as jnp

    from pypulsar_tpu.parallel import make_sweep_plan
    from pypulsar_tpu.parallel.staged import (
        _MaskedSource,
        _ReaderSource,
        _downsampled_blocks,
        _mask_tag,
    )
    from pypulsar_tpu.parallel.sweep import (
        AccumParts,
        SweepCheckpoint,
        sweep_stream,
    )

    factor = max(1, int(downsamp))
    probe = _ReaderSource(reader)  # full-file view for geometry
    T = probe.nsamples // factor   # downsampled samples (the sweep grid)
    dms = np.asarray(dms, dtype=np.float64)
    # group padding so groups divide the mesh axis and land on the
    # compile plane's bucket ladder (same rule as staged._run_step;
    # group_size<=0 resolves inside make_sweep_plan, so resolve it
    # first for the ceiling arithmetic)
    from pypulsar_tpu.parallel.sweep import (
        choose_group_size,
        padded_group_count,
    )

    gs = group_size
    if gs <= 0:
        gs = choose_group_size(dms, probe.frequencies,
                               probe.tsamp * factor, nsub)
    ndm = 1 if mesh is None else mesh.shape["dm"]
    pad_groups_to = padded_group_count(-(-len(dms) // gs), ndm)
    group_size = gs
    plan = make_sweep_plan(dms, probe.frequencies, probe.tsamp * factor,
                           nsub=nsub, group_size=group_size,
                           widths=tuple(widths),
                           pad_groups_to=pad_groups_to)
    if chunk_payload is None:
        from pypulsar_tpu.parallel.sweep import default_chunk_payload

        chunk_payload = default_chunk_payload(plan, ndm=ndm)
    payload = min(chunk_payload, T)
    if payload <= plan.min_overlap:
        payload = min(T, 2 * plan.min_overlap + 1)

    # common per-channel baseline: the FILE's first (downsampled) block,
    # computed the same way sweep_stream would (f32 mean of the ingested
    # block, mask fill applied first when masking), so a 1-host run
    # bit-matches plain sweep_flat
    src0 = _ReaderSource(reader, 0, min(payload, T) * factor)
    if rfimask is not None:
        src0 = _MaskedSource(src0, rfimask)
    _, first = next(iter(_downsampled_blocks(
        src0, factor, payload, plan.min_overlap)))
    baseline = jnp.mean(jnp.asarray(first, dtype=jnp.float32), axis=1,
                        keepdims=True)

    # contiguous whole-chunk windows, chunk-balanced across hosts
    # (coordinates below are DOWNSAMPLED samples; raw file offsets scale
    # by the factor)
    nchunks = -(-T // payload)
    per = -(-nchunks // count)
    s0 = min(rank * per * payload, T)
    s1 = min((rank + 1) * per * payload, T)
    if s0 >= s1:  # more hosts than chunks: identity contribution
        D, W = plan.n_trials, len(plan.widths)
        return plan, AccumParts(
            0, np.zeros(D), np.zeros(D),
            np.full((D, W), -np.inf, np.float32),
            np.zeros((D, W), np.int64),
            float(np.asarray(baseline, np.float64).sum()))
    src = _ReaderSource(reader, s0 * factor, s1 * factor)
    if rfimask is not None:
        src = _MaskedSource(src, rfimask)
    blocks = _downsampled_blocks(src, factor, payload, plan.min_overlap)
    ckpt = (SweepCheckpoint(f"{checkpoint_base}.r{rank}",
                            every=checkpoint_every)
            if checkpoint_base else None)
    # ds tag only when downsampling: ds=1 results are bit-identical to
    # the pre-downsamp format, and tagging them would spuriously
    # invalidate every existing plain time-shard checkpoint on resume
    ds_tag = f"/ds={factor}" if factor > 1 else ""
    ctx = f"/window={s0}:{s1}{ds_tag}" + _mask_tag(rfimask)

    def block_factory(cursor_ds: int):
        """Seek-resume within this rank's window (round 5): re-root the
        stream at the checkpoint cursor instead of re-shipping the
        window's pre-cursor bytes. The cursor sits on a payload
        boundary, so the re-rooted window keeps the seam alignment."""
        from pypulsar_tpu.parallel.staged import _reroot_source

        seeked = _reroot_source(src, cursor_ds * factor)
        if seeked is None:
            return _downsampled_blocks(src, factor, payload,
                                       plan.min_overlap)
        return _downsampled_blocks(seeked, factor, payload,
                                   plan.min_overlap)

    return plan, sweep_stream(plan, blocks, payload, mesh=mesh,
                              chan_major=True, baseline=baseline,
                              engine=engine, checkpoint=ckpt,
                              checkpoint_context=ctx,
                              keep_chunk_peaks=keep_chunk_peaks,
                              finalize=False,
                              block_factory=block_factory)


def barrier(name: str = "pypulsar_barrier"):
    """Cross-host synchronization point (no-op single-process). Used by
    the time-sharded --write-dats flow: every rank must finish writing
    its segment files before rank 0 concatenates them."""
    if process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def time_sharded_ddplan(
    path_or_reader,
    ddplan,
    nsub: int = 64,
    group_size: int = 32,
    chunk_payload: Optional[int] = None,
    mesh=None,
    widths=None,
    engine: str = "auto",
    rfimask=None,
    rank: Optional[int] = None,
    count: Optional[int] = None,
    checkpoint_base: Optional[str] = None,
    checkpoint_every: int = 16,
):
    """DDplan-staged sweep of ONE file with the TIME axis sharded across
    hosts (VERDICT r4 item 3 — the realistic production shape: a staged
    plan over a single long file whose host->device wire is the
    bottleneck).

    Each DDstep is an independent flat sweep at its own downsampling, so
    the step loop simply runs :func:`time_shard_local_accum` per step —
    each host streams 1/P of the RAW bytes per step, and steps with
    downsamp > 1 additionally downsample on the HOST before the wire
    when that shrinks the shipped bytes further
    (staged._host_downsample_wins: an 8-bit file at downsamp >= 4 ships
    2/downsamp B per raw sample instead of 1 B) — so host k ships
    ~1/(P*max(downsamp/2, 1)) of each step's bytes. Merged accumulators
    cross DCN per step (~KBs). Every host returns the same
    StagedSweepResult; checkpoints go to
    ``{checkpoint_base}.step{i}.r{rank}``.
    """
    from pypulsar_tpu.parallel.staged import StagedSweepResult, StepResult
    from pypulsar_tpu.parallel.sweep import finalize_sweep, merge_accum_parts

    if rank is None:
        rank = process_index()
    if count is None:
        count = process_count()
    steps = []
    for i, st in enumerate(ddplan.DDsteps):
        dms = np.asarray(st.DMs, dtype=np.float64)
        base = f"{checkpoint_base}.step{i}" if checkpoint_base else None
        plan, local = time_shard_local_accum(
            path_or_reader, dms, rank, count, nsub=nsub,
            group_size=group_size, chunk_payload=chunk_payload, mesh=mesh,
            widths=widths, engine=engine, rfimask=rfimask,
            checkpoint_base=base, checkpoint_every=checkpoint_every,
            downsamp=int(st.downsamp))
        parts = _allgather_accums(local, count)
        merged = merge_accum_parts(parts)
        res = finalize_sweep(plan, merged.n, merged.s, merged.ss,
                             merged.mb, merged.ab, merged.baseline_sum)
        # the plan's dt already carries the step's downsampling factor
        steps.append(StepResult(downsamp=int(st.downsamp),
                                dt=float(plan.dt), result=res))
    return StagedSweepResult(steps=steps)


def _allgather_accums(local, count: int, with_peaks: bool = False,
                      nr: int = 0):
    """All ranks' AccumParts, in rank order. Packs every field into one
    f64 matrix so the collective is a single fixed-shape all-gather
    (``ab`` int64 sample positions are exact in f64 below 2^53).
    ``with_peaks`` additionally gathers the per-chunk peak records
    ([nr, W] per chunk; chunk counts differ per rank, so counts gather
    first and arrays pad to the max — every rank must pass the same
    ``with_peaks`` or the collectives deadlock)."""
    from pypulsar_tpu.parallel.sweep import AccumParts

    if count == 1:
        return [local]
    actual = process_count()
    if actual != count:
        # gathering with a mismatched grid would silently drop whole
        # windows (only `actual` rows come back) and finalize wrong SNRs
        raise ValueError(
            f"time-shard count {count} != jax process count {actual}; "
            f"for in-process testing merge time_shard_local_accum parts "
            f"with sweep.merge_accum_parts instead")
    from jax.experimental import multihost_utils

    D, W = local.mb.shape
    packed = np.concatenate([
        np.full(1, float(local.n)),
        np.full(1, local.baseline_sum),
        np.asarray(local.s, np.float64),
        np.asarray(local.ss, np.float64),
        np.asarray(local.mb, np.float64).ravel(),
        np.asarray(local.ab, np.float64).ravel(),
    ])
    gathered = np.asarray(multihost_utils.process_allgather(packed))
    parts = []
    for row in gathered:
        o = 2
        s = row[o:o + D]; o += D
        ss = row[o:o + D]; o += D
        mb = row[o:o + D * W].reshape(D, W).astype(np.float32); o += D * W
        ab = row[o:o + D * W].reshape(D, W).astype(np.int64)
        parts.append(AccumParts(int(row[0]), s, ss, mb, ab, float(row[1])))
    if with_peaks:
        nloc = len(local.chunk_mb)
        counts = np.asarray(multihost_utils.process_allgather(
            np.asarray([nloc], np.int64))).reshape(-1)
        m = int(counts.max())
        if m:
            # native dtypes (f32 peaks, i64 positions) in two gathers:
            # a single f64 buffer would cost 16 B/cell vs these 12 — at
            # survey scale (2700 chunks x 2000 trials x 6 widths) that
            # is hundreds of MB of DCN per host
            mb_buf = np.zeros((m, nr, W), np.float32)
            ab_buf = np.zeros((m, nr, W), np.int64)
            if nloc:
                mb_buf[:nloc] = np.stack(local.chunk_mb)
                ab_buf[:nloc] = np.stack(local.chunk_ab)
            g_mb = np.asarray(multihost_utils.process_allgather(mb_buf))
            g_ab = np.asarray(multihost_utils.process_allgather(ab_buf))
            for r in range(count):
                c = int(counts[r])
                parts[r] = parts[r]._replace(
                    chunk_mb=tuple(g_mb[r, i] for i in range(c)),
                    chunk_ab=tuple(g_ab[r, i] for i in range(c)))
    return parts


def multi_host_sweep(
    files: Sequence[str],
    dms=None,
    nsub: int = 64,
    group_size: int = 32,
    chunk_payload: Optional[int] = None,
    mesh=None,
    topk_per_file: int = 16,
    open_reader=None,
    *,
    ddplan=None,
    downsamp: int = 1,
    widths=None,
    engine: str = "auto",
    rfimask=None,
    checkpoint_base: Optional[str] = None,
    checkpoint_every: int = 16,
    per_file=None,
) -> np.ndarray:
    """Sweep a file list across hosts; return the merged candidate table.

    Every host sweeps ``shard_files(files)`` with the local engine (its
    own ICI mesh if ``mesh`` is given), then the per-file top-k summaries
    are all-gathered over DCN and merged by SNR. Output columns:
    ``(file_index, dm, snr, width_bins, sample, downsamp)``; every host
    returns the same merged table.

    Either a flat ``dms`` grid or a staged ``ddplan``
    (plan.ddplan.DDplan, executed per-step at its own downsampling —
    parallel.staged.sweep_ddplan) drives each file's sweep.
    ``per_file(file_index, path, staged_result)`` runs on the host that
    swept the file, right after its sweep — the artifact hook the CLI
    uses to write real per-file ``.cands``/``.dat`` products (VERDICT r3
    item 5). ``checkpoint_base`` enables in-sweep checkpointing at
    ``{checkpoint_base}.f{i}`` per file.
    """
    from pypulsar_tpu.parallel.staged import sweep_ddplan, sweep_flat
    from pypulsar_tpu.parallel.sweep import DEFAULT_WIDTHS

    if (dms is None) == (ddplan is None):
        raise ValueError("exactly one of dms / ddplan must be given")
    if widths is None:
        widths = DEFAULT_WIDTHS
    if open_reader is None:
        from pypulsar_tpu.io import filterbank

        open_reader = filterbank.FilterbankFile

    rows = []
    files = list(files)
    for fi in range(process_index(), len(files), process_count()):
        reader = open_reader(files[fi])
        ckpt = (f"{checkpoint_base}.f{fi}" if checkpoint_base else None)
        try:
            if ddplan is not None:
                staged = sweep_ddplan(reader, ddplan, nsub=nsub,
                                      group_size=group_size,
                                      widths=widths,
                                      chunk_payload=chunk_payload,
                                      mesh=mesh, engine=engine,
                                      rfimask=rfimask,
                                      checkpoint_path=ckpt,
                                      checkpoint_every=checkpoint_every)
            else:
                staged = sweep_flat(reader, dms, downsamp=downsamp,
                                    nsub=nsub, group_size=group_size,
                                    widths=widths,
                                    chunk_payload=chunk_payload, mesh=mesh,
                                    engine=engine, rfimask=rfimask,
                                    checkpoint_path=ckpt,
                                    checkpoint_every=checkpoint_every)
        finally:
            close = getattr(reader, "close", None)
            if close is not None:
                close()
        if per_file is not None:
            per_file(fi, files[fi], staged)
        for c in staged.best(topk_per_file):
            rows.append([fi, c["dm"], c["snr"], c["width_bins"],
                         c["sample"], c["downsamp"]])
    local = np.asarray(rows, dtype=np.float64).reshape(-1, 6)
    # pad_to must be identical on every host (static collective shape):
    # size for the largest per-host file share
    max_share = -(-len(files) // max(process_count(), 1))
    merged = allgather_candidates(local, pad_to=topk_per_file * max(max_share, 1))
    order = np.argsort(merged[:, 2])[::-1]
    return merged[order]
