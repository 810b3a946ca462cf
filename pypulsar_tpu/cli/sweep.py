"""DM sweep from the command line — the framework's prepsubband-equivalent.

Reads a SIGPROC filterbank or PSRFITS file, runs the sharded TPU sweep
engine over a DM range (flat grid or a DDplan2b staged plan executed
per-step at its own downsample factor), and writes a single-pulse
candidate list; optionally per-DM dedispersed .dat/.inf time series.

This is the user-facing workload BASELINE.md configs[2] names: the
reference generates the plan (utils/DDplan2b.py:202-273) and hands
execution to PRESTO's prepsubband/single_pulse_search; here the whole
pipeline runs inside the framework on device.

Candidate file format (``{outbase}.cands``)::

    # DM      SNR    time_s     sample  width_bins  downsamp
    80.0000   12.31  0.700000   700     2           1
"""

from __future__ import annotations

import argparse
import os

import numpy as np

from pypulsar_tpu.io.opener import open_reader
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.tune import knobs

# ``{outbase}`` + this exists while a flat run may have tmps staged
RUN_MARKER = ".sweep.inprogress"


def _engine_arg(value: str) -> str:
    """argparse validator for ``--engine``: checked against the ENGINES
    registry AT PARSE TIME with a difflib closest-match hint (the
    cli/__main__ unknown-tool pattern) — an unknown engine used to
    surface as a ValueError deep inside resolve_engine, mid-run, after
    the reader was already streaming."""
    from pypulsar_tpu.parallel.sweep import ENGINES

    valid = ("auto",) + ENGINES
    if value in valid:
        return value
    import difflib

    close = difflib.get_close_matches(value, valid, n=1)
    hint = "; did you mean %r?" % close[0] if close else ""
    raise argparse.ArgumentTypeError(
        "unknown sweep engine %r%s (expected one of %s)"
        % (value, hint, ", ".join(valid)))


def _apply_tuning(args, reader) -> None:
    """Round-17 auto-tuning consult for the flat single-file path:
    install the cached throughput config for this run's ACTUAL geometry
    (tune/cache.py keys: nchan, nsamp bucket, dtype, engine, backend,
    jax version) before any chunk geometry is resolved. Env vars and
    explicit flags still win; PYPULSAR_TPU_TUNE=off disables."""
    from pypulsar_tpu import tune
    from pypulsar_tpu.parallel.sweep import resolve_engine

    try:
        nchan = len(np.asarray(reader.frequencies))
        nsamp = int(getattr(reader, "nsamples", 0) or 0) or None
        dtype = "nbits%d" % int(getattr(reader, "nbits", 32) or 32)
        engine = resolve_engine(args.engine)
    except Exception:  # noqa: BLE001 - tuning is a passenger, never the payload
        return
    tune.apply_cached("sweep", nchan=nchan, nsamp=nsamp, dtype=dtype,
                      engine=engine)
    if args.accel_search:
        ds = max(1, int(args.downsamp))
        tune.apply_cached("accel",
                          nsamp=(nsamp // ds if nsamp else None),
                          zmax=int(args.accel_zmax))


def _write_cands(path, cands, extra_cols=()):
    """Write candidate/event/pulse rows atomically (tmp + os.replace —
    downstream consumers must never see a truncated table); ``extra_cols``
    appends (header, key, fmt) columns after the shared six. The finite
    gate drops any row with a non-finite DM/SNR/time (counted in
    ``data.nonfinite_cands_dropped``): garbage in the stream can degrade
    a run, never poison its published tables."""
    from pypulsar_tpu.resilience.dataguard import finite_rows
    from pypulsar_tpu.resilience.journal import atomic_write_text

    cands = finite_rows(cands, ("dm", "snr", "time_sec"),
                        what=os.path.basename(path))
    lines = ["# DM      SNR      time_s       sample    width_bins  "
             "downsamp" + "".join("  " + h for h, _, _ in extra_cols)
             + "\n"]
    for c in cands:
        lines.append(
            f"{c['dm']:<9.4f} {c['snr']:<8.3f} {c['time_sec']:<12.6f} "
            f"{c['sample']:<9d} {c['width_bins']:<11d} "
            f"{c['downsamp']:<8d}"
            + "".join("  " + fmt % c[k] for _, k, fmt in extra_cols)
            + "\n")
    atomic_write_text(path, "".join(lines))


def _write_dats_auto(outbase, reader, dms, args, rfimask=None):
    """--write-dats dispatcher: the in-memory exact writer for data that
    fits comfortably on device, the streamed two-stage writer
    (staged.write_dats_streamed, prepsubband semantics) past that — a
    900 s x 1024-chan window is 57.6 GB as resident f32, far beyond
    HBM. PYPULSAR_TPU_DATS_RESIDENT_LIMIT (bytes, default 2e9) sets the
    crossover."""
    import numpy as _np

    from pypulsar_tpu.parallel.staged import _make_source, write_dats_streamed

    T = _make_source(reader).nsamples
    C = len(_np.asarray(reader.frequencies))
    limit = float(knobs.env_float("PYPULSAR_TPU_DATS_RESIDENT_LIMIT"))
    if 4.0 * C * T <= limit:
        _write_dats(outbase, reader, dms, args.downsamp, rfimask=rfimask)
    else:
        write_dats_streamed(outbase, reader, dms, downsamp=args.downsamp,
                            nsub=args.nsub, group_size=args.group_size,
                            rfimask=rfimask, engine=args.engine,
                            chunk_payload=args.chunk, verbose=True)


def _write_dats(outbase, reader, dms, downsamp, rfimask=None):
    """Write per-DM dedispersed time series (.dat + .inf), flat mode only.
    ``rfimask`` applies the sweep's median-mid80 mask fill so the .dat
    series reflects the masked data the candidates came from. One
    difference remains: fill values here are whole-file per-channel
    statistics, while the streaming sweep computes them per chunk —
    masked cells can differ where a channel's level drifts."""
    from pypulsar_tpu.io.datfile import write_dat
    from pypulsar_tpu.parallel.staged import _make_source

    spec = reader.get_spectra(0, _make_source(reader).nsamples)
    if rfimask is not None:
        hifreq_first = bool(np.asarray(spec.freqs)[0]
                            > np.asarray(spec.freqs)[-1])
        chanmask = rfimask.get_chan_mask(0, spec.numspectra,
                                         hifreq_first=hifreq_first)
        spec = spec.masked(chanmask, maskval="median-mid80")
    if downsamp > 1:
        spec = spec.downsample(downsamp)
    freqs = np.asarray(spec.freqs)
    from pypulsar_tpu.parallel.staged import make_dat_inf

    for dm in dms:
        ts = np.asarray(spec.dedispersed_timeseries(float(dm)),
                        dtype=np.float32)
        inf = make_dat_inf(f"{outbase}_DM{dm:.2f}", reader, float(dm),
                           len(ts), float(spec.dt), freqs)
        write_dat(f"{outbase}_DM{dm:.2f}", ts, inf)


def _make_ddplan(reader, args):
    """DDplan2b plan from a reader's header geometry + the CLI's
    --lodm/--hidm/--plan-numsub/--resolution (shared by the single-file
    and multi-file paths)."""
    import numpy as np

    from pypulsar_tpu.plan.ddplan import Observation

    freqs = np.asarray(reader.frequencies, dtype=np.float64)
    bw = abs(freqs.max() - freqs.min()) + abs(
        freqs[1] - freqs[0] if len(freqs) > 1 else 0.0)
    obs = Observation(dt=float(reader.tsamp),
                      fctr=float(freqs.mean()),
                      BW=float(bw), numchan=len(freqs))
    return obs.gen_ddplan(args.lodm, args.hidm,
                          numsub=args.plan_numsub,
                          resolution=args.resolution)


def _remove_stale_checkpoints(base):
    """Remove exactly the checkpoint files a run rooted at ``base`` could
    have written (never a glob: a prefix pattern could match unrelated
    user files living next to the checkpoint)."""
    stale = [base, base + ".tmp.npz"]
    for i in range(256):
        stale += [f"{base}.step{i}.npz",
                  f"{base}.step{i}.npz.tmp.npz",
                  f"{base}.step{i}.done.npz",
                  f"{base}.step{i}.done.npz.tmp.npz"]
    for fn in stale:
        if os.path.exists(fn):
            os.remove(fn)


def _close(reader):
    close = getattr(reader, "close", None)
    if close is not None:
        close()


def _emit_events(staged, outbase, args):
    """Write the --all-events artifacts (.events multi-event list +
    .pulses friends-of-friends groups) — shared by the flat single-file
    and time-shard paths so grouping defaults cannot diverge."""
    from pypulsar_tpu.parallel.events import group_events

    events = staged.events(args.threshold)
    _write_cands(outbase + ".events", events)
    # grouping tolerances follow the search grid unless overridden:
    # one pulse spans adjacent trials (DM) and boxcar widths (time)
    dm_tol = (args.group_dm_tol if args.group_dm_tol is not None
              else max(3.0 * args.dmstep, 1.0))
    time_tol = (args.group_time_tol if args.group_time_tol is not None
                else 4.0 * max(e["width_sec"] for e in events)
                if events else 0.02)
    pulses = group_events(events, time_tol=time_tol, dm_tol=dm_tol)
    _write_cands(outbase + ".pulses", pulses, extra_cols=(
        ("n_hits", "n_hits", "%-7d"), ("dm_lo", "dm_lo", "%-8.3f"),
        ("dm_hi", "dm_hi", "%-8.3f")))
    print(f"# {len(events)} above-threshold events -> {outbase}.events; "
          f"{len(pulses)} grouped pulses -> {outbase}.pulses "
          f"(time_tol={time_tol:.4g}s, dm_tol={dm_tol:.4g})")


def _load_mask(args):
    """The --mask rfifind mask, or None (shared by all three sweep
    entry paths)."""
    if not args.maskfile:
        return None
    from pypulsar_tpu.io.rfimask import RfifindMask

    return RfifindMask(args.maskfile)


def _main_multi(args, ap, widths):
    """Multi-file / multi-host sweep (SURVEY.md §2.4 rows 4-5): this
    host's round-robin share of the file list is swept locally (flat or
    DDplan-staged), REAL per-file artifacts are written next to each
    swept file (``{base}.cands``; flat mode honors ``--write-dats``), and
    the per-file top-k summaries are all-gathered over DCN into one
    merged table every host writes identically
    (``{outbase}_merged.cands``)."""
    import numpy as np

    from pypulsar_tpu.parallel import distributed as dist
    from pypulsar_tpu.parallel import make_mesh

    files = list(args.infile)
    rfimask = _load_mask(args)
    mesh = None
    if args.mesh:
        # lease_devices, NOT jax.local_devices()[:N]: under a scheduler
        # gang lease the thread's leased chips come first (two leased
        # runs must never both grab chips 0..N-1), and under
        # jax.distributed it stays host-local (the global list includes
        # other hosts' devices, which a host-local shard_map cannot
        # address)
        from pypulsar_tpu.parallel.mesh import lease_devices

        mesh = make_mesh([args.mesh], ("dm",),
                         devices=lease_devices(args.mesh))
    if args.all_events:
        ap.error("--all-events is a single-file option")

    ddplan = None
    dms = None
    if args.ddplan:
        if args.hidm is None:
            ap.error("--ddplan requires --hidm")
        # plan geometry from the FIRST file's header so every host
        # executes the identical plan (survey files share geometry)
        reader0 = open_reader(files[0])
        try:
            ddplan = _make_ddplan(reader0, args)
        finally:
            _close(reader0)
        if dist.process_index() == 0:
            print(f"# DDplan: {len(ddplan.DDsteps)} steps, "
                  f"{sum(s.numDMs for s in ddplan.DDsteps)} DM trials, "
                  f"{len(files)} files over {dist.process_count()} hosts")
    else:
        if args.numdms is None:
            ap.error("flat mode requires --numdms (or use --ddplan)")
        dms = args.lodm + args.dmstep * np.arange(args.numdms)

    if args.checkpoint and not args.resume:
        # clean only THIS host's round-robin share: on shared storage a
        # slow rank cleaning all indices would race a fast rank already
        # writing its fresh checkpoints
        for fi in range(dist.process_index(), len(files),
                        dist.process_count()):
            _remove_stale_checkpoints(f"{args.checkpoint}.f{fi}")

    def per_file(fi, path, staged):
        base = os.path.splitext(path)[0]
        hits = staged.above_threshold(args.threshold)
        _write_cands(base + ".cands", hits)
        if args.write_dats and not args.ddplan:
            reader = open_reader(path)
            try:
                _write_dats_auto(base, reader, dms, args,
                            rfimask=rfimask)
            finally:
                _close(reader)
        print(f"# [host {dist.process_index()}] {path}: "
              f"{staged.n_trials} trials, {len(hits)} detections "
              f">= {args.threshold} sigma -> {base}.cands")

    merged = dist.multi_host_sweep(
        files, dms, nsub=args.nsub, group_size=args.group_size,
        chunk_payload=args.chunk, mesh=mesh, topk_per_file=args.topk,
        open_reader=open_reader, ddplan=ddplan, downsamp=args.downsamp,
        widths=widths, engine=args.engine, rfimask=rfimask,
        checkpoint_base=args.checkpoint,
        checkpoint_every=args.checkpoint_every, per_file=per_file)

    outbase = args.outbase or (os.path.splitext(files[0])[0] + "_multi")
    rows = [dict(dm=m[1], snr=m[2], sample=int(m[4]),
                 width_bins=int(m[3]), downsamp=int(m[5]),
                 file=files[int(m[0])]) for m in merged]
    from pypulsar_tpu.resilience.journal import atomic_open

    # atomic (PL003): the merged table is the multi-host run's one
    # artifact — a kill mid-write must not leave a torn table
    with atomic_open(outbase + "_merged.cands", "w") as f:
        f.write("# DM      SNR      sample    width_bins  downsamp  file\n")
        for r in rows:
            f.write(f"{r['dm']:<9.4f} {r['snr']:<8.3f} {r['sample']:<9d} "
                    f"{r['width_bins']:<11d} {r['downsamp']:<9d} "
                    f"{r['file']}\n")
    print(f"# merged: {len(rows)} candidates over {len(files)} files "
          f"({dist.process_count()} hosts) -> {outbase}_merged.cands")
    for r in rows[: args.topk]:
        print(f"DM {r['dm']:8.3f}  SNR {r['snr']:7.2f}  sample "
              f"{r['sample']:9d}  width {r['width_bins']:3d}  "
              f"ds {r['downsamp']}  {r['file']}")
    return 0


def _write_dats_timeshard(outbase, reader, dms, args, rfimask, dist):
    """Time-sharded --write-dats: rank k streams its whole-chunk window
    once more through the streamed writer (staged.write_dats_streamed),
    writing ``{outbase}_DM*.wK.dat`` segments; after a barrier rank 0
    concatenates the segments in rank order (bit-exact vs the sequential
    writer — tests/test_staged.py) and stamps the .inf sidecars with the
    full length. Requires a shared filesystem across ranks, the same
    assumption the merged .cands artifact already makes."""
    from pypulsar_tpu.parallel.staged import (dats_geometry, write_dat_infs,
                                              write_dats_streamed)
    from pypulsar_tpu.resilience.journal import atomic_open

    rank, count = dist.process_index(), dist.process_count()
    plan, payload, T = dats_geometry(reader, dms, downsamp=args.downsamp,
                                     nsub=args.nsub,
                                     group_size=args.group_size,
                                     chunk_payload=args.chunk)
    nchunks = -(-T // payload)
    per = -(-nchunks // count)
    s0 = min(rank * per * payload, T)
    s1 = min((rank + 1) * per * payload, T)
    if s0 < s1:
        write_dats_streamed(outbase, reader, dms, downsamp=args.downsamp,
                            nsub=args.nsub, group_size=args.group_size,
                            rfimask=rfimask, engine=args.engine,
                            chunk_payload=payload, window=(s0, s1),
                            suffix=f".w{rank}", write_inf=False)
    dist.barrier("write_dats_segments")
    if rank != 0:
        return
    import shutil

    for dm in dms:
        base = f"{outbase}_DM{dm:.2f}"
        # atomic concat (PL003): a kill mid-concat must not leave a
        # torn .dat posing as the full observation; each segment is
        # dropped as it is consumed so peak disk stays ~1x
        with atomic_open(base + ".dat", "wb") as out:
            for r in range(count):
                seg = f"{base}.w{r}.dat"
                if os.path.exists(seg):
                    with open(seg, "rb") as f:
                        shutil.copyfileobj(f, out, 1 << 24)
                    os.remove(seg)
    write_dat_infs(outbase, reader, dms, T,
                   float(reader.tsamp) * max(1, args.downsamp))


def _main_timeshard(args, ap, widths):
    """One file, its time axis sharded across hosts (VERDICT r4: the
    streamed sweep is wire-bound per host, BENCHNOTES; time windows cut
    each host's wire bytes by 1/P while the merge traffic is ~KBs).
    Supports --ddplan (per-step time-sharded sweeps,
    distributed.time_sharded_ddplan) and --write-dats (each rank writes
    its window's .dat segments, rank 0 concatenates after a barrier)."""
    import numpy as np

    from pypulsar_tpu.parallel import distributed as dist
    from pypulsar_tpu.parallel import make_mesh
    from pypulsar_tpu.parallel.staged import StagedSweepResult, StepResult

    infile = args.infile[0]
    outbase = args.outbase or os.path.splitext(infile)[0]
    if not args.ddplan and args.numdms is None:
        ap.error("flat mode requires --numdms (or use --ddplan)")
    rfimask = _load_mask(args)
    mesh = None
    if args.mesh:
        # lease-aware device resolution (see _main_multi)
        from pypulsar_tpu.parallel.mesh import lease_devices

        mesh = make_mesh([args.mesh], ("dm",),
                         devices=lease_devices(args.mesh))
    if args.checkpoint and not args.resume:
        rank = dist.process_index()
        _remove_stale_checkpoints(f"{args.checkpoint}.r{rank}")
        # time_sharded_ddplan roots its per-step checkpoints at
        # {base}.step{i}.r{rank} (step BEFORE rank — the reverse order
        # of the flat path's step files)
        for i in range(256):
            for fn in (f"{args.checkpoint}.step{i}.r{rank}",
                       f"{args.checkpoint}.step{i}.r{rank}.tmp.npz"):
                if os.path.exists(fn):
                    os.remove(fn)
    reader = open_reader(infile)
    try:
        dt = float(reader.tsamp)
        if args.ddplan:
            if args.hidm is None:
                ap.error("--ddplan requires --hidm")
            plan = _make_ddplan(reader, args)
            if dist.process_index() == 0:
                print(f"# DDplan: {len(plan.DDsteps)} steps, "
                      f"{sum(s.numDMs for s in plan.DDsteps)} total DM "
                      f"trials, time-sharded over "
                      f"{dist.process_count()} hosts")
            staged = dist.time_sharded_ddplan(
                reader, plan, nsub=args.nsub, group_size=args.group_size,
                chunk_payload=args.chunk, mesh=mesh, widths=widths,
                engine=args.engine, rfimask=rfimask,
                checkpoint_base=args.checkpoint,
                checkpoint_every=args.checkpoint_every)
            dms = None
        else:
            dms = args.lodm + args.dmstep * np.arange(args.numdms)
            res = dist.time_sharded_sweep(
                reader, dms, nsub=args.nsub, group_size=args.group_size,
                chunk_payload=args.chunk, mesh=mesh, widths=widths,
                engine=args.engine, rfimask=rfimask,
                checkpoint_base=args.checkpoint,
                checkpoint_every=args.checkpoint_every,
                downsamp=args.downsamp,
                keep_chunk_peaks=args.all_events)
            staged = StagedSweepResult(
                steps=[StepResult(downsamp=args.downsamp,
                                  dt=dt * args.downsamp, result=res)])
        if args.write_dats:
            _write_dats_timeshard(outbase, reader, dms, args, rfimask,
                                  dist)
    finally:
        _close(reader)
    hits = staged.above_threshold(args.threshold)
    if dist.process_index() == 0:
        _write_cands(outbase + ".cands", hits)
        if args.all_events:
            _emit_events(staged, outbase, args)
    print(f"# [host {dist.process_index()}/{dist.process_count()}] "
          f"time-sharded: {staged.n_trials} DM trials, {len(hits)} "
          f"detections >= {args.threshold} sigma -> {outbase}.cands")
    for c in staged.best(args.topk):
        print(f"DM {c['dm']:8.3f}  SNR {c['snr']:7.2f}  t "
              f"{c['time_sec']:10.4f}s  width {c['width_bins']:3d} bins "
              f"({c['width_sec']*1e3:.2f} ms)  ds {c['downsamp']}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="sweep",
        description="DM-trial sweep of a .fil/.fits file on the TPU engine")
    ap.add_argument("infile", nargs="+",
                    help=".fil or PSRFITS input(s). More than one file "
                         "engages the multi-file batch axis: each file is "
                         "swept on this host's share (round-robin across "
                         "hosts under jax.distributed) with per-file "
                         ".cands artifacts plus one merged table")
    ap.add_argument("-o", "--outbase", default=None,
                    help="output basename (default: input sans extension)")
    ap.add_argument("--lodm", type=float, default=0.0, help="lowest trial DM")
    ap.add_argument("--dmstep", type=float, default=1.0,
                    help="flat-mode DM step (pc/cm^3)")
    ap.add_argument("--numdms", type=int, default=None,
                    help="flat-mode number of DM trials")
    ap.add_argument("--ddplan", action="store_true",
                    help="derive a staged DDplan2b plan from --lodm/--hidm "
                         "and execute each step at its own downsampling")
    ap.add_argument("--hidm", type=float, default=None,
                    help="highest DM (required with --ddplan)")
    ap.add_argument("--plan-numsub", type=int, default=0,
                    help="DDplan subband count hint (prepsubband staging)")
    ap.add_argument("--resolution", type=float, default=0.0,
                    help="DDplan acceptable time resolution (ms)")
    ap.add_argument("-s", "--nsub", type=int, default=64,
                    help="sweep-engine subbands (two-stage dedispersion)")
    ap.add_argument("--group-size", type=int, default=0,
                    help="DM trials per stage-1 group; 0 (default) picks "
                         "the largest group whose extra subband smearing "
                         "stays under one sample (25%% faster at dense "
                         "trial spacing, measured BENCHNOTES.md)")
    ap.add_argument("--downsamp", type=int, default=1,
                    help="flat-mode downsample factor")
    ap.add_argument("--chunk", type=int, default=None,
                    help="streaming chunk payload in (downsampled) samples")
    ap.add_argument("--widths", default="1,2,4,8,16,32",
                    help="comma-separated boxcar widths in bins")
    ap.add_argument("--threshold", type=float, default=6.0,
                    help="SNR threshold for the .cands file")
    ap.add_argument("-k", "--topk", type=int, default=10,
                    help="candidates to print")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard DM trials over this many devices — the "
                         "sweep pass AND the --accel-search handoff "
                         "(DM-sharded dedispersion, batch-sharded "
                         "prep+search; artifacts byte-identical at any "
                         "device count). Devices come from the active "
                         "gang lease when the survey scheduler placed "
                         "this run, else the local device list")
    ap.add_argument("--engine", default="auto", type=_engine_arg,
                    help="chunk-kernel formulation: auto (fourier on "
                         "TPU, gather elsewhere), fourier, or gather "
                         "(the bit-parity reference); validated here "
                         "against the ENGINES registry with a "
                         "closest-match hint")
    ap.add_argument("--mask", dest="maskfile", default=None,
                    help="rfifind .mask file (ours or PRESTO's) applied "
                         "per block with median-mid80 fill")
    ap.add_argument("--write-dats", action="store_true",
                    help="flat mode: also write per-DM .dat/.inf series "
                         "(with --accel-search this becomes an optional "
                         "TEE of the handoff's own stream — always the "
                         "STREAMED two-stage writer's bytes, i.e. "
                         "prepsubband semantics, even below the "
                         "PYPULSAR_TPU_DATS_RESIDENT_LIMIT crossover "
                         "where plain --write-dats picks the exact "
                         "in-memory writer)")
    ap.add_argument("--accel-search", action="store_true",
                    help="flat single-file mode: after the sweep, stream "
                         "every DM trial's dedispersed series DIRECTLY "
                         "into the batched acceleration search "
                         "(parallel.accelpipe.sweep_accel_stream) and "
                         "write {outbase}_DM*_ACCEL_*.cand files — no "
                         ".dat write + re-read between the stages "
                         "(745.9 s of the round-5 configs[4] chain); "
                         "candidate tables are bit-identical to the "
                         ".dat round trip (parity-tested)")
    ap.add_argument("--accel-only", action="store_true",
                    help="with --accel-search: skip the single-pulse "
                         "sweep pass and its .cands, running only the "
                         "dedisperse->accel handoff")
    ap.add_argument("--spectral", action="store_true",
                    help="with --accel-search: serve the accel search "
                         "from device-resident fused spectra "
                         "(parallel.specfuse) — the per-trial series "
                         "never round-trips through the host and prep "
                         "collapses to one dispatch per DM slice, with "
                         "candidate tables BIT-identical to the "
                         "streamed device-prep handoff; "
                         "PYPULSAR_TPU_SPECFUSE_MODE=decimate "
                         "additionally elides the per-trial "
                         "irfft+rfft pair outright on single-chunk "
                         "power-of-two geometries (circular boundary "
                         "semantics, opt-in). Excludes --write-dats "
                         "(no series to tee) and "
                         "--no-accel-device-prep")
    ap.add_argument("--accel-zmax", type=float, default=200.0,
                    help="accel handoff: max drift in Fourier bins "
                         "(default 200)")
    ap.add_argument("--accel-dz", type=float, default=2.0,
                    help="accel handoff: drift step in bins (default 2)")
    ap.add_argument("--accel-numharm", type=int, default=8,
                    choices=(1, 2, 4, 8),
                    help="accel handoff: max harmonics summed (default 8)")
    ap.add_argument("--accel-sigma", type=float, default=2.0,
                    help="accel handoff: candidate significance floor "
                         "(default 2)")
    ap.add_argument("--accel-batch", type=int, default=None,
                    help="accel handoff: spectra per device dispatch "
                         "against the shared template banks (default: "
                         "the tuned PYPULSAR_TPU_ACCEL_BATCH knob — "
                         "env var > auto-tuning cache > 32; an explicit "
                         "value here always wins)")
    ap.add_argument("--accel-max-cands", type=int, default=200,
                    help="accel handoff: cap on written candidates per "
                         "trial (default 200)")
    ap.add_argument("--accel-device-prep", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="accel handoff: rfft + deredden each batch on "
                         "device (default on, the matched-candidate "
                         "contract path; --no-accel-device-prep uses "
                         "the byte-parity host prep)")
    ap.add_argument("--accel-skip-existing", action="store_true",
                    help="accel handoff: skip trials whose .cand already "
                         "exists (restart a killed run without "
                         "re-searching finished trials; tables stay "
                         "bit-identical to an uninterrupted run)")
    ap.add_argument("--accel-prefetch", type=int, default=1,
                    help="accel handoff: batches prepped ahead of the "
                         "device search (accel.pipe.pending_depth "
                         "gauge; 0 = inline). Default 1")
    ap.add_argument("--group-time-tol", type=float, default=None,
                    help="event-grouping time tolerance in seconds "
                         "(default: 4x the widest boxcar)")
    ap.add_argument("--group-dm-tol", type=float, default=None,
                    help="event-grouping DM tolerance (default: 3x the "
                         "trial step, floor 1)")
    ap.add_argument("--all-events", action="store_true",
                    help="flat mode: record the strongest peak per "
                         "streaming chunk for every (DM, width) and write "
                         "all above-threshold events to {outbase}.events. "
                         "Event granularity is one per chunk, so --chunk "
                         "sets the minimum pulse separation (defaults to "
                         "16384 samples with this flag)")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="persist in-sweep state to PATH for --resume")
    ap.add_argument("--checkpoint-every", type=int, default=16,
                    help="chunks between checkpoint writes (default 16)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from an existing --checkpoint file "
                         "(without this flag stale checkpoints are removed)")
    ap.add_argument("--journal", default=None, metavar="PATH.jsonl",
                    help="flat single-file mode: keep a per-run JSONL "
                         "work-unit journal (resilience.RunJournal) of "
                         "completed artifacts across the sweep->accel "
                         "chain, with per-output size/sha256 validation "
                         "on resume — a truncated artifact is redone, "
                         "never trusted; rerunning with the same journal "
                         "skips validated-complete units")
    ap.add_argument("--time-shard", action="store_true",
                    help="multi-host mode for ONE file: each host streams "
                         "its own contiguous window of the time axis "
                         "(overlap-save seams) and ~KB accumulators merge "
                         "over DCN — the scale-out for a single file whose "
                         "host->device wire is the bottleneck "
                         "(parallel.distributed.time_sharded_sweep). Flat "
                         "mode only; every host computes the identical "
                         "result and rank 0 writes the artifacts")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="multi-host mode: jax.distributed coordinator "
                         "(defaults to $PYPULSAR_TPU_COORDINATOR; no-op "
                         "when unset)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="multi-host mode: total host count "
                         "($PYPULSAR_TPU_NUM_PROCESSES)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="multi-host mode: this host's rank "
                         "($PYPULSAR_TPU_PROCESS_ID)")
    from pypulsar_tpu.resilience import faultinject

    telemetry.add_telemetry_flag(
        ap, what="per-chunk spans, H2D/D2H byte counters, device stats")
    faultinject.add_fault_flag(ap)
    args = ap.parse_args(argv)

    faultinject.configure_from_env()
    if args.fault_inject:
        faultinject.configure(args.fault_inject)
    with telemetry.session_from_flag(args.telemetry, tool="sweep"), \
            telemetry.span("cli.sweep", aggregate=False):
        return _main_parsed(args, ap)


def _main_parsed(args, ap):
    from pypulsar_tpu.parallel import distributed as dist
    from pypulsar_tpu.parallel import make_mesh
    from pypulsar_tpu.parallel.staged import sweep_ddplan, sweep_flat

    if args.ddplan and args.write_dats:
        ap.error("--write-dats is a flat-mode option (DDplan steps use "
                 "varying time resolutions)")
    if args.ddplan and args.downsamp != 1:
        ap.error("--downsamp is a flat-mode option (DDplan sets per-step "
                 "downsampling itself)")
    if args.all_events and args.ddplan:
        ap.error("--all-events is a flat-mode option")
    if args.all_events and args.chunk is None:
        # without chunking the whole series is one chunk and the event
        # list degenerates to the single best peak per (DM, width)
        args.chunk = 16384
    if args.resume and not args.checkpoint:
        ap.error("--resume requires --checkpoint PATH")
    if args.accel_search:
        if args.ddplan:
            ap.error("--accel-search is a flat-mode option (the handoff "
                     "searches one fixed time resolution)")
        if args.time_shard or len(args.infile) > 1:
            ap.error("--accel-search streams ONE file on this host")
    if args.accel_only and not args.accel_search:
        ap.error("--accel-only requires --accel-search")
    if args.spectral:
        if not args.accel_search:
            ap.error("--spectral requires --accel-search (it is the "
                     "fused sweep->accel handoff)")
        if args.write_dats:
            ap.error("--spectral has no time series to tee: drop "
                     "--write-dats or use the streamed handoff")
        if not args.accel_device_prep:
            ap.error("--spectral IS device prep: it cannot combine "
                     "with --no-accel-device-prep")
    if args.journal and (args.ddplan or args.time_shard
                         or len(args.infile) > 1):
        ap.error("--journal is a flat single-file option (the journal "
                 "manifests one sweep->accel chain; DDplan/multi-host "
                 "runs have their own checkpoint machinery)")
    widths = tuple(int(w) for w in args.widths.split(","))
    dist.initialize(args.coordinator, args.num_processes, args.process_id)
    if args.time_shard:
        if len(args.infile) > 1:
            ap.error("--time-shard sweeps ONE file (file batching is the "
                     "default multi-file mode)")
        if args.downsamp < 1:
            ap.error("--downsamp must be >= 1")
        return _main_timeshard(args, ap, widths)
    if len(args.infile) > 1 or dist.is_distributed():
        if args.accel_search:
            # the multi-host path never reaches the handoff branch;
            # exiting 0 with no .cand files would be a silent no-op
            ap.error("--accel-search is a single-host option (the "
                     "handoff runs on this host's flat single-file "
                     "path)")
        return _main_multi(args, ap, widths)
    args.infile = args.infile[0]
    outbase = args.outbase or os.path.splitext(args.infile)[0]
    if args.checkpoint and not args.resume:
        _remove_stale_checkpoints(args.checkpoint)
    # batch head: reader open (format sniff + header), then mask load and
    # tuned config (the block source and the sweep plan itself are built
    # per step: staged.sweep_flat / _run_step, same span name)
    reader = open_reader(args.infile)
    with telemetry.span("sweep.plan"):
        rfimask = _load_mask(args)
        _apply_tuning(args, reader)
    mesh = None
    if args.mesh:
        # build the mesh from the LEASED device set, never bare
        # jax.devices()[:N] — under the survey scheduler's gang leases
        # two concurrent observations would otherwise silently share
        # chips 0..N-1 (the mesh/lease collision)
        from pypulsar_tpu.parallel.mesh import lease_devices

        mesh = make_mesh([args.mesh], ("dm",),
                         devices=lease_devices(args.mesh))

    rc = 0
    if args.ddplan:
        if args.hidm is None:
            ap.error("--ddplan requires --hidm")
        plan = _make_ddplan(reader, args)
        print(f"# DDplan: {len(plan.DDsteps)} steps, "
              f"{sum(s.numDMs for s in plan.DDsteps)} total DM trials")
        staged = sweep_ddplan(reader, plan, nsub=args.nsub,
                              group_size=args.group_size, widths=widths,
                              chunk_payload=args.chunk, mesh=mesh,
                              verbose=True,
                              checkpoint_path=args.checkpoint,
                              checkpoint_every=args.checkpoint_every,
                              engine=args.engine, rfimask=rfimask)
    else:
        if args.numdms is None:
            ap.error("flat mode requires --numdms (or use --ddplan)")
        dms = args.lodm + args.dmstep * np.arange(args.numdms)
        journal = None
        journal_done = set()
        if args.journal:
            from pypulsar_tpu.resilience.journal import RunJournal

            journal = RunJournal(
                args.journal,
                _journal_fingerprint(args, dms, widths, outbase),
                tool="sweep-accel")
            journal_done = journal.completed()
        with telemetry.span("sweep.plan", n_trials=len(dms)) as sp:
            listed, removed = _clear_killed_run(outbase, dms, args)
            if sp is not None:
                sp.set(listed=listed, removed=removed)
        staged = None
        if not args.accel_only:
            if journal is not None and "sweep:cands" in journal_done:
                # the manifest says the single-pulse pass's artifacts are
                # on disk, complete and checksum-valid — resume straight
                # into the accel chain instead of re-sweeping
                print(f"# journal: {outbase}.cands validated complete; "
                      f"skipping the single-pulse sweep pass")
            else:
                staged = sweep_flat(reader, dms, downsamp=args.downsamp,
                                    nsub=args.nsub,
                                    group_size=args.group_size,
                                    widths=widths, chunk_payload=args.chunk,
                                    mesh=mesh,
                                    checkpoint_path=args.checkpoint,
                                    checkpoint_every=args.checkpoint_every,
                                    engine=args.engine,
                                    keep_chunk_peaks=args.all_events,
                                    rfimask=rfimask)
                # publish (and journal) the sweep artifacts BEFORE the
                # accel stage: a kill during the (long) accel chain must
                # not force a resumed run to re-sweep
                _emit_sweep_artifacts(staged, outbase, args, journal)
                staged = None
        if args.accel_search:
            # streamed sweep->accel handoff: the dedispersed series feed
            # prep_spectra_batch/accel_search_batch in RAM; --write-dats
            # tees the identical bytes to disk instead of gating on them
            from pypulsar_tpu.fourier.accelsearch import AccelSearchConfig
            from pypulsar_tpu.parallel.accelpipe import sweep_accel_stream

            acfg = AccelSearchConfig(
                zmax=args.accel_zmax, dz=args.accel_dz,
                numharm=args.accel_numharm, sigma_min=args.accel_sigma)
            summary = sweep_accel_stream(
                reader, dms, acfg, outbase,
                batch=args.accel_batch, downsamp=args.downsamp,
                nsub=args.nsub,
                # pass the flag through unchanged (0 = auto resolves
                # inside make_sweep_plan): the .dat round trip resolves
                # it the same way, which the bit-parity contract needs —
                # stage-1 groups dedisperse at the GROUP mean DM, so a
                # different group size is a different series
                group_size=args.group_size,
                rfimask=rfimask, engine=args.engine,
                chunk_payload=args.chunk, write_dats=args.write_dats,
                max_cands=args.accel_max_cands,
                device_prep=args.accel_device_prep,
                skip_existing=args.accel_skip_existing,
                prefetch_depth=args.accel_prefetch,
                # --mesh now spans the WHOLE chain: the handoff shards
                # the (dm x spectrum) axes over the same devices the
                # sweep pass used (artifacts byte-identical at any k)
                journal=journal, mesh=mesh, spectral=args.spectral,
                verbose=True)
            print(f"# accel handoff: {summary['n_searched']} trials "
                  f"searched, {summary['n_skipped']} skipped"
                  + (f", {summary['serial_fallbacks']} serial fallbacks"
                     if summary["serial_fallbacks"] else "")
                  + (f", {summary['n_failed']} FAILED"
                     if summary["n_failed"] else ""))
            if summary["n_failed"]:
                # match cli/accelsearch: a partially-failed run must not
                # exit 0 (drivers gate bench records on the return code)
                # — but the completed single-pulse sweep's artifacts
                # below must still be written first
                rc = 1
        elif args.write_dats:
            _write_dats_auto(outbase, reader, dms, args, rfimask=rfimask)
        if journal is not None:
            journal.close()
        # every writer has renamed its tmp into place
        os.remove(outbase + RUN_MARKER)

    if staged is not None:  # the DDplan path emits at the end
        _emit_sweep_artifacts(staged, outbase, args, None)
    return rc


def _journal_fingerprint(args, dms, widths, outbase) -> str:
    """Hash of everything that determines the flat chain's artifacts —
    including ``outbase``, which names them: a rerun under a different -o
    must produce its own artifacts, not skip against the old ones. A
    journal written under different parameters must not be resumed."""
    import hashlib

    h = hashlib.sha256()
    h.update(np.asarray(dms, dtype=np.float64).tobytes())
    h.update(np.int64(widths).tobytes())
    h.update(np.float64([args.threshold, args.accel_zmax, args.accel_dz,
                         args.accel_sigma]).tobytes())
    h.update(np.int64([args.downsamp, args.nsub, args.group_size,
                       args.accel_numharm, int(bool(args.accel_search)),
                       int(bool(args.all_events)),
                       args.accel_max_cands,
                       # device- and host-prep candidates only match
                       # within tolerance, not bit-identically: a resume
                       # must not mix prep provenances in one run (the
                       # spectral fused path is a third provenance)
                       int(bool(args.accel_device_prep)),
                       int(bool(args.spectral))]).tobytes())
    h.update((args.infile + "|" + (args.maskfile or "")
              + "|" + outbase).encode())
    return h.hexdigest()


def _clear_killed_run(outbase, dms, args):
    """Remove a killed run's tmp debris and mark this run in progress.
    Debris outlives only a killed run, and a killed run leaves its marker
    behind, so a clean run pays one stat whatever the directory holds:
    four os.path.exists a trial took 0.64-0.84 s a batch at 1024 trials
    on a v5e host (PERF.md §5). Returns (names tested, files removed)."""
    marker = outbase + RUN_MARKER
    if os.path.exists(marker):
        return _remove_stale_output_tmps(outbase, dms, args)
    open(marker, "w").close()
    return 0, 0


def _remove_stale_output_tmps(outbase, dms, args):
    """Remove tmp debris a killed run's atomic writers can leave — the
    EXACT derived names only (never a glob: a prefix pattern could match
    unrelated user files): per-DM .dat/.inf staging tmps plus the accel
    handoff's .cand/.txtcand tmps. Returns (names tested, files removed)."""
    from pypulsar_tpu.parallel.accelpipe import accel_out_names

    listed = removed = 0
    for dm in dms:
        base = f"{outbase}_DM{dm:.2f}"
        stale = [base + ".dat.tmp", base + ".inf.tmp"]
        candfn, txtfn = accel_out_names(base, args.accel_zmax, 0.0)
        stale += [candfn + ".tmp", txtfn + ".tmp"]
        listed += len(stale)
        for fn in stale:
            if os.path.exists(fn):
                os.remove(fn)
                removed += 1
    return listed, removed


def _emit_sweep_artifacts(staged, outbase, args, journal):
    """Write the single-pulse artifacts (.cands + optional .events/
    .pulses), record them in the run journal, and print the summary —
    one definition for the flat and DDplan paths."""
    with telemetry.span("sweep.finalize") as sp:  # event extraction
        hits = staged.above_threshold(args.threshold)
        if sp is not None:
            sp.set(rows=len(hits))
    with telemetry.span("sweep.write", rows=len(hits)):
        _write_cands(outbase + ".cands", hits)
        outputs = [outbase + ".cands"]
        if args.all_events:
            _emit_events(staged, outbase, args)
            outputs += [outbase + ".events", outbase + ".pulses"]
        if journal is not None:
            journal.done("sweep:cands", outputs)
    print(f"# {staged.n_trials} DM trials swept; {len(hits)} detections "
          f">= {args.threshold} sigma -> {outbase}.cands")
    for c in staged.best(args.topk):
        print(f"DM {c['dm']:8.3f}  SNR {c['snr']:7.2f}  t "
              f"{c['time_sec']:10.4f}s  width {c['width_bins']:3d} bins "
              f"({c['width_sec']*1e3:.2f} ms)  ds {c['downsamp']}")


if __name__ == "__main__":
    raise SystemExit(main())
