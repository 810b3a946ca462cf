"""Run the full search chain over a fleet of observations (``survey``).

The one-command form of the per-tool chain (rfifind -> sweep
--accel-search -> sift -> foldbatch -> pfd_snr), orchestrated per
observation by the survey scheduler (``pypulsar_tpu.survey``):
device-bound stages take an exclusive device lease while host-bound
stages (sift, SNR summaries) overlap on a bounded worker pool; every
completed stage lands in a fingerprinted per-observation manifest, so a
killed fleet resumes with ``--resume`` (validated stages skipped, torn
ones redone) and a persistently failing observation is quarantined while
the rest of the fleet completes.

Usage::

    python -m pypulsar_tpu.cli survey beam*.fil -o out/ --numdms 256 \
        --accel-zmax 200 --max-host-workers 4 --telemetry-dir out/tlm
    python -m pypulsar_tpu.cli survey --status -o out/     # progress table
    python -m pypulsar_tpu.cli survey beam*.fil -o out/ --resume

Artifacts land at ``out/<stem>.*`` with exactly the bytes the serial
per-tool chain would write (the stages ARE the serial tools, invoked
in-process); the manifest is ``out/<stem>.survey.jsonl``. With
``--telemetry-dir`` each observation writes one trace plus one fleet
trace, all summarizable together via
``tlmsum 'out/tlm/*.jsonl'`` (fleet roll-up mode).

Multi-host (round 18)::

    python -m pypulsar_tpu.cli survey beam*.fil -o out/ --hosts 3
    # or, one process per machine against a shared out/:
    PYPULSAR_TPU_HOST_ID=nodeA python -m pypulsar_tpu.cli survey \
        beam*.fil -o out/ --host-id nodeA

``--hosts M`` launches M host processes of THIS command (rank env vars
``PYPULSAR_TPU_NUM_PROCESSES``/``PYPULSAR_TPU_PROCESS_ID`` set per
child, the same grid ``parallel.distributed`` reads) against the shared
``--outdir``; ``--host-id`` joins an existing fleet as one named host.
Observations are claimed through fsync'd, fencing-token'd lease files
under ``out/_fleet/`` — no coordinator service. A host that dies (or
goes heartbeat-silent past ``PYPULSAR_TPU_HOST_LEASE_S``) has its
in-flight observations adopted by the survivors, resuming from their
manifests exactly like ``--resume``; its late writes are rejected by
the fencing token. ``--status`` then adds a host-liveness block and a
per-observation owner column.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys


def build_parser():
    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.resilience import faultinject

    p = argparse.ArgumentParser(
        prog="survey",
        description="Orchestrate the rfifind -> sweep --accel-search -> "
                    "sift -> foldbatch -> pfd_snr chain over a fleet of "
                    "observations (TPU backend).")
    p.add_argument("infile", nargs="*",
                   help=".fil/.fits observations (omit with --status)")
    p.add_argument("-o", "--outdir", required=True,
                   help="directory for all artifacts + manifests; each "
                        "observation's chain is rooted at "
                        "<outdir>/<input stem>")
    p.add_argument("--status", action="store_true",
                   help="print the fleet progress table read from the "
                        "manifests in --outdir and exit")
    p.add_argument("--follow", action="store_true",
                   help="with --status: refresh the progress table "
                        "every PYPULSAR_TPU_OBS_FOLLOW_S seconds "
                        "(default 2) until interrupted; with "
                        "--status-port N it polls the live endpoint at "
                        "127.0.0.1:N instead of re-reading the files")
    p.add_argument("--status-port", type=int, default=None, metavar="N",
                   help="serve the live --status snapshot as JSON at "
                        "http://127.0.0.1:N/status.json plus Prometheus "
                        "metrics at /metrics for the duration of the "
                        "run (0 picks a free port; also "
                        "PYPULSAR_TPU_OBS_STATUS_PORT; default off)")
    p.add_argument("--resume", action="store_true",
                   help="replan from the per-observation manifests: "
                        "stages whose recorded artifacts validate "
                        "(size+sha256) are skipped, torn ones redone")
    p.add_argument("--max-host-workers", type=int, default=2,
                   help="bounded pool for host-bound stages (sift, SNR "
                        "summaries) overlapping device time (default 2)")
    p.add_argument("--devices", type=int, default=1,
                   help="exclusive device leases for device-bound "
                        "stages (default 1: one device-bound stage at a "
                        "time)")
    p.add_argument("--gang", default="auto", metavar="K|auto",
                   help="device-count per gang-able stage (the sweep "
                        "stage runs `--mesh K` over K leased chips — "
                        "ONE observation spanning K devices; artifacts "
                        "byte-identical at any K). An integer pins the "
                        "gang width; 'auto' (default) stays "
                        "fleet-parallel while ready device stages fill "
                        "the chips and widens gangs onto idle chips, "
                        "weighted by the measured per-stage cost — "
                        "each decision is recorded in the fleet trace "
                        "(survey.gang_decision)")
    p.add_argument("--retries", type=int, default=1,
                   help="bounded per-stage retries (jittered exponential "
                        "backoff) before the observation is quarantined "
                        "(default 1)")
    g = p.add_argument_group(
        "multi-host fleet (shared-directory coordination plane)")
    g.add_argument("--hosts", type=int, default=0, metavar="M",
                   help="launch M host processes of this command against "
                        "the shared --outdir (observations claimed via "
                        "fenced lease files under <outdir>/_fleet; a "
                        "dead host's in-flight observations are adopted "
                        "by survivors). Each child gets "
                        "PYPULSAR_TPU_PROCESS_ID/NUM_PROCESSES and a "
                        "hostN id. A chip belongs to ONE process, so the "
                        "children run on the CPU backend unless "
                        "JAX_PLATFORMS is already set (the launcher "
                        "says which at start-up). On a machine with "
                        "chips use one process with --devices N, which "
                        "drives every chip of the host, or start one "
                        "`survey --host-id NAME` per chip yourself, "
                        "each confined to its own chip. 0 (default): "
                        "single-process")
    g.add_argument("--host-id", default=None, metavar="NAME",
                   help="join the fleet under --outdir as ONE host named "
                        "NAME (what --hosts children do; set it yourself "
                        "to run one process per machine against a shared "
                        "filesystem; also PYPULSAR_TPU_HOST_ID)")
    g.add_argument("--host-lease", type=float, default=None, metavar="S",
                   help="heartbeat-silence bound before a host is "
                        "declared dead and its observations adoptable "
                        "(also PYPULSAR_TPU_HOST_LEASE_S; default 10)")
    g = p.add_argument_group(
        "streaming daemon (round 23: multi-tenant admission + shedding)")
    g.add_argument("--daemon", action="store_true",
                   help="run as a long-lived ingest service: watch "
                        "directories (--watch) and accept socket "
                        "submissions (--daemon-port), admitting "
                        "arrivals through per-tenant token-bucket "
                        "quotas + the resource guard into the running "
                        "fleet; past --queue-bound the daemon SHEDS "
                        "lowest-priority unaccepted work (accepted "
                        "work is journal-manifested and survives "
                        "kill+restart); SIGTERM drains cleanly")
    g.add_argument("--watch", action="append", default=[],
                   metavar="DIR[:TENANT]",
                   help="watch DIR for arriving .fil/.sf/.raw files "
                        "(ingested once size-stable for --quiesce "
                        "seconds) billed to TENANT (default "
                        "'default'); repeatable")
    g.add_argument("--daemon-port", type=int, default=None, metavar="N",
                   help="accept '<tenant> <path>' submissions on "
                        "127.0.0.1:N, one verdict line back per "
                        "request (0 picks a free port; default off)")
    g.add_argument("--tenant", action="append", default=[],
                   metavar="NAME[:PRIO[:RATE[:BURST]]]",
                   help="pin one tenant's admission contract: higher "
                        "PRIO sheds last; RATE admissions/s refill a "
                        "BURST-deep token bucket (RATE 0 = unmetered). "
                        "Unlisted tenants get the "
                        "PYPULSAR_TPU_DAEMON_TENANT_* defaults; "
                        "repeatable")
    g.add_argument("--queue-bound", type=int, default=None, metavar="N",
                   help="bounded accept queue: past N pending "
                        "(unaccepted) arrivals the daemon sheds lowest "
                        "priority / thinnest quota first (also "
                        "PYPULSAR_TPU_DAEMON_QUEUE_BOUND; default 64)")
    g.add_argument("--quiesce", type=float, default=None, metavar="S",
                   help="watch-lane quiesce window: a file becomes an "
                        "arrival only once its size is stable for S "
                        "seconds (also PYPULSAR_TPU_DAEMON_QUIESCE_S; "
                        "default 1)")
    g.add_argument("--daemon-poll", type=float, default=None,
                   metavar="S",
                   help="service-loop tick: watch scan + admission "
                        "pump + status mirror (also "
                        "PYPULSAR_TPU_DAEMON_POLL_S; default 0.5)")
    g.add_argument("--daemon-idle-exit", type=float, default=None,
                   metavar="S",
                   help="drain after S seconds with no arrivals and "
                        "nothing in flight (bounded soaks/tests; also "
                        "PYPULSAR_TPU_DAEMON_IDLE_EXIT_S; default off "
                        "= run until SIGTERM)")
    g = p.add_argument_group(
        "fleet health (deadlines, heartbeats, device strikes, admission)")
    g.add_argument("--stall-timeout", type=float, default=None,
                   metavar="S",
                   help="heartbeat-silence bound: a stage recording no "
                        "telemetry activity for S seconds is interrupted "
                        "by the watchdog and retried/quarantined like "
                        "any other failure (also PYPULSAR_TPU_STALL_S; "
                        "default off)")
    g.add_argument("--stage-deadline", type=float, default=None,
                   metavar="S",
                   help="uniform wall-clock deadline applied to EVERY "
                        "stage, overriding the per-stage "
                        "deadline_s/deadline_per_mb declarations "
                        "(default: per-stage declarations only)")
    g.add_argument("--strike-limit", type=int, default=None, metavar="K",
                   help="quarantine a device lease out of the pool after "
                        "K OOM/device-fault strikes; in-flight gangs "
                        "retry shrunk to the surviving chips (also "
                        "PYPULSAR_TPU_DEVICE_STRIKES; default 3)")
    g.add_argument("--min-free-mb", type=float, default=None, metavar="MB",
                   help="admission gate: pause launching new stages while "
                        "free disk under --outdir is below MB (in-flight "
                        "stages continue; also PYPULSAR_TPU_MIN_FREE_MB; "
                        "default 32, 0 disables)")
    g.add_argument("--max-pending", type=float, default=None, metavar="N",
                   help="admission gate: pause launching new stages while "
                        "any ship-ahead *.pending_depth gauge exceeds N "
                        "(default: off)")
    g.add_argument("--max-bad-frac", type=float, default=None,
                   metavar="FRAC",
                   help="ingest data-quality threshold: an observation "
                        "whose input reports more than FRAC of its "
                        "samples missing/invalid is quarantined with "
                        "reason 'data' (distinct from runtime "
                        "quarantine) instead of running degraded; "
                        "salvageable inputs below the bar run on their "
                        "valid prefix (also PYPULSAR_TPU_MAX_BAD_FRAC; "
                        "default 0.5)")
    p.add_argument("--telemetry-dir", default=None, metavar="DIR",
                   help="write one JSONL trace per observation plus one "
                        "fleet trace (fleet.jsonl) here; summarize "
                        "together with `tlmsum 'DIR/*.jsonl'`")
    # stage knobs (grouped; names mirror the per-tool flags)
    g = p.add_argument_group("mask stage (rfifind)")
    g.add_argument("--no-mask", dest="mask", action="store_false",
                   help="skip the RFI-mask stage (sweep runs unmasked)")
    g.add_argument("--mask-time", type=float, default=1.0,
                   help="rfifind seconds per statistics interval "
                        "(default 1.0)")
    g = p.add_argument_group("sweep stage (flat DM grid + accel handoff)")
    g.add_argument("--lodm", type=float, default=0.0)
    g.add_argument("--dmstep", type=float, default=1.0)
    g.add_argument("--numdms", type=int, default=32)
    g.add_argument("-s", "--nsub", type=int, default=64)
    g.add_argument("--group-size", type=int, default=0)
    g.add_argument("--downsamp", type=int, default=1)
    g.add_argument("--chunk", type=int, default=None)
    g.add_argument("--threshold", type=float, default=6.0)
    g.add_argument("--accel-zmax", type=float, default=200.0)
    g.add_argument("--accel-dz", type=float, default=2.0)
    g.add_argument("--accel-numharm", type=int, default=8,
                   choices=(1, 2, 4, 8))
    g.add_argument("--accel-sigma", type=float, default=2.0)
    g.add_argument("--accel-batch", type=int, default=None,
                   help="spectra per accel dispatch (default: the tuned "
                        "PYPULSAR_TPU_ACCEL_BATCH knob — env > "
                        "auto-tuning cache > 32; explicit value wins)")
    g.add_argument("--spectral", action="store_true",
                   help="spectral fusion (round 15): the sweep stage "
                        "serves accel-search from device-resident fused "
                        "spectra (sweep --spectral) instead of teeing "
                        "per-DM .dats, and the fold stage streams the "
                        "raw file. A science knob (it is part of the "
                        "manifest fingerprint): changing it restarts "
                        "affected manifests")
    g = p.add_argument_group("sift stage")
    g.add_argument("--sift-sigma", type=float, default=4.0)
    g.add_argument("--sift-min-hits", type=int, default=2)
    g.add_argument("--sift-min-dm", type=float, default=None)
    g = p.add_argument_group("fold stage")
    g.add_argument("--fold-nbins", type=int, default=64)
    g.add_argument("--fold-npart", type=int, default=32)
    g.add_argument("--fold-batch", type=int, default=32)
    telemetry.add_telemetry_flag(
        p, what="fleet trace: per-stage spans + scheduler counters; "
                "--telemetry-dir is the multi-trace form")
    faultinject.add_fault_flag(p)
    faultinject.add_chaos_flag(p)
    return p


def _status_text(outdir: str, port=None):
    """One rendered progress table (or None when no manifests exist):
    read from a live ``--status-port`` endpoint when ``port`` is given,
    else straight from the manifest/plane files."""
    from pypulsar_tpu.survey.state import format_status

    if port:
        import json
        import urllib.request

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/status.json", timeout=5) as r:
            snap = json.load(r)
        if not snap.get("rows"):
            return None
        return format_status(snap["rows"], health=snap.get("health"),
                             plane=snap.get("plane"),
                             capsules=snap.get("capsules"),
                             tenants=snap.get("tenants"))
    from pypulsar_tpu.obs.statusd import capsules_by_obs
    from pypulsar_tpu.survey.daemon import read_tenant_status
    from pypulsar_tpu.survey.fleet import read_plane_status
    from pypulsar_tpu.survey.state import (
        MANIFEST_SUFFIX,
        read_fleet_health,
        status_rows,
    )

    paths = sorted(glob.glob(os.path.join(outdir, "*" + MANIFEST_SUFFIX)))
    if not paths:
        return None
    return format_status(status_rows(paths),
                         health=read_fleet_health(outdir),
                         plane=read_plane_status(outdir),
                         capsules=capsules_by_obs(outdir),
                         tenants=read_tenant_status(outdir))


def _status(outdir: str, follow: bool = False, port=None) -> int:
    text = _status_text(outdir, port=port)
    if text is None:
        print(f"# no survey manifests under {outdir!r}", file=sys.stderr)
        return 1
    print(text)
    if not follow:
        return 0
    import time as _time

    from pypulsar_tpu.tune import knobs

    interval = max(0.2, float(knobs.env_float(
        "PYPULSAR_TPU_OBS_FOLLOW_S")))
    try:
        while True:
            _time.sleep(interval)
            text = _status_text(outdir, port=port)
            # ANSI clear + home: a refreshing view, not a scrolling log
            sys.stdout.write("\033[2J\033[H")
            print(text if text is not None
                  else f"# no survey manifests under {outdir!r}")
            sys.stdout.flush()
    except KeyboardInterrupt:
        return 0


def _launch_hosts(args, argv) -> int:
    """The ``--hosts M`` launcher: M child processes of this same
    command (``--hosts`` stripped, per-child ``--host-id``), each a
    full fleet host claiming observations through the shared plane.
    The rank env vars are the SAME grid ``parallel.distributed`` reads,
    so a ``jax.distributed`` coordinator (real multi-machine TPU pods)
    threads through unchanged — on collective-less CPU backends the
    children simply never call initialize() and coordinate purely
    through the plane files.

    A chip belongs to one process at a time, and M children of one
    machine cannot share its chips unless each is confined to its own.
    The launcher does not partition chips: its children run on the CPU
    backend unless the operator already set ``JAX_PLATFORMS`` (then
    they inherit it and the operator owns the one-process-per-chip
    split). It prints which, so the CPU choice is never silent; it
    stays off JAX itself."""
    import subprocess

    child_argv = []
    skip = 0
    for a in (argv if argv is not None else sys.argv[1:]):
        if skip:
            skip -= 1
            continue
        if a == "--hosts":
            skip = 1
            continue
        if a.startswith("--hosts="):
            continue
        child_argv.append(a)
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms:
        print(f"# survey: --hosts {args.hosts}: children inherit "
              f"JAX_PLATFORMS={platforms}; a chip belongs to one process "
              f"— confining each child to its own chip is yours to do")
    else:
        platforms = "cpu"
        print(f"# survey: --hosts {args.hosts}: children run on the CPU "
              f"backend (JAX_PLATFORMS=cpu). For the chips of this "
              f"machine use one process with --devices N")
    procs = []
    for rank in range(args.hosts):
        env = dict(os.environ)
        env["PYPULSAR_TPU_NUM_PROCESSES"] = str(args.hosts)
        env["PYPULSAR_TPU_PROCESS_ID"] = str(rank)
        env["JAX_PLATFORMS"] = platforms
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "pypulsar_tpu.cli", "survey",
             *child_argv, "--host-id", f"host{rank}"], env=env))
    rc = 0
    for rank, proc in enumerate(procs):
        code = proc.wait()
        print(f"# survey: host{rank} (pid {proc.pid}) exited {code}")
        rc = max(rc, abs(code))
    return rc


def _observations(infiles, outdir):
    from pypulsar_tpu.survey.state import Observation

    obs = []
    seen = set()
    for fn in infiles:
        stem = os.path.splitext(os.path.basename(fn))[0]
        if stem in seen:
            raise ValueError(
                f"duplicate observation stem {stem!r}: fleet inputs must "
                f"have distinct basenames (their artifact chains share "
                f"{outdir!r})")
        seen.add(stem)
        obs.append(Observation(stem, fn, os.path.join(outdir, stem)))
    return obs


def main(argv=None):
    p = build_parser()
    args = p.parse_args(argv)
    if args.status:
        return _status(args.outdir, follow=args.follow,
                       port=args.status_port)
    if not args.infile and not (args.daemon and
                                (args.watch or
                                 args.daemon_port is not None)):
        p.error("give at least one observation (or --status, or "
                "--daemon with --watch/--daemon-port)")
    if args.hosts and args.hosts < 1:
        p.error(f"--hosts must be >= 1, got {args.hosts}")
    if args.hosts and args.host_id:
        p.error("--hosts launches its own named hosts; give one or the "
                "other")
    if args.daemon and (args.hosts or args.host_id):
        p.error("--daemon is a single-host service; run one daemon "
                "per host, each with its own --outdir")
    if args.hosts:
        os.makedirs(args.outdir, exist_ok=True)
        return _launch_hosts(args, argv)
    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.resilience import faultinject

    faultinject.configure_from_env()
    if args.fault_inject:
        faultinject.configure(args.fault_inject)
    if args.fault_chaos:
        try:
            faultinject.configure_chaos(args.fault_chaos)
        except ValueError as e:
            print(f"survey: {e}", file=sys.stderr)
            return 2
    os.makedirs(args.outdir, exist_ok=True)
    from pypulsar_tpu.survey.fleet import ENV_HOST_ID
    from pypulsar_tpu.tune import knobs

    host = args.host_id or knobs.env_str(ENV_HOST_ID) or None
    fleet_trace = args.telemetry
    if args.telemetry_dir:
        os.makedirs(args.telemetry_dir, exist_ok=True)
        if fleet_trace is None:
            # per-host fleet traces: M hosts sharing one telemetry dir
            # must not clobber each other's scheduler trace
            name = f"fleet.{host}.jsonl" if host else "fleet.jsonl"
            fleet_trace = os.path.join(args.telemetry_dir, name)
    meta = {"tool": "survey"}
    if host:
        # the stitched timeline's lane key: tlmtrace maps each trace
        # file to a process lane by its meta host
        meta["host"] = host
    with telemetry.session_from_flag(fleet_trace, **meta):
        return _run(args)


def _survey_config(args):
    from pypulsar_tpu.survey.dag import SurveyConfig

    return SurveyConfig(
        mask=args.mask, mask_time=args.mask_time,
        lodm=args.lodm, dmstep=args.dmstep, numdms=args.numdms,
        nsub=args.nsub, group_size=args.group_size,
        downsamp=args.downsamp, chunk=args.chunk,
        threshold=args.threshold,
        accel_zmax=args.accel_zmax, accel_dz=args.accel_dz,
        accel_numharm=args.accel_numharm, accel_sigma=args.accel_sigma,
        accel_batch=args.accel_batch, accel_spectral=args.spectral,
        sift_sigma=args.sift_sigma, sift_min_hits=args.sift_min_hits,
        sift_min_dm=args.sift_min_dm,
        fold_nbins=args.fold_nbins, fold_npart=args.fold_npart,
        fold_batch=args.fold_batch)


def _parse_gang(args):
    """The --gang flag's value, or None + a printed error."""
    gang = args.gang
    if gang != "auto":
        try:
            gang = int(gang)
        except ValueError:
            print(f"survey: --gang must be an integer or 'auto', got "
                  f"{gang!r}", file=sys.stderr)
            return None
        if gang > args.devices:
            print(f"survey: --gang {gang} exceeds --devices "
                  f"{args.devices}", file=sys.stderr)
            return None
    return gang


def _run(args) -> int:
    from pypulsar_tpu.survey.scheduler import FleetScheduler

    if args.daemon:
        return _run_daemon(args)
    try:
        obs = _observations(args.infile, args.outdir)
    except ValueError as e:
        print(f"survey: {e}", file=sys.stderr)
        return 2
    cfg = _survey_config(args)
    gang = _parse_gang(args)
    if gang is None:
        return 2
    plane = None
    host_id = args.host_id or None
    if host_id is None:
        from pypulsar_tpu.survey.fleet import ENV_HOST_ID
        from pypulsar_tpu.tune import knobs

        host_id = knobs.env_str(ENV_HOST_ID) or None
    if host_id is not None:
        # multi-host: join the shared plane, and give the jax
        # distributed runtime its chance too (env-driven; a no-op
        # without a coordinator address — the plane itself needs no
        # collectives, so CPU fleets coordinate purely through files)
        from pypulsar_tpu.parallel import distributed
        from pypulsar_tpu.survey.fleet import FleetPlane

        try:
            distributed.initialize()
        except Exception as e:  # noqa: BLE001 - collective-less backend
            print(f"# survey[{host_id}]: jax.distributed unavailable "
                  f"({type(e).__name__}); coordinating via the plane "
                  f"files only")
        plane = FleetPlane(args.outdir, host_id=host_id,
                           lease_s=args.host_lease)
    try:
        sched = FleetScheduler(
            obs, cfg, max_host_workers=args.max_host_workers,
            devices=args.devices, retries=args.retries,
            resume=args.resume,
            telemetry_dir=args.telemetry_dir, gang=gang,
            stall_s=args.stall_timeout,
            stage_deadline=args.stage_deadline,
            strike_limit=args.strike_limit, min_free_mb=args.min_free_mb,
            max_pending=args.max_pending, max_bad_frac=args.max_bad_frac,
            plane=plane, verbose=True)
    except ValueError as e:  # e.g. --devices beyond the real chip count
        print(f"survey: {e}", file=sys.stderr)
        return 2
    server = None
    status_port = args.status_port
    if status_port is None:
        from pypulsar_tpu.tune import knobs

        port = int(knobs.env_int("PYPULSAR_TPU_OBS_STATUS_PORT"))
        status_port = port if port > 0 else None
    if status_port is not None:
        from pypulsar_tpu.obs.statusd import StatusServer

        try:
            server = StatusServer(args.outdir, status_port).start()
            print(f"# survey: live status at {server.url}/status.json "
                  f"(+ Prometheus {server.url}/metrics)")
        except OSError as e:
            # observability is a passenger: a taken port must not stop
            # the fleet
            print(f"# survey: --status-port {status_port} disabled "
                  f"({e})", file=sys.stderr)
    try:
        result = sched.run()
    finally:
        if server is not None:
            server.close()
    n_stages = len(sched.stages)
    tag = f"[{host_id}] " if host_id else ""
    print(f"# survey: {tag}{len(obs)} observations x {n_stages} stages "
          f"in {result.wall:.2f}s — {len(result.ran)} stages run, "
          f"{len(result.skipped)} skipped (validated), "
          f"{result.retried} retried, "
          f"{len(result.quarantined)} observations quarantined")
    if plane is not None:
        print(f"#   multi-host: {len(result.remote_done)} observations "
              f"finished by other hosts, {len(result.adopted)} adopted "
              f"here ({', '.join(result.adopted) or 'none'}), "
              f"{len(result.ceded)} ceded to adopters")
    if result.timeouts:
        print(f"#   watchdog interrupts: {result.timeouts} "
              f"(deadline/stall; see survey.deadline_exceeded / "
              f"survey.stage_stalled events in the traces)")
    if result.evicted_devices:
        print(f"#   device leases QUARANTINED mid-fleet: "
              f"{sorted(result.evicted_devices)} (see "
              f"_fleet_health.json / survey --status)")
    for name, q in sorted(result.quarantined.items()):
        tag = ("DATA-QUARANTINED" if q.get("reason") == "data"
               else "QUARANTINED")
        print(f"#   {tag} {name} at {q['stage']}: {q['error']}")
    if not result.ok:
        return 1
    return 0


def _parse_watch(spec: str):
    """``DIR[:TENANT]`` — a bare DIR bills the ``default`` tenant."""
    d, sep, tenant = spec.rpartition(":")
    if sep and d and tenant and os.sep not in tenant:
        return d, tenant
    return spec, "default"


def _run_daemon(args) -> int:
    """The ``--daemon`` service: a SurveyDaemon around a service-mode
    fleet, SIGTERM/SIGINT wired to a clean drain, positional infiles
    fed through the same admission path as every other arrival."""
    import signal

    from pypulsar_tpu.survey.daemon import SurveyDaemon, parse_tenant_spec

    gang = _parse_gang(args)
    if gang is None:
        return 2
    try:
        tenants = [parse_tenant_spec(s) for s in args.tenant]
    except ValueError as e:
        print(f"survey: {e}", file=sys.stderr)
        return 2
    watch = [_parse_watch(s) for s in args.watch]
    daemon = SurveyDaemon(
        args.outdir, _survey_config(args),
        tenants=tenants, watch=watch,
        initial=[("default", fn) for fn in args.infile],
        port=args.daemon_port,
        queue_bound=args.queue_bound, quiesce_s=args.quiesce,
        poll_s=args.daemon_poll, idle_exit_s=args.daemon_idle_exit,
        min_free_mb=args.min_free_mb, max_pending=args.max_pending,
        verbose=True,
        max_host_workers=args.max_host_workers, devices=args.devices,
        retries=args.retries, telemetry_dir=args.telemetry_dir,
        gang=gang, stall_s=args.stall_timeout,
        stage_deadline=args.stage_deadline,
        strike_limit=args.strike_limit,
        max_bad_frac=args.max_bad_frac)
    for signum in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(signum, lambda *_: daemon.request_drain())
        except ValueError:
            pass  # not the main thread (tests drive run() directly)
    server = None
    status_port = args.status_port
    if status_port is None:
        from pypulsar_tpu.tune import knobs

        port = int(knobs.env_int("PYPULSAR_TPU_OBS_STATUS_PORT"))
        status_port = port if port > 0 else None
    if status_port is not None:
        from pypulsar_tpu.obs.statusd import StatusServer

        try:
            server = StatusServer(args.outdir, status_port).start()
            print(f"# survey: live status at {server.url}/status.json "
                  f"(+ Prometheus {server.url}/metrics)")
        except OSError as e:
            print(f"# survey: --status-port {status_port} disabled "
                  f"({e})", file=sys.stderr)
    print("# survey: daemon up — SIGTERM drains (accepted work "
          "finishes; the unaccepted queue is shed with recorded "
          "reasons)")
    try:
        result = daemon.run()
    finally:
        if server is not None:
            server.close()
    s = daemon.stats()
    print(f"# survey: daemon drained — {s['submitted']} submitted, "
          f"{s['accepted']} accepted, {s['shed']} shed, "
          f"{s['quarantined']} quarantined, {s['completed']} completed")
    if result is not None and not result.ok:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
