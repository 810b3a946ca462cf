"""Fleet scheduler: device leases + a bounded host pool over the obs DAG.

Survey-scale pipelines are throughput systems (arXiv:1601.01165 frames
dedispersion surveys exactly this way): the accelerator must stay
saturated while host-side IO, prep and post-processing for OTHER beams
proceed concurrently. The serial per-tool chain leaves the device idle
during every sift and pfd_snr; this scheduler runs the per-observation
stage DAG (:mod:`.dag`) over the whole fleet with two execution lanes:

- **device lane** — ``device_bound`` stages queue for exclusive device
  leases drawn from a pool of N chips (default 1: one device-bound
  stage at a time). The queue is priority + FIFO: deeper stages first
  (drain observations toward completion, bounding in-flight
  intermediate artifacts), submission order breaking ties. A stage
  whose spec declares ``devices_max > 1`` may be **gang-leased**: one
  execution holds k chips at once (the stage's ``gang_argv`` spans
  them, e.g. ``sweep --mesh k``), the alternative placement to
  fleet-parallel k-obs-x-1-chip. ``gang`` picks the shape — a fixed k,
  or ``"auto"``: fleet-parallel while enough ready device stages exist
  to fill the chips, widening gangs (scaled by the measured per-stage
  cost share from this run's completed stages — the numbers the obs
  traces record) when chips would otherwise idle. Every placement
  decision lands in the fleet trace as a ``survey.gang_decision`` event
  (k, chips, reason) and in the observation's trace. Gang acquisition
  is FIFO with full reservation (an older waiting claim reserves freed
  chips), so a wide gang can never starve behind a stream of 1-chip
  stages. Leased chips publish thread-locally
  (``parallel.mesh.device_lease``), which is where ``cli/sweep
  --mesh`` resolves its mesh devices — two concurrent gangs can never
  both address chips 0..k-1.
- **host lane** — host-bound stages (sift, pfd_snr summaries) run on a
  bounded worker pool (``max_host_workers``), overlapping the device
  lane.

Failure policy: a stage that raises an ordinary Exception (including a
nonzero CLI exit, an injected IO fault, an OOM that escaped the in-stage
halving) retries up to ``retries`` times with bounded, seeded-jitter
exponential backoff (lockstep retries of leases that failed together
would collide again; ``resilience.retry.backoff_delay``); past that the
OBSERVATION is quarantined — recorded in its manifest, its remaining
stages cancelled, the fleet continues — instead of aborting the run. A
BaseException (``faultinject.InjectedKill``, KeyboardInterrupt) unwinds
the whole fleet like a signal: nothing is marked done that did not
finish, and a ``--resume`` replans from the manifests.

Fleet health (round 12, ``resilience.health``): stages heartbeat
through the telemetry they already record (activity hooks); a watchdog
thread interrupts a stage that outruns its declared deadline
(``StageSpec.deadline_s``/``deadline_per_mb``, or the uniform
``stage_deadline`` override) or stops heartbeating for ``stall_s``
(``--stall-timeout`` / ``PYPULSAR_TPU_STALL_S``) — the interrupt is an
ordinary Exception, so a hung stage lands in the same retry ->
quarantine path, with ``survey.deadline_exceeded`` /
``survey.stage_stalled`` events in the fleet and obs traces and its
lease(s) reclaimed. Device-fault/OOM failures charge strikes against
the leased chips (``parallel.mesh.device_health``); a chip past K
strikes is evicted from the pool mid-fleet (never the last healthy
one) and retried gangs shrink to the survivors — placement is excluded
from fingerprints, so the shrunk retry's artifacts stay byte-identical.
Before launching new work the scheduler consults the
``resilience.health.ResourceGuard`` admission gate (free disk under the
artifact root, ship-ahead ``*.pending_depth`` backpressure): a failing
gate pauses *scheduling* (``survey.admission_paused``), never the
stages in flight. Per-device verdicts are mirrored to
``<outdir>/_fleet_health.json`` for ``survey --status``.

Fault points (``--fault-inject`` / PYPULSAR_TPU_FAULTS), armed at stage
boundaries: ``survey.stage_start`` / ``survey.stage_done`` (any stage,
Nth hit) and the per-stage ``survey.stage_start.<name>`` /
``survey.stage_done.<name>``. ``stage_done`` trips AFTER the artifacts
are written but BEFORE the manifest records them — the torn-stage window
a resume must redo.

Multi-host fleet (round 18, ``survey.fleet``): pass a registered
:class:`~pypulsar_tpu.survey.fleet.FleetPlane` and this scheduler
becomes ONE HOST of an M-host fleet sharing the artifact directory.
Observations are then not pre-assigned: a claim/adopt loop takes them
one at a time through the plane's fenced lease files (at most
``devices`` in flight per host, so a slow host never hoards the queue),
opens the per-obs manifest lazily UNDER the held claim (token-stamped,
fence-checked on every append), and resumes an adopted observation from
its journal exactly as a single-host ``--resume`` would — validated
stages skip, torn ones redo, bytes identical. A host whose heartbeat
goes silent past ``PYPULSAR_TPU_HOST_LEASE_S`` has its in-flight
observations adopted by survivors; if it was merely stalled (netstall,
paused VM) and wakes, its next manifest append raises ``StaleLeaseError``
and the observation is CEDED — not retried, not quarantined: the adopter
owns it now (host-aware failure policy). Hosts charge
:class:`~pypulsar_tpu.resilience.health.HostHealth` strikes on the
deaths they observe (and on their own cedes); a host past the strike
limit stops claiming new work and drains out. Each host's stage spans
and fleet events are stamped ``host=<id>`` so ``tlmsum`` renders the
per-host roll-up.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from pypulsar_tpu.obs import flightrec, telemetry, tracing
from pypulsar_tpu.parallel import broker as broker_mod
from pypulsar_tpu.resilience import faultinject
from pypulsar_tpu.resilience import health as health_mod
from pypulsar_tpu.resilience import locks as locks_mod
from pypulsar_tpu.resilience.retry import backoff_delay, is_oom_error
from pypulsar_tpu.survey import fleet as fleet_mod
from pypulsar_tpu.survey.dag import StageSpec, SurveyConfig, build_dag, stage_names
from pypulsar_tpu.tune import knobs as knobs_mod
from pypulsar_tpu.survey.state import (
    Observation,
    ObsManifest,
    ObsTrace,
    fleet_fingerprint,
    write_fleet_health,
)

__all__ = ["FleetResult", "FleetScheduler"]

# bounded, jittered backoff between retries of a failed stage (base *
# 2^attempt capped, then scaled by seeded jitter — see
# resilience.retry.backoff_delay): the delay runs on a timer thread,
# NOT the lane worker, so a backing-off observation never stalls the
# device lease or a host slot
RETRY_BACKOFF_BASE_S = 0.25
RETRY_BACKOFF_MAX_S = 5.0

# auto-gang cost gate: a gang-able stage whose measured mean cost is
# under this share of the whole device chain runs 1-chip even when
# chips idle — k chips on a minor stage buys k x the lease churn for a
# sliver of wall time (env-overridable: a fleet of near-equal stages
# may want a lower bar)
GANG_COST_MIN_FRAC = health_mod.env_float(
    "PYPULSAR_TPU_GANG_COST_MIN_FRAC", 0.25)

_UNSET = object()  # _n_jax_devices cache sentinel (None = no backend)

_PENDING, _QUEUED, _RUNNING, _DONE, _QUARANTINED, _REMOTE = range(6)

# Stages whose device work submits typed units to the batch broker
# (round 24), and the broker party kind each stage registers as.  Only
# these stages are eligible for batch-lane claims.
_BROKER_UNITS = {"sweep": "accel", "fold": "fold"}


@dataclass
class FleetResult:
    """What one scheduler run did: ``ran`` (executed this run, in
    completion order), ``skipped`` (validated complete from the
    manifests — the resume contract's receipt), ``quarantined``
    (obs -> failing stage + error), ``retried`` stage-retry count."""

    ran: List[Tuple[str, str]] = field(default_factory=list)
    skipped: List[Tuple[str, str]] = field(default_factory=list)
    quarantined: Dict[str, Dict[str, str]] = field(default_factory=dict)
    retried: int = 0
    timeouts: int = 0  # watchdog interrupts (deadline + stall)
    evicted_devices: List[int] = field(default_factory=list)
    wall: float = 0.0
    # multi-host bookkeeping (empty without a plane): observations this
    # host ADOPTED from a dead/left host, observations it CEDED to a
    # higher fencing token, and observations other live hosts finished
    adopted: List[str] = field(default_factory=list)
    ceded: List[str] = field(default_factory=list)
    remote_done: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.quarantined


class _Task:
    __slots__ = ("obs_i", "stage", "state", "attempts", "seq",
                 "last_dev_ids", "last_real_dev_ids", "last_error",
                 "done_recorded", "lane_seq")

    def __init__(self, obs_i: int, stage: StageSpec):
        self.obs_i = obs_i
        self.stage = stage
        self.state = _PENDING
        self.attempts = 0
        self.seq = -1
        self.last_dev_ids: Optional[List[int]] = None
        self.last_real_dev_ids: Optional[List[int]] = None
        self.last_error = ""
        # set the instant the manifest records this execution done: a
        # watchdog interrupt landing after that point must finish the
        # task, not retry it
        self.done_recorded = False
        # queue seq this task was batch-lane-claimed at (round 24): the
        # lane runs the task out-of-band, so its original queue entry
        # goes stale; a worker popping THAT seq consumes it silently. A
        # retry re-enqueue gets a new seq and runs normally.
        self.lane_seq: Optional[int] = None


class FleetScheduler:
    """See module docstring. ``stages`` defaults to the standard five-
    stage DAG (:func:`build_dag`); tests inject synthetic DAGs."""

    def __init__(self, observations: Sequence[Observation],
                 cfg: Optional[SurveyConfig] = None, *,
                 stages: Optional[Sequence[StageSpec]] = None,
                 max_host_workers: int = 2, devices: int = 1,
                 retries: int = 1, resume: bool = False,
                 telemetry_dir: Optional[str] = None,
                 gang="auto",
                 stall_s: Optional[float] = None,
                 stage_deadline: Optional[float] = None,
                 strike_limit: Optional[int] = None,
                 min_free_mb: Optional[float] = None,
                 max_pending: Optional[float] = None,
                 max_bad_frac: Optional[float] = None,
                 jitter_rng=None,
                 plane: Optional["fleet_mod.FleetPlane"] = None,
                 verbose: bool = False,
                 service: bool = False):
        self.cfg = cfg if cfg is not None else SurveyConfig()
        self.stages = list(stages) if stages is not None \
            else build_dag(self.cfg)
        self._by_name = {s.name: s for s in self.stages}
        self._depth = {s.name: i for i, s in enumerate(self.stages)}
        for s in self.stages:
            for d in s.deps:
                if d not in self._by_name:
                    raise ValueError(f"stage {s.name!r} depends on "
                                     f"unknown stage {d!r}")
        self.obs = list(observations)
        names = [o.name for o in self.obs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate observation names: {names}")
        self.max_host_workers = max(1, int(max_host_workers))
        self.devices = max(1, int(devices))
        self._njax: object = _UNSET
        if self.devices > 1:
            n = self._n_jax_devices()
            if n is not None and self.devices > n:
                # a lease IS a chip: more leases than chips would wrap
                # onto chip 0 and report k-chip work that ran on one
                raise ValueError(
                    f"--devices {self.devices} exceeds the {n} local "
                    f"JAX device(s): a device lease is one real chip")
        self.retries = max(0, int(retries))
        self.resume = resume
        self.telemetry_dir = telemetry_dir
        if telemetry_dir:
            # ObsTrace silently disables itself on an unopenable path
            # (observability is a passenger) — a missing directory would
            # drop every trace, so create it here for library callers,
            # not just the CLI
            try:
                os.makedirs(telemetry_dir, exist_ok=True)
            except OSError:
                pass
        if gang != "auto":
            gang = max(1, int(gang))
            if gang > self.devices:
                raise ValueError(f"--gang {gang} exceeds the "
                                 f"{self.devices} device leases")
        self.gang = gang
        self.verbose = verbose

        # fleet health: heartbeats + watchdog, device strikes, admission
        if stall_s is None:
            stall_s = health_mod.env_float(health_mod.ENV_STALL_S, None)
        self.stall_s = stall_s
        self.stage_deadline = stage_deadline
        self.jitter_rng = jitter_rng
        self._hb = health_mod.HeartbeatRegistry()
        self._watchdog: Optional[health_mod.Watchdog] = None
        self._health = self._make_device_health(strike_limit)
        root = (os.path.dirname(self.obs[0].outbase) or "."
                if self.obs else ".")
        self._health_dir = root if self.obs else None
        self._guard = health_mod.ResourceGuard(
            root,
            min_free_bytes=(min_free_mb * 1e6
                            if min_free_mb is not None else None),
            max_pending=max_pending)
        # degrade-vs-quarantine threshold for the INGEST data-quality
        # verdict (resilience.dataguard): an observation whose input
        # reports more than this fraction of its samples missing/invalid
        # is data-quarantined before burning any device time
        if max_bad_frac is None:
            from pypulsar_tpu.resilience import dataguard

            max_bad_frac = dataguard.max_bad_frac_default()
        self.max_bad_frac = float(max_bad_frac)
        self._admission_blocked = False  # one event per pause episode

        # ONE mutex behind two guards (the bare lock for state peeks,
        # the condition for wait/notify) — lockdep-tracked under a
        # single name, so the order graph sees them as the one lock
        # they are (docs/ARCHITECTURE.md "Concurrency model")
        self._lock = locks_mod.TrackedLock("survey.sched")
        self._cv = locks_mod.TrackedCondition("survey.sched",
                                              lock=self._lock)
        self._device_q: "queue.PriorityQueue" = queue.PriorityQueue()
        self._host_q: "queue.PriorityQueue" = queue.PriorityQueue()
        self._seq = 0
        self._stop = False
        self._fatal: Optional[BaseException] = None
        self._tasks: Dict[Tuple[int, str], _Task] = {
            (i, s.name): _Task(i, s)
            for i in range(len(self.obs)) for s in self.stages}
        # the device POOL gangs draw from (lease ids 0..devices-1) and
        # the FIFO claim line that keeps wide gangs starvation-free
        self._free_ids = set(range(self.devices))
        self._claims: List[Tuple[object, List[int]]] = []
        # obs index -> its leases so far (first ask, every chip held, the
        # chips of the last lease, moves): where the next one-chip lease
        # is asked for, and what the survey.obs span reports
        self._obs_leases: Dict[int, dict] = {}
        self._stage_cost: Dict[str, List[float]] = {}  # name -> [s, n]
        self.result = FleetResult()
        self._manifests: List[Optional[ObsManifest]] = []
        self._traces: List[Optional[ObsTrace]] = []
        # per-obs causal trace ids (round 21): minted once in each
        # manifest, so kill+resume and adoption continue the SAME trace
        self._trace_ids: List[Optional[str]] = []
        # obs index -> dead host it was adopted from; consumed by the
        # FIRST stage span after adoption (the lane-handover link the
        # stitched trace renders)
        self._adopted_from: Dict[int, str] = {}
        # a stage that consumed more than this fraction of its watchdog
        # budget without tripping it emits survey.slo_burn — the
        # early-warning margin tlmsum's SLO section accounts
        self._slo_frac = knobs_mod.env_float("PYPULSAR_TPU_OBS_SLO_FRAC")
        self._t0 = 0.0

        # multi-host plane (round 18): observations are CLAIMED, not
        # pre-assigned — the claim/adopt loop owns admission, manifests
        # open lazily under a held claim, and every manifest append is
        # fenced by the claim's token
        self.plane = plane
        self.host_id = plane.host_id if plane is not None else None
        self._owned: set = set()            # obs indices we hold claims on
        self._obs_tokens: Dict[int, int] = {}
        self._terminal_remote: set = set()  # obs another host finished
        # at most `devices` claimed-but-unfinished obs per host: a host
        # must not hoard the queue it cannot drain (the surplus-host /
        # idle-adopter contract rides on unclaimed obs staying visible)
        self._claim_ahead = max(1, self.devices)
        self._host_health = (health_mod.HostHealth()
                             if plane is not None else None)
        self._claim_thread: Optional[threading.Thread] = None
        self._warm_thread: Optional[threading.Thread] = None
        self._plane_owned_here = False  # register()ed by this run()

        # service mode (round 23): the fleet does NOT exit when every
        # task is terminal — the daemon keeps submit()ing observations
        # into the running DAG, and only request_drain() restores the
        # batch run-to-completion exit contract
        self._service = bool(service)
        self._draining = False
        # obs indices whose input file existence is re-verified at every
        # stage launch (daemon submissions: a source that vanishes
        # between admit and stage start is a LOUD data-quarantine, not a
        # crash or a retry loop). Batch obs are exempt — stub-stage
        # fleets legitimately run against paths that never exist.
        self._verify_input: set = set()
        # optional terminal-edge hook (obs_name, state) the daemon uses
        # for tenant accounting; failures are swallowed (a passenger)
        self.on_obs_terminal = None
        # optional obs_name -> tenant resolver for the candidate-store
        # ingest edge (the daemon points this at its admission books)
        self.tenant_of = None
        # set once run() has opened the initial manifests and promoted
        # the initial obs: submit() before this point would race the
        # startup manifest pass (the daemon waits on it)
        self._ready = locks_mod.TrackedEvent("survey.sched.ready")

    # -- manifests ----------------------------------------------------------

    def _clean_stale_outputs(self, obs: Observation) -> None:
        """Scrub every artifact the stages would enumerate for this
        observation (plus the sweep's chain journal). Runs only when the
        manifest is FRESH — a reconfigured rerun into the same outdir
        must not let the previous grid's files leak into the glob-driven
        stage inputs/outputs (sift would cluster old-grid .cand trails,
        snr would summarize orphaned archives), which would diverge from
        a clean-dir serial chain."""
        stale = [f"{obs.outbase}.chain.jsonl"]
        for s in self.stages:
            stale += s.outputs(obs, self.cfg)
        for path in stale:
            try:
                os.remove(path)
            except OSError:
                pass

    def _open_manifests(self) -> None:
        if self.plane is not None:
            # multi-host mode: manifests open LAZILY in _claim_obs,
            # under the held claim — three hosts eagerly opening (and
            # fresh-scrubbing) every manifest at startup would race each
            # other over observations none of them own yet
            self._manifests = [None] * len(self.obs)
            self._traces = [None] * len(self.obs)
            self._trace_ids = [None] * len(self.obs)
            return
        snames = stage_names(self.stages)
        for obs in self.obs:
            if not self.resume and os.path.exists(obs.manifest):
                # a fresh (non-resume) fleet starts from scratch — the
                # same contract as `sweep --checkpoint` without --resume
                os.remove(obs.manifest)
            m = ObsManifest(obs.manifest,
                            fleet_fingerprint(obs, self.cfg, snames))
            if m.fresh:
                # new manifest OR a restart after changed params/input:
                # nothing will be skipped, so nothing stale may linger
                self._clean_stale_outputs(obs)
            m.plan(obs, snames)
            self._manifests.append(m)
            tid = self._mint_trace(m)
            self._trace_ids.append(tid)
            trace = None
            if self.telemetry_dir:
                trace = ObsTrace(
                    os.path.join(self.telemetry_dir, f"{obs.name}.jsonl"),
                    obs.name, append=self.resume, trace_id=tid)
            self._traces.append(trace)

    def _mint_trace(self, m: ObsManifest) -> Optional[str]:
        """The observation's causal trace_id (minted once, persisted in
        the manifest — see ObsManifest.ensure_trace). Observability is a
        passenger: a failure here runs the observation untraced."""
        try:
            return m.ensure_trace(tracing.new_trace_id)
        except (fleet_mod.StaleLeaseError, OSError):
            return None

    # -- ingest data validation ---------------------------------------------

    def _validate_ingest(self) -> None:
        """Validate every observation's INPUT before any stage runs
        (resilience.dataguard.validate_input): a recognized-but-broken
        file, or one whose data-quality report exceeds --max-bad-frac,
        is quarantined with reason ``"data"`` — distinct from runtime
        quarantine, because the fix is a re-transfer, not a retry.
        Salvageable inputs record their report in the manifest (the
        --status / tlmsum denominators) and DEGRADE: the readers carry
        the valid prefix through the chain. In multi-host mode each obs
        is validated at CLAIM time instead (``_claim_obs``): only the
        claim holder may write the verdict into the manifest."""
        for i in range(len(self.obs)):
            self._validate_ingest_one(i)

    def _validate_ingest_one(self, i: int) -> bool:
        """Ingest-validate one observation; returns False when it was
        data-quarantined (the claim holder records the verdict)."""
        from pypulsar_tpu.io.errors import DataFormatError
        from pypulsar_tpu.resilience import dataguard

        obs = self.obs[i]
        try:
            report = dataguard.validate_input(obs.infile)
        except DataFormatError as e:
            self._quarantine_data(i, f"{type(e).__name__}: {e}")
            return False
        except Exception as e:  # noqa: BLE001 - see below
            # an unexpected validation failure (OSError on a flaky
            # mount, a codec corner the wrappers missed) must not
            # abort the WHOLE fleet at startup — admit the obs and
            # let the stage machinery's retry->quarantine own it
            print(f"# survey: {obs.name}: ingest validation failed "
                  f"({type(e).__name__}: {e}); admitting unchecked")
            return True
        if report is None:
            return True  # unrecognized/missing: the stage reports it
        self._manifests[i].note_data_quality(report)
        bad = float(report.get("bad_frac", 0.0) or 0.0)
        if bad > self.max_bad_frac:
            self._quarantine_data(
                i, f"data-quality bad_frac {bad:.3f} exceeds "
                   f"--max-bad-frac {self.max_bad_frac:.3f}")
            return False
        if bad and self.verbose:
            print(f"# survey: {obs.name}: degraded input admitted "
                  f"(bad_frac {bad:.3f} <= {self.max_bad_frac:.3f})")
        return True

    def _quarantine_data(self, obs_i: int, error: str) -> None:
        obs = self.obs[obs_i]
        self._manifests[obs_i].quarantine("ingest", error, reason="data")
        telemetry.counter("survey.data_quarantines")
        telemetry.event("survey.quarantine", obs=obs.name,
                        stage="ingest", reason="data")
        trace = self._traces[obs_i]
        if trace is not None:
            trace.event("survey.quarantine", stage="ingest",
                        reason="data")
        print(f"# survey: DATA-QUARANTINED {obs.name} at ingest: {error} "
              f"(fleet continues)")
        self._postmortem("data_quarantine", obs_i,
                         extra={"error": error})
        with self._cv:
            for s in self.stages:
                t = self._tasks[(obs_i, s.name)]
                if t.state != _DONE:
                    t.state = _QUARANTINED
            self.result.quarantined[obs.name] = {
                "stage": "ingest", "error": error, "reason": "data"}
            self._maybe_stop_locked()
            self._cv.notify_all()
        self._plane_mark_terminal(obs_i, "quarantined")

    # -- scheduling core ----------------------------------------------------

    def _enqueue_locked(self, task: _Task) -> None:
        task.state = _QUEUED
        self._seq += 1
        task.seq = self._seq
        # deeper stages first (finish observations, free their
        # intermediates), FIFO within a depth
        entry = (-self._depth[task.stage.name], task.seq, task)
        (self._device_q if task.stage.device_bound
         else self._host_q).put(entry)

    def _promote_locked(self, obs_i: int) -> None:
        for s in self.stages:
            task = self._tasks[(obs_i, s.name)]
            if task.state != _PENDING:
                continue
            if all(self._tasks[(obs_i, d)].state == _DONE for d in s.deps):
                self._enqueue_locked(task)

    def _finished_locked(self) -> bool:
        return all(t.state in (_DONE, _QUARANTINED, _REMOTE)
                   for t in self._tasks.values())

    def _maybe_stop_locked(self) -> None:
        """Stop the fleet when every task is terminal — unless service
        mode holds it open for future :meth:`submit` calls (only a
        :meth:`request_drain` restores the batch exit contract). Every
        terminal edge funnels through here so the service-mode liveness
        rule lives in exactly one place."""
        if self._finished_locked() \
                and not (self._service and not self._draining):
            self._stop = True

    # -- service mode (round 23) --------------------------------------------

    def submit(self, obs: Observation, *, resume: bool = True,
               verify_input: bool = True) -> int:
        """Register ONE new observation with a RUNNING service-mode
        fleet and promote its ready stages. The daemon's ingest edge:
        the manifest is opened and planned immediately (the accepted-
        work durability contract — an accepted observation survives
        kill+restart exactly like a batch obs), journal-validated
        stages are skipped (``resume=True``, the default, makes a
        daemon-restart resubmission idempotent: zero re-runs of
        validated stages), and ingest validation may data-quarantine
        the observation before any stage runs. Returns the obs index.

        Thread-safe against the workers: the manifest/trace open runs
        outside the scheduler lock (it blocks on disk), registration
        appends under the lock (list appends — existing indices never
        move), and the tasks become visible to workers only at the
        final promote."""
        if not self._service:
            raise RuntimeError("submit() requires service=True")
        with self._lock:
            if any(o.name == obs.name for o in self.obs):
                raise ValueError(f"duplicate observation name "
                                 f"{obs.name!r}")
        snames = stage_names(self.stages)
        if not resume and os.path.exists(obs.manifest):
            os.remove(obs.manifest)
        m = ObsManifest(obs.manifest,
                        fleet_fingerprint(obs, self.cfg, snames))
        if m.fresh:
            self._clean_stale_outputs(obs)
        m.plan(obs, snames)
        tid = self._mint_trace(m)
        trace = None
        if self.telemetry_dir:
            trace = ObsTrace(
                os.path.join(self.telemetry_dir, f"{obs.name}.jsonl"),
                obs.name, append=resume, trace_id=tid)
        with self._cv:
            i = len(self.obs)
            self.obs.append(obs)
            self._manifests.append(m)
            self._trace_ids.append(tid)
            self._traces.append(trace)
            for s in self.stages:
                self._tasks[(i, s.name)] = _Task(i, s)
            if verify_input:
                self._verify_input.add(i)
        if not self._validate_ingest_one(i):
            return i  # data-quarantined before any stage ran
        done = m.done_stages() if resume else set()
        with self._cv:
            for s in self.stages:
                if s.name in done:
                    self._tasks[(i, s.name)].state = _DONE
                    self.result.skipped.append((obs.name, s.name))
                    telemetry.counter("survey.stages_skipped")
            self._promote_locked(i)
            obs_complete = all(
                self._tasks[(i, s.name)].state == _DONE
                for s in self.stages)
            self._cv.notify_all()
        if obs_complete:
            # every stage already journal-validated: terminal on arrival
            self._plane_mark_terminal(i, "done")
        return i

    def request_drain(self) -> None:
        """End service mode: finish everything submitted so far, then
        exit :meth:`run` with the ordinary batch verdict (the SIGTERM
        half of the daemon's overload contract)."""
        with self._cv:
            self._draining = True
            self._maybe_stop_locked()
            self._cv.notify_all()

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        """Block until :meth:`run` has finished its startup manifest
        pass (service mode: the point after which :meth:`submit` is
        safe)."""
        return self._ready.wait(timeout)

    # -- multi-host claim / adopt loop --------------------------------------

    def _manifest_current(self, obs_i: int) -> bool:
        """Does the observation's on-disk manifest carry THIS run's
        fingerprint? A terminal plane claim is only trustworthy
        together with a matching manifest — a claim left 'done' by a
        PREVIOUS configuration's fleet must be re-opened and re-run,
        exactly as a single-host rerun restarts a mismatched manifest
        (finding: stale terminal claims must not short-circuit a
        reconfigured rerun)."""
        obs = self.obs[obs_i]
        want = fleet_fingerprint(obs, self.cfg,
                                 stage_names(self.stages))
        import json

        try:
            with open(obs.manifest) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    rec = json.loads(line)
                    return (rec.get("type") == "journal"
                            and rec.get("fingerprint") == want)
        except (OSError, ValueError):
            pass
        return False

    def _interrupt_lost_stages_locked(self, obs_i: int) -> None:
        """Our claim on ``obs_i`` is gone (a survivor adopted it while
        we were presumed dead): async-interrupt any stage of it still
        RUNNING with StaleLeaseError so its artifact writes stop within
        one poll tick — waiting for the stage's next manifest append
        could leave a zombie writer racing the adopter for minutes.
        A DEFERRED delivery (the stage holds a tracked lock right now)
        is fine: the claim loop calls this every poll tick, so the
        interrupt retries until it lands at an unlocked boundary."""
        for entry in self._hb.active():
            task = entry.payload
            if getattr(task, "obs_i", None) == obs_i:
                health_mod.interrupt_thread(entry.thread_id,
                                            fleet_mod.StaleLeaseError)

    def _plane_mark_terminal(self, obs_i: int, state: str) -> None:
        """Best-effort claim closeout (done/quarantined). Losing the
        fence here means a survivor adopted the observation while its
        last write was in flight — the adopter revalidates and closes
        it out itself, so the local verdict simply stands down.

        Every obs-terminal edge (done / quarantined / data-quarantined)
        funnels through here, which is why the service-mode terminal
        hook also rides it: the daemon's tenant books settle on the
        same edges the multi-host plane does."""
        with self._lock:
            held = self._obs_leases.pop(obs_i, None)
        if held is not None:
            # the observation among its neighbours: first lease asked
            # for to terminal state, every chip it held, how often a
            # lease fell on another chip than the one before (sink-only)
            telemetry.record_span(
                "survey.obs", time.perf_counter() - held["t_ask"],
                aggregate=False, obs=self.obs[obs_i].name, state=state,
                chips=sorted(held["chips"]), moves=held["moves"])
        cb = self.on_obs_terminal
        if cb is not None:
            try:
                cb(self.obs[obs_i].name, state)
            except Exception:  # noqa: BLE001 - accounting is a passenger
                pass
        if state == "done":
            # publish to the candidate store UNDER the still-held claim
            # (round 25) — the fenced append is what makes a dead
            # host's late publish a no-op
            self._publish_candidates(obs_i)
        if self.plane is None:
            return
        token = self._obs_tokens.get(obs_i)
        if token is None:
            return
        try:
            self.plane.mark_terminal(
                self.obs[obs_i].name, token, state,
                trace_id=self._trace_ids[obs_i])
        except fleet_mod.StaleLeaseError:
            self._cede_obs(obs_i, already_terminal=True)

    def _publish_candidates(self, obs_i: int) -> None:
        """Candidate-store ingest (round 25): normalize this done
        observation's terminal artifacts and publish them, fenced under
        the obs claim when a plane is live.  A passenger like the
        terminal hook — it only READS stage outputs and writes only
        under ``_fleet/candstore/``, so per-obs artifacts stay
        byte-identical and a store failure never fails the obs.
        ``PYPULSAR_TPU_CANDSTORE=0`` restores the store-less fleet."""
        from pypulsar_tpu import candstore as candstore_mod

        if not candstore_mod.enabled():
            return
        obs = self.obs[obs_i]
        outdir = os.path.dirname(obs.outbase) or "."
        token = self._obs_tokens.get(obs_i)
        fence = None
        if self.plane is not None and token is not None:
            fence = (lambda o=obs.name, t=token:
                     self.plane.fence(o, t))
        tenant = "default"
        resolver = self.tenant_of
        if resolver is not None:
            try:
                tenant = str(resolver(obs.name) or "default")
            except Exception:  # noqa: BLE001 - accounting passenger
                tenant = "default"
        try:
            candstore_mod.publish_obs(
                outdir, obs.name, obs.outbase, obs.infile,
                tenant=tenant, trace_id=self._trace_ids[obs_i],
                fence=fence, token=token)
        except fleet_mod.StaleLeaseError:
            pass  # adopter owns the obs now; it will publish
        except Exception:  # noqa: BLE001 - the store is a passenger
            pass

    def _claim_obs(self, i: int, token: int, adopted_from=None) -> None:
        """Take ownership of one claimed observation: open its manifest
        UNDER the held claim (token-stamped, fenced), scrub stale
        artifacts only when the manifest is fresh, validate ingest, mark
        journal-validated stages done (an adopted obs resumes exactly
        like a single-host ``--resume``) and promote the rest."""
        obs = self.obs[i]
        snames = stage_names(self.stages)
        m = ObsManifest(
            obs.manifest, fleet_fingerprint(obs, self.cfg, snames),
            token=token,
            fence=lambda o=obs.name, t=token: self.plane.fence(o, t))
        # re-verify the claim BEFORE the destructive scrub: a residual
        # double-claim loser (both racers passed the settle re-read)
        # must not delete the winner's freshly written artifacts — the
        # fence raises here, before anything is touched
        self.plane.fence(obs.name, token)
        if m.fresh:
            self._clean_stale_outputs(obs)
        m.plan(obs, snames)
        self._manifests[i] = m
        # SAME trace_id the previous owner minted (the manifest is the
        # shared source of truth): the adopter's spans continue the
        # observation's causal story, they don't start a new one
        self._trace_ids[i] = self._mint_trace(m)
        if self.telemetry_dir and self._traces[i] is None:
            # append: an adopted observation's trace keeps the dead
            # host's recorded spans — exactly the forensics worth having
            self._traces[i] = ObsTrace(
                os.path.join(self.telemetry_dir, f"{obs.name}.jsonl"),
                obs.name, append=True, trace_id=self._trace_ids[i])
        with self._cv:
            self._owned.add(i)
            self._obs_tokens[i] = token
        if adopted_from:
            self.result.adopted.append(obs.name)
            # the lane-handover link: the first stage span this host
            # runs for the adopted obs carries adopted_from, so the
            # stitched trace shows WHERE the trace hopped hosts
            self._adopted_from[i] = adopted_from
            trace = self._traces[i]
            if trace is not None:
                # no `host` attr here: the adopter's fleet trace already
                # carries the host-keyed event (the plane emits it), and
                # summarizing both traces together must not double-count
                # the adoption in the per-host roll-up
                trace.event("survey.obs_adopted",
                            adopted_from=adopted_from, token=token)
            if self.verbose:
                print(f"# survey[{self.host_id}]: ADOPTED {obs.name} "
                      f"from silent host {adopted_from!r} "
                      f"(token {token}); resuming from its manifest")
        if not self._validate_ingest_one(i):
            return  # data-quarantined under our claim
        done = m.done_stages()
        with self._cv:
            for s in self.stages:
                task = self._tasks[(i, s.name)]
                if s.name in done:
                    task.state = _DONE
                    self.result.skipped.append((obs.name, s.name))
                    telemetry.counter("survey.stages_skipped")
                else:
                    task.state = _PENDING
                    task.attempts = 0  # a fresh owner gets fresh retries
            self._promote_locked(i)
            self._maybe_stop_locked()
            self._cv.notify_all()

    def _claim_failed(self, i: int, token: int, e: Exception) -> None:
        """A claim we won but cannot act on (foreign-tool manifest,
        unreadable outdir): close it out as quarantined so the fleet
        sees a verdict instead of a wedge held by a silent owner."""
        obs = self.obs[i]
        self._owned.discard(i)
        self._obs_tokens.pop(i, None)
        err = f"{type(e).__name__}: {e}"
        print(f"# survey[{self.host_id}]: cannot open {obs.name}: "
              f"{err}; quarantining the claim")
        with self._cv:
            # terminal HERE: later poll ticks must not re-read our own
            # quarantined claim as another host's verdict and report it
            # 'finished remotely'
            self._terminal_remote.add(i)
            for s in self.stages:
                t = self._tasks[(i, s.name)]
                if t.state != _DONE:
                    t.state = _QUARANTINED
            self.result.quarantined[obs.name] = {
                "stage": "claim", "error": err}
            self._cv.notify_all()
        try:
            self.plane.mark_terminal(obs.name, token, "quarantined")
        except (fleet_mod.StaleLeaseError, OSError):
            pass
        self._postmortem("claim_quarantined", i, extra={"error": err})

    def _cede_obs(self, i: int, already_terminal: bool = False) -> None:
        """This host's claim on obs ``i`` was superseded (a survivor
        adopted it while we were stalled/presumed dead): stand down
        WITHOUT retry or quarantine — the adopter owns the observation
        now, and the fencing token has already made our late writes
        no-ops. Non-done tasks return to _PENDING so the claim loop can
        re-adopt if the new owner dies in turn."""
        obs = self.obs[i]
        with self._cv:
            if i not in self._owned:
                return
            self._owned.discard(i)
            self._obs_tokens.pop(i, None)
            for s in self.stages:
                t = self._tasks[(i, s.name)]
                if t.state not in (_DONE, _REMOTE):
                    t.state = _PENDING
            self._cv.notify_all()
        m, self._manifests[i] = self._manifests[i], None
        if m is not None:
            m.close()
        self.result.ceded.append(obs.name)
        telemetry.counter("survey.obs_ceded")
        telemetry.event("survey.obs_ceded", host=self.host_id,
                        obs=obs.name)
        if self._host_health is not None and not already_terminal:
            # repeated losses mean THIS host keeps going silent under
            # work (flaky node): past the strike limit it stops
            # claiming and drains out
            self._host_health.strike(self.host_id, kind="ceded",
                                     error=f"lost {obs.name} to a "
                                           f"higher fencing token")
        if self.verbose:
            print(f"# survey[{self.host_id}]: CEDED {obs.name} to its "
                  f"adopter (stale fencing token); fleet continues")
        self._postmortem("obs_ceded", i)

    def _plane_poll(self) -> None:
        """One claim-loop tick: claim unowned observations (orphans
        first — adoption is the liveness path), observe terminal states
        other hosts recorded, and stop the fleet when every observation
        is globally terminal."""
        hosts = self.plane.hosts()
        claims = self.plane.claims()
        with self._lock:
            owned_open = sum(
                1 for i in self._owned
                if any(self._tasks[(i, s.name)].state
                       not in (_DONE, _QUARANTINED, _REMOTE)
                       for s in self.stages))
            owned_now = set(self._owned)
        # zombie self-check FIRST: if any claim we think we hold now
        # carries someone else's token, we were adopted away (netstall,
        # long GC, partition) — interrupt the running stage NOW instead
        # of letting it race the adopter's writes until its next
        # manifest append
        for i in owned_now:
            tok = self._obs_tokens.get(i)
            c = claims.get(self.obs[i].name)
            if tok is not None and (c is None
                                    or c.get("token") != tok):
                with self._lock:
                    self._interrupt_lost_stages_locked(i)
        barred = (self._host_health is not None
                  and self._host_health.is_quarantined(self.host_id))
        for i, obs in enumerate(self.obs):
            with self._lock:
                if i in self._owned or i in self._terminal_remote:
                    continue
            c = claims.get(obs.name)
            state = c.get("state", "running") if c else None
            holder = str(c.get("host", "")) if c else None
            if c is not None and state in ("done", "quarantined"):
                reopen = not self._manifest_current(i)
                if not reopen and self.resume and state == "done":
                    # an EXPLICIT --resume in plane mode re-validates a
                    # done claim's artifacts (size+sha256, the single-
                    # host resume contract): a corrupted artifact
                    # re-opens the claim instead of being trusted
                    try:
                        m = ObsManifest(self.obs[i].manifest,
                                        fleet_fingerprint(
                                            self.obs[i], self.cfg,
                                            stage_names(self.stages)))
                        done = m.done_stages()
                        m.close()
                        reopen = any(s.name not in done
                                     for s in self.stages)
                    except Exception:  # noqa: BLE001 - unreadable
                        reopen = True  # manifest: redo, never trust
                if reopen:
                    # terminal under a DIFFERENT configuration (or the
                    # manifest is gone / fails validation): the verdict
                    # does not apply to THIS run — re-open the claim
                    # and re-run, the plane-mode form of the restart-
                    # on-fingerprint-mismatch contract
                    if not barred and owned_open < self._claim_ahead:
                        token = self.plane.claim(obs.name,
                                                 allow_terminal=True)
                        if token is not None:
                            try:
                                self._claim_obs(i, token)
                            except fleet_mod.StaleLeaseError:
                                continue
                            except Exception as e:  # noqa: BLE001 - same
                                # contract as the claim handler below
                                self._claim_failed(i, token, e)
                                continue
                            owned_open += 1
                    continue
                # another host closed it out: record the remote verdict
                # and mark the tasks terminal locally
                with self._cv:
                    self._terminal_remote.add(i)
                    for s in self.stages:
                        t = self._tasks[(i, s.name)]
                        if t.state != _DONE:
                            t.state = _REMOTE
                    if state == "quarantined" \
                            and obs.name not in self.result.quarantined:
                        self.result.quarantined[obs.name] = {
                            "stage": "?", "error":
                                f"quarantined by host {holder!r}",
                            "host": holder}
                    self.result.remote_done.append(obs.name)
                    self._cv.notify_all()
                continue
            if barred or owned_open >= self._claim_ahead:
                continue
            holder_live = (c is not None and holder != self.host_id
                           and self.plane.is_live(hosts.get(holder)))
            if holder_live:
                continue  # a live host is on it
            adopted_from = (holder if c is not None
                            and holder != self.host_id else None)
            token = self.plane.claim(obs.name)
            if token is None:
                continue  # lost the race (or it went terminal meanwhile)
            if adopted_from and self._host_health is not None:
                # charge the death we just observed: the account the
                # fleet-health JSON and --status render per host
                self._host_health.strike(
                    adopted_from, kind="adopted",
                    error=f"{obs.name} orphaned (heartbeat silent)")
            try:
                self._claim_obs(i, token, adopted_from=adopted_from)
            except fleet_mod.StaleLeaseError:
                continue  # out-adopted during setup: theirs now
            except Exception as e:  # noqa: BLE001 - a claim we cannot
                # act on must not be held forever: _claim_failed closes
                # it out as quarantined (a verdict, not a wedge)
                self._claim_failed(i, token, e)
                continue
            owned_open += 1
        with self._cv:
            self._maybe_stop_locked()
            if self._stop:
                self._cv.notify_all()

    def _plane_loop(self) -> None:
        """The claim/adopt daemon: poll fast enough that adoption lands
        within ~one heartbeat of the lease expiring, slow enough that M
        idle hosts do not hammer the shared directory."""
        poll = max(0.05, min(self.plane.heartbeat_s, 0.5))
        while not self._stop:
            try:
                self._plane_poll()
            except Exception as e:  # noqa: BLE001 - the claim loop must
                # outlive transient plane IO errors (shared-FS hiccup):
                # a dead claim loop would strand every unclaimed obs
                telemetry.event("survey.claim_loop_error",
                                error=type(e).__name__)
            time.sleep(poll)

    # -- fleet health -------------------------------------------------------

    @staticmethod
    def _make_device_health(strike_limit):
        """The process-global mesh registry when jax is importable (so
        mesh-building code and the scheduler share one account), a
        local one otherwise — either way FRESH per fleet: strikes are
        runtime state, not survey state, and a resumed fleet gives
        every chip a clean slate."""
        try:
            from pypulsar_tpu.parallel import mesh as mesh_mod

            return mesh_mod.reset_device_health(strike_limit)
        except Exception:  # noqa: BLE001 - no jax backend: local account
            return health_mod.DeviceHealth(strike_limit)

    def _healthy_ids(self) -> List[int]:
        # lease i is local device i (the pool never exceeds the chip
        # count), so lease ids index the real-chip strike account
        return [i for i in range(self.devices)
                if not self._health.is_quarantined(i)]

    def _deadline_for(self, stage: StageSpec, obs: Observation):
        if self.stage_deadline is not None:
            return self.stage_deadline
        return stage.deadline_for(obs)

    def _needs_watchdog(self) -> bool:
        return (self.stall_s is not None
                or self.stage_deadline is not None
                or any(s.deadline_s is not None
                       or s.deadline_per_mb is not None
                       for s in self.stages))

    def _on_stage_expired(self, entry, reason: str) -> None:
        """Watchdog callback: record the verdict, then interrupt the
        stage's worker thread (StageDeadlineExceeded / StageStalled are
        ordinary Exceptions — the worker's retry/quarantine policy owns
        the rest, and its finally blocks release the lease)."""
        task = entry.payload
        obs = self.obs[task.obs_i]
        now = time.monotonic()
        if reason == "deadline":
            name = "survey.deadline_exceeded"
            after = now - entry.started
            exc = health_mod.StageDeadlineExceeded
        else:
            name = "survey.stage_stalled"
            after = now - entry.last_beat
            exc = health_mod.StageStalled
        # interrupt FIRST, and only while the entry is still live: if
        # the stage finished between expired() and here, the async
        # exception would land wherever that worker thread is NEXT —
        # outside _execute's try, killing the worker and hanging the
        # fleet. (The remaining finish-vs-raise race is closed by the
        # worker loop's StageTimeout catch and the done_recorded
        # guard in _handle_failure.)
        if not self._hb.is_active(entry):
            telemetry.event("survey.late_interrupt", obs=obs.name,
                            stage=task.stage.name)
            return
        res = health_mod.interrupt_thread(entry.thread_id, exc)
        if res is health_mod.DEFERRED:
            # the stage currently holds a lockdep-tracked lock: an
            # async exception landing there could strand the lock or
            # tear a locked invariant. The verdict STANDS — re-arm the
            # entry so the next watchdog tick retries; delivery lands
            # at the first unlocked boundary (round 19 contract;
            # regression: tests/test_lockdep.py)
            self._hb.rearm(entry)
            telemetry.event("survey.interrupt_deferred", obs=obs.name,
                            stage=task.stage.name, reason=reason)
            return
        if not res:
            telemetry.event("survey.late_interrupt", obs=obs.name,
                            stage=task.stage.name)
            return
        with self._lock:
            self.result.timeouts += 1
        telemetry.counter("survey.watchdog_interrupts")
        telemetry.event(name, obs=obs.name, stage=task.stage.name,
                        after_s=round(after, 3))
        trace = self._traces[task.obs_i]
        if trace is not None:
            trace.event(name, stage=task.stage.name,
                        after_s=round(after, 3))
        if self.verbose:
            print(f"# survey: WATCHDOG {obs.name}: {task.stage.name} "
                  f"{reason} after {after:.1f}s; interrupting worker")
        self._postmortem(f"watchdog_{reason}", task.obs_i,
                         extra={"stage": task.stage.name,
                                "after_s": round(after, 3)})

    def _strike_leases(self, task: "_Task", err: Exception) -> None:
        """Charge the failed execution's leased chips when the error
        indicts the DEVICE (OOM that escaped in-stage halving, dead
        chip, failed collective, injected device fault). Eviction
        spares the last healthy lease — an empty pool is a hung fleet
        — and every verdict lands in the fleet-health JSON."""
        ids = task.last_dev_ids
        if not ids:
            return
        oom = is_oom_error(err)
        if not oom and not health_mod.is_device_fault(err):
            return
        kind = "oom" if oom else "device"
        # charge the REAL chips the execution was pinned to (the id
        # space `parallel.mesh` filters by)
        reals = task.last_real_dev_ids or list(ids)
        evicted: List[int] = []
        for r in reals:
            allow = len(self._healthy_ids()) > 1
            if self._health.strike(r, kind=kind, error=str(err)[:200],
                                   allow_quarantine=allow):
                if r < self.devices:
                    evicted.append(r)
        if evicted:
            with self._cv:
                self._free_ids.difference_update(evicted)
                self.result.evicted_devices.extend(evicted)
                self._cv.notify_all()
            telemetry.event("survey.device_evicted", devs=evicted,
                            stage=task.stage.name,
                            obs=self.obs[task.obs_i].name,
                            healthy=len(self._healthy_ids()))
            print(f"# survey: QUARANTINED device lease(s) {evicted} "
                  f"after {self._health.limit} strikes "
                  f"({type(err).__name__}); pool shrinks to "
                  f"{len(self._healthy_ids())} chips, gangs retry "
                  f"shrunk")
            self._postmortem("device_evicted", task.obs_i,
                             extra={"devices": evicted,
                                    "stage": task.stage.name})
        self._write_health_json()

    def _postmortem(self, reason: str, obs_i: Optional[int] = None,
                    extra: Optional[dict] = None) -> None:
        """Freeze the flight recorder into a capsule at a failure edge
        (quarantine, watchdog verdict, eviction, cede, crash): the last
        N telemetry records land under ``<outdir>/_fleet/postmortem/``
        so every QUARANTINED ``--status`` row has its explanation on
        disk even when ``--telemetry`` was off. Best-effort by
        construction (flightrec.dump never raises)."""
        if self._health_dir is None:
            return
        path = flightrec.dump(
            os.path.join(fleet_mod.plane_dir(self._health_dir),
                         "postmortem"),
            reason, host=self.host_id,
            obs=self.obs[obs_i].name if obs_i is not None else None,
            extra=extra)
        if path is not None and self.verbose:
            print(f"# survey: postmortem capsule {path}")

    def _write_health_json(self) -> None:
        """Mirror the per-device verdicts next to the manifests so
        ``survey --status`` (a different process, maybe much later)
        can render chip health alongside observation progress."""
        if self._health_dir is None:
            return
        snap = self._health.snapshot()
        hosts = (self._host_health.snapshot()
                 if self._host_health is not None else {})
        if not snap and not self.result.evicted_devices and not hosts:
            return
        payload = {
            "pool": self.devices,
            "strike_limit": self._health.limit,
            "devices": {str(i): v for i, v in snap.items()},
        }
        if hosts:
            payload["hosts"] = hosts
            payload["host_strike_limit"] = self._host_health.limit
        write_fleet_health(self._health_dir, payload)

    def _wait_admission(self) -> None:
        """Block until the resource gate admits new work (or the fleet
        stops). Pauses are episodes: one ``survey.admission_paused``
        event when the gate first refuses, one ``..._resumed`` when it
        clears — not one per poll."""
        reason = self._guard.admit()
        if reason is None:
            return
        with self._lock:
            first = not self._admission_blocked
            self._admission_blocked = True
        if first:
            telemetry.counter("survey.admission_pauses")
            telemetry.event("survey.admission_paused", reason=reason)
            print(f"# survey: admission paused ({reason}); in-flight "
                  f"stages continue, new launches wait")
        while not self._stop:
            time.sleep(0.2)
            reason = self._guard.admit()
            if reason is None:
                with self._lock:
                    self._admission_blocked = False
                telemetry.event("survey.admission_resumed")
                return

    # -- execution ----------------------------------------------------------

    def _execute(self, task: _Task, gang: int = 1,
                 dev_ids: Optional[List[int]] = None) -> None:
        obs = self.obs[task.obs_i]
        stage = task.stage
        if task.obs_i in self._verify_input \
                and not os.path.exists(obs.infile):
            # a daemon-accepted source that vanished between admit and
            # stage start (mover rolled it back, tenant deleted it): a
            # LOUD data-quarantine — re-transfer territory, not a crash
            # and not a retry loop burning attempts on ENOENT
            self._quarantine_data(
                task.obs_i,
                f"input file vanished after admission: {obs.infile}")
            return
        tid = (self._trace_ids[task.obs_i]
               if task.obs_i < len(self._trace_ids) else None)
        budget = self._deadline_for(stage, obs)
        span_attrs = {"obs": obs.name}
        if self.host_id is not None:
            span_attrs["host"] = self.host_id
        if dev_ids is not None:
            span_attrs["dev"] = dev_ids
        if gang > 1:
            span_attrs["gang"] = gang
        if budget is not None:
            # the SLO denominator, carried ON the span so tlmsum can
            # account burn from the trace alone
            span_attrs["budget_s"] = round(float(budget), 3)
        adopted_src = self._adopted_from.pop(task.obs_i, None)
        if adopted_src is not None:
            span_attrs["adopted_from"] = adopted_src
        t_rel = time.perf_counter() - self._t0
        t0 = time.perf_counter()
        # liveness entry: the watchdog interrupts this thread on
        # deadline/stall; any telemetry the stage records (spans,
        # counters — chunk cadence on every hot path) beats it. The
        # entry covers the stage_start/stage_done fault boundaries and
        # the manifest append too — a hang at a boundary must not sleep
        # in a window the watchdog cannot see (it holds the lease).
        task.done_recorded = False
        hb = self._hb.start(f"{obs.name}:{stage.name}",
                            deadline_s=budget,
                            stall_s=self.stall_s, payload=task,
                            obs=obs.name, stage=stage.name,
                            trace_id=tid)
        sp_sid = None
        try:
            faultinject.trip("survey.stage_start")
            faultinject.trip(f"survey.stage_start.{stage.name}")
            # the stage span is its trace's ROOT (parent_id unset): every
            # span the stage's kernels record nests under it, and helper
            # threads adopt the context so their beats land on this
            # heartbeat entry (the round-21 attribution fix)
            with telemetry.trace_context(trace_id=tid, obs=obs.name,
                                         stage=stage.name):
                telemetry.counter("survey.stages_run")
                with telemetry.span(f"survey.stage.{stage.name}",
                                    **span_attrs) as sp:
                    stage.execute(obs, self.cfg, gang=gang)
                if sp is not None:
                    sp_sid = getattr(sp, "sid", None)
            dur = time.perf_counter() - t0
            faultinject.trip("survey.stage_done")
            faultinject.trip(f"survey.stage_done.{stage.name}")
            outputs = stage.outputs(obs, self.cfg)
            self._manifests[task.obs_i].mark_done(stage.name, outputs)
            task.done_recorded = True
        finally:
            self._hb.finish(hb)
        slo_burn = (budget is not None and budget > 0
                    and dur > self._slo_frac * float(budget))
        if slo_burn:
            # consumed most of the watchdog budget WITHOUT tripping it:
            # the early warning that a deadline is about to start
            # costing retries
            telemetry.counter("survey.slo_burns")
            telemetry.event("survey.slo_burn", obs=obs.name,
                            stage=stage.name,
                            budget_s=round(float(budget), 3),
                            frac=round(dur / float(budget), 3))
            # SLO burn gates batching: collapse the broker's coalesce
            # window so latency-critical work dispatches immediately
            # instead of widening batches (round 24)
            broker_mod.note_pressure(f"slo_burn:{stage.name}")
        trace = self._traces[task.obs_i]
        if trace is not None:
            tr_attrs = {"outputs": len(outputs)}
            if self.host_id is not None:
                tr_attrs["host"] = self.host_id
            if dev_ids is not None:
                tr_attrs["dev"] = dev_ids
            if gang > 1:
                tr_attrs["gang"] = gang
            if budget is not None:
                tr_attrs["budget_s"] = round(float(budget), 3)
            if adopted_src is not None:
                tr_attrs["adopted_from"] = adopted_src
            trace.span(f"survey.stage.{stage.name}", t_rel, dur,
                       span_id=sp_sid, **tr_attrs)
            if slo_burn:
                trace.event("survey.slo_burn", stage=stage.name,
                            frac=round(dur / float(budget), 3))
        if self.verbose:
            print(f"# survey: {obs.name}: {stage.name} done "
                  f"({dur:.2f}s, {len(outputs)} artifacts"
                  + (f", gang x{gang} on chips {dev_ids}"
                     if gang > 1 else "") + ")")
        with self._cv:
            task.state = _DONE
            if stage.device_bound:
                # the measured per-stage cost the auto-gang policy
                # consults (same numbers the obs trace records)
                ent = self._stage_cost.setdefault(stage.name, [0.0, 0])
                ent[0] += dur
                ent[1] += 1
            self.result.ran.append((obs.name, stage.name))
            self._promote_locked(task.obs_i)
            obs_complete = all(
                self._tasks[(task.obs_i, s.name)].state == _DONE
                for s in self.stages)
            self._maybe_stop_locked()
            self._cv.notify_all()
        if obs_complete:
            # close the claim out so other hosts read this observation
            # terminal instead of waiting on our heartbeat forever
            self._plane_mark_terminal(task.obs_i, "done")

    def _requeue_retry(self, task: _Task) -> None:
        """Timer callback re-enqueuing a backing-off task — unless its
        observation was quarantined, ceded to an adopter, or the fleet
        stopped while it waited: a retry must not resurrect a stage
        this host no longer owns."""
        with self._cv:
            if self._stop or task.state in (_QUARANTINED, _REMOTE):
                return
            if self.plane is not None and task.obs_i not in self._owned:
                return
            self._enqueue_locked(task)
            self._cv.notify_all()

    def _handle_failure(self, task: _Task, err: Exception) -> None:
        obs = self.obs[task.obs_i]
        stage = task.stage
        if self.plane is not None \
                and isinstance(err, fleet_mod.StaleLeaseError):
            # host-aware failure policy: a stale fencing token is not a
            # stage failure — a survivor adopted the observation while
            # this host was stalled/presumed dead. Cede it: no retry
            # (the adopter is already running it), no quarantine (the
            # observation is healthy), no device strike (the chip did
            # nothing wrong).
            self._cede_obs(task.obs_i)
            return
        with self._lock:
            if task.state == _QUARANTINED:
                # another stage of this observation quarantined it while
                # this one was running: its failure is already verdict
                return
            if task.state == _DONE:
                # a watchdog interrupt that landed AFTER the stage
                # completed (the unavoidable async-exc race window):
                # the work is done and recorded; nothing to retry
                telemetry.event("survey.late_interrupt", obs=obs.name,
                                stage=stage.name)
                return
        if task.done_recorded:
            # the interrupt landed between the manifest's done record
            # and the task-state update in _execute's tail: the work
            # IS complete — finish the task instead of re-running (or
            # phantom-quarantining) a stage whose artifacts validate
            telemetry.event("survey.late_interrupt", obs=obs.name,
                            stage=stage.name)
            with self._cv:
                if task.state != _DONE:
                    task.state = _DONE
                    self.result.ran.append((obs.name, stage.name))
                    self._promote_locked(task.obs_i)
                    self._maybe_stop_locked()
                    self._cv.notify_all()
            return
        self._strike_leases(task, err)
        error = f"{type(err).__name__}: {err}"
        task.last_error = error
        telemetry.counter("survey.stage_failures")
        telemetry.event("survey.stage_failed", obs=obs.name,
                        stage=stage.name, error=type(err).__name__)
        if task.attempts < self.retries:
            task.attempts += 1
            self.result.retried += 1
            delay = backoff_delay(RETRY_BACKOFF_BASE_S, task.attempts,
                                  RETRY_BACKOFF_MAX_S, self.jitter_rng)
            # the attempt + error excerpt land in the manifest so
            # --status (any process, any time) can show WHY a stage is
            # retrying, not just that it is slow
            try:
                self._manifests[task.obs_i].note_retry(
                    stage.name, task.attempts, error)
            except fleet_mod.StaleLeaseError:
                # adopted away between the failure and its verdict:
                # the retry belongs to the new owner
                self._cede_obs(task.obs_i)
                return
            telemetry.event("survey.stage_retry", obs=obs.name,
                            stage=stage.name, attempt=task.attempts)
            if self.verbose:
                print(f"# survey: {obs.name}: {stage.name} failed "
                      f"({type(err).__name__}: {err}); retry "
                      f"{task.attempts}/{self.retries} in {delay:.2f}s")
            # re-enqueue from a timer, not this worker: the backoff must
            # not hold the device lease / host slot idle. The fleet
            # cannot finish early — the task stays non-terminal until
            # the timer fires and the retry settles.
            timer = threading.Timer(delay, self._requeue_retry, (task,))
            timer.daemon = True
            timer.start()
            return
        # bounded retries exhausted: quarantine the OBSERVATION — the
        # fleet continues, the verdict is recorded, and a later resume
        # may try again (the operator explicitly asked)
        try:
            self._manifests[task.obs_i].quarantine(stage.name, error)
        except fleet_mod.StaleLeaseError:
            # the adopter owns the observation (and its verdicts) now
            self._cede_obs(task.obs_i)
            return
        telemetry.event("survey.quarantine", obs=obs.name,
                        stage=stage.name, error=type(err).__name__)
        trace = self._traces[task.obs_i]
        if trace is not None:
            trace.event("survey.quarantine", stage=stage.name)
        print(f"# survey: QUARANTINED {obs.name} at {stage.name}: {error} "
              f"(fleet continues)")
        self._postmortem("quarantine", task.obs_i,
                         extra={"stage": stage.name, "error": error})
        with self._cv:
            for s in self.stages:
                t = self._tasks[(task.obs_i, s.name)]
                if t.state != _DONE:
                    t.state = _QUARANTINED
            self.result.quarantined[obs.name] = {"stage": stage.name,
                                                 "error": error}
            self._maybe_stop_locked()
            self._cv.notify_all()
        self._plane_mark_terminal(task.obs_i, "quarantined")

    # -- gang leases --------------------------------------------------------

    def _gang_size(self, task: _Task) -> Tuple[int, str]:
        """(k, reason) — how many chips THIS execution gets. Fixed
        ``gang`` pins k; ``"auto"`` picks fleet-parallel while enough
        ready device-bound stages exist to fill the chips and widens a
        gang-able stage onto idle chips otherwise, gated by the
        measured per-stage cost share (see GANG_COST_MIN_FRAC)."""
        stage = task.stage
        gmax = min(int(getattr(stage, "devices_max", 1)), self.devices)
        # a quarantined chip is out of the pool: gangs SHRINK to the
        # surviving leases (placement is not science — artifacts stay
        # byte-identical at the new width)
        healthy = len(self._healthy_ids())
        if healthy < self.devices:
            gmax = min(gmax, max(1, healthy))
        if gmax <= 1:
            return 1, ("single-device stage" if healthy >= self.devices
                       else f"shrunk to {healthy} healthy chip(s)")
        if self.gang != "auto":
            k = min(int(self.gang), gmax)
            reason = f"fixed --gang {self.gang}"
            if k < int(self.gang):
                reason += f" shrunk to {k} ({healthy} healthy chips)"
            return k, reason
        with self._lock:
            other_ready = sum(
                1 for t in self._tasks.values()
                if t is not task and t.stage.device_bound
                and t.state in (_QUEUED, _RUNNING))
            cost = {n: c[0] / max(c[1], 1)
                    for n, c in self._stage_cost.items() if c[1]}
        idle = self.devices - 1 - other_ready
        if idle <= 0:
            return 1, (f"fleet-parallel: {other_ready} other ready "
                       f"device stages fill the {self.devices} chips")
        k = min(gmax, 1 + idle)
        total = sum(cost.values())
        mine = cost.get(stage.name)
        if mine is not None and total > 0:
            frac = mine / total
            if frac < GANG_COST_MIN_FRAC:
                return 1, (f"measured {stage.name} cost share "
                           f"{frac:.0%} < {GANG_COST_MIN_FRAC:.0%} of "
                           f"the device chain: gang not worth it")
            return k, (f"gang x{k}: {idle} idle chips and "
                       f"{stage.name} owns {frac:.0%} of the measured "
                       f"device chain")
        return k, f"gang x{k}: {idle} idle chips, cost unmeasured yet"

    def _acquire_devices(self, k: int,
                         prefer: Optional[int] = None) -> Optional[List[int]]:
        """Block until k lease ids are free and claim them. FIFO with
        full reservation: an older waiting claim reserves freed chips
        (up to its need) before any younger claim may take them, so a
        wide gang cannot starve behind 1-chip traffic. Returns None
        when the fleet is unwinding (fatal).

        A one-chip claim takes ``prefer`` when that chip is free at the
        grant (the chip of the observation's last lease: placement then
        repeats from stage to stage and from run to run, and what a
        stage left on the chip stays near) and the lowest free chip
        otherwise; it never waits for the preferred one.

        The claim SHRINKS if devices are quarantined while it waits —
        a gang asking for chips that no longer exist must retry at the
        surviving width, not park forever (``need`` is a mutable cell
        so older claims' reservations shrink with them)."""
        ticket = object()
        need = [k]
        with self._cv:
            self._claims.append((ticket, need))
            try:
                while True:
                    if self._stop and self._fatal is not None:
                        return None
                    need[0] = min(need[0],
                                  max(1, len(self._healthy_ids())))
                    rem = len(self._free_ids)
                    grant = False
                    for t, n in self._claims:
                        if t is ticket:
                            grant = rem >= need[0]
                            break
                        rem -= min(n[0], rem)  # older claims reserve
                    if grant:
                        ids = ([prefer] if need[0] == 1
                               and prefer in self._free_ids
                               else sorted(self._free_ids)[:need[0]])
                        self._free_ids.difference_update(ids)
                        telemetry.gauge(
                            "survey.lanes_in_flight",
                            self.devices - len(self._free_ids))
                        return ids
                    self._cv.wait(0.1)
            finally:
                self._claims.remove((ticket, need))

    def _release_devices(self, ids: List[int]) -> None:
        with self._cv:
            # a lease quarantined while this execution held it never
            # returns to the pool
            self._free_ids.update(
                i for i in ids
                if not self._health.is_quarantined(i))
            telemetry.gauge("survey.lanes_in_flight",
                            self.devices - len(self._free_ids))
            self._cv.notify_all()

    def _n_jax_devices(self) -> Optional[int]:
        """Real JAX device count, cached; None without a backend."""
        if self._njax is _UNSET:
            try:
                import jax

                self._njax = len(jax.local_devices())
            except Exception:  # noqa: BLE001 - no backend
                self._njax = None
        return self._njax

    def _jax_gang(self, ids: List[int]) -> Optional[list]:
        """The JAX devices backing lease ids, or None when no binding
        is needed. With one lease (the default) the process default
        device already IS the lease; with several, the stage pins via
        ``jax.default_device`` + ``parallel.mesh.device_lease`` so k
        leases really are k chips, not k-fold oversubscription of
        device 0. Guarded: a jax-less run (stub DAGs) skips binding.

        Lease ``i`` is local device ``i``: the constructor refuses a
        pool larger than the real device count, so distinct leases are
        distinct chips and a gang never doubles up on one."""
        if self.devices <= 1:
            return None
        try:
            import jax

            devs = jax.local_devices()
        except Exception:  # noqa: BLE001 - no backend: nothing to pin
            return None
        return [devs[i] for i in ids]

    def _run_device_task(self, task: _Task) -> None:
        """One device-lane execution: decide the gang shape, take the
        lease(s), account it, run :meth:`_run_leased` under it."""
        k, reason = self._gang_size(task)
        t_ask = time.perf_counter()
        with self._cv:
            held = self._obs_leases.setdefault(
                task.obs_i, {"t_ask": t_ask, "chips": set(), "last": None,
                             "moves": 0, "holding": None})
            # the chip of its last lease; before its first, the chip of
            # its place in the fleet, so four beams on four chips land
            # where they landed in the run before
            prefer = (held["last"][0] if held["last"]
                      else task.obs_i % self.devices)
            # the stage this one waited for is done, but its thread may
            # not have given the chip back yet (it queues its successor
            # before it unwinds): let it, or this claim takes a
            # neighbour's chip and every beam in lockstep moves one on
            while held["holding"] in task.stage.deps and not self._stop:
                self._cv.wait(0.05)
        ids = self._acquire_devices(k, prefer)
        if ids is None:  # fleet unwinding while we waited
            return
        t_lease = time.perf_counter()
        wait_s = t_lease - t_ask
        telemetry.counter("survey.lease_wait_s", wait_s)
        if len(ids) < k:  # pool shrank while waiting: gang shrinks too
            k = len(ids)
            reason += f"; shrunk to {k} while waiting"
        task.last_dev_ids = list(ids)
        task.last_real_dev_ids = None
        with self._lock:
            # a move: no chip in common with the lease before
            moved = bool(held["last"]) and not set(ids) & set(held["last"])
            held["moves"] += moved
            held["chips"].update(ids)
            held["last"] = list(ids)
            held["holding"] = task.stage.name
        telemetry.counter("survey.lease_moves", int(moved))
        try:
            # the lease as the pool sees it: k chips held from grant to
            # release, whatever the stage does with them (sink-only, the
            # stage span inside it carries the aggregated wall)
            with telemetry.span("survey.lease", aggregate=False,
                                stage=task.stage.name, k=k, chips=ids,
                                wait_s=round(wait_s, 6)):
                self._run_leased(task, k, ids, reason)
        finally:
            held_s = time.perf_counter() - t_lease
            telemetry.counter("survey.lease_chip_s", k * held_s)
            telemetry.counter(f"survey.lease_chip_s.{task.stage.name}",
                              k * held_s)
            for i in ids:  # the same seconds by chip
                telemetry.counter(f"survey.lease_chip_s.chip{i}", held_s)
            held["holding"] = None
            self._release_devices(ids)

    def _run_leased(self, task: _Task, k: int, ids: List[int],
                    reason: str) -> None:
        """The body of one lease: record the decision, bind the chips,
        run the lane."""
        obs = self.obs[task.obs_i]
        telemetry.event("survey.gang_decision", obs=obs.name,
                        stage=task.stage.name, k=k, chips=ids,
                        reason=reason)
        trace = self._traces[task.obs_i]
        if trace is not None:
            trace.event("survey.gang_decision", stage=task.stage.name,
                        k=k, chips=ids, reason=reason)
        gang_devs = self._jax_gang(ids)
        if gang_devs is not None:
            task.last_real_dev_ids = [
                int(getattr(d, "id", i))
                for i, d in zip(ids, gang_devs)]
        mates = self._claim_lane_mates(task, k)
        if gang_devs is not None:
            import jax

            from pypulsar_tpu.parallel.mesh import device_lease

            with jax.default_device(gang_devs[0]), \
                    device_lease(gang_devs):
                self._run_lane(task, mates, k, ids, pinned=True)
        else:
            self._run_lane(task, mates, k, ids, pinned=False)

    def _claim_lane_mates(self, task: _Task, k: int) -> List[_Task]:
        """Round 24 batch lanes.  A single-chip lease taken for a
        broker-submitting stage widens into a *batch lane*: it claims up
        to ``PYPULSAR_TPU_BROKER_LANE - 1`` queued same-stage tasks and
        runs them concurrently UNDER THIS LEASE, so their device
        dispatches meet in the batch broker and fuse instead of
        serializing on separate exclusive leases.  Claims are skipped
        for gangs (k > 1), non-broker stages, when the broker/lanes are
        off, while a chip of the pool stands free, and whenever the
        resource guard is refusing launches."""
        if k != 1 or task.stage.name not in _BROKER_UNITS:
            return []
        if not broker_mod.enabled() or broker_mod.lane_width() <= 1:
            return []
        if self._guard.admit() is not None:
            return []  # under resource pressure: no extra tenants
        width = broker_mod.lane_width()
        mates: List[_Task] = []
        with self._lock:
            if self._stop or self._free_ids:
                # a free chip runs a queued task at once and alone: a
                # lane is for tasks that would otherwise wait for this
                # lease's chip (with one lease: every queued one)
                return []
            for t in self._tasks.values():
                if len(mates) >= width - 1:
                    break
                if t is task or t.state != _QUEUED:
                    continue
                if t.stage.name != task.stage.name:
                    continue
                if self.plane is not None and t.obs_i not in self._owned:
                    continue
                if self._obs_leases.get(t.obs_i, {}).get(
                        "holding") in t.stage.deps:
                    # queued by a stage whose thread has not given its
                    # chip back yet (beams in lockstep finish together):
                    # that chip is free in a moment, and the task's own
                    continue
                # claim: run out of band, leave a stale queue entry
                # that _worker_step consumes by seq match
                t.state = _RUNNING
                t.lane_seq = t.seq
                mates.append(t)
        return mates

    def _run_lane(self, task: _Task, mates: List[_Task], k: int,
                  ids: List[int], *, pinned: bool) -> None:
        """Execute the leader task, plus any lane mates in sibling
        threads that re-enter the leader's device pin + lease.  All
        lane members register as broker parties for the stage's unit
        kind *before* any of them runs, so the first submitter's batch
        window knows how many peers to wait for; each member withdraws
        its party as it finishes so trailing uneven batches never stall
        on departed peers."""
        dev_ids = ids if pinned else None
        if not mates:
            self._execute(task, gang=k, dev_ids=dev_ids)
            return
        # scope must be computed inside the pinned context so leader
        # and mates (which re-enter the same lease) key identically
        party = (_BROKER_UNITS[task.stage.name], broker_mod.device_scope())
        bk = broker_mod.get_broker()
        names = [self.obs[t.obs_i].name for t in mates]
        telemetry.counter("broker.lane_grants", len(mates))
        telemetry.event("survey.lane_decision", stage=task.stage.name,
                        leader=self.obs[task.obs_i].name, mates=names,
                        width=1 + len(mates), chips=ids)
        # pre-register every member (leader included) before anything
        # executes: closes the race where the leader submits before a
        # mate thread has spun up and the batch dispatches solo
        for _ in range(1 + len(mates)):
            bk._party_enter(party)

        def _mate_body(t: _Task) -> None:
            try:
                try:
                    if pinned:
                        import jax

                        from pypulsar_tpu.parallel.mesh import device_lease

                        gang_devs = self._jax_gang(ids)
                        with jax.default_device(gang_devs[0]), \
                                device_lease(gang_devs):
                            self._execute(t, gang=k, dev_ids=dev_ids)
                    else:
                        self._execute(t, gang=k)
                finally:
                    bk._party_exit(party)
            except Exception as e:  # stage failure: normal retry path
                self._handle_failure(t, e)
            except BaseException as e:  # injected kill etc: fleet-fatal
                with self._cv:
                    if self._fatal is None:
                        self._fatal = e
                    self._stop = True
                    self._cv.notify_all()

        threads = []
        for t in mates:
            th = threading.Thread(
                target=_mate_body, args=(t,), daemon=True,
                name=f"lane-{self.obs[t.obs_i].name}-{t.stage.name}")
            th.start()
            threads.append(th)
        try:
            try:
                self._execute(task, gang=k, dev_ids=dev_ids)
            finally:
                bk._party_exit(party)
        finally:
            for th in threads:
                th.join()

    def _worker(self, q: "queue.PriorityQueue",
                device_lane: bool = False) -> None:
        while True:
            try:
                self._worker_step(q, device_lane)
            except StopIteration:
                return
            except health_mod.StageTimeout:
                # an async watchdog interrupt that lost the race with
                # stage completion and landed between tasks: the
                # verdict was already withdrawn (late_interrupt); the
                # worker must survive, or its queue lane dies and the
                # fleet hangs
                telemetry.event("survey.late_interrupt")

    def _worker_step(self, q: "queue.PriorityQueue",
                     device_lane: bool) -> None:
        """One take-a-task-and-run-it iteration; raises StopIteration
        to shut the worker down."""
        try:
            _, seq, task = q.get(timeout=0.05)
        except queue.Empty:
            if self._stop:
                raise StopIteration
            return
        # resource preflight: low disk / backpressure pauses the
        # LAUNCH of this stage (in-flight work keeps running and is
        # what frees the resource); re-checked after the pause
        self._wait_admission()
        with self._lock:
            if self._stop and self._fatal is not None:
                return  # fleet is unwinding: drop queued work
            if task.seq != seq:
                # the task was re-enqueued since this entry was put
                # (lane-claimed then retried): a younger entry owns it
                return
            if task.lane_seq == seq:
                # a batch lane ran (or is running) this task out of
                # band: this is its stale queue entry — consume it
                task.lane_seq = None
                return
            if task.state in (_QUARANTINED, _REMOTE):
                return  # cancelled / finished remotely while queued
            if self.plane is not None \
                    and task.obs_i not in self._owned:
                return  # ceded while queued: the adopter runs it
            task.state = _RUNNING
        try:
            if device_lane:
                self._run_device_task(task)
            else:
                self._execute(task)
        except Exception as e:  # noqa: BLE001 - retry/quarantine policy
            self._handle_failure(task, e)
        except BaseException as e:  # injected kill / interrupt
            with self._cv:
                if self._fatal is None:
                    self._fatal = e
                self._stop = True
                self._cv.notify_all()
            raise StopIteration

    # -- warm-pool precompile (round 22) ------------------------------------

    def _obs_geometry(self, i: int) -> Optional[dict]:
        """One observation's stage geometry for the compile plane's
        warmers: the raw header (channel table, sample time, length)
        plus the fleet config's grid — everything a warmer needs to
        rebuild the shapes its stage will dispatch. None when the
        header cannot be read (the stage machinery owns that error)."""
        from pypulsar_tpu.io.opener import open_reader

        import numpy as np

        cfg = self.cfg
        try:
            r = open_reader(self.obs[i].infile)
            try:
                freqs = np.asarray(r.frequencies, dtype=np.float64)
                tsamp = float(r.tsamp)
                nsamp = int(getattr(r, "number_of_samples", 0)
                            or getattr(r, "nsamples", 0) or 0)
            finally:
                close = getattr(r, "close", None)
                if close is not None:
                    close()
        except Exception:  # noqa: BLE001 - warm pool never fails a fleet
            return None
        return dict(
            dms=cfg.lodm + cfg.dmstep * np.arange(max(1, cfg.numdms)),
            freqs=freqs, dt=tsamp, n_samples=nsamp,
            downsamp=max(1, cfg.downsamp), nsub=cfg.nsub,
            group_size=cfg.group_size, chunk_payload=cfg.chunk,
            fold_nbins=cfg.fold_nbins, fold_npart=cfg.fold_npart,
            fold_batch=cfg.fold_batch)

    def _warm_placement(self):
        """The placement of the lowest healthy chip, for the warm pool's
        thread: with several leases every stage thread is pinned to its
        lease's chip and the plane keys executables by that placement, so
        a warmer running unpinned would compile programs no stage ever
        finds. What the pool compiles for that chip the other chips'
        lanes load (the plane hands a one-chip program from the chip
        that compiled it to the others); with one lease nothing is
        pinned, here as there."""
        import contextlib

        healthy = self._healthy_ids()
        devs = self._jax_gang([min(healthy)]) if healthy else None
        if devs is None:
            return contextlib.nullcontext()
        import jax

        return jax.default_device(devs[0])

    def _would_gang(self, obs_i: int, stage_name: str) -> bool:
        """True when a lease taken now would span several chips for this
        stage: its sharded programs belong to the mesh of that lease,
        which the one-chip warmers cannot lower for."""
        task = self._tasks.get((obs_i, stage_name))
        return task is not None and self._gang_size(task)[0] > 1

    def _warmpool_loop(self) -> None:
        """Host-pool precompile daemon: while the devices chew on the
        current observations, AOT-compile the next ready observation's
        (stage, geometry) set through the compile plane's registered
        warmers, so its first dispatch finds a ready executable instead
        of a trace+compile stall on the critical path. Purely an
        optimization: every failure is swallowed (counted by the plane
        as ``compile.warm_error``) and the loop exits once every
        observation is warmed or already running."""
        import pypulsar_tpu.fold.engine  # noqa: F401 - registers warmers
        import pypulsar_tpu.parallel.sweep  # noqa: F401
        from pypulsar_tpu.compile import warm_stage, warmable_stages

        warmed: set = set()
        while not self._stop:
            target = None
            with self._lock:
                for i in range(len(self.obs)):
                    if i in warmed:
                        continue
                    if len(self.obs) > 1 and i < self.devices:
                        # the first wave of a fleet starts at once, every
                        # observation on a chip of its own: its stages
                        # build what they need, and a pool racing them
                        # only decides by the hair of a start whose
                        # frames key an entry and whether a later run of
                        # the same fleet builds the pool's guess mid-step
                        warmed.add(i)
                        continue
                    states = [self._tasks[(i, s.name)].state
                              for s in self.stages]
                    if all(st in (_DONE, _QUARANTINED, _REMOTE)
                           for st in states):
                        warmed.add(i)  # nothing left to warm for
                        continue
                    if any(st == _RUNNING for st in states):
                        warmed.add(i)  # too late: already on a device
                        continue
                    target = i
                    break
            if target is None:
                return  # every observation warmed or started
            warmed.add(target)
            geo = self._obs_geometry(target)
            if geo is None:
                continue
            obs = self.obs[target]
            t_rel = time.perf_counter() - self._t0
            t0 = time.perf_counter()
            n = 0
            with telemetry.span("survey.precompile", obs=obs.name), \
                    self._warm_placement():
                for stage in warmable_stages():
                    if self._stop:
                        break
                    if self._would_gang(target, stage):
                        continue
                    n += warm_stage(stage, **geo)
            dur = time.perf_counter() - t0
            telemetry.counter("survey.precompiled", n)
            trace = self._traces[target]
            if trace is not None:
                trace.span("survey.precompile", t_rel, dur, compiled=n)
            if self.verbose and n:
                print(f"# survey: {obs.name}: warm pool precompiled "
                      f"{n} executable(s) in {dur:.2f}s")

    # -- entry point --------------------------------------------------------

    def run(self) -> FleetResult:
        """Run the fleet to completion (or first fatal error). Returns
        the :class:`FleetResult`; re-raises a BaseException (injected
        kill, KeyboardInterrupt) after the in-flight stages settle."""
        self._t0 = time.perf_counter()
        self._open_manifests()
        if self.plane is not None:
            if self.plane.token is None:
                self.plane.register()
                self._plane_owned_here = True
        else:
            self._validate_ingest()
        if self._needs_watchdog():
            # heartbeats ride the telemetry the stages already record;
            # the hook is process-global, so it is installed only for
            # the run and removed in the finally below
            telemetry.add_activity_hook(self._hb.beat)
            self._watchdog = health_mod.Watchdog(self._hb,
                                                 self._on_stage_expired)
            self._watchdog.start()
        try:
            if self.plane is not None:
                # multi-host: nothing is pre-assigned — the claim loop
                # admits observations as it wins their leases (and
                # adopts orphans as hosts die); an initial tick before
                # the workers start gives them something to chew on
                self._plane_poll()
                self._claim_thread = threading.Thread(
                    target=self._plane_loop,
                    name=f"survey-claims-{self.host_id}", daemon=True)
                self._claim_thread.start()
            else:
                with self._cv:
                    for i in range(len(self.obs)):
                        done = (self._manifests[i].done_stages()
                                if self.resume else set())
                        for s in self.stages:
                            if s.name in done:
                                self._tasks[(i, s.name)].state = _DONE
                                self.result.skipped.append(
                                    (self.obs[i].name, s.name))
                                telemetry.counter("survey.stages_skipped")
                        self._promote_locked(i)
                    self._maybe_stop_locked()
            self._ready.set()
            if knobs_mod.env_str("PYPULSAR_TPU_COMPILE_WARMPOOL") \
                    not in ("0", "off", "none"):
                # warm-pool precompile rides the host pool's spare
                # cycles; a daemon so a hung compile cannot block exit
                self._warm_thread = threading.Thread(
                    target=self._warmpool_loop, name="survey-warmpool",
                    daemon=True)
                self._warm_thread.start()
            workers = (
                [threading.Thread(target=self._worker,
                                  args=(self._device_q, True),
                                  name=f"survey-device{d}")
                 for d in range(self.devices)]
                + [threading.Thread(target=self._worker,
                                    args=(self._host_q,),
                                    name=f"survey-host{h}")
                   for h in range(self.max_host_workers)])
            for w in workers:
                w.start()
            try:
                with self._cv:
                    while not self._stop:
                        self._cv.wait(0.1)
            except BaseException as e:  # Ctrl+C lands HERE, not in a worker
                # stop + fatal so workers drop queued work (and an
                # admission-paused worker wakes) instead of polling
                # forever under a join() that never returns
                with self._cv:
                    if self._fatal is None:
                        self._fatal = e
                    self._stop = True
                    self._cv.notify_all()
            for w in workers:
                w.join()
        finally:
            self._ready.set()  # never leave a service waiter hanging
            if self._watchdog is not None:
                self._watchdog.stop()
                self._watchdog = None
                telemetry.remove_activity_hook(self._hb.beat)
            if self._claim_thread is not None:
                self._claim_thread.join(timeout=5.0)
                self._claim_thread = None
            if self._warm_thread is not None:
                self._warm_thread.join(timeout=5.0)
                self._warm_thread = None
            self._write_health_json()
            self.result.wall = time.perf_counter() - self._t0
            # what the pool offered: every chip for the scheduler's wall
            # (survey.lease_chip_s over this is the leased share)
            telemetry.counter("survey.pool_chip_s",
                              self.devices * self.result.wall)
            for m in self._manifests:
                if m is not None:
                    m.close()
            for t in self._traces:
                if t is not None:
                    t.close()
            if self.plane is not None and self._plane_owned_here:
                # retire the host lease (LEFT, not DEAD). An InjectedKill
                # unwinds through here too — its lease reads LEFT with
                # claims still running, which is equally adoptable; only
                # a true SIGKILL/os._exit skips this and leaves the
                # lease to go silent (DEAD after the lease bound)
                self.plane.close()
        if self._fatal is not None:
            # the capsule for the run that ended in a bang: the last N
            # telemetry records before the unhandled crash/interrupt
            self._postmortem(
                "crash",
                extra={"error": f"{type(self._fatal).__name__}: "
                                f"{self._fatal}"})
            raise self._fatal
        return self.result
