"""The knob registry: the single read path for every ``PYPULSAR_TPU_*``
tunable (round 17).

Before this module, every hot path carried its own ``os.environ.get``
with its own inline default — 25+ knobs scattered across sweep, the
accel handoff, specfuse, prefetch, foldpipe, the fleet scheduler and the
CLIs — and the one-off BENCHNOTES A/B wisdom (2^18 FFT chunks, +41%;
``--batch 32``) was frozen into source constants nobody could move.
This registry makes each knob a *declaration* — name, type, default,
which stage it binds to, and (for throughput knobs) the bounded search
domain the auto-tuner may explore — and makes the resolution order
explicit and uniform::

    trial override  >  env var  >  tuned (cache) value  >  default

- **trial override**: a thread-local overlay the bounded searcher
  (tune/search.py) installs around each timed trial — never visible
  outside a search.
- **env var**: the operator always wins. A knob pinned by env is also
  *excluded from search* (tune/search.py skips it).
- **tuned value**: a process-global overlay installed from the persisted
  geometry-keyed cache (tune/cache.py) by the stage entry points.
- **default**: the declared value, the same constant the old inline
  reads carried.

Numeric knobs tolerate a typo'd env value by falling through to the
next layer (the repo-wide "a bad knob must never abort a fleet"
contract, inherited from resilience.health.env_float). String knobs
pass the raw value through untouched — selection knobs like
``PYPULSAR_TPU_SHIFT_BACKEND`` keep their own loud validation.

Science-invariance contract: a knob that can change *results* (engine
selection, decimate mode, shift backend …) is declared
``invariant=False`` and is NEVER searched or cached — tuning may only
move throughput knobs. ``variant_engines`` narrows that per engine:
``PYPULSAR_TPU_SWEEP_CHUNK`` is byte-invariant for the gather
engine (measured: identical .dat bytes across chunk lengths) but
changes f32 rounding under ``fourier`` (chunk-length-dependent FFT
rounding, the same fact parallel/staged.py fingerprints), so the sweep
search domain drops it when the resolved engine is ``fourier``.

This module is imported from bootstrap paths (native/__init__,
ops/kernels) — it must stay stdlib-only with no package imports.

psrlint PL011 enforces that no raw ``PYPULSAR_TPU_*`` env read exists
outside this file; PL004 keeps the README "Runtime knobs" table synced
with the declarations below (the ``env_knob`` helper name is one of the
registration idioms PL004 recognizes).
"""

from __future__ import annotations

import hashlib
import os
import threading
from dataclasses import dataclass
from typing import Any, Dict, Iterator, Optional, Tuple

__all__ = [
    "Knob",
    "all_knobs",
    "apply_tuned",
    "clear_tuned",
    "config_digest",
    "current_config",
    "env_float",
    "env_int",
    "env_raw",
    "env_str",
    "env_value",
    "knob",
    "searchable_knobs",
    "trial_overrides",
    "tuned_overlay",
]

_MISSING = object()


@dataclass(frozen=True)
class Knob:
    """One tunable: a typed declaration replacing an inline env read."""

    env: str                 # "PYPULSAR_TPU_SWEEP_CHUNK"
    ktype: str               # "int" | "float" | "str"
    default: Any             # value when nothing else is set
    stage: str               # pipeline stage the knob binds to
    domain: Tuple = ()       # bounded search candidates ((): not searched)
    invariant: bool = True   # False: can change RESULTS -> never searched
    variant_engines: Tuple[str, ...] = ()  # engines where results vary
    help: str = ""

    def parse(self, raw: str) -> Any:
        """Typed parse of an env/cache string; raises ValueError on
        garbage (callers fall through to the next precedence layer)."""
        if self.ktype == "int":
            return int(float(raw))  # "5e9" style accepted, like int(float())
        if self.ktype == "float":
            return float(raw)
        return raw


_REGISTRY: Dict[str, Knob] = {}

# process-global tuned overlay (installed from the persisted cache by
# the stage entry points; each knob binds to exactly ONE stage, so two
# concurrent stages of different kinds never collide on a key)
_tuned: Dict[str, Any] = {}
# a plain stdlib lock at bootstrap (this module may not import the
# package); resilience.locks swaps in its lockdep-tracked wrapper the
# first time the resilience layer loads (_adopt_bootstrap_locks) — a
# leaf in the canonical hierarchy, never held across another acquire
_tuned_lock = threading.Lock()

# thread-local trial overlay stack (the searcher's timed candidates)
_tls = threading.local()


def env_knob(env: str, ktype: str, default: Any, stage: str,
             domain: Tuple = (), invariant: bool = True,
             variant_engines: Tuple[str, ...] = (),
             help: str = "") -> Knob:  # noqa: A002 - mirrors argparse
    """Declare + register one knob (the registration idiom PL004 scans
    for alongside ``ENV_*`` constant bindings)."""
    k = Knob(env, ktype, default, stage, tuple(domain), invariant,
             tuple(variant_engines), help)
    _REGISTRY[env] = k
    return k


def knob(env: str) -> Knob:
    return _REGISTRY[env]


def all_knobs(stage: Optional[str] = None) -> Iterator[Knob]:
    for k in _REGISTRY.values():
        if stage is None or k.stage == stage:
            yield k


def searchable_knobs(stage: str, engine: Optional[str] = None):
    """The knobs the bounded searcher may move for ``stage``: declared
    domain, results-invariant (for the active ``engine``), and not
    pinned by the operator's environment (env always wins)."""
    for k in all_knobs(stage):
        if not k.domain or not k.invariant:
            continue
        if engine is not None and engine in k.variant_engines:
            continue
        if env_raw(k.env) is not None:
            continue
        yield k


# ---------------------------------------------------------------------------
# overlays

def apply_tuned(config: Dict[str, Any], source: str = "cache") -> Dict[str, Any]:
    """Install tuned values (from the persisted cache or a finished
    search) into the process-global overlay. Unregistered names and
    results-affecting knobs are dropped — a cache file can never flip
    an engine or a mode, only throughput knobs. Returns what was
    actually applied."""
    applied = {}
    for name, value in (config or {}).items():
        k = _REGISTRY.get(name)
        if k is None or not k.invariant:
            continue
        try:
            applied[name] = k.parse(str(value))
        except (TypeError, ValueError):
            continue
    with _tuned_lock:
        _tuned.update(applied)
    return applied


def clear_tuned() -> None:
    with _tuned_lock:
        _tuned.clear()


def tuned_overlay() -> Dict[str, Any]:
    with _tuned_lock:
        return dict(_tuned)


class trial_overrides:
    """Context manager: highest-precedence thread-local overlay for ONE
    timed search trial. Never escapes the thread or the block."""

    def __init__(self, config: Dict[str, Any]):
        self._config = dict(config)

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self._config)
        return self._config

    def __exit__(self, *exc):
        _tls.stack.pop()
        return False


def _trial_value(name: str):
    stack = getattr(_tls, "stack", None)
    if stack:
        for cfg in reversed(stack):
            if name in cfg:
                return cfg[name]
    return _MISSING


# ---------------------------------------------------------------------------
# the read path

def env_raw(name: str) -> Optional[str]:
    """The ONE raw environment read in the package (PL011's blessed
    site). Empty string counts as unset, matching the historical
    ``os.environ.get(X, "") or default`` idiom."""
    raw = os.environ.get(name)
    return raw if raw else None


def env_value(name: str, default: Any = _MISSING,
              overlays: bool = True) -> Any:
    """Resolve ``name`` through trial > env > tuned > default.

    Registered knobs use their declared type and default (a ``default``
    argument is ignored — the declaration is the single source of
    truth). Unregistered names behave like the historical typo-tolerant
    ``env_float`` helper: raw env value parsed as ``default``'s flavor,
    garbage/unset -> ``default``.

    ``overlays=False`` skips the trial AND tuned layers (pure
    ``env > default``) — for consumers whose RESULTS depend on the knob
    (e.g. the single-pulse detector's per-chunk statistics): the
    operator's env var is an explicit, fingerprinted choice, but the
    auto-tuner must never reach them.
    """
    k = _REGISTRY.get(name)
    if overlays:
        tv = _trial_value(name)
        if tv is not _MISSING:
            return tv
    raw = env_raw(name)
    if k is None:
        fallback = None if default is _MISSING else default
        if raw is None:
            return fallback
        try:
            return float(raw) if isinstance(fallback, (int, float)) \
                else raw
        except ValueError:
            return fallback
    if raw is not None:
        if k.ktype == "str":
            return raw
        try:
            return k.parse(raw)
        except ValueError:
            pass  # typo'd numeric knob: fall through, never abort
    if overlays:
        with _tuned_lock:
            if name in _tuned:
                return _tuned[name]
    return k.default


def env_int(name: str, default: Any = _MISSING,
            overlays: bool = True) -> Optional[int]:
    v = env_value(name, default, overlays)
    return None if v is None else int(v)


def env_float(name: str, default: Any = _MISSING,
              overlays: bool = True) -> Optional[float]:
    v = env_value(name, default, overlays)
    return None if v is None else float(v)


def env_str(name: str, default: Any = _MISSING,
            overlays: bool = True) -> Optional[str]:
    v = env_value(name, default, overlays)
    return None if v is None else str(v)


def current_config(stage: Optional[str] = None) -> Dict[str, Any]:
    """The fully-resolved value of every (stage-filtered) knob — what a
    search starts from and what the bench records as 'effective'."""
    return {k.env: env_value(k.env) for k in all_knobs(stage)}


def config_digest(stage: str) -> str:
    """Digest of a stage's fully-resolved knob config (trial > env >
    tuned > default). This is THE config component of every dispatch
    key: the compile plane keys its AOT executables with it (round 17)
    and the batch broker keys its coalescing queues with it (round 24),
    so two observations coalesce only when they would have compiled the
    very same executable."""
    if not stage:
        return ""
    blob = repr(sorted(current_config(stage).items())).encode()
    return hashlib.sha1(blob).hexdigest()[:12]


# ---------------------------------------------------------------------------
# declarations — one row per knob, same defaults the inline reads carried
# ---------------------------------------------------------------------------

# -- sweep ------------------------------------------------------------------
env_knob("PYPULSAR_TPU_SWEEP_CHUNK", "int", 1 << 18, "sweep",
         domain=(1 << 16, 1 << 17, 1 << 18, 1 << 19, 1 << 20),
         variant_engines=("fourier",),
         help="streaming FFT chunk length in samples (rounded up to a "
              "power of two); the round-5 v5e A/B found 2^18 +41% over "
              "2^17. Tuned values reach only the byte-invariant "
              "series/handoff paths (gather; fourier's FFT "
              "rounding is chunk-dependent, so its search domain drops "
              "the knob) — the single-pulse DETECTOR resolves this "
              "knob env-only (its per-chunk statistics make the chunk "
              "part of its results)")
env_knob("PYPULSAR_TPU_HOST_DOWNSAMP", "str", None, "sweep",
         invariant=False,
         help="force the staged sweep's pre-ship downsample on (1) or "
              "off (0) the host; default is a wire-bytes policy")
env_knob("PYPULSAR_TPU_DATS_RESIDENT_LIMIT", "float", 2e9, "sweep",
         help="raw-file bytes above which --write-dats streams instead "
              "of building the series in memory")

# -- accel ------------------------------------------------------------------
env_knob("PYPULSAR_TPU_ACCEL_BATCH", "int", 32, "accel",
         domain=(8, 16, 32, 64),
         help="spectra per batched accel-search dispatch (the old "
              "hand-pinned --batch 32; CLI flags still win)")
env_knob("PYPULSAR_TPU_ACCEL_HBM", "float", 5e9, "accel",
         domain=(2e9, 5e9, 8e9),
         help="per-device HBM bytes the batched accel search plans for")
env_knob("PYPULSAR_TPU_ACCEL_STREAM_RAM", "float", 12e9, "accel",
         help="host RAM for the in-RAM sweep->accel handoff")
env_knob("PYPULSAR_TPU_ACCEL_BANK_CACHE", "float", 4e9, "accel",
         help="host RAM bytes for the cached accel template-bank "
              "arrays (the round-4 _BANK_CACHE_LIMIT constant); a "
              "single bank larger than this bypasses the cache")

# -- specfuse ---------------------------------------------------------------
env_knob("PYPULSAR_TPU_SPECFUSE_HBM", "float", 8e9, "specfuse",
         help="device bytes for one --spectral fused DM slice")
env_knob("PYPULSAR_TPU_SPECFUSE_MODE", "str", "stitch", "specfuse",
         invariant=False,
         help="stitch (bit-exact default) or decimate (circular "
              "semantics) — results-affecting, never searched")

# -- fold -------------------------------------------------------------------
env_knob("PYPULSAR_TPU_FOLD_STREAM_RAM", "float", 12e9, "fold",
         help="host RAM for foldbatch's streamed raw pass")
env_knob("PYPULSAR_TPU_FOLD_BINIDX_RAM", "float", 4e9, "fold",
         help="bytes of fold one-hot bin matrices per refinement "
              "dispatch")

# -- prefetch / pipelining --------------------------------------------------
env_knob("PYPULSAR_TPU_SHIP_AHEAD", "str", "1", "prefetch",
         help="0 disables every background ship-ahead/prefetch thread")
env_knob("PYPULSAR_TPU_PREFETCH_TIMEOUT", "float", 900.0, "prefetch",
         help="seconds a prefetch consumer waits per item before "
              "declaring the producer wedged; <=0 disables")

# -- fleet health (survey scheduler) ---------------------------------------
env_knob("PYPULSAR_TPU_STALL_S", "float", None, "fleet",
         invariant=False,
         help="heartbeat-silence bound before the watchdog interrupts "
              "a stage; unset = stall detection off")
env_knob("PYPULSAR_TPU_DEVICE_STRIKES", "int", 3, "fleet",
         invariant=False,
         help="OOM/device-fault strikes before a lease is quarantined")
env_knob("PYPULSAR_TPU_MIN_FREE_MB", "float", 32.0, "fleet",
         invariant=False,
         help="admission-gate free-disk floor (MB; 0 disables)")
env_knob("PYPULSAR_TPU_GANG_COST_MIN_FRAC", "float", 0.25, "fleet",
         invariant=False,
         help="--gang auto cost share below which a stage stays 1-chip")
env_knob("PYPULSAR_TPU_ADMIT_RESUME_MARGIN", "float", 0.25, "fleet",
         invariant=False,
         help="admission-gate hysteresis: once paused, resume only with "
              "this fractional slack past the floor/bound (0 = the old "
              "flappy threshold-equality behavior)")

# -- streaming daemon (round 23) --------------------------------------------
env_knob("PYPULSAR_TPU_DAEMON_QUEUE_BOUND", "int", 64, "daemon",
         invariant=False,
         help="daemon accept-queue bound: arrivals past this many "
              "admitted-but-unscheduled observations shed the lowest-"
              "priority unaccepted entry (daemon.shed)")
env_knob("PYPULSAR_TPU_DAEMON_QUIESCE_S", "float", 1.0, "daemon",
         invariant=False,
         help="watch-dir quiesce window: a file is ingested only after "
              "its size has been stable this long (a half-written .fil "
              "is never admitted)")
env_knob("PYPULSAR_TPU_DAEMON_POLL_S", "float", 0.5, "daemon",
         invariant=False,
         help="daemon watch-directory scan cadence (seconds)")
env_knob("PYPULSAR_TPU_DAEMON_TENANT_RATE", "float", 0.0, "daemon",
         invariant=False,
         help="default per-tenant token-bucket refill rate "
              "(admissions/second) for tenants without an explicit "
              "--tenant spec; 0 = unmetered")
env_knob("PYPULSAR_TPU_DAEMON_TENANT_BURST", "float", 8.0, "daemon",
         invariant=False,
         help="default per-tenant token-bucket burst capacity (the "
              "bucket depth an idle tenant accumulates)")
env_knob("PYPULSAR_TPU_DAEMON_IDLE_EXIT_S", "float", 0.0, "daemon",
         invariant=False,
         help="daemon auto-drain after this many seconds with no "
              "arrivals and an empty fleet (0 = run until SIGTERM; the "
              "bounded-soak/test hook)")

# -- batch broker (round 24) ------------------------------------------------
env_knob("PYPULSAR_TPU_BROKER", "str", "1", "broker",
         invariant=False,
         help="0 disables the cross-observation batch broker entirely: "
              "every stage dispatches per-obs exactly as before round "
              "24 (byte- and dispatch-identical)")
env_knob("PYPULSAR_TPU_BROKER_WAIT_MS", "float", 100.0, "broker",
         domain=(25.0, 100.0, 400.0),
         help="bounded latency window a broker leader holds an open "
              "batch for same-key batchmates before dispatching "
              "under-full; SLO burn collapses it to zero")
env_knob("PYPULSAR_TPU_BROKER_LANE", "int", 4, "broker",
         invariant=False,
         help="batch-lane width: max same-stage observations the "
              "scheduler co-schedules on one device lease so their "
              "dispatches can coalesce (1 = exclusive leases only)")
env_knob("PYPULSAR_TPU_BROKER_SLO_HOLD_S", "float", 30.0, "broker",
         invariant=False,
         help="seconds after an SLO burn or daemon shed during which "
              "the broker stops waiting for batchmates (latency "
              "pressure gates coalescing width)")

# -- candidate data plane (round 25) ----------------------------------------
env_knob("PYPULSAR_TPU_CANDSTORE", "str", "1", "candstore",
         invariant=False,
         help="0 disables the candidate store entirely: the fleet runs "
              "store-less exactly as before round 25 (per-obs "
              "artifacts are byte-identical either way; this only "
              "gates the _fleet/candstore/ ingest edge)")
env_knob("PYPULSAR_TPU_CANDSTORE_SEGMENT_BYTES", "float", 4e6,
         "candstore", invariant=False,
         help="segment-log rotation bound: appends roll to a new "
              "seg-*.jsonl once the active segment reaches this size")
env_knob("PYPULSAR_TPU_CANDSTORE_COMPACT_RECORDS", "int", 2048,
         "candstore", invariant=False,
         help="compact the segment log into the indexed snapshot once "
              "it holds this many records (0 disables auto-compaction; "
              "cands --compact still forces one)")
env_knob("PYPULSAR_TPU_CANDSTORE_TOL_P", "float", 1e-3, "candstore",
         invariant=False,
         help="default FRACTIONAL period tolerance for store queries "
              "(--near) and cross-obs harmonic clustering")
env_knob("PYPULSAR_TPU_CANDSTORE_TOL_DM", "float", 0.5, "candstore",
         invariant=False,
         help="default absolute DM tolerance for store queries (--near) "
              "and cross-obs harmonic clustering")

# -- data integrity ---------------------------------------------------------
env_knob("PYPULSAR_TPU_MAX_BAD_FRAC", "float", 0.5, "data",
         invariant=False,
         help="ingest degrade-vs-quarantine bad-sample fraction bar")
env_knob("PYPULSAR_TPU_DATAGUARD", "str", "1", "data",
         invariant=False,
         help="0 disables the on-device non-finite stream scrub")

# -- concurrency / lockdep --------------------------------------------------
env_knob("PYPULSAR_TPU_LOCKDEP", "str", "warn", "concurrency",
         invariant=False,
         help="lock-discipline runtime mode: warn (default; a detected "
              "acquisition-order cycle emits a lockdep.order_violation "
              "telemetry event), strict (raise LockOrderError, the "
              "offending lock is never held), off (disable held-set/"
              "order tracking entirely)")
env_knob("PYPULSAR_TPU_RACE_SEED", "int", 0, "concurrency",
         invariant=False,
         help="seed for the interleaving stress harness's deterministic "
              "lock-boundary pauses (bench.py --race)")
env_knob("PYPULSAR_TPU_RACE_PAUSE_US", "float", 0.0, "concurrency",
         invariant=False,
         help="arm seeded pauses of up to this many microseconds at "
              "every tracked lock acquire/release (0 = off); widens "
              "race windows for the --race harness and its subprocess "
              "children")

# -- fault injection --------------------------------------------------------
env_knob("PYPULSAR_TPU_FAULTS", "str", None, "faults",
         invariant=False,
         help="armed deterministic fault spec (kind:point[:N],...)")
env_knob("PYPULSAR_TPU_CHAOS", "str", None, "faults",
         invariant=False,
         help="seeded probabilistic chaos SEED:RATE[:kind+kind...]")
env_knob("PYPULSAR_TPU_HANG_S", "float", 30.0, "faults",
         invariant=False,
         help="upper bound on an injected hang")

# -- engine / backend selection --------------------------------------------
env_knob("PYPULSAR_TPU_SHIFT_BACKEND", "str", None, "engine",
         invariant=False,
         help="waterfall shift kernel override (fourier/gather) — "
              "results-affecting, never searched")
env_knob("PYPULSAR_TPU_NO_NATIVE", "str", None, "engine",
         invariant=False,
         help="any value disables the native compiled helpers")

# -- multi-host -------------------------------------------------------------
env_knob("PYPULSAR_TPU_COORDINATOR", "str", None, "multihost",
         invariant=False,
         help="coordinator address for sweep --distributed")
env_knob("PYPULSAR_TPU_NUM_PROCESSES", "int", 1, "multihost",
         invariant=False,
         help="multi-host process count")
env_knob("PYPULSAR_TPU_PROCESS_ID", "int", 0, "multihost",
         invariant=False,
         help="multi-host process rank")
env_knob("PYPULSAR_TPU_HOST_LEASE_S", "float", 10.0, "multihost",
         invariant=False,
         help="survey-fleet host-lease bound: a host whose heartbeat "
              "is silent this long is DEAD and its in-flight "
              "observations become adoptable")
env_knob("PYPULSAR_TPU_HOST_HEARTBEAT_S", "float", 0.0, "multihost",
         invariant=False,
         help="host-lease renewal cadence (0 = lease bound / 4)")
env_knob("PYPULSAR_TPU_HOST_SETTLE_S", "float", 0.2, "multihost",
         invariant=False,
         help="claim settle window: write -> re-read delay resolving "
              "the common double-adoption race before stage work starts")
env_knob("PYPULSAR_TPU_HOST_ID", "str", None, "multihost",
         invariant=False,
         help="survey-fleet host identity override (the --hosts "
              "launcher sets one per child)")
env_knob("PYPULSAR_TPU_HOST_STRIKES", "int", 3, "multihost",
         invariant=False,
         help="adoption/cede strikes before a host stops claiming new "
              "observations")

# -- observability (round 21) ----------------------------------------------
env_knob("PYPULSAR_TPU_OBS_FLIGHTREC", "int", 256, "obs",
         invariant=False,
         help="crash flight recorder ring size (telemetry records kept "
              "in memory per process, dumped to _fleet/postmortem/ on "
              "quarantine/watchdog/eviction/crash); 0 disables")
env_knob("PYPULSAR_TPU_OBS_STATUS_PORT", "int", 0, "obs",
         invariant=False,
         help="default port for the survey live status/metrics "
              "endpoint (0 = off unless --status-port is given)")
env_knob("PYPULSAR_TPU_OBS_FOLLOW_S", "float", 2.0, "obs",
         invariant=False,
         help="refresh cadence of `survey --status --follow` (seconds)")
env_knob("PYPULSAR_TPU_OBS_STATUSD_TTL_S", "float", 0.25, "obs",
         invariant=False,
         help="live status/metrics endpoint snapshot cache TTL "
              "(seconds): scrapes within the window reuse one "
              "snapshot so aggressive pollers cannot stampede the "
              "scheduler's lock")
env_knob("PYPULSAR_TPU_OBS_SLO_FRAC", "float", 0.8, "obs",
         invariant=False,
         help="fraction of a stage's deadline budget consumed (without "
              "tripping the watchdog) that emits a survey.slo_burn "
              "event")

# -- compilation plane (round 22) -------------------------------------------
env_knob("PYPULSAR_TPU_COMPILE_CACHE", "str", "1", "compile",
         invariant=False,
         help="0/off disables the plane's persistent XLA cache set-up. "
              "The cache lives where JAX_COMPILATION_CACHE_DIR says, "
              "else at <checkout>/.jax_cache")
env_knob("PYPULSAR_TPU_COMPILE_AOT", "str", "1", "compile",
         invariant=False,
         help="0 disables the plane's in-process AOT executable "
              "registry (plane_jit degrades to plain jax.jit dispatch)")
env_knob("PYPULSAR_TPU_COMPILE_BUCKETS", "str", "1", "compile",
         invariant=False,
         help="0 disables geometry bucketing of batch axes (DM trial "
              "groups, accel spectrum batches, fold candidate batches); "
              "bucket choice never changes artifact bytes")
env_knob("PYPULSAR_TPU_COMPILE_WARMPOOL", "str", "1", "compile",
         invariant=False,
         help="0 disables the fleet scheduler's warm-pool AOT "
              "precompile of upcoming observations' stage executables")

# -- misc data --------------------------------------------------------------
env_knob("PYPULSAR_TPU_HASLAM", "str", "", "data",
         invariant=False,
         help="path to the Haslam 408 MHz map FITS")

# -- the tuner's own knobs --------------------------------------------------
env_knob("PYPULSAR_TPU_TUNE", "str", "cache", "tune",
         invariant=False,
         help="auto-tuning mode: cache (consult the persisted cache; "
              "default), search (cache miss runs the bounded on-line "
              "search), off/0 (disable consults entirely)")
env_knob("PYPULSAR_TPU_TUNE_CACHE", "str", "", "tune",
         invariant=False,
         help="tuning-cache JSON path (default "
              "~/.cache/pypulsar_tpu/tune.json)")
env_knob("PYPULSAR_TPU_TUNE_TRIALS", "int", 20, "tune",
         invariant=False,
         help="trial budget per bounded stage search")
env_knob("PYPULSAR_TPU_TUNE_SEED", "int", 0, "tune",
         invariant=False,
         help="seed for the searcher's synthetic measurement data")
