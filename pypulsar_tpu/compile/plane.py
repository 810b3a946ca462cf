"""The compilation plane: persistent XLA cache + AOT executable
registry + warm-pool precompile hooks (round 22).

Every ``jax.jit`` outside ``ops/`` leaf kernels dispatches through
:func:`plane_jit` (psrlint PL018 enforces it). The wrapper layers
three caches:

1. **Persistent XLA cache**: placed from outside. Where
   ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself keeps its cache
   there and the plane sets no directory in code; where it is not, the
   cache goes to one fixed, git-ignored directory inside the checkout
   (:data:`DEFAULT_CACHE_DIR` — the path is part of XLA's cache key, so
   it never carries a temp name, pid or time). A geometry compiled by
   ANY process sharing that directory is a disk hit everywhere else.
   Configured lazily, once per process, the first time the plane
   compiles anything; ``PYPULSAR_TPU_COMPILE_CACHE=0`` is the off
   switch.
2. **In-process AOT executable registry**: per-wrapper executables
   from ``jit(f).lower(...).compile()`` keyed by (stage, static
   argument values, dynamic leaf shapes/dtypes/shardings, default device, jax
   version, device kind, resolved tuned-config digest). A repeat
   geometry skips tracing entirely — ``compile.cache_hit`` — and a
   tuned config change (round 17) keys a *different* entry, so tuning
   trials are never charged another trial's first-trace compile.
3. **Warm-pool precompile**: pipeline stages register warmers
   (:func:`register_warmer`); the fleet scheduler's host pool calls
   :func:`warm_stage` for the next ready observation's geometry while
   devices are busy, so a cold fleet's first device dispatch finds a
   warm executable (``wrapper.warm(...)`` lowers from
   ``jax.ShapeDtypeStruct`` — no data needed).

Anything the AOT path cannot key faithfully — tracer inputs (a
plane-wrapped fn called under an outer trace), variadic signatures,
multi-device arrays whose sharding names no mesh — or that refuses to lower, or
whose arguments the compiled executable rejects before it runs, goes to
the held plain ``jax.jit`` and counts ``compile.aot_fallback``; a site
can opt out wholesale with
``aot=False`` (the plane still owns its telemetry). An exception
while a compiled program EXECUTES (a device error, an OOM) is not a
keying problem and propagates; a cache directory that cannot be set up
raises.

Cross-host accounting: the XLA disk cache is opaque, so on every
in-process miss the plane probes a sidecar marker
(``<cache>/plane/<digest>.json`` under whichever directory the cache
lives in, written atomically after each
compile, digest excludes process-local identity) and counts
``compile.persistent_hit`` when another process/host compiled that
key first — the counter the multi-host test asserts on.
"""

from __future__ import annotations

import functools
import hashlib
import inspect
import itertools
import json
import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from pypulsar_tpu.compile.registry import (  # noqa: F401  (re-export)
    OPS_LEAF_ALLOWLIST, bucket_rows, bucket_size, buckets_enabled,
)
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.tune import knobs

__all__ = [
    "plane_jit",
    "PlaneJit",
    "configure_persistent_cache",
    "persistent_cache_dir",
    "note_bucket_pad",
    "register_warmer",
    "warmable_stages",
    "warm_stage",
]


class _Unkeyable(Exception):
    """Inputs the AOT registry cannot key faithfully -> plain jit."""


# ---------------------------------------------------------------------------
# persistent XLA cache

_cache_lock = threading.Lock()
_cache_state: Dict[str, Any] = {"configured": False, "dir": None}

# the checkout root: <root>/pypulsar_tpu/compile/plane.py
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_CACHE_OFF = ("0", "off", "none", "false")
_CACHE_ON = ("1", "on", "true")


def _resolve_cache_dir() -> str:
    """Where the persistent cache lives, setting the fixed default in
    JAX only when nothing outside placed it."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # JAX read the variable when it was imported: follow it, set
        # nothing
        path = jax.config.jax_compilation_cache_dir
        if not path:
            raise RuntimeError(
                "JAX_COMPILATION_CACHE_DIR is set but jax holds no cache "
                "directory: the variable must be in the environment "
                "before jax is imported")
        return path
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def configure_persistent_cache() -> Optional[str]:
    """Set up the persistent XLA cache (see the module docstring for
    where it lives; ``PYPULSAR_TPU_COMPILE_CACHE=0`` disables the
    set-up). Resolved once per process — idempotent, thread-safe,
    returns the active directory or None when switched off. A
    directory that cannot be created or a misplaced variable raises:
    "no cache" is never a silent outcome."""
    with _cache_lock:
        if _cache_state["configured"]:
            return _cache_state["dir"]
        _cache_state["configured"] = True
    # The jax.config updates below go through JAX's own global config
    # machinery and must not run under our lock; the once-per-process
    # latch above already guarantees a single configuring thread (a
    # concurrent caller may briefly observe dir=None, which only skips
    # the accounting sidecar for that one dispatch).
    try:
        raw = str(knobs.env_str("PYPULSAR_TPU_COMPILE_CACHE")).strip().lower()
        if raw in _CACHE_OFF:
            return None
        if raw not in _CACHE_ON:
            raise ValueError(
                f"PYPULSAR_TPU_COMPILE_CACHE={raw!r}: the knob is an on/off "
                f"switch; place the cache with JAX_COMPILATION_CACHE_DIR")
        path = _resolve_cache_dir()
        # cache everything: the CPU-toy geometries tests exercise
        # compile in microseconds, and tiny executables are exactly
        # the ones a mixed-geometry fleet recompiles the most
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update(
            "jax_persistent_cache_enable_xla_caches", "all")
        # names are part of the program: JAX's default key ignores an
        # operation's metadata, so a cache written before a program was
        # named (or renamed) keeps serving the executable with the OLD
        # module scopes, and a profile of today's code prints
        # yesterday's names (measured, PR 24: jit__masked_block came
        # back from PR 23's cache without its scopes). Keyed on the
        # metadata, a trace names the code that ran; the price is one
        # recompile when a jitted function's source lines move.
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)
    except BaseException:
        with _cache_lock:
            _cache_state["configured"] = False  # fail again, loudly
        raise
    _cache_state["dir"] = path
    return path


def persistent_cache_dir() -> Optional[str]:
    """The active persistent cache directory (configuring lazily)."""
    return configure_persistent_cache()


def _marker_path(digest: str) -> Optional[str]:
    root = _cache_state["dir"] if _cache_state["configured"] \
        else configure_persistent_cache()
    if not root:
        return None
    return os.path.join(root, "plane", f"{digest}.json")


def _probe_marker(digest: str, meta: Dict[str, Any]) -> bool:
    """True when another process already compiled this key (the
    cross-host ``compile.persistent_hit`` probe); records our own
    marker intent in ``meta`` for :func:`_write_marker`."""
    path = _marker_path(digest)
    if path is None:
        return False
    meta["marker_path"] = path
    return os.path.exists(path)


def _write_marker(meta: Dict[str, Any], payload: Dict[str, Any]) -> None:
    path = meta.get("marker_path")
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as fh:
            json.dump(payload, fh, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # accounting sidecar only — never worth failing a dispatch


# ---------------------------------------------------------------------------
# keying helpers

def _aot_enabled() -> bool:
    raw = knobs.env_str("PYPULSAR_TPU_COMPILE_AOT")
    return str(raw) not in ("0", "off", "none")


def _device_key() -> str:
    """The thread's placement context: ``jax.default_device`` is
    thread-local (the scheduler sets it per gang lease), and an AOT
    executable is pinned to the device it lowered under — so placement
    MUST key the registry or a lease on chip 3 would silently run on
    chip 0."""
    dd = jax.config.jax_default_device
    return "auto" if dd is None else str(dd)


def _default_device_str() -> str:
    """Where jit lands a host input: the thread's jax.default_device,
    else the backend's first device (cached — process-stable)."""
    dd = jax.config.jax_default_device
    if dd is not None:
        return str(dd)
    d0 = _kind_cache.get("dev0")
    if d0 is None:
        d0 = str(jax.devices()[0])  # psrlint: ignore[PL002] -- registry-key metadata (jit's implicit placement target), not a compute placement
        _kind_cache["dev0"] = d0
    return d0


def _leaf_key(x: Any) -> Tuple:
    if isinstance(x, jax.core.Tracer):
        raise _Unkeyable("tracer input")
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        if isinstance(x, jax.Array):
            try:
                devs = x.devices()
            except Exception:
                raise _Unkeyable("unreadable placement")
            if len(devs) != 1:
                return ("a", tuple(shape), str(dtype), _sharding_key(x))
            d = str(next(iter(devs)))
            # an array already sitting where jit would commit a host
            # input keys like a host input — so a ShapeDtypeStruct
            # warm covers both call forms
            return ("a", tuple(shape), str(dtype),
                    "host" if d == _default_device_str() else d)
        return ("a", tuple(shape), str(dtype), "host")
    if isinstance(x, (bool, int, float, complex)) or x is None:
        # python scalars trace to weak-typed arrays: the TYPE picks
        # the dtype, the value never affects the executable
        return ("s", type(x).__name__)
    raise _Unkeyable(f"unkeyable leaf {type(x).__name__}")


def _config_digest(stage: str) -> str:
    """Digest of the stage's fully-resolved knob config (trial > env >
    tuned > default) — the round-17 fix: a tuned config change keys a
    different executable. Round 24 hoisted the digest itself into
    ``knobs.config_digest`` so the batch broker coalesces on the exact
    key the plane compiles under."""
    return knobs.config_digest(stage)


_WRAPPER_IDS = itertools.count()


# ---------------------------------------------------------------------------
# the wrapper

class PlaneJit:
    """Drop-in for ``jax.jit`` that dispatches through the plane's AOT
    executable registry (see module docstring for the cache layers and
    the fallback ladder)."""

    def __init__(self, fn: Callable, *, static_argnames=(),
                 stage: str = "", name: Optional[str] = None,
                 aot: bool = True):
        if isinstance(static_argnames, str):
            static_argnames = (static_argnames,)
        self._fn = fn
        self._static = tuple(static_argnames)
        self._stage = stage
        self.__name__ = name or getattr(fn, "__name__", "fn")
        # what the profiler prints is the jitted function's __name__
        # (the "XLA Modules" line reads jit_<name>) and the name scopes
        # of its operations: jit a twin that carries the wrapper's name
        # and runs the body under "<stage>.<name>". functools.wraps keeps
        # the signature static_argnames and _split bind against.
        scope = f"{stage}.{self.__name__}" if stage else self.__name__

        @functools.wraps(fn)
        def named(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)

        named.__name__ = named.__qualname__ = self.__name__
        self._jit = (jax.jit(named, static_argnames=self._static)
                     if self._static else jax.jit(named))
        self._uid = next(_WRAPPER_IDS)
        self._compiled: Dict[Tuple, Any] = {}
        # program key (the registry key without its chip) -> the Event
        # the first thread to build it sets when it is done
        self._first: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()
        self._aot = bool(aot)
        try:
            sig = inspect.signature(fn)
        except (TypeError, ValueError):
            sig = None
        if sig is None or any(
                p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD)
                for p in sig.parameters.values()):
            self._aot = False  # can't map statics -> positions
        self._sig = sig

    # -- keying ------------------------------------------------------------

    def _split(self, args, kwargs):
        """Bind the call, split static vs dynamic arguments, and build
        the registry key. Returns (key, persist_digest, dynamics)."""
        ba = self._sig.bind(*args, **kwargs)
        ba.apply_defaults()
        statics, dyn_keys, dynamics = [], [], []
        for pname, value in ba.arguments.items():
            if pname in self._static:
                statics.append((pname, repr(value)))
            else:
                leaves, treedef = jax.tree_util.tree_flatten(value)
                dyn_keys.append(
                    (pname, str(treedef),
                     tuple(_leaf_key(leaf) for leaf in leaves)))
                dynamics.append(value)
        shape_key = (tuple(statics), tuple(dyn_keys))
        cfg = _config_digest(self._stage)
        key = (shape_key, _device_key(), cfg)
        blob = repr((self.__name__, self._stage, jax.__version__,
                     _device_kind(), shape_key, cfg)).encode()
        return key, hashlib.sha1(blob).hexdigest(), dynamics

    @staticmethod
    def _program_key(key) -> Optional[Tuple]:
        """``key`` without its chip, when the program is one chip's and
        the thread is pinned to a lease's chip (``jax.default_device``):
        every array leaf is a host input or sits on that chip ("host"),
        so the executable is the same on every chip of the kind and the
        one compiled first can be loaded onto the others. None for an
        unpinned thread (one lease: nothing to share) and for a key that
        names a placement (a gang's mesh, an array on another chip)."""
        (statics, dyn_keys), _, cfg = key
        if not isinstance(jax.config.jax_default_device, jax.Device):
            return None
        for _, _, leaves in dyn_keys:
            if any(leaf[0] == "a" and leaf[3] != "host" for leaf in leaves):
                return None
        return (statics, dyn_keys), cfg

    # -- dispatch ----------------------------------------------------------

    def __call__(self, *args, **kwargs):
        if not self._aot or not _aot_enabled():
            return self._jit(*args, **kwargs)
        try:
            key, digest, dynamics = self._split(args, kwargs)
        except (_Unkeyable, TypeError):
            telemetry.counter("compile.aot_fallback")
            return self._jit(*args, **kwargs)
        with self._lock:
            compiled = self._compiled.get(key)
        if compiled is None:
            compiled = self._compile(key, digest, args, kwargs)
            if compiled is None:  # lowering refused -> plain jit
                return self._jit(*args, **kwargs)
        else:
            telemetry.counter("compile.cache_hit")
        try:
            return compiled(*dynamics)
        except (TypeError, ValueError):
            # the executable rejected its ARGUMENTS before running
            # (shape drift inside a pytree, a sharding or donation
            # mismatch the key missed): plain jit retraces. Anything
            # raised while the program executes — a device error, an
            # OOM — is a real failure and propagates.
            telemetry.counter("compile.aot_fallback")
            return self._jit(*args, **kwargs)

    def _compile(self, key, digest, args, kwargs):
        """The executable for ``key``. A one-chip program that another
        chip of this kind has built, or is building (four lanes in
        lockstep miss together: one builds, the others wait for it), is
        not compiled again: the persistent cache holds one entry for all
        chips (:func:`_chips_share_entries`), and this chip's lowering
        reads it and loads the binary under its own device assignment.
        One compile a program, not one a chip."""
        program = self._program_key(key)
        first = waited = None
        if program is not None and _chips_share_entries():
            with self._lock:
                waited = self._first.get(program)
                if waited is None:  # this thread builds it
                    first = self._first[program] = threading.Event()
        if waited is not None:
            waited.wait()
            with self._lock:
                if key in self._compiled:  # this chip's own, meanwhile
                    return self._compiled[key]
        try:
            # ONE call site for every build: the frames above a lowering
            # are in its cache key (metadata), so a second line here
            # would be a second key for the same program
            return self._build(key, digest, args, kwargs,
                               sibling=waited is not None)
        finally:
            if first is not None:
                first.set()

    def _build(self, key, digest, args, kwargs, sibling=False):
        """Lower and compile under ``key``. ``sibling``: another chip has
        built this program; if the persistent cache then serves it,
        nothing was compiled and the load is counted as one
        (``compile.chip_load``), not as a miss."""
        configure_persistent_cache()
        meta: Dict[str, Any] = {}
        cross_host = _probe_marker(digest, meta)
        label = self._stage or self.__name__
        _tls.persistent_hit = False
        t0 = time.perf_counter()
        try:
            compiled = self._jit.lower(*args, **kwargs).compile()
        except Exception:
            telemetry.counter("compile.aot_fallback")
            with self._lock:
                self._aot = False  # this fn will never lower; stop trying
            return None
        dt = time.perf_counter() - t0
        if sibling and _tls.persistent_hit:
            telemetry.counter("compile.chip_load")
            telemetry.counter("compile.chip_load_ms", dt * 1e3)
            with self._lock:
                return self._compiled.setdefault(key, compiled)
        telemetry.counter("compile.cache_miss")
        telemetry.counter("compile.ms", dt * 1e3)
        if cross_host:
            telemetry.counter("compile.persistent_hit")
        # first-dispatch span: steady-state hits stay span-free, so
        # tlmsum's compilation roll-up shows first-vs-steady directly
        chip = getattr(jax.config.jax_default_device, "id", None)
        telemetry.record_span(
            f"compile.first.{label}", dt,
            **({} if chip is None else {"chip": chip, "fn": self.__name__}))
        _write_marker(meta, {
            "fn": self.__name__, "stage": self._stage,
            "jax": jax.__version__, "device_kind": _device_kind(),
        })
        with self._lock:
            self._compiled.setdefault(key, compiled)
        return compiled

    # -- precompile --------------------------------------------------------

    def warm(self, *args, **kwargs) -> bool:
        """AOT-compile for the given (possibly abstract —
        ``jax.ShapeDtypeStruct``) arguments without dispatching; the
        warm-pool entry point. True when this call compiled (or found
        cross-host), False on a registry hit or fallback."""
        if not self._aot or not _aot_enabled():
            return False
        try:
            key, digest, _ = self._split(args, kwargs)
        except (_Unkeyable, TypeError):
            return False
        with self._lock:
            if key in self._compiled:
                return False
        return self._compile(key, digest, args, kwargs) is not None

    # -- introspection (tests / bench) ------------------------------------

    def cache_size(self) -> int:
        with self._lock:
            return len(self._compiled)


_kind_cache: Dict[str, str] = {}

# ---------------------------------------------------------------------------
# one persistent-cache entry a one-chip program, whichever chip asks
#
# JAX keeps the device assignment in the persistent cache's key on every
# backend but the GPU's (jax/_src/cache_key.py), so a program built for
# chip 0 is a miss on chip 3 and every lane of a fleet compiles it again.
# The binary is the same on every chip of a kind, and the runtime loads a
# serialized executable under the device assignment it is handed (what a
# cache hit does anyway). So the plane hashes a one-replica, one-partition
# program under the process's FIRST local device whichever chip asks: that
# chip's key is letter for letter what it was (one lease, the default
# device), the others read its entry. Nothing is serialized again in
# process: the CPU backend cannot serialize an executable that it loaded
# from the cache (it executes with "Function ... not found"). Whether this
# runtime really runs such a load on the asking chip is tried once a
# process, on a two-line program, before any key is shared.

# persistent_hit: JAX's cache served this thread; probing: it runs the probe
_tls = threading.local()
_share = {"ok": None}  # None: not tried yet
_share_lock = threading.Lock()
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _on_cache_event(name: str, **kw) -> None:
    if name == _CACHE_HIT_EVENT:  # recorded on the compiling thread
        _tls.persistent_hit = True


def _hash_under_first_device() -> None:
    """Wrap JAX's hash of the compile options (once): a single-device
    assignment is hashed as the first local device's while sharing is on."""
    import copy

    import numpy as np
    from jax._src import cache_key
    from jax._src.lib import xla_client as xc

    held = cache_key._hash_serialized_compile_options
    first_id = jax.local_devices()[0].id

    def hash_options(hash_obj, options, strip_device_assignment=False):
        da = options.device_assignment
        if (_share["ok"] or getattr(_tls, "probing", False)) \
                and not strip_device_assignment and da is not None \
                and da.replica_count() == 1 and da.computation_count() == 1:
            options = copy.deepcopy(options)
            options.device_assignment = xc.DeviceAssignment.create(
                np.array([[first_id]]))
        return held(hash_obj, options, strip_device_assignment)

    cache_key._hash_serialized_compile_options = hash_options


def _probe_shared_entry() -> bool:
    """Build a two-line program for the first local chip, ask for it on
    the second: True when the cache served it there, it ran on that chip,
    and computed the same."""
    import numpy as np

    devs = jax.local_devices()[:2]
    if len(devs) < 2:
        return False

    def _plane_chip_probe(x):
        return x * 3.0 + 1.0

    x = np.arange(8, dtype=np.float32)
    outs = []
    for d in devs:
        _tls.persistent_hit = False
        with jax.default_device(d):
            outs.append(jax.jit(_plane_chip_probe).lower(x).compile()(x))
    served = _tls.persistent_hit
    return bool(served and outs[1].devices() == {devs[1]}
                and np.array_equal(np.asarray(outs[0]), np.asarray(outs[1])))


def _chips_share_entries() -> bool:
    """True when one-chip programs share one persistent-cache entry over
    this process's chips: the cache is on and the runtime passed
    :func:`_probe_shared_entry` (tried once a process, under the shared
    key, while the other lanes wait; a runtime that fails it keeps a key
    a chip, as before)."""
    if _share["ok"] is None:
        with _share_lock:
            if _share["ok"] is None:
                ok = False
                if configure_persistent_cache():
                    # the other lanes wait on this lock for the verdict:
                    # they must not build under a key a chip meanwhile
                    _hash_under_first_device()
                    jax.monitoring.register_event_listener(  # psrlint: ignore[PL013] -- registers a callback, dispatches nothing
                        _on_cache_event)
                    _tls.probing = True  # this thread hashes shared keys
                    try:
                        ok = _probe_shared_entry()
                    except Exception:  # noqa: BLE001 - then a key a chip
                        ok = False
                    finally:
                        _tls.probing = False
                _share["ok"] = ok
                telemetry.event("compile.chips_share_entries", ok=ok)
    return bool(_share["ok"])


def _device_kind() -> str:
    """Backend device kind, resolved lazily (touching jax.devices() at
    import would initialize the backend before CLIs pick a platform)."""
    k = _kind_cache.get("kind")
    if k is None:
        k = jax.devices()[0].device_kind  # psrlint: ignore[PL002] -- cache-key metadata (hardware KIND, not a compute placement); no lease involved
        _kind_cache["kind"] = k
    return k


def _sharding_key(x: Any) -> Tuple:
    """Placement of a committed multi-device array (a gang lease's
    sharded batch): the device ids of its mesh IN ORDER plus the
    partition spec — what the lowering reads off the argument, so the
    executable compiled under this key accepts exactly the arrays that
    key to it, and a gang on chips {2,3} never finds the executable
    lowered for {0,1}. A sharding that names no mesh is unkeyable."""
    s = x.sharding
    mesh, spec = getattr(s, "mesh", None), getattr(s, "spec", None)
    if mesh is None or spec is None:
        raise _Unkeyable(f"multi-device input under {type(s).__name__}")
    return ("mesh", tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names), tuple(mesh.devices.shape), str(spec))


def plane_jit(fn: Optional[Callable] = None, *, static_argnames=(),
              stage: str = "", name: Optional[str] = None,
              aot: bool = True):
    """``jax.jit`` through the compilation plane. Usable as a direct
    wrapper (``plane_jit(f, stage="fold")``) or a decorator factory
    (``@plane_jit(static_argnames=("nbins",), stage="fold")``).
    ``aot=False`` keeps plain-jit dispatch (for factories that close
    over meshes/shardings) while still routing through the plane."""
    if fn is None:
        return lambda f: PlaneJit(f, static_argnames=static_argnames,
                                  stage=stage, name=name, aot=aot)
    return PlaneJit(fn, static_argnames=static_argnames, stage=stage,
                    name=name, aot=aot)


def note_bucket_pad(n_real: int, n_padded: int) -> None:
    """Account one bucketing decision: the pad fraction gauge and the
    padded-row counter the bench reads."""
    if n_padded <= 0:
        return
    telemetry.gauge("compile.bucket_pad_frac",
                    (n_padded - n_real) / float(n_padded))
    if n_padded > n_real:
        telemetry.counter("compile.bucket_pad_rows", n_padded - n_real)


# ---------------------------------------------------------------------------
# warm-pool registry

_warmers: Dict[str, Callable[..., int]] = {}
_warmers_lock = threading.Lock()


def register_warmer(stage: str, fn: Callable[..., int]) -> None:
    """Register ``stage``'s precompile planner: ``fn(**geometry)``
    lowers that stage's wrappers for one observation geometry and
    returns how many executables it compiled. Last registration wins
    (re-import safe)."""
    with _warmers_lock:
        _warmers[stage] = fn


def warmable_stages() -> Tuple[str, ...]:
    with _warmers_lock:
        return tuple(sorted(_warmers))


def warm_stage(stage: str, **geometry) -> int:
    """Run ``stage``'s registered warmer for ``geometry``; 0 when no
    warmer is registered or the warmer declined. Never raises — the
    warm pool is an optimization, not a correctness path."""
    with _warmers_lock:
        fn = _warmers.get(stage)
    if fn is None:
        return 0
    try:
        return int(fn(**geometry) or 0)
    except Exception:
        telemetry.counter("compile.warm_error")
        return 0
