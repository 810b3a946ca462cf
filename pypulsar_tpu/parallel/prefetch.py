"""Bounded background prefetch of an iterator — the shared ship-ahead core.

Three hot paths in the framework have the same shape: a producer whose
per-item latency is wire or disk time (host->device block ships, .dat
reads + host prep, batch stacking + device prep) feeding a consumer whose
latency is device time (the sweep chunk kernel, the accel stage scans).
Run on one thread they serialize — the round-4 streamed sweep measured 0%
overlap until the ship moved to its own thread, and the round-5 accel A/B
still showed 6.4 of 8.7 s/spectrum of *serial host time* for exactly this
reason. The fix is always the same bounded producer/consumer pattern, so
it lives here once:

- a single worker thread pulls ``items``, applies ``transform`` (the
  expensive half — e.g. ``jnp.asarray`` riding the wire, or a .dat read),
  and parks results in a FIFO queue of ``depth`` slots;
- the consumer sees items in order; worker exceptions re-raise at the
  consumer's next pull (never swallowed in the thread);
- an abandoned consumer (error or early exit) signals the worker and
  drains the queue so a put-parked worker exits instead of producing the
  rest of a 57 GB stream; a ``close()`` on ``items`` is honored;
- under an active telemetry session the queue fill is recorded to the
  ``{name}.pending_depth`` gauge on every put — tlmsum's gauges table
  then shows how deep the pipeline actually ran. The worker records
  BEFORE parking on a full queue, so the gauge counts its in-hand item
  too: max == depth+1 means the producer kept fully ahead; max 0-1
  means the consumer starved.

Resilience (round 7): the transform retries transient IO errors with
exponential backoff (``retries``/``retry_on`` — a survey pass must not
abort over one NFS hiccup; each retry emits a ``resilience.worker_retry``
telemetry event), and the consumer enforces a per-item deadline
(``timeout``, default ``PYPULSAR_TPU_PREFETCH_TIMEOUT`` or 900 s; 0
disables) so a wedged producer fails LOUDLY with a TimeoutError naming
the pipeline instead of parking the whole run on ``q.get()`` forever.
The worker-side fault point ``{name}.produce`` sits inside the retry
loop, so ``tests/test_resilience.py`` can prove both policies.

``PYPULSAR_TPU_SHIP_AHEAD=0`` disables the thread globally (inline
transform, e.g. for single-threaded debugging); ordering and values are
identical either way — threading only moves WHEN work happens.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Optional, Tuple

from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.resilience.locks import TrackedEvent
from pypulsar_tpu.resilience.retry import RETRY_BACKOFF_MAX_S  # noqa: F401
from pypulsar_tpu.tune import knobs

__all__ = ["prefetch"]

ENV_TIMEOUT = "PYPULSAR_TPU_PREFETCH_TIMEOUT"
DEFAULT_TIMEOUT_S = 900.0
# how long the consumer's cleanup path waits for a (possibly wedged)
# worker before abandoning it: the thread is a daemon, so leaking it is
# safe — spinning on join() forever is the wedge we exist to prevent
CLEANUP_DEADLINE_S = 5.0


def _resolve_timeout(timeout: Optional[float]) -> Optional[float]:
    if timeout is None:
        timeout = float(knobs.env_float(ENV_TIMEOUT))
    return None if timeout <= 0 else timeout


def _produce(xf: Callable, item, name: str, retries: int,
             retry_backoff: float, retry_on: Tuple[type, ...]):
    """One item through the (fault-instrumented) transform with the
    shared transient-error retry policy (resilience.retry_transient) —
    used by the worker thread and the inline (SHIP_AHEAD=0) path alike
    so retry semantics cannot diverge."""
    from pypulsar_tpu.resilience import faultinject
    from pypulsar_tpu.resilience.retry import retry_transient

    def attempt():
        faultinject.trip(f"{name}.produce")
        return xf(item)

    return retry_transient(attempt, retries=retries, backoff=retry_backoff,
                           retry_on=retry_on, what=name)


def prefetch(items: Iterable, depth: int = 2, name: str = "prefetch",
             transform: Optional[Callable] = None,
             thread_name: Optional[str] = None,
             retries: int = 0, retry_backoff: float = 0.1,
             retry_on: Tuple[type, ...] = (OSError,),
             timeout: Optional[float] = None):
    """Yield ``transform(item)`` for each item, produced ``depth`` ahead
    on a background thread (see module docstring for the contract).

    ``retries``: transform attempts re-run up to this many times on
    ``retry_on`` exceptions (exponential backoff from ``retry_backoff``
    seconds). ``timeout``: per-item consumer deadline in seconds (None =
    the ``PYPULSAR_TPU_PREFETCH_TIMEOUT`` env default; <= 0 disables)."""
    xf = transform if transform is not None else (lambda it: it)
    gauge_name = f"{name}.pending_depth"

    if knobs.env_str("PYPULSAR_TPU_SHIP_AHEAD") == "0":
        for item in items:
            yield _produce(xf, item, name, retries, retry_backoff,
                           retry_on)
        return

    deadline = _resolve_timeout(timeout)
    q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    _done = object()
    stop = TrackedEvent("prefetch.stop")
    # the consumer's causal identity, captured HERE (construction runs
    # on the stage's thread): the worker re-enters it so its telemetry
    # lands on the stage's trace and its beats refresh the stage's
    # heartbeat entry — not the producer thread's nonexistent one
    # (round 21; the PR 7 attribution caveat this closes)
    trace_ctx = telemetry.current_context()
    # and its placement (the lease's chip, thread-local as well): what
    # the worker ships and preps must land on the stage's chip
    from pypulsar_tpu.parallel import mesh

    held = mesh.placement()

    def worker():
        with telemetry.adopt_context(trace_ctx), mesh.adopt_placement(held):
            try:
                for item in items:
                    if stop.is_set():  # consumer gone: stop producing
                        return
                    out = _produce(xf, item, name, retries,
                                   retry_backoff, retry_on)
                    if telemetry.is_active():  # gauges are thread-safe
                        telemetry.gauge(gauge_name, q.qsize() + 1)
                    q.put(out)
            except BaseException as e:  # noqa: BLE001 - re-raised in consumer
                q.put(e)
                return
            q.put(_done)

    t = threading.Thread(target=worker,
                         name=thread_name or f"pypulsar-{name}",
                         daemon=True)
    t.start()
    try:
        while True:
            try:
                item = q.get(timeout=deadline)
            except queue.Empty:
                telemetry.event("resilience.prefetch_timeout",
                                pipeline=name, timeout_s=deadline)
                raise TimeoutError(
                    f"prefetch {name!r}: producer delivered nothing for "
                    f"{deadline:.0f}s (worker "
                    f"{'alive' if t.is_alive() else 'dead'}); the "
                    f"pipeline would otherwise wedge silently — raise "
                    f"{ENV_TIMEOUT} if items legitimately take longer"
                ) from None
            if item is _done:
                break
            if isinstance(item, BaseException):
                raise item
            if telemetry.is_active():
                telemetry.gauge(gauge_name, q.qsize())
            yield item
    finally:
        # consumer abandoned mid-stream (error or early exit): signal the
        # worker, then drain queue slots so a put-parked worker can see
        # the signal and exit instead of producing the rest of the
        # stream. Deadline-bounded: a worker wedged INSIDE its transform
        # never exits, and the cleanup must not inherit its wedge (the
        # thread is a daemon — abandoning it is safe)
        stop.set()
        give_up = time.monotonic() + CLEANUP_DEADLINE_S
        while t.is_alive() and time.monotonic() < give_up:
            try:
                q.get_nowait()
            except queue.Empty:
                t.join(timeout=0.1)
        close = getattr(items, "close", None)
        if close is not None:
            close()
