"""OOM-adaptive dispatch: halve the batch, back off, re-run.

The survey-scale accel stage has already exhausted a TPU at
(B=32, N=2^21, zmax=200), which is why fourier/accelsearch.py budgets
HBM up front. But budgets are estimates: an XLA fusion holding one extra temporary, a
neighbour process on a shared device, or a conservative-enough-but-wrong
bytes-per-cell model can still produce a recoverable
``RESOURCE_EXHAUSTED`` — and on backends that DO raise it, aborting a
multi-hour survey over one oversized dispatch is the wrong trade. The
real-time dedispersion literature treats adaptive reconfiguration as a
first-class runtime concern (Sclocco et al., arXiv:1601.01165,
1601.05052); this module is that policy for the dispatch axis every hot
path already has:

- the sweep's trial-group axis (``parallel/sweep.py`` chunk dispatch),
- the accel handoff's spectrum batches (``parallel/accelpipe.py``),
- the batched stage runner's HBM chunks (``fourier/accelsearch.py``).

All three axes are *embarrassingly independent* — per-group scans and
per-spectrum searches share no state — so halving a failed dispatch and
re-running the halves is bit-identical to the original dispatch, which is
what lets the fault-injection suite pin recovery down to byte-equal
candidate tables.

Every halving emits a ``resilience.oom_backoff`` telemetry event and
bumps the ``resilience.oom_backoffs`` counter, so ``tlmsum`` shows how a
degraded run survived.
"""

from __future__ import annotations

import random
import time
from typing import Callable, List, Optional, Tuple

from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.resilience import faultinject

__all__ = ["backoff_delay", "halving_dispatch", "is_oom_error",
           "retry_transient"]

# bounded backoff before re-dispatching after an OOM: gives the allocator
# (and any neighbour briefly holding the memory) time to settle, without
# ever stalling a survey for more than ~seconds per halving
BACKOFF_BASE_S = 0.05
BACKOFF_MAX_S = 2.0

# bound on the transient-IO retry backoff (shared with the prefetch
# worker policy): an NFS hiccup gets seconds to clear, a real outage
# still fails within ~retries * 5 s
RETRY_BACKOFF_MAX_S = 5.0

# OSError subclasses that are configuration errors, not IO weather: a
# typo'd path or bad permission fails identically on every attempt —
# retrying it only delays the real error and mislabels it as transient
NON_TRANSIENT_OS_ERRORS = (FileNotFoundError, PermissionError,
                           IsADirectoryError, NotADirectoryError)

# process-default jitter source for backoff delays; tests inject their
# own seeded random.Random for determinism
_JITTER_RNG = random.Random()


def backoff_delay(base: float, attempt: int, cap: float,
                  rng: Optional[random.Random] = None) -> float:
    """Jittered bounded exponential backoff: ``base * 2^(attempt-1)``
    (capped at ``cap``) scaled by a uniform factor in [0.5, 1.0).

    The jitter is the point, not a refinement: the pure deterministic
    schedule retries *in lockstep* — N leases that fail together (one
    flaky chip, one NFS blip) all come back at exactly base, 2*base,
    4*base and collide again, the classic thundering-herd retry storm.
    ``rng`` is injectable so tests stay deterministic
    (``random.Random(seed)``); None uses the process-default source."""
    delay = min(base * (2 ** (max(1, attempt) - 1)), cap)
    r = rng if rng is not None else _JITTER_RNG
    return delay * (0.5 + 0.5 * r.random())


def retry_transient(fn, *, retries: int = 2, backoff: float = 0.1,
                    retry_on: Tuple[type, ...] = (OSError,),
                    what: str = "io",
                    rng: Optional[random.Random] = None):
    """Run ``fn()`` retrying ``retry_on`` failures with bounded
    exponential backoff — the transient-IO policy of the prefetch
    workers, usable at any read site (a survey pass must not abort over
    one NFS hiccup). Permanent OSError subclasses
    (``NON_TRANSIENT_OS_ERRORS``) are never retried. Each retry emits a
    ``resilience.worker_retry`` event; exhaustion re-raises the last
    error."""
    attempt = 0
    while True:
        try:
            return fn()
        except retry_on as e:
            if isinstance(e, NON_TRANSIENT_OS_ERRORS):
                raise
            if attempt >= retries:
                raise
            attempt += 1
            delay = backoff_delay(backoff, attempt, RETRY_BACKOFF_MAX_S,
                                  rng)
            telemetry.counter("resilience.worker_retries")
            telemetry.event("resilience.worker_retry", pipeline=what,
                            attempt=attempt, error=type(e).__name__,
                            delay_s=round(delay, 3))
            print(f"# {what}: transient {type(e).__name__} ({e}); "
                  f"retry {attempt}/{retries} in {delay:.2f}s")
            time.sleep(delay)


def is_oom_error(e: BaseException) -> bool:
    """True for a device out-of-memory failure: an XlaRuntimeError-style
    RESOURCE_EXHAUSTED (matched on the message — jaxlib's exception types
    move between versions, the status string does not) or an injected
    OOM. Never true for KeyboardInterrupt-class BaseExceptions."""
    if isinstance(e, faultinject.InjectedOOM):
        return True
    if not isinstance(e, Exception):
        return False
    msg = str(e)
    return ("RESOURCE_EXHAUSTED" in msg
            or "out of memory" in msg.lower()
            or "OutOfMemory" in type(e).__name__)


def halving_dispatch(
    run: Callable[[int, int], object],
    n: int,
    *,
    min_size: int = 1,
    what: str = "dispatch",
    max_halvings: int = 16,
) -> List[Tuple[int, int, object]]:
    """Run ``run(lo, hi)`` over ``[0, n)``, halving any slice whose
    dispatch raises a device OOM (``is_oom_error``) until slices reach
    ``min_size``; returns ``[(lo, hi, result), ...]`` in index order.

    ``run`` must be a pure function of its slice (each item's result
    independent of the slicing) — the property that makes the recovery
    bit-identical. ``min_size`` > 1 keeps slices on a required multiple
    (e.g. a sharded batch axis must stay divisible by the mesh); an OOM
    at ``min_size`` re-raises, as does any non-OOM error.
    ``max_halvings`` bounds pathological retry storms (a "successful"
    dispatch that OOMs every time at every size is a real failure)."""
    if n <= 0:
        return []
    min_size = max(1, int(min_size))
    halvings = 0
    out: List[Tuple[int, int, object]] = []
    stack = [(0, n)]  # LIFO with right half pushed first -> index order
    while stack:
        lo, hi = stack.pop()
        try:
            out.append((lo, hi, run(lo, hi)))
            continue
        except Exception as e:  # noqa: BLE001 - classified below
            if not is_oom_error(e) or hi - lo <= min_size:
                raise
            if halvings >= max_halvings:
                raise
            err = e
        halvings += 1
        size = hi - lo
        # split on a min_size multiple so constrained axes stay legal
        half = max(min_size, ((size // 2) // min_size) * min_size)
        mid = lo + half
        telemetry.counter("resilience.oom_backoffs")
        telemetry.event("resilience.oom_backoff", what=what, size=size,
                        new_size=half, error=type(err).__name__)
        delay = backoff_delay(BACKOFF_BASE_S, halvings, BACKOFF_MAX_S)
        print(f"# {what}: device OOM at size {size}; backing off "
              f"{delay:.2f}s and retrying as {half} + {size - half}")
        time.sleep(delay)
        stack.append((mid, hi))
        stack.append((lo, mid))
    return out
