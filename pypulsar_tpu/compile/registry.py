"""The compilation plane's stdlib half: bucket geometry + the ops
allowlist (round 22).

This module is deliberately jax-free so psrlint (PL018) and host-side
planners can import it without touching the accelerator stack. The
jax-facing half — the persistent XLA cache wiring, the ``plane_jit``
wrapper and its AOT executable registry — lives in
:mod:`pypulsar_tpu.compile.plane`.

**Bucket ladder.** Geometry bucketing rounds a batch axis up to a
canonical size so two observations with nearby-but-distinct geometries
collapse onto ONE compiled executable instead of two traces. The
ladder is ``{2**k} ∪ {3·2**k}`` — 1, 2, 3, 4, 6, 8, 12, 16, 24, 32,
48, 64 … — which keeps worst-case padding under 25 % past 4 while
staying stable under :func:`resilience.oom.halving_dispatch` (every
rung halves onto a smaller rung). Bucketing applies ONLY to axes that
already have an exact-parity padding path (DM trial groups via
``pad_groups_to``, accel spectrum batches and fold candidate batches
via replicate-last-row): padded work is computed and then dropped, so
artifact bytes never change. The time/FFT axis is NEVER bucketed —
padding it changes FFT lengths and therefore results.

**Fingerprints.** Bucket choice is runtime policy, not science —
exactly like gang placement (PR 6) it is excluded from every
journal/manifest fingerprint, so a fleet resumes byte-identically
across a bucket-policy change.

**Ops allowlist.** PL018 locks raw ``jax.jit`` down to
``pypulsar_tpu/compile/`` plus the leaf kernel modules listed in
:data:`OPS_LEAF_ALLOWLIST`: those are the innermost per-chunk kernels
that higher layers already dispatch through plane-wrapped runners, so
re-wrapping them would only double-count the same compiles.
"""

from __future__ import annotations

from typing import Tuple

from pypulsar_tpu.tune import knobs

__all__ = [
    "OPS_LEAF_ALLOWLIST",
    "bucket_floor",
    "bucket_size",
    "bucket_rows",
    "buckets_enabled",
]

# ops/ leaf kernel modules explicitly registered with the compilation
# plane: raw jax.jit is allowed here (and ONLY here) because every
# call site is reached through a plane-wrapped stage runner one layer
# up — the plane already owns their compile telemetry and caching.
OPS_LEAF_ALLOWLIST: Tuple[str, ...] = (
    "pypulsar_tpu/ops/kernels.py",
    "pypulsar_tpu/ops/fourier_dedisperse.py",
    "pypulsar_tpu/ops/pallas_kernels.py",
    "pypulsar_tpu/ops/rfifind.py",
)


def buckets_enabled() -> bool:
    """Geometry bucketing on/off (``PYPULSAR_TPU_COMPILE_BUCKETS``)."""
    raw = knobs.env_str("PYPULSAR_TPU_COMPILE_BUCKETS")
    return str(raw) not in ("0", "off", "none")


def bucket_size(n: int) -> int:
    """Smallest ladder value (``2**k`` or ``3·2**k``) >= ``n``."""
    n = int(n)
    if n <= 1:
        return max(n, 0)
    p2 = 1 << (n - 1).bit_length()
    k3 = -(-n // 3)  # smallest m with 3*m >= n
    p3 = 3 * (1 << max(0, (k3 - 1).bit_length()))
    return p3 if n <= p3 < p2 else p2


def bucket_floor(n: int) -> int:
    """Largest ladder value (``2**k`` or ``3·2**k``) <= ``n`` — for
    rounding a budget-derived batch cap DOWN onto the ladder (rounding
    a memory cap up could overshoot the budget). Identity when
    bucketing is off."""
    n = int(n)
    if n <= 1 or not buckets_enabled():
        return max(n, 0)
    p2 = 1 << (n.bit_length() - 1)
    p3 = 3 * (1 << max(0, (n // 3).bit_length() - 1)) if n >= 3 else 0
    return max(p2, p3 if p3 <= n else 0)


def bucket_rows(n: int, multiple: int = 1) -> int:
    """Canonical padded row count for a batch axis of ``n`` rows that
    must also be a multiple of ``multiple`` (a device-mesh axis).
    With bucketing disabled this degrades to the pre-round-22
    behavior: plain round-up to ``multiple``."""
    n = int(n)
    m = max(1, int(multiple))
    if n <= 0:
        return 0
    if not buckets_enabled():
        return -(-n // m) * m
    b = bucket_size(n)
    return -(-b // m) * m
