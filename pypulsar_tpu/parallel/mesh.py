"""Device mesh construction + the thread-local gang lease registry.

The scale axes of this domain (SURVEY.md §2.4): DM trials (embarrassingly
parallel — the data-parallel analogue), the time axis (long-context analogue,
sharded with halo exchange since dedispersion is a pure per-channel shift),
and multi-beam/multi-file batches across hosts over DCN.

The **gang lease** half solves the mesh/lease collision: the survey
scheduler hands a stage k exclusive chips, but every mesh-building call
site used to root itself at ``jax.local_devices()[0]`` — two gang-leased
observations would silently build meshes over the SAME chips 0..k-1.
:func:`device_lease` publishes the leased device set thread-locally;
:func:`lease_devices` is the ONE resolver every mesh builder goes
through (the active lease first, then the thread's ``jax.default_device``
as the root of the local-device ring, then plain ``jax.local_devices()``),
so a mesh built inside a lease can only address the leased chips.

The lease registry also carries **device health** (round 12): a
process-global :class:`~pypulsar_tpu.resilience.health.DeviceHealth`
strike account (:func:`device_health`), keyed by REAL jax device ids.
The survey scheduler shares this account (``reset_device_health`` per
fleet) and charges OOMs, collective failures and injected device
faults against the real chips the failing execution was pinned to; a
chip past ``PYPULSAR_TPU_DEVICE_STRIKES`` is quarantined, the
scheduler evicts every lease mapping to it from the pool mid-fleet
(in-flight gangs retry shrunk to the surviving chips), and the
non-leased resolver path here skips quarantined chips
(:func:`healthy_devices`).
"""

from __future__ import annotations

import contextlib
import threading
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from pypulsar_tpu.resilience.health import DeviceHealth

_tls = threading.local()

# process-global strike account, keyed by device/lease id; reset per
# fleet by the survey scheduler (and per test via reset_device_health)
_device_health = DeviceHealth()


def device_health() -> DeviceHealth:
    """The process-global per-device strike/quarantine registry."""
    return _device_health


def reset_device_health(limit: Optional[int] = None) -> DeviceHealth:
    """Fresh strike account (new fleet / test isolation); ``limit``
    overrides ``PYPULSAR_TPU_DEVICE_STRIKES``."""
    global _device_health
    _device_health = DeviceHealth(limit)
    return _device_health


def healthy_devices(devices) -> list:
    """``devices`` minus the quarantined ones — unless that empties the
    list (an all-quarantined host must stay usable: degraded beats
    dead)."""
    kept = [d for d in devices
            if not _device_health.is_quarantined(int(getattr(d, "id", -1)))]
    return kept if kept else list(devices)


@contextlib.contextmanager
def device_lease(devices):
    """Publish ``devices`` as THIS thread's exclusive device gang for the
    block (re-entrant: an inner lease shadows, then restores, the outer).
    The survey scheduler wraps each device-bound stage in one; any mesh
    built below it via :func:`lease_devices` sees only these chips."""
    prev = getattr(_tls, "lease", None)
    _tls.lease = tuple(devices)
    try:
        yield _tls.lease
    finally:
        _tls.lease = prev


def placement() -> tuple:
    """This thread's placement: its ``jax.default_device`` and its
    lease. Both are thread-local, so a helper thread a stage starts
    (the ship-ahead worker) sees neither unless it is handed them:
    capture here on the stage's thread, re-enter with
    :func:`adopt_placement` on the helper's."""
    return jax.config.jax_default_device, current_lease()


@contextlib.contextmanager
def adopt_placement(held: tuple):
    """Run the block where the thread that called :func:`placement`
    ran: a lease on chip 3 ships and preps on chip 3, not on the
    process's first chip. With nothing pinned it is a no-op."""
    default, lease = held
    with contextlib.ExitStack() as stack:
        if default is not None:
            stack.enter_context(jax.default_device(default))
        if lease:
            stack.enter_context(device_lease(lease))
        yield


def current_lease() -> Optional[tuple]:
    """The active thread's leased device tuple, or None outside a lease."""
    return getattr(_tls, "lease", None)


def lease_device_ids() -> Optional[List[int]]:
    """Integer device ids of the active lease (telemetry attribution
    stamps these on span/counter records), or None outside a lease."""
    lease = current_lease()
    if not lease:
        return None
    return [int(getattr(d, "id", -1)) for d in lease]


def lease_devices(k: Optional[int] = None) -> list:
    """The device set this thread's work may address, optionally cut to
    ``k``. Resolution order: the active :func:`device_lease` (the gang);
    else ``jax.local_devices()`` rotated so the thread's
    ``jax.default_device`` (a single-chip lease) comes first; else plain
    ``jax.local_devices()``. Raises when fewer than ``k`` are
    addressable — a gang must never silently spill past its lease."""
    lease = current_lease()
    if lease:
        # a lease is the scheduler's verdict: it already excluded
        # quarantined chips, so the gang is taken as granted
        devs = list(lease)
    else:
        devs = healthy_devices(jax.local_devices())
        default = jax.config.jax_default_device
        if default is not None and default in devs:
            i = devs.index(default)
            devs = devs[i:] + devs[:i]
    if k is not None:
        if len(devs) < k:
            raise ValueError(
                f"need {k} devices but this thread's lease/host offers "
                f"only {len(devs)} ({[str(d) for d in devs]})")
        devs = devs[:k]
    return devs


def make_mesh(
    axis_sizes: Optional[Sequence[int]] = None,
    axis_names: Sequence[str] = ("dm", "time"),
    devices=None,
) -> Mesh:
    """Build a Mesh over available devices.

    Default: all devices on the 'dm' axis (1 on 'time') — DM-trial sharding
    needs no communication until the final candidate reduction, so it rides
    ICI most efficiently (BASELINE.json north star).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if axis_sizes is None:
        axis_sizes = [n] + [1] * (len(axis_names) - 1)
    if int(np.prod(axis_sizes)) != n:
        raise ValueError(f"axis sizes {axis_sizes} do not multiply to {n} devices")
    dev_array = mesh_utils.create_device_mesh(tuple(axis_sizes), devices=devices)
    return Mesh(dev_array, tuple(axis_names))


def gang_mesh(k: int) -> Mesh:
    """A 1-D 'dm' mesh over this thread's k leased/addressable devices —
    the one-call form every DM-sharding CLI path uses (see module
    docstring for the resolution order)."""
    return make_mesh([k], ("dm",), devices=lease_devices(k))
