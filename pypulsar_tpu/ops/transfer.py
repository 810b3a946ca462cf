"""Complex-array boundary helpers.

The framework's rule is: complex64 lives only inside jit. It was adopted
for a backend that could not move complex buffers across an executable
boundary; whether a directly attached TPU needs it has not been
re-measured (ROADMAP design queue: candidates for removal). Until then
every stage keeps one convention, which also keeps host<->device traffic
in plain float32 planes.

Every jit signature that logically takes/returns a complex array takes/
returns separate real and imaginary float32 planes instead, recombined
with ``jax.lax.complex`` on entry and split with ``.real``/``.imag``
before returning. These helpers cover the host side of that contract.
"""

from __future__ import annotations

import jax
import numpy as np

from pypulsar_tpu.obs import telemetry

__all__ = ["split_complex", "to_host_complex", "join_planes", "pull_host",
           "ship"]


def ship(x, dtype=None):
    """Host -> device: ``jnp.asarray(x, dtype)``, the call every ship
    site made itself, under one ``h2d.ship`` span that carries the bytes
    put on the link (also added to the ``h2d.bytes`` counter). The span
    is as long as the call holds its thread; nothing waits for the copy
    to land. An array that already lives on a device passes through
    uncounted. Sink-only span (``aggregate=False``): ships nest inside
    the sweep loop's stages and run on the ship-ahead worker, whose wall
    overlaps the main thread's."""
    import jax.numpy as jnp

    if isinstance(x, jax.Array):
        return jnp.asarray(x, dtype=dtype)
    wire = np.dtype(dtype if dtype is not None
                    else getattr(x, "dtype", np.float32))
    nbytes = int(np.size(x)) * wire.itemsize
    with telemetry.span("h2d.ship", aggregate=False, bytes=nbytes):
        out = jnp.asarray(x, dtype=dtype)
    telemetry.counter("h2d.bytes", nbytes)
    return out


def pull_host(*arrays):
    """Fetch several device arrays to host in ONE batched transfer.

    Every individual ``np.asarray(device_array)`` pull is its own
    synchronous device->host round trip; ``jax.device_get`` issues the
    fetches together and waits once. Use this for every multi-output
    pull on a hot path. Always returns a tuple
    (same arity as the arguments), so star-splatted call sites unpack
    predictably even for one output. Under an active telemetry session
    the pull is a ``d2h.pull`` span (sink-only; its wall includes the
    wait for the programs that produce the arrays) and is accounted to
    the ``d2h.bytes``/``d2h.pulls`` counters."""
    if not telemetry.is_active():
        return jax.device_get(arrays)
    nbytes = sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays)
    with telemetry.span("d2h.pull", aggregate=False, bytes=nbytes,
                        arrays=len(arrays)):
        out = jax.device_get(arrays)
    telemetry.counter("d2h.bytes", nbytes)
    telemetry.counter("d2h.pulls")
    return out


def join_planes(re, im):
    """Recombine float planes into complex — INSIDE jit only (the result
    must not cross an executable boundary). The canonical other half of
    :func:`split_complex`: plane order is (real, imaginary)."""
    import jax.lax

    return jax.lax.complex(re, im)


def split_complex(arr):
    """(re, im) float32 planes of a possibly-complex array.

    Host arrays split in NumPy; device arrays (already past a boundary,
    so CPU/TPU-internal backends only) split with eager ``.real``/
    ``.imag``. Real input gets a zero imaginary plane."""
    if isinstance(arr, jax.Array):
        import jax.numpy as jnp

        if jnp.iscomplexobj(arr):
            return (arr.real.astype(jnp.float32),
                    arr.imag.astype(jnp.float32))
        return arr.astype(jnp.float32), jnp.zeros_like(arr, jnp.float32)
    a = np.asarray(arr)
    if np.iscomplexobj(a):
        return (np.ascontiguousarray(a.real, dtype=np.float32),
                np.ascontiguousarray(a.imag, dtype=np.float32))
    return a.astype(np.float32), np.zeros_like(a, dtype=np.float32)


def to_host_complex(re, im) -> np.ndarray:
    """Host complex64 from separate (device or host) float planes — the
    device->host pull happens per real plane, which every backend
    supports; both planes fetch in one batched transfer (pull_host)."""
    re, im = pull_host(re, im)
    return (np.asarray(re, dtype=np.float32)
            + 1j * np.asarray(im, dtype=np.float32)).astype(np.complex64)
