"""Raw file blocks onto the device: the timed read and the ingest program.

A SIGPROC reader hands out blocks as the file holds them
(``FilterbankFile.iter_blocks(raw=True)``: ``[time, chan]`` in the
file's dtype and channel order, sub-byte samples still PACKED). They
ship like that and are unpacked, transposed, widened and band-flipped
by ONE device program, :func:`_ingest_tc`. Both readers of a raw file
go through it: the sweep's block source (``parallel/staged.py``, which
wants high-frequency-first rows) and the mask stage (``ops/rfifind.py``,
which wants the ``.mask`` convention, low-frequency-first); each passes
the ``flip`` its convention needs. There is no second unpack on a device
path.
"""

from __future__ import annotations

import jax.numpy as jnp

from pypulsar_tpu.compile import plane_jit
from pypulsar_tpu.obs import telemetry


@plane_jit(static_argnames=("flip", "nbits"), stage="sweep")
def _ingest_tc(raw_tc, flip: bool, nbits: int = 8):
    """Device-side block ingest: [time, chan] native-dtype block ->
    [chan, time] float32, optionally band-flipped. Keeping the transpose,
    widening cast and flip INSIDE one program means an 8-bit file ships
    1 byte/sample over the host->device link instead of 4, and no eager
    per-block ops pay
    dispatch latency. uint->f32 is exact, so results are bit-identical
    to the host-side path.

    ``nbits`` < 8 means ``raw_tc`` is PACKED [time, nchans*nbits//8]
    uint8 (io/filterbank.py sub-byte layout, low bits = lower channel)
    and is unpacked HERE, on device — a 4-bit file ships half the bytes
    of its 8-bit expansion and yields bit-identical f32 ingest (VERDICT
    r4 item 2; parity: tests/test_io.py, tests/test_staged.py,
    tests/test_rfifind.py)."""
    if nbits < 8:
        spb = 8 // nbits
        mask = jnp.uint8((1 << nbits) - 1)
        parts = [(raw_tc >> jnp.uint8(nbits * i)) & mask
                 for i in range(spb)]
        raw_tc = jnp.stack(parts, axis=-1).reshape(
            raw_tc.shape[0], raw_tc.shape[1] * spb)
    d = raw_tc.T.astype(jnp.float32)
    return jnp.flip(d, axis=0) if flip else d


def ingest_nbits(reader) -> int:
    """The ``nbits`` :func:`_ingest_tc` takes for ``reader``'s raw
    blocks: the sample width where samples are packed into bytes, else 8
    (8/16/32-bit samples ship unpacked in their own dtype)."""
    nbits = int(getattr(reader, "nbits", 8) or 8)
    return nbits if nbits < 8 else 8


def _timed_reads(raw_blocks):
    """``raw_blocks`` with each pull from the reader under an ``io.read``
    span (on whichever thread iterates: the sweep's ship-ahead worker,
    the mask stage's own), its on-disk bytes added to ``io.bytes_read``."""
    it = iter(raw_blocks)
    try:
        while True:
            with telemetry.span("io.read", aggregate=False) as sp:
                item = next(it, None)
                if item is not None and sp is not None:
                    sp.set(samples=int(item[1].shape[0]),
                           bytes=int(item[1].nbytes))
            if item is None:
                return
            telemetry.counter("io.bytes_read", int(item[1].nbytes))
            yield item
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()
