"""Pallas TPU kernel for the dedispersion hot loop: shifted gather-sum.

The sweep engine's inner operation (both subband stages) is

    out[o, t] = sum_k  data[rows[o, k],  shifts[o, k] + t]

i.e. sum K shifted rows of a [R, L] array into each of O outputs.  The
XLA formulation (vmapped ``lax.dynamic_slice``) lowers to a generic
gather measured ~26 GB/s effective on v5e (3% of the HBM roofline;
BENCHNOTES.md round-3 A/B — the Fourier phase-multiply engine has since
superseded both).  This kernel instead streams each row segment HBM->VMEM with
explicit double-buffered DMA whose offsets come from scalar-prefetched
shift tables, and accumulates in VMEM — the access pattern the hardware
DMA engines are built for.

``shifted_gather_sum`` defaults to the lax formulation everywhere and the
Pallas path is opt-in (``backend='pallas'``): it is validated in interpret
mode only, and today's TPU compiler (jax 0.9.0 / libtpu 0.0.34, compiling
for a described v5e) REFUSES it — "Slice shape along dimension 0 must be
aligned to tiling (8), but is 1" at the row DMA in ``get_dma``, which
copies ONE row of the [R, L] HBM array where the (8, 128) tiling wants
eight. ``tests/test_chip_compile.py`` pins that verdict (strict xfail);
the kernel is not on the survey path (the Fourier engine is the TPU
default). Making the DMA tile-aligned (an 8-row slab per copy, or a
[R, 1, L] layout) is the repair if the kernel is ever wanted.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from pypulsar_tpu.ops.pallas_kernels import _on_tpu  # noqa: F401 (shared)

T_BLOCK = 2048  # lanes per grid step (multiple of 128)


def _gather_sum_kernel(rows_ref, shifts_ref, data_ref, out_ref,
                       *, K: int, t_block: int):
    """One (o, j) tile: out[o, j*t_block : (j+1)*t_block] accumulated over
    the K shifted source rows, with double-buffered row DMA."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    o = pl.program_id(0)
    j = pl.program_id(1)
    t0 = j * t_block

    def body(scratch, acc, sem):
        def get_dma(slot, k):
            row = rows_ref[o, k]
            start = shifts_ref[o, k] + t0
            return pltpu.make_async_copy(
                data_ref.at[row, pl.ds(start, t_block)],
                scratch.at[slot],
                sem.at[slot],
            )

        get_dma(0, 0).start()
        acc[:] = jnp.zeros((t_block,), out_ref.dtype)

        def loop_body(k, _):
            slot = k % 2

            @pl.when(k + 1 < K)
            def _start_next():
                get_dma((k + 1) % 2, k + 1).start()

            get_dma(slot, k).wait()
            acc[:] += scratch[slot]

        jax.lax.fori_loop(0, K, loop_body, None)
        out_ref[:] = acc[:]

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM((2, t_block), out_ref.dtype),
        acc=pltpu.VMEM((t_block,), out_ref.dtype),
        sem=pltpu.SemaphoreType.DMA((2,)),
    )


def _pallas_gather_sum(data, rows, shifts, out_len: int,
                       interpret: bool = False, t_block: int = T_BLOCK):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    O, K = rows.shape
    # lane alignment: tile width must be a multiple of 128
    t_block = min(t_block, max(128, -(-out_len // 128) * 128))
    n_t = -(-out_len // t_block)
    padded_len = n_t * t_block
    # the last tile reads up to shift + padded_len <= shift + out_len +
    # t_block; the caller guarantees shift + out_len <= L (same contract
    # as the lax path), so t_block zeros of tail padding keep every DMA
    # in bounds
    data = jnp.pad(data, ((0, 0), (0, t_block)))
    # flat 1-D output (block = one tile) sidesteps the (8, 128) 2-D block
    # alignment constraint; row o occupies [o*padded_len, (o+1)*padded_len)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(O, n_t),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((t_block,),
                               lambda o, j, *_, _nt=n_t: (o * _nt + j,),
                               memory_space=pltpu.VMEM),
    )
    out = pl.pallas_call(
        partial(_gather_sum_kernel, K=K, t_block=t_block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((O * padded_len,), data.dtype),
        interpret=interpret,
    )(rows, shifts, data)
    return out.reshape(O, padded_len)[:, :out_len]


def _lax_gather_sum(data, rows, shifts, out_len: int):
    """Reference formulation: vmapped dynamic-slice gather + sum."""
    def one_out(r, s):
        picked = data[r]  # [K, L]
        sliced = jax.vmap(
            lambda row, st: jax.lax.dynamic_slice(row, (st,), (out_len,))
        )(picked, s)
        return sliced.sum(axis=0)

    return jax.vmap(one_out)(rows, shifts)


@partial(jax.jit, static_argnames=("out_len", "backend"))
def shifted_gather_sum(data, rows, shifts, out_len: int,
                       backend: str = "auto"):
    """out[o, t] = sum_k data[rows[o, k], shifts[o, k] + t] for
    t in [0, out_len).

    ``data`` is [R, L] float32; ``rows``/``shifts`` are [O, K] int32 with
    every window ``shifts + out_len`` (after internal padding to the tile
    size) within L.  ``backend``: 'pallas', 'lax', 'interpret', or 'auto'
    (pallas on TPU).
    """
    data = jnp.asarray(data)
    rows = jnp.asarray(rows, jnp.int32)
    shifts = jnp.asarray(shifts, jnp.int32)
    if backend == "auto":
        # the TPU compiler refuses the kernel's single-row DMA (see the
        # module docstring); opt in explicitly
        backend = "lax"
    if backend == "pallas":
        return _pallas_gather_sum(data, rows, shifts, out_len)
    if backend == "interpret":
        return _pallas_gather_sum(data, rows, shifts, out_len,
                                  interpret=True)
    return _lax_gather_sum(data, rows, shifts, out_len)
