"""Pallas TPU kernels for the detection hot loop.

``boxcar_stats`` is the per-trial matched-filter statistics stage of the
DM sweep (parallel/sweep.py): given dedispersed time series ts[D, T], for
every trial compute the payload sum and sum-of-squares plus, for each
boxcar width w, the maximum (and argmax) of the w-sample running sum over
windows starting in the payload.

The XLA formulation materializes a [D, T] window-sum array per width in
HBM (W passes over HBM).  The Pallas kernel streams a block of trials
through VMEM once: the cumulative sum is formed in VMEM scratch and every
width's windowed difference, max, and argmax are reduced in-register —
HBM traffic drops from (W+1) x D x T reads to a single one.

``backend='auto'`` picks the kernel on TPU and the lax formulation on any
other platform (CPU tests run the kernel in interpret mode).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

D_BLOCK = 8  # float32 sublane count: one tile of trials per grid step


def _shift_left(x, k: int):
    """x[:, t] -> x[:, t+k], zero-filled at the tail (static slice)."""
    if k == 0:
        return x
    return jnp.concatenate(
        [x[:, k:], jnp.zeros((x.shape[0], k), x.dtype)], axis=1)


def _boxcar_kernel(ts_ref, halo_ref, s_ref, ss_ref, mb_ref, ab_ref,
                   *, widths: Tuple[int, ...], stat_len: int,
                   t_block: int):
    """One [D_BLOCK, t_block] time tile (plus max-width halo): partial
    payload stats and per-width windowed max, accumulated across the time
    grid axis (same output block revisited per j; init at j == 0).

    Window sums come from a dyadic doubling table instead of a cumsum
    (``cumsum`` has no Pallas TPU lowering, and the doubling scheme also
    avoids the cumsum's cancellation error at large T): dy[k][t] =
    sum ts[t : t+2^k), built with log2(maxw) shifted adds; an arbitrary
    width is the sum of its binary components at increasing offsets.
    """
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    maxw = max(widths)
    data = jnp.concatenate([ts_ref[:, :], halo_ref[:, :]], axis=1)

    # window starts (and payload samples) valid within this tile
    t0 = j * t_block
    local_idx = jax.lax.broadcasted_iota(jnp.int32, (D_BLOCK, t_block), 1)
    valid = (t0 + local_idx) < stat_len

    payload = jnp.where(valid, data[:, :t_block], 0.0)
    part_s = jnp.sum(payload, axis=-1)
    part_ss = jnp.sum(payload * payload, axis=-1)

    dyadic = [data]
    k = 0
    while (1 << (k + 1)) <= maxw:
        step = 1 << k
        dyadic.append(dyadic[k] + _shift_left(dyadic[k], step))
        k += 1

    neg = jnp.asarray(-jnp.inf, data.dtype)
    local_mb = []
    local_ab = []
    for w in widths:
        box = None
        off = 0
        for bit in range(int(w).bit_length()):
            if w & (1 << bit):
                part = _shift_left(dyadic[bit], off)
                box = part if box is None else box + part
                off += 1 << bit
        box = jnp.where(valid, box[:, :t_block], neg)
        local_mb.append(jnp.max(box, axis=-1))
        local_ab.append(t0 + jnp.argmax(box, axis=-1).astype(jnp.int32))
    lmb = jnp.stack(local_mb, axis=-1)
    lab = jnp.stack(local_ab, axis=-1)

    @pl.when(j == 0)
    def _init():
        s_ref[:, 0] = part_s
        ss_ref[:, 0] = part_ss
        mb_ref[:, :] = lmb
        ab_ref[:, :] = lab

    @pl.when(j > 0)
    def _accumulate():
        s_ref[:, 0] += part_s
        ss_ref[:, 0] += part_ss
        better = lmb > mb_ref[:, :]
        mb_ref[:, :] = jnp.where(better, lmb, mb_ref[:, :])
        ab_ref[:, :] = jnp.where(better, lab, ab_ref[:, :])


def _pallas_boxcar_stats(ts, widths: Tuple[int, ...], stat_len: int,
                         interpret: bool = False, t_block: int = 8192):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    D, T = ts.shape
    W = len(widths)
    maxw = int(max(widths))
    # TPU lane constraint: the halo block's last dim must be a multiple
    # of 128; time blocks must be a multiple of the halo width so its
    # block index is integral
    halo = -(-maxw // 128) * 128
    t_block = max(halo, (t_block // halo) * halo)
    n_t = -(-stat_len // t_block)
    pad_d = (-D) % D_BLOCK
    # pad the time axis so every tile's halo read stays in bounds.  With
    # the default widths (maxw=32 < halo=128) this fires on every call;
    # the copy is of the [D, T] detection series only (a few percent of
    # the dedispersion stage's traffic), the price of a lane-aligned
    # halo block.
    pad_t = max(n_t * t_block + halo - T, 0)
    if pad_d or pad_t:
        ts = jnp.pad(ts, ((0, pad_d), (0, pad_t)))
    Dp = D + pad_d

    kernel = partial(_boxcar_kernel, widths=tuple(int(w) for w in widths),
                     stat_len=stat_len, t_block=t_block)
    # under shard_map (the DM-sharded sweep chunk) the outputs vary over
    # the mesh axes exactly as the trial rows do; shard_map's check needs
    # that said on the kernel's out_shape
    vma = jax.typeof(ts).vma
    s, ss, mb, ab = pl.pallas_call(
        kernel,
        grid=(Dp // D_BLOCK, n_t),
        in_specs=[
            pl.BlockSpec((D_BLOCK, t_block), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            # halo: the samples after the tile (offset in halo units)
            pl.BlockSpec((D_BLOCK, halo),
                         lambda i, j, _tb=t_block, _h=halo:
                         (i, (j + 1) * _tb // _h),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((D_BLOCK, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((D_BLOCK, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((D_BLOCK, W), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((D_BLOCK, W), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Dp, 1), ts.dtype, vma=vma),
            jax.ShapeDtypeStruct((Dp, 1), ts.dtype, vma=vma),
            jax.ShapeDtypeStruct((Dp, W), ts.dtype, vma=vma),
            jax.ShapeDtypeStruct((Dp, W), jnp.int32, vma=vma),
        ],
        interpret=interpret,
    )(ts, ts)
    return s[:D, 0], ss[:D, 0], mb[:D], ab[:D]


def _lax_boxcar_stats(ts, widths: Tuple[int, ...], stat_len: int):
    """Reference lax formulation (same math as parallel/sweep.py)."""
    payload = ts[:, :stat_len]
    s = payload.sum(axis=-1)
    ss = (payload * payload).sum(axis=-1)
    cs = jnp.concatenate(
        [jnp.zeros((ts.shape[0], 1), ts.dtype),
         jnp.cumsum(ts, axis=-1)], axis=-1)
    maxs, args = [], []
    for w in widths:
        box = cs[:, w:w + stat_len] - cs[:, :stat_len]
        maxs.append(box.max(axis=-1))
        args.append(box.argmax(axis=-1).astype(jnp.int32))
    return s, ss, jnp.stack(maxs, -1), jnp.stack(args, -1)


def _on_tpu() -> bool:
    # lazy import: parallel.sweep imports this module at load time, so a
    # module-level ops -> parallel.mesh import would cycle; resolving
    # through the lease registry (PL002) keeps the backend probe honest
    # under a gang lease. A backend that cannot be asked raises.
    from pypulsar_tpu.parallel.mesh import lease_devices

    return lease_devices()[0].platform == "tpu"


@partial(jax.jit, static_argnames=("widths", "stat_len", "backend"))
def boxcar_stats(ts, widths: Tuple[int, ...], stat_len: int,
                 backend: str = "auto"):
    """(sum[D], sumsq[D], maxbox[D, W], argbox[D, W]) over ts[D, T] with
    windows starting in the first ``stat_len`` samples.

    ``backend``: 'pallas' (TPU kernel), 'lax', 'interpret' (pallas
    interpreter, for tests), or 'auto' (pallas on TPU, lax elsewhere).
    """
    ts = jnp.asarray(ts)
    if ts.shape[1] < stat_len + max(widths):
        raise ValueError(
            f"time axis {ts.shape[1]} shorter than stat_len+max(width) "
            f"= {stat_len + max(widths)}")
    widths = tuple(int(w) for w in widths)
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "lax"
    with jax.named_scope("boxcar.scan"):
        if backend == "pallas":
            return _pallas_boxcar_stats(ts, widths, stat_len)
        if backend == "interpret":
            return _pallas_boxcar_stats(ts, widths, stat_len,
                                        interpret=True)
        return _lax_boxcar_stats(ts, widths, stat_len)
