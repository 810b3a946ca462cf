"""The survey chain's device programs, compiled for a described TPU v5e.

No test in this file runs anything: the TPU's compiler is installed in
the sandbox and compiles for a chip that is described, not attached
(``jax.experimental.topologies``), so these pin what interpret mode and
the CPU backend cannot see — a Mosaic kernel the compiler refuses, a
program that does not fit the device's memory, a collective that crept
into a communication-free sharding. Shapes are chip_smoke.py's: the
repo's one telescope geometry at full width (1024 channels, 64 us,
1200-1500 MHz), 64 DM trials at step 2, the default 2^18 FFT chunk, a
2^22-sample pointing (2^21-bin spectra), zmax 50, numharm 8.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU's library, and every xdist
worker imports every test file. All cases live in this one file, so one
worker holds the library; they compile in the test's own process with
the persistent compilation cache off (an entry compiled for a described
chip cannot be read back without one).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

# one v5e chip: 16 GB HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16e9

NCHAN, TSAMP = 1024, 64e-6
NSUB, NUMDMS, DMSTEP = 64, 64, 2.0
NSAMP = 1 << 22
ZMAX, NUMHARM = 50.0, 8
ACCEL_BATCH = 16  # what the handoff picks at 2^22 samples (accelpipe)
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("dm",))


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def on_tpu(monkeypatch):
    """Kernel selection asks the lease registry for the platform, and
    here that is the CPU: steer ``backend='auto'`` to the Pallas kernel
    as it resolves on the chip."""
    from pypulsar_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _sweep_geometry(mesh=None):
    """The plan and chunk shape the streamed sweep dispatches for the
    smoke's DM grid (parallel/sweep.py `_warm_sweep` derivation)."""
    from pypulsar_tpu.parallel.sweep import (
        _mesh_pad_groups,
        choose_group_size,
        default_chunk_payload,
        make_sweep_plan,
    )

    freqs = 1500.0 - (300.0 / NCHAN) * np.arange(NCHAN)
    dms = DMSTEP * np.arange(NUMDMS)
    group = choose_group_size(dms, freqs, TSAMP, NSUB)
    plan = make_sweep_plan(
        dms, freqs, TSAMP, nsub=NSUB, group_size=group,
        pad_groups_to=_mesh_pad_groups(len(dms), group, mesh))
    payload = default_chunk_payload(plan, tuned=False)
    out_len = payload + max(plan.widths)
    need = out_len + plan.max_shift2 + plan.max_shift1
    assert need == 1 << 18  # the default chunk IS the 2^18 FFT
    return plan, payload, out_len, need


def _chunk_args(plan, need, data_sh, table_sh):
    return (_sds((NCHAN, need), jnp.float32, data_sh),
            _sds(plan.stage1_bins.shape, jnp.int32, table_sh),
            _sds(plan.stage2_bins.shape, jnp.int32, table_sh))


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return m.temp_size_in_bytes, m.argument_size_in_bytes


def test_fourier_sweep_chunk(one_chip, on_tpu):
    """The single-pulse pass's chunk program: Fourier dedispersion of
    64 trials + the Pallas boxcar kernel, and it fits with the stream's
    four pending chunk buffers beside the executing program."""
    from pypulsar_tpu.parallel.sweep import sweep_chunk

    plan, payload, out_len, need = _sweep_geometry()
    compiled = sweep_chunk._jit.lower(
        *_chunk_args(plan, need, one_chip, one_chip),
        NSUB, out_len, plan.max_shift2, tuple(plan.widths), payload,
        engine="fourier").compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp, args = _device_bytes(compiled)
    chunk_buffer = 4 * NCHAN * need
    max_pending = 4  # sweep_stream's default
    assert temp + args + max_pending * chunk_buffer < V5E_HBM_BYTES


def test_pallas_boxcar_at_chunk_shape(one_chip):
    from pypulsar_tpu.ops.pallas_kernels import _pallas_boxcar_stats

    plan, payload, out_len, _need = _sweep_geometry()
    compiled = jax.jit(
        lambda ts: _pallas_boxcar_stats(ts, tuple(plan.widths), payload)
    ).lower(_sds((plan.group_size, out_len), jnp.float32,
                 one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fourier_series_chunk(one_chip):
    """The accel handoff's dedispersion: the same chunk, the series out."""
    from pypulsar_tpu.parallel.sweep import dedisperse_series_chunk

    plan, _payload, out_len, need = _sweep_geometry()
    compiled = dedisperse_series_chunk._jit.lower(
        *_chunk_args(plan, need, one_chip, one_chip),
        NSUB, out_len, plan.max_shift2, "fourier").compile()
    temp, args = _device_bytes(compiled)
    assert temp + args < V5E_HBM_BYTES


def test_mask_fill_selects_without_a_sort(one_chip):
    """The sweep stage's mask fill at the streamed block's shape (one
    chunk: payload + overlap samples of every channel, 34 one-second
    intervals of a 2^19-sample pointing): the per-channel median is an
    exact selection, so neither what is lowered nor what the chip's
    compiler makes of it holds a sort (two of them were a third of the
    survey cell's device time, PERF.md PR 30)."""
    import re

    from pypulsar_tpu.parallel.staged import _masked_block

    plan, payload, _out_len, need = _sweep_geometry()
    assert payload + plan.min_overlap == need
    pts = int(round(1.0 / TSAMP))
    lowered = _masked_block._jit.lower(
        _sds((NCHAN, need), jnp.float32, one_chip),
        _sds((34, NCHAN), jnp.bool_, one_chip),
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        pts=pts)
    text = lowered.as_text()
    assert "stablehlo.while" in text and "stablehlo.sort" not in text
    compiled = lowered.compile()
    assert not re.search(r"\bsort\(", compiled.as_text())
    temp, args = _device_bytes(compiled)
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == 4 * NCHAN * need
    assert temp + args + out < V5E_HBM_BYTES / 2


def test_rfifind_block_stats(one_chip):
    from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len
    from pypulsar_tpu.ops.rfifind import _block_stats_impl

    pts = int(round(1.0 / TSAMP))  # --mask-time 1.0
    ints_per_read = 16
    compiled = _block_stats_impl.lower(
        _sds((NCHAN, ints_per_read * pts), jnp.float32, one_chip),
        pts=pts, n_fft=fourier_chunk_len(pts)).compile()
    temp, args = _device_bytes(compiled)
    assert temp + args < V5E_HBM_BYTES


def test_rfifind_raw_ingest(one_chip):
    """The mask stage's ingest of one read of a 2-bit pointing as the
    file holds it (16 one-second intervals x 256 packed bytes a
    spectrum), band flipped to the .mask convention; its float32 output
    is block_stats' input, so both are resident at once."""
    from pypulsar_tpu.ops.ingest import _ingest_tc

    pts = int(round(1.0 / TSAMP))
    compiled = _ingest_tc._jit.lower(
        _sds((16 * pts, NCHAN * 2 // 8), jnp.uint8, one_chip),
        flip=True, nbits=2).compile()
    temp, args = _device_bytes(compiled)
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == 4 * NCHAN * 16 * pts
    assert temp + args + out < V5E_HBM_BYTES / 4


# -- the second geometry: GBNCC at 350 MHz, 4096 channels x 81.92 us -------
# (benchmark/configs/gbncc-350.json), at the lengths plan/lengths.py gives a
# 16 GB chip; the 1024-channel constants do not compile there at all


def _gbncc_geometry():
    import json
    import os

    from pypulsar_tpu.parallel.sweep import (
        choose_group_size,
        make_sweep_plan,
        planned_payload,
    )
    from pypulsar_tpu.plan import lengths

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "gbncc-350.json")) as f:
        cfg = json.load(f)
    C = cfg["nchan"]
    freqs = cfg["fch1"] - cfg["bw"] / C * np.arange(C)
    dms = cfg["dm_lo"] + cfg["dm_step"] * np.arange(cfg["dm_trials"])
    group = choose_group_size(dms, freqs, cfg["tsamp"], cfg["nsub"])
    plan = make_sweep_plan(dms, freqs, cfg["tsamp"], nsub=cfg["nsub"],
                           group_size=group)
    pts = int(round(cfg["mask_time"] / cfg["tsamp"]))
    planned = lengths.plan_lengths(C, cfg["nsub"], plan.min_overlap,
                                   plan.n_trials, V5E_HBM_BYTES,
                                   interval_samples=pts)
    payload = planned_payload(plan, planned)
    assert payload == cfg["chunk"]
    return cfg, plan, planned, payload, pts


@pytest.mark.parametrize("program", ["sweep", "series"])
def test_gbncc_chunk_program_is_what_the_planner_counted(one_chip, on_tpu,
                                                         program):
    """The chunk programs at 4096 channels and the planned 2^16 chunk:
    argument, temporaries and result fit the planner's count, which adds
    the stream's other buffers; at the 1024-channel default of 2^18 the
    block alone is 4.3 GB and its transform's temporaries four times
    that."""
    from pypulsar_tpu.parallel.sweep import (dedisperse_series_chunk,
                                             sweep_chunk)
    from pypulsar_tpu.plan import lengths

    cfg, plan, planned, payload, _pts = _gbncc_geometry()
    C, need = cfg["nchan"], payload + plan.min_overlap
    assert planned.chunk == 1 << 16 and need <= planned.chunk
    args = (_sds((C, need), jnp.float32, one_chip),
            _sds(plan.stage1_bins.shape, jnp.int32, one_chip),
            _sds(plan.stage2_bins.shape, jnp.int32, one_chip))
    if program == "sweep":
        compiled = sweep_chunk._jit.lower(
            *args, cfg["nsub"], payload + max(plan.widths),
            plan.max_shift2, tuple(plan.widths), payload,
            engine="fourier").compile()
    else:
        compiled = dedisperse_series_chunk._jit.lower(
            *args, cfg["nsub"], payload, plan.max_shift2,
            "fourier").compile()
    temp, argb = _device_bytes(compiled)
    out = compiled.memory_analysis().output_size_in_bytes
    # 16 bytes a sample of every channel of temporaries, 4 of argument
    assert temp <= 1.05 * 16 * C * planned.chunk
    assert temp + argb + out <= planned.chunk_need <= planned.budget
    assert planned.chunk_need == lengths.chunk_bytes(
        C, cfg["nsub"], plan.n_trials, planned.chunk)


def test_gbncc_mask_block_is_what_the_planner_counted(one_chip):
    """The mask stage's block at 4096 channels: 8 one-second intervals,
    where 16 ask the compiler for 16.00 G of the chip's 15.75 G."""
    from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len
    from pypulsar_tpu.ops.rfifind import _block_stats_impl

    cfg, _plan, planned, _payload, pts = _gbncc_geometry()
    assert planned.mask_intervals == 8
    compiled = _block_stats_impl.lower(
        _sds((cfg["nchan"], planned.mask_intervals * pts), jnp.float32,
             one_chip),
        pts=pts, n_fft=fourier_chunk_len(pts)).compile()
    temp, argb = _device_bytes(compiled)
    packed = cfg["nchan"] * planned.mask_intervals * pts
    # the count leaves out only the three small result tables
    assert temp + argb + packed <= 1.001 * planned.mask_need
    assert planned.mask_need <= planned.budget


def _prep_args(batch, sharding, table_sh):
    from pypulsar_tpu.fourier.kernels import deredden_schedule

    sch = deredden_schedule(NSAMP // 2 + 1)
    return sch, (
        _sds((batch, NSAMP), jnp.float32, sharding),
        *(_sds(a.shape, jnp.int32, table_sh)
          for a in (sch.starts, sch.lens, sch.elem_block, sch.elem_off)))


def test_prep_spectra_batch(one_chip):
    """rfft + deredden of one handoff batch of 2^22-sample series; the
    pipeline holds prefetch_depth + 2 = 3 prepped batches at once."""
    from pypulsar_tpu.fourier.kernels import _prep_spectra_kernel

    sch, args = _prep_args(ACCEL_BATCH, one_chip, one_chip)
    compiled = _prep_spectra_kernel._jit.lower(
        *args, maxlen=sch.maxlen).compile()
    temp, argb = _device_bytes(compiled)
    out = compiled.memory_analysis().output_size_in_bytes
    assert temp + argb + 3 * out < V5E_HBM_BYTES


def _accel_stage(batch, mesh_devs, spec_sh, table_sh):
    from pypulsar_tpu.fourier import accelsearch as acc

    cfg = acc.AccelSearchConfig(zmax=ZMAX, dz=2.0, numharm=NUMHARM,
                                sigma_min=2.0)
    N = NSAMP // 2 + 1
    (zs, ws, stages, segw, rlo, rhi, banks, front, Np, _numindep,
     _thresh) = acc._search_setup(N, NSAMP * TSAMP, cfg)
    assert max(stages) == NUMHARM and N - 1 == 1 << 21
    Z, Wn = len(zs), len(ws)
    grid_lo, n_seg, lo, hi = acc._ladder_grid(stages, rlo, rhi, N, segw)
    rungs, tfs, idxs = acc._ladder_banks(banks, stages, grid_lo, segw, front)
    assert len(tfs) == 8  # every ratio bank once, not sum(H) = 15
    # the batch chunk accel_search_batch dispatches: what the default
    # per-device PYPULSAR_TPU_ACCEL_HBM budget (5e9) admits of the batch
    per_dev = max(1, int(5e9) // acc._stage_chunk_bytes(tfs, Z, Wn, segw))
    chunk = min(batch, per_dev * max(1, len(mesh_devs)))
    runner = acc._make_ladder_runner(
        segw, Z, Wn, cfg.topk, rungs, mesh_devs=mesh_devs)
    return runner._jit.lower(
        _sds((chunk, 2, Np), jnp.float32, spec_sh),
        tuple(_sds(t.shape, t.dtype, table_sh) for t in tfs),
        tuple(_sds(i.shape, i.dtype, table_sh) for i in idxs),
        grid_lo,
        *(_sds((len(stages),), dt, table_sh)
          for dt in (jnp.int32, jnp.int32, jnp.float32)),
        _sds((n_seg,), jnp.int32, table_sh))


def test_accel_search_stage(one_chip):
    """The whole harmonic ladder (all 8 ratio banks, four detections a
    segment) of the zmax-50 search over 2^21-bin spectra, inside the
    accel HBM budget the batch chunking plans against."""
    compiled = _accel_stage(ACCEL_BATCH, (), one_chip, one_chip).compile()
    temp, args = _device_bytes(compiled)
    assert temp + args < V5E_HBM_BYTES


def test_fold_parts(one_chip):
    """The fold stage's batched one-hot fold (candidates sharing one
    dedispersed series) and the 2-D fold_parts of the per-tool path."""
    from pypulsar_tpu.fold.engine import (
        _fold_parts_batch_jit,
        _fold_parts_jit,
    )

    nbins, npart, K = 64, 32, 4
    _fold_parts_batch_jit._jit.lower(
        _sds((NSAMP,), jnp.float32, one_chip),
        _sds((K, NSAMP), jnp.int32, one_chip), nbins, npart).compile()
    _fold_parts_jit._jit.lower(
        _sds((64, 1 << 20), jnp.float32, one_chip),
        _sds((1 << 20,), jnp.int32, one_chip), nbins, npart).compile()


def test_sharded_sweep_step_four_chips(mesh4, on_tpu):
    """`--gang 4`: trial groups over the 'dm' axis of the four chips,
    the chunk replicated, the Pallas boxcar inside shard_map — and no
    collective: DM sharding talks to nobody until the host reduces."""
    from pypulsar_tpu.parallel.sweep import make_sharded_sweep_chunk

    plan, payload, out_len, need = _sweep_geometry(mesh4)
    fn = make_sharded_sweep_chunk(mesh4, NSUB, out_len, plan.max_shift2,
                                  tuple(plan.widths), payload,
                                  engine="fourier")
    compiled = fn._jit.lower(*_chunk_args(
        plan, need, NamedSharding(mesh4, P()),
        NamedSharding(mesh4, P("dm")))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not [c for c in COLLECTIVES if c in text]
    temp, args = _device_bytes(compiled)  # per device
    assert temp + args + 4 * 4 * NCHAN * need < V5E_HBM_BYTES


def test_sharded_series_chunk_four_chips(mesh4):
    """`--gang 4`, the accel handoff's dedispersion: the series chunk
    with trial groups over 'dm', and no collective either."""
    from pypulsar_tpu.parallel.sweep import make_sharded_series_chunk

    plan, _payload, out_len, need = _sweep_geometry(mesh4)
    fn = make_sharded_series_chunk(mesh4, NSUB, out_len, plan.max_shift2,
                                   engine="fourier")
    compiled = fn._jit.lower(*_chunk_args(
        plan, need, NamedSharding(mesh4, P()),
        NamedSharding(mesh4, P("dm")))).compile()
    assert not [c for c in COLLECTIVES if c in compiled.as_text()]
    temp, args = _device_bytes(compiled)  # per device
    assert temp + args < V5E_HBM_BYTES


def test_sharded_accel_search_four_chips(topo, mesh4):
    """`--gang 4`: the handoff batch (32 spectra under a gang of 4)
    sharded over the same mesh through prep and the harmonic ladder."""
    from pypulsar_tpu.fourier.kernels import _prep_spectra_kernel

    batch = 2 * ACCEL_BATCH
    shd, rep = NamedSharding(mesh4, P("dm")), NamedSharding(mesh4, P())
    sch, args = _prep_args(batch, shd, rep)
    prep = _prep_spectra_kernel._jit.lower(
        *args, maxlen=sch.maxlen).compile()
    stage = _accel_stage(batch, tuple(topo.devices), shd, rep).compile()
    for compiled in (prep, stage):
        assert not [c for c in COLLECTIVES if c in compiled.as_text()]
        temp, argb = _device_bytes(compiled)
        assert temp + argb < V5E_HBM_BYTES
