"""Spectral fusion: serve accel-search from the sweep's own spectra.

The streamed handoff (parallel/accelpipe.py) still round-trips every DM
trial through the time domain: the Fourier sweep engine holds each
trial's spectrum ``Xts`` on device, ``irfft``s it to a series chunk,
pulls the chunk to a host buffer — and ``prep_spectra_batch``
immediately undoes all of that with a fresh whole-series ``rfft``. The
accel stage is the chain's measured weak link (15.77x vs 113-9,896x
elsewhere) and already runs at 85% of its FFT roofline (BENCHNOTES
round 6), so the remaining win is doing FEWER transforms, not faster
ones — Fourier-domain dedispersion (PAPERS.md 2110.03482: one forward
transform of the raw data serves every trial; 1201.5380: the
shift-and-sum itself is bandwidth-cheap once the transform is
amortized). This module is that path, in two regimes — and the regime
choice is the parity-gate decision ISSUE 10 called for:

- **stitched** (the DEFAULT — the design that survives the parity
  gate at every geometry): per-chunk dedispersed rows — the sweep's
  own kernel, bit-identical values — scatter straight into a
  device-resident ``[D, T]`` buffer (overlap-save valid windows
  partition the time axis), and ONE fused ``prep_spectra_batch``
  dispatch per DM slice transforms the whole buffer. Candidates are
  BIT-identical to the streamed device-prep path (same rows, same prep
  kernel, per-row math). The series never crosses the host link
  (``specfuse.bytes_on_device``: the per-chunk D2H pull and the prep
  H2D re-ship are both gone) and prep collapses from one dispatch per
  batch to one per slice. The buffer is
  HBM-resident, which is why the all-at-once option is bounded by the
  2^26-sample / 275 GB cliff parallel/staged.py documents: past the
  ``PYPULSAR_TPU_SPECFUSE_HBM`` budget the caller slices the DM axis,
  one extra raw pass per slice — the accelpipe RAM-slicing contract.
- **decimated** (opt-in via ``PYPULSAR_TPU_SPECFUSE_MODE=decimate``;
  needs a single Fourier chunk covering the observation, ``n_fft % T
  == 0`` — i.e. power-of-two series lengths — and the 'fourier'
  engine): the sweep's spectra kernel
  (ops.fourier_dedisperse.sweep_chunk_spectra) hands over each trial's
  ``Xts`` pre-irfft and DECIMATES it onto the T-point grid — the
  per-trial irfft AND the per-trial whole-series rfft are both gone,
  zero transforms per trial, counted on
  ``specfuse.fft_pairs_elided``. The catch, measured during round 10
  and documented in the kernel's docstring: decimation IS circular
  dedispersion (the 2110.03482 convention), while the time-domain
  engines use PRESTO's zero-padded linear shifts, so the final
  ``max_total_shift`` samples — boundary garbage under either
  convention — differ by real data and the candidate tables are NOT
  byte-identical at toy scale. Hence opt-in, not default: the
  structural win is real and counted, the parity default stays exact.

Both regimes honor the handoff's existing machinery: RAM-budgeted DM
slicing (the caller's), ``halving_dispatch`` OOM recovery on every
device dispatch, ``--mesh k`` DM sharding with spectra staying
``P('dm')``-sharded end to end, journal/resume (the caller's; the
``specfuse.after_stitch`` kill-point marks the new stage boundary), and
prefetch overlap (batch gathers slice the resident planes).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from pypulsar_tpu.compile import plane_jit
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.resilience import faultinject
from pypulsar_tpu.tune import knobs

__all__ = ["fused_spectra_slice", "spectral_trial_bytes"]


def spectral_trial_bytes(T: int) -> int:
    """Device bytes ONE trial occupies while a slice is fused: the
    stitched series row (4T f32) plus the prepped spectrum planes
    (8*(T//2+1)). The decimated regime skips the series buffer, but
    budgeting for the worse regime keeps the caller's DM-slice choice
    regime-independent (a slice must not OOM because the geometry fell
    back to stitching)."""
    return 4 * T + 8 * (T // 2 + 1)


def _make_sharded_spectra_chunk(mesh, nsub, n_fft, dec_stride, dec_len,
                                mean_len):
    """Spectra kernel with trial groups sharded over the mesh 'dm' axis
    — the decimated regime's twin of sweep.make_sharded_series_chunk.
    The chunk replicates; each device computes only its local groups'
    spectra and the planes concatenate in group order (P('dm')), so the
    values are bit-identical to the unsharded kernel's."""
    from jax.sharding import PartitionSpec as P

    from pypulsar_tpu.ops.fourier_dedisperse import sweep_chunk_spectra_impl

    def impl(data, s1, s2):
        return sweep_chunk_spectra_impl(data, s1, s2, nsub, n_fft,
                                        dec_stride, dec_len, mean_len)

    fn = jax.shard_map(impl, mesh=mesh,
                       in_specs=(P(), P("dm"), P("dm")),
                       out_specs=(P("dm"), P("dm")))
    # mesh-closing factory: AOT keying is unsound across meshes, so the
    # plane keeps plain-jit dispatch (aot=False) and owns the telemetry
    return plane_jit(fn, stage="specfuse", name="specfuse_sharded_chunk",
                     aot=False)


def fused_spectra_slice(
    reader,
    dms,
    schedule=None,
    downsamp: int = 1,
    nsub: int = 64,
    group_size: int = 32,
    rfimask=None,
    engine: str = "auto",
    chunk_payload: Optional[int] = None,
    mesh=None,
    verbose: bool = False,
) -> dict:
    """One pass over ``reader``: every trial in ``dms`` fused to its
    PREPPED (dereddened) T-point spectrum, device-resident.

    Returns ``dict(re, im, n_real, T, dt_eff, regime)`` — ``re``/``im``
    are ``[Dpad, T//2+1]`` float32 planes (``Dpad`` pads trials to the
    stage-1 group and mesh multiples; rows ``[:n_real]`` are the real
    trials, in ``dms`` order), consumable directly by
    ``accel_search_batch`` via row gathers. ``schedule`` is the
    ``deredden_schedule(T//2+1)`` (built here when omitted).

    ``PYPULSAR_TPU_SPECFUSE_MODE``: ``stitch`` (default — bit-exact
    parity with the streamed path) or ``decimate`` (opt-in
    zero-transforms-per-trial regime with CIRCULAR boundary semantics,
    module docstring; falls back to stitched where its geometry gate
    fails).
    """
    from pypulsar_tpu.fourier.kernels import (
        deredden_schedule,
        prep_spectra_batch,
    )
    from pypulsar_tpu.ops.fourier_dedisperse import (
        fourier_chunk_len,
        sweep_chunk_spectra,
    )
    from pypulsar_tpu.parallel.staged import (
        _MaskedSource,
        _ReaderSource,
        _downsampled_blocks,
        dats_geometry,
    )
    from pypulsar_tpu.parallel.sweep import (
        dedisperse_series_chunk,
        make_sharded_series_chunk,
        make_sweep_plan,
        resolve_engine,
    )
    from pypulsar_tpu.resilience import dataguard
    from pypulsar_tpu.resilience.retry import halving_dispatch

    factor = max(1, int(downsamp))
    dms = np.asarray(dms, dtype=np.float64)
    probe = _ReaderSource(reader)
    # round-17 auto-tuning consult at the fused slice's own geometry
    # (the SPECFUSE_HBM slice budget is this stage's knob); env wins
    from pypulsar_tpu import tune

    tune.apply_cached("specfuse", nchan=len(probe.frequencies),
                      nsamp=int(probe.nsamples) // factor)
    plan, payload, T = dats_geometry(reader, dms, downsamp=factor,
                                     nsub=nsub, group_size=group_size,
                                     chunk_payload=chunk_payload)
    dt_eff = probe.tsamp * factor
    ndm = 1 if mesh is None else int(mesh.shape["dm"])
    dev_ids = ([int(getattr(d, "id", -1)) for d in mesh.devices.flat]
               if mesh is not None else None)
    from pypulsar_tpu.parallel.sweep import padded_group_count

    padded_groups = padded_group_count(plan.n_groups, ndm)
    if padded_groups != plan.n_groups:
        # padded groups replicate the last real trial (group math is
        # independent; rows [:n_real] below are untouched)
        plan = make_sweep_plan(dms, probe.frequencies, dt_eff,
                               nsub=nsub, group_size=plan.group_size,
                               widths=(1,), pad_groups_to=padded_groups)
    if schedule is None:
        schedule = deredden_schedule(T // 2 + 1)

    engine_r = resolve_engine(engine)
    need = payload + plan.min_overlap
    n_fft = fourier_chunk_len(need)
    n_chunks = -(-T // payload)
    # decimate is OPT-IN (circular boundary semantics — module
    # docstring) and additionally geometry-gated; anything else stitches
    decimated = (knobs.env_str("PYPULSAR_TPU_SPECFUSE_MODE") == "decimate"
                 and engine_r == "fourier" and n_chunks == 1
                 and T > 1 and n_fft % T == 0)
    if verbose:
        mode = ("decimated (0 transforms/trial)" if decimated
                else "stitched (%d chunks)" % n_chunks)
        print(f"# specfuse: {len(dms)} trials x {T} samples, "
              f"{mode}, engine={engine_r}")

    src = dataguard.guard_source(_ReaderSource(reader))
    if rfimask is not None:
        src = _MaskedSource(src, rfimask)
    s1b = jnp.asarray(plan.stage1_bins)
    s2b = jnp.asarray(plan.stage2_bins)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec_dm = NamedSharding(mesh, P("dm"))
        s1b = jax.device_put(s1b, spec_dm)
        s2b = jax.device_put(s2b, spec_dm)
    Dpad = plan.n_trials
    n_real = len(dms)
    F = T // 2 + 1

    def group_dispatch(make_whole, make_slice):
        """Run a per-chunk device dispatch over the trial-group axis
        under the OOM-halving policy: ``make_whole()`` dispatches every
        group (the hot path — uses the pre-laid full tables);
        ``make_slice(s1, s2)`` a group slice. Per-group math is
        independent, so concatenated halves are bit-identical."""
        def run(lo, hi):
            faultinject.trip("specfuse.chunk_dispatch")
            if (lo, hi) == (0, plan.n_groups):
                return make_whole()
            s1_sl, s2_sl = s1b[lo:hi], s2b[lo:hi]
            if mesh is not None:
                s1_sl = jax.device_put(s1_sl, spec_dm)
                s2_sl = jax.device_put(s2_sl, spec_dm)
            return make_slice(s1_sl, s2_sl)

        return halving_dispatch(run, plan.n_groups, min_size=ndm,
                                what="specfuse.chunk")

    def _concat(parts):
        outs = [r for _, _, r in parts]
        if len(outs) == 1:
            return outs[0]
        if isinstance(outs[0], tuple):
            return tuple(jnp.concatenate([o[j] for o in outs])
                         for j in range(len(outs[0])))
        return jnp.concatenate(outs)

    attrs = dict(n_trials=n_real, n_samples=int(T),
                 regime="decimated" if decimated else "stitched")
    if dev_ids is not None:
        attrs["dev"] = dev_ids
    with telemetry.span("specfuse_slice", aggregate=False, **attrs):
        if decimated:
            stride, dlen = n_fft // T, F
            sharded_fn = (None if mesh is None else
                          _make_sharded_spectra_chunk(
                              mesh, plan.nsub, n_fft, stride, dlen, T))
            _pos, block = next(iter(_downsampled_blocks(
                src, factor, payload, plan.min_overlap)))
            L = int(block.shape[1])
            if L < need:
                block = jnp.pad(block, ((0, 0), (0, need - L)))
            chunk_attrs = {} if dev_ids is None else {"dev": dev_ids}
            with telemetry.span("specfuse_spectra", **chunk_attrs):
                raw = _concat(group_dispatch(
                    lambda: (sharded_fn(block, s1b, s2b)
                             if sharded_fn is not None else
                             sweep_chunk_spectra(block, s1b, s2b,
                                                 plan.nsub, n_fft, stride,
                                                 dlen, T)),
                    lambda a, b: (sharded_fn(block, a, b)
                                  if sharded_fn is not None else
                                  sweep_chunk_spectra(block, a, b,
                                                      plan.nsub, n_fft,
                                                      stride, dlen, T))))
            telemetry.counter("specfuse.fft_pairs_elided", n_real)
            if dev_ids is not None:
                for d in dev_ids:
                    telemetry.counter(
                        f"device{d}.specfuse.fft_pairs_elided", n_real)
            faultinject.trip("specfuse.after_stitch")  # stage kill-point
            with telemetry.span("specfuse_prep", **chunk_attrs):
                re_p, im_p = prep_spectra_batch(spectra=raw,
                                                schedule=schedule,
                                                mesh=mesh)
            regime = "decimated"
        else:
            sharded_fn = (None if mesh is None else
                          make_sharded_series_chunk(
                              mesh, plan.nsub, payload, plan.max_shift2,
                              engine_r))
            buf = jnp.zeros((Dpad, T), dtype=jnp.float32)
            if mesh is not None:
                buf = jax.device_put(buf, NamedSharding(mesh, P("dm")))
            for pos, block in _downsampled_blocks(src, factor, payload,
                                                  plan.min_overlap):
                L = int(block.shape[1])
                if L < need:  # tail: zero-pad to the static chunk shape
                    block = jnp.pad(block, ((0, 0), (0, need - L)))
                valid = min(payload, T - pos)
                chunk_attrs = dict(valid=int(valid))
                if dev_ids is not None:
                    chunk_attrs["dev"] = dev_ids
                with telemetry.span("specfuse_stitch", **chunk_attrs):
                    series = _concat(group_dispatch(
                        lambda: (sharded_fn(block, s1b, s2b)
                                 if sharded_fn is not None else
                                 dedisperse_series_chunk(
                                     block, s1b, s2b, plan.nsub, payload,
                                     plan.max_shift2, engine_r)),
                        lambda a, b: (sharded_fn(block, a, b)
                                      if sharded_fn is not None else
                                      dedisperse_series_chunk(
                                          block, a, b, plan.nsub, payload,
                                          plan.max_shift2, engine_r))))
                    # the valid window partitions the time axis exactly
                    # (overlap-save): the scatter REPLACES the old D2H
                    # pull of the same f32 values, so the resident
                    # series is bit-identical to the streamed host buf
                    buf = buf.at[:, pos:pos + valid].set(
                        series[:, :valid].astype(jnp.float32))
                telemetry.counter("specfuse.chunks_stitched")
                if dev_ids is not None:
                    for d in dev_ids:
                        telemetry.counter(
                            f"device{d}.specfuse.chunks_stitched")
                if verbose:
                    print(f"# specfuse chunk at {pos}: {valid} samples "
                          f"x {n_real} DMs stitched on device")
            faultinject.trip("specfuse.after_stitch")  # stage kill-point
            prep_attrs = {} if dev_ids is None else {"dev": dev_ids}
            with telemetry.span("specfuse_prep", **prep_attrs):
                def prep_run(lo, hi):
                    return prep_spectra_batch(buf[lo:hi],
                                              schedule=schedule,
                                              mesh=mesh)

                re_p, im_p = _concat(halving_dispatch(
                    prep_run, Dpad, min_size=ndm, what="specfuse.prep"))
            regime = "stitched"
        # the series bytes the streamed path would have moved over the
        # host link (per-chunk D2H pull + prep H2D re-ship), kept on
        # device — the "bytes kept on device" acceptance counter
        telemetry.counter("specfuse.bytes_on_device", 8 * n_real * T)
    return dict(re=re_p, im=im_p, n_real=n_real, T=T, dt_eff=dt_eff,
                regime=regime)
