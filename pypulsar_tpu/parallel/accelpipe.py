"""Pipelined sweep->accel handoff: dedispersed series stream straight
into the batched acceleration search, no .dat round trip.

The round-5 configs[4] measurement (BENCHNOTES.md) put 745.9 s of the
4364.8 s chain into writing per-DM .dat files to disk only to re-read
them for the accel stage, and the per-spectrum A/B showed 6.4 of
8.7 s/spectrum of *serial host time* even with ``--device-prep`` — the
classic pipeline-bubble pair the GPU dedispersion literature solves by
streaming transfers behind compute (Barsdell et al. 2012; Sclocco et
al. 2016), and that the sweep engine already solved with its ship-ahead
pattern (parallel/staged.py, io_overlap_frac = 1.0). This module gives
the accel stage the same treatment:

- :func:`sweep_accel_stream` streams the observation ONCE through the
  sweep's own two-stage chunk kernel (staged.iter_dedispersed_chunks —
  the values are bit-identical to what the .dat writer puts on disk,
  parity-tested), accumulates every trial's series in a host buffer,
  and hands batches to ``prep_spectra_batch`` + ``accel_search_batch``.
  ``--write-dats`` survives as an optional tee of the identical bytes.
- The host half of each accel batch (row gather + device prep dispatch)
  runs one batch AHEAD of the device search on the shared prefetch core
  (parallel/prefetch.py): batch N+1 preps while batch N searches, with
  the queue fill on the ``accel.pipe.pending_depth`` gauge so tlmsum
  shows the overlap that was actually achieved.
- Host RAM for the series buffer is budgeted
  (``PYPULSAR_TPU_ACCEL_STREAM_RAM``, default 12 GB — the same bytes the
  .dat files used to occupy on disk, now never written): a trial set too
  large for the budget is processed in DM slices, each slice one more
  pass over the raw file. The log says when that trade is being made.

Restartability mirrors the batched CLI: ``skip_existing`` skips trials
whose .cand already exists (the .cand is written atomically last, so a
killed run resumes without re-searching finished trials and the final
candidate tables are bit-identical to an uninterrupted run), and a
failed batched dispatch degrades to per-spectrum serial host-prep
searches instead of failing its whole batch.
"""

from __future__ import annotations

import hashlib
import os
from typing import Optional, Tuple

import numpy as np

from pypulsar_tpu.compile import bucket_floor, bucket_rows, note_bucket_pad
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.parallel import broker as broker_mod
from pypulsar_tpu.resilience import faultinject, health
from pypulsar_tpu.tune import knobs
from pypulsar_tpu.resilience.journal import RunJournal, candfile_complete
from pypulsar_tpu.resilience.retry import halving_dispatch

__all__ = [
    "accel_out_names",
    "stream_series",
    "sweep_accel_stream",
    "write_candfiles",
]


def _broker_concat_rows(payloads):
    """Fuse same-key accel batch payloads on the spectrum axis — either
    device-resident (re, im) plane tuples (a device concat, no host
    round trip) or host-prepped complex arrays. Per-spectrum results
    are independent (the halving contract), so the fused search demuxes
    bit-identically."""
    if isinstance(payloads[0], tuple):
        import jax.numpy as jnp

        return tuple(jnp.concatenate([pl[i] for pl in payloads])
                     for i in range(len(payloads[0])))
    return np.concatenate([np.asarray(pl) for pl in payloads])


def accel_out_names(outbase: str, zmax: float, wmax: float = 0.0
                    ) -> Tuple[str, str]:
    """(candfn, txtfn) for one spectrum under the PRESTO naming scheme —
    the ONE definition shared by cli/accelsearch and the streamed
    handoff, so the two paths' artifacts can never diverge in name."""
    ztag = int(round(zmax))
    if wmax > 0:
        ztag = f"{ztag}_JERK_{int(round(wmax))}"
    return f"{outbase}_ACCEL_{ztag}.cand", f"{outbase}_ACCEL_{ztag}.txtcand"


def write_candfiles(candfn: str, txtfn: str, cands, T: float,
                    max_cands: int = 200) -> str:
    """Write one spectrum's .txtcand + .cand pair (shared by the .dat CLI
    and the streamed handoff). Both writes are atomic (tmp + os.replace)
    and ordered .txtcand first, .cand last: the .cand's existence is the
    restart completeness marker, and resilience.candfile_complete uses
    the pair's header/row-count agreement to tell a legitimately empty
    result from a killed run's debris."""
    from pypulsar_tpu.io.prestocand import write_rzwcands
    from pypulsar_tpu.resilience.dataguard import finite_cands
    from pypulsar_tpu.resilience.journal import atomic_write_text

    # finite gate BEFORE the cap: a NaN-sigma row must not occupy one of
    # the max_cands slots, and no non-finite value may reach the tables
    cands = finite_cands(cands, T, what=os.path.basename(candfn))
    cands = cands[:max_cands]
    lines = ["# cand   sigma    power  numharm          r          z"
             "        freq(Hz)       fdot(Hz/s)      period(s)\n"]
    for i, c in enumerate(cands):
        freq = c.freq(T)
        lines.append(
            f"{i + 1:6d} {c.sigma:7.2f} {c.power:8.2f} {c.numharm:8d} "
            f"{c.r:10.2f} {c.z:10.2f} {freq:15.8f} "
            f"{c.fdot(T):16.6e} {1.0 / freq:14.10f}\n"
        )
    atomic_write_text(txtfn, "".join(lines))
    write_rzwcands(candfn, [c.as_fourierprops() for c in cands])
    return candfn


def stream_series(
    reader,
    dms,
    downsamp: int = 1,
    nsub: int = 64,
    group_size: int = 32,
    rfimask=None,
    engine: str = "auto",
    chunk_payload: Optional[int] = None,
    dat_outbase: Optional[str] = None,
    mesh=None,
    verbose: bool = False,
) -> Tuple[np.ndarray, float]:
    """One pass over ``reader``: every DM trial's full dedispersed series
    as a host ``[D, T_ds]`` float32 buffer, plus the effective sampling
    time. ``dat_outbase`` tees the IDENTICAL bytes to ``.dat``/``.inf``
    files as they stream (the optional --write-dats path). ``mesh``
    shards the trial groups of each chunk over its 'dm' devices
    (staged.iter_dedispersed_chunks) — rows stay bit-identical, so the
    tee and every downstream artifact are unchanged by the chip count."""
    from pypulsar_tpu.parallel.staged import (
        _ReaderSource,
        dat_append_rows,
        dat_finalize_paths,
        dat_truncate_paths,
        dats_geometry,
        iter_dedispersed_chunks,
        write_dat_infs,
    )

    factor = max(1, int(downsamp))
    dms = np.asarray(dms, dtype=np.float64)
    dt_eff = _ReaderSource(reader).tsamp * factor
    _plan, _payload, T = dats_geometry(reader, dms, downsamp=factor,
                                       nsub=nsub, group_size=group_size,
                                       chunk_payload=chunk_payload)
    buf = np.empty((len(dms), T), dtype=np.float32)
    paths = None
    if dat_outbase is not None:
        # the tee shares write_dats_streamed's writer helpers, so the
        # two paths' .dat byte streams have ONE definition
        paths = dat_truncate_paths(dat_outbase, dms)
    attrs = dict(n_trials=len(dms), n_samples=int(T))
    if mesh is not None:
        attrs["dev"] = [int(getattr(d, "id", -1))
                        for d in mesh.devices.flat]
    with telemetry.span("accel_stream_sweep", aggregate=False, **attrs):
        for pos, rows in iter_dedispersed_chunks(
                reader, dms, downsamp=factor, nsub=nsub,
                group_size=group_size, rfimask=rfimask, engine=engine,
                chunk_payload=chunk_payload, mesh=mesh, verbose=verbose):
            buf[:, pos:pos + rows.shape[1]] = rows
            if paths is not None:
                dat_append_rows(paths, rows)
    if dat_outbase is not None:
        dat_finalize_paths(paths)
        write_dat_infs(dat_outbase, reader, dms, T, dt_eff)
    return buf, dt_eff


def _host_prep_rows(rows: np.ndarray, schedule) -> np.ndarray:
    """The CLI host-prep path (f64-capable np.fft.rfft + device deredden)
    applied to in-RAM series rows — byte-for-byte what prepare_one would
    compute from the corresponding .dat file."""
    from pypulsar_tpu.fourier.kernels import deredden

    return np.stack([
        np.asarray(deredden(np.fft.rfft(r).astype(np.complex64),
                            schedule=schedule))
        for r in rows])


def _run_fingerprint(dms, config, outbase: str, downsamp: int, nsub: int,
                     group_size: int, max_cands: int, device_prep: bool,
                     rfimask, spectral: bool = False) -> str:
    """Journal fingerprint of everything that determines this handoff's
    artifacts — ``max_cands`` (caps the .cand contents), ``device_prep``
    (host/device candidates match only within tolerance, never
    bit-identically), ``spectral`` (the fused path's decimated regime
    likewise matches only within tolerance) and the applied rfimask (a
    different zap table is a different series). Resuming under
    different parameters must start over, exactly the SweepCheckpoint
    contract."""
    from pypulsar_tpu.parallel.staged import _mask_tag

    h = hashlib.sha256()
    h.update(np.asarray(dms, dtype=np.float64).tobytes())
    h.update(np.float64([config.zmax, config.dz, config.sigma_min,
                         config.wmax, config.dw]).tobytes())
    h.update(np.int64([config.numharm, downsamp, nsub,
                       group_size, max_cands,
                       int(bool(device_prep)),
                       int(bool(spectral))]).tobytes())
    h.update(outbase.encode())
    h.update(_mask_tag(rfimask).encode())
    return h.hexdigest()


def sweep_accel_stream(
    reader,
    dms,
    config,
    outbase: str,
    batch: Optional[int] = None,
    downsamp: int = 1,
    nsub: int = 64,
    group_size: int = 32,
    rfimask=None,
    engine: str = "auto",
    chunk_payload: Optional[int] = None,
    write_dats: bool = False,
    max_cands: int = 200,
    device_prep: bool = True,
    skip_existing: bool = False,
    prefetch_depth: int = 1,
    journal_path: Optional[str] = None,
    journal: Optional[RunJournal] = None,
    mesh=None,
    spectral: bool = False,
    verbose: bool = False,
) -> dict:
    """Dedisperse ``dms`` over ``reader`` and accel-search every trial,
    writing ``{outbase}_DM{dm:.2f}_ACCEL_{zmax}.cand/.txtcand`` exactly
    as ``cli accelsearch`` would for the corresponding .dat files — but
    with the series handed over in RAM (see module docstring). Returns a
    summary dict (searched/skipped counts, serial fallbacks, paths).

    Resume: ``skip_existing`` skips trials whose .cand/.txtcand pair
    VALIDATES (resilience.candfile_complete — a zero-byte .cand from a
    killed run is redone, not trusted); ``journal_path`` additionally
    keeps a fingerprinted work-unit journal (resilience.RunJournal) whose
    entries are size/sha256-checked on load, so a truncated or swapped
    artifact is also redone. A batched search that hits device
    RESOURCE_EXHAUSTED auto-halves with bounded backoff
    (resilience.retry.halving_dispatch) before the serial fallback is
    even considered.

    Multi-chip: ``mesh`` (a 1-D 'dm' Mesh, e.g. parallel.mesh.gang_mesh)
    makes ONE observation span every mesh device end to end — the sweep
    side shards each chunk's trial groups (sharded
    iter_dedispersed_chunks), the prep side shards the batch rows
    (prep_spectra_batch(mesh=...)), and the search side shard_maps the
    spectrum axis (accel_search_batch over the SAME devices). Batches
    pad to a device multiple by replicating the last row (padding
    results drop deterministically before the writers), the per-batch
    HBM budget scales by the device count (each chip holds only its
    shard), and the .cand/.txtcand writers consume per-device results
    in trial order — so artifacts are byte-identical to the 1-device
    run, which the multi-chip parity tests and the BENCH_r09 record
    assert. NOTE: ``mesh`` is a placement choice, not science — it is
    deliberately absent from the journal fingerprint, so a gang-leased
    resume can pick up a 1-chip run's journal and vice versa.

    ``spectral`` routes the handoff through the FUSED path
    (parallel/specfuse.py): per DM slice, every trial's prepped T-point
    spectrum is built device-resident — the series never crosses the
    host link and prep collapses to one dispatch per slice, with
    candidates BIT-identical to this path's device-prep output
    (stitched regime, the default); ``PYPULSAR_TPU_SPECFUSE_MODE=
    decimate`` opts eligible geometries into the zero-transforms-per-
    trial regime (circular boundary semantics — specfuse docstring).
    Requires ``device_prep`` (the fused spectra ARE the device prep)
    and excludes ``write_dats`` (the tee would resurrect the time
    series the fusion exists to skip; use the streamed path when .dats
    are wanted)."""
    from pypulsar_tpu.fourier.accelsearch import (
        accel_search,
        accel_search_batch,
    )
    from pypulsar_tpu.fourier.kernels import (
        deredden_schedule,
        prep_spectra_batch,
    )

    if spectral and write_dats:
        raise ValueError("spectral fusion has no time series to tee: "
                         "--write-dats needs the streamed (non-spectral) "
                         "handoff")
    if spectral and not device_prep:
        raise ValueError("spectral fusion IS device prep: host prep "
                         "(device_prep=False) contradicts spectral=True")
    if batch is None:
        # the tuned-default path (round 17): the old hand-pinned 32
        # now lives in the knob registry, where the geometry-keyed
        # tuning cache can move it; an explicit batch= / CLI flag wins
        batch = max(1, knobs.env_int("PYPULSAR_TPU_ACCEL_BATCH"))
    dms = np.asarray(dms, dtype=np.float64)
    ndm = 1 if mesh is None else int(mesh.shape["dm"])
    mesh_devs = (tuple(mesh.devices.flat) if mesh is not None else None)
    dev_ids = ([int(getattr(d, "id", -1)) for d in mesh_devs]
               if mesh_devs else None)
    D = len(dms)
    bases = [f"{outbase}_DM{dm:.2f}" for dm in dms]
    names = [accel_out_names(b, config.zmax, config.wmax) for b in bases]
    units = [f"cand:DM{dm:.2f}" for dm in dms]
    own_journal = journal is None and bool(journal_path)
    if own_journal:
        journal = RunJournal(journal_path, _run_fingerprint(
            dms, config, outbase, downsamp, nsub, group_size, max_cands,
            device_prep, rfimask, spectral), tool="sweep-accel")
    journal_done: set = (journal.completed() if journal is not None
                         else set())

    def trial_done(i: int) -> bool:
        if journal is not None and units[i] in journal_done:
            return True  # journal entries are already disk-validated
        return skip_existing and candfile_complete(names[i][0],
                                                   names[i][1])

    todo = [i for i in range(D) if not trial_done(i)]
    n_skipped = D - len(todo)
    if n_skipped and verbose:
        print(f"# {n_skipped}/{D} trials already have validated .cands, "
              f"skipping")
    if not todo and not write_dats:
        if own_journal:
            journal.close()
        return {"n_searched": 0, "n_skipped": n_skipped, "n_failed": 0,
                "serial_fallbacks": 0,
                "cand_paths": [n[0] for n in names]}

    # host-RAM budget for the series buffer: past it, the trial set is
    # processed in DM slices of one extra raw-file pass each (wire/IO
    # traded for RAM; the .dat path paid the same bytes to disk instead)
    from pypulsar_tpu.parallel.staged import (
        _ReaderSource,
        dats_geometry,
        write_dat_infs,
    )

    if group_size <= 0:
        # resolve the auto group size ONCE over the FULL grid: the .dat
        # round trip resolves it that way, and a RAM-sliced run must not
        # let a slice's spacing pick a different (series-changing) group
        from pypulsar_tpu.parallel.sweep import choose_group_size

        src0 = _ReaderSource(reader)
        group_size = choose_group_size(dms, src0.frequencies,
                                       src0.tsamp * max(1, downsamp),
                                       nsub)
    _plan, _payload, T = dats_geometry(reader, dms, downsamp=downsamp,
                                       nsub=nsub, group_size=group_size,
                                       chunk_payload=chunk_payload)
    # .inf sidecars are written EVEN without the .dat payloads: cli/sift
    # and the plotting tools resolve each trial's DM and T from
    # {base}.inf, and the sidecars are KBs against the 745.9 s of payload
    # IO the handoff exists to kill (the tee rewrites them, harmlessly)
    write_dat_infs(outbase, reader, dms, T,
                   _ReaderSource(reader).tsamp * max(1, downsamp))
    if spectral:
        # fused slices live on DEVICE (series buffer + prepped planes),
        # so the slice budget is HBM, not host RAM
        from pypulsar_tpu.parallel.specfuse import spectral_trial_bytes

        budget = int(knobs.env_float("PYPULSAR_TPU_SPECFUSE_HBM"))
        slice_dms = max(batch,
                        int(budget // max(spectral_trial_bytes(T), 1)))
    else:
        budget = int(knobs.env_float("PYPULSAR_TPU_ACCEL_STREAM_RAM"))
        slice_dms = max(batch, int(budget // (4 * max(T, 1))))
    # slices MUST align to stage-1 group boundaries: make_sweep_plan
    # regroups each slice's consecutive DMs from its own start, and a
    # misaligned slice shifts every later trial into a group with a
    # different mean DM — silently different series, broken .dat parity
    # (caught by review: 4/8 tables diverged at slice=6, group=4)
    slice_dms = max(group_size, (slice_dms // group_size) * group_size)
    if slice_dms < D and verbose:
        print(f"# series buffer {4 * D * T / 1e9:.1f} GB exceeds the "
              f"{budget / 1e9:.1f} GB budget; streaming in "
              f"{-(-D // slice_dms)} DM slices of {slice_dms} "
              f"(one raw-file pass each)")

    # device-prep residency cap (the same knob the batched CLI uses):
    # series + planes + rfft workspace is ~24 bytes/sample per spectrum.
    # Unlike the sequential CLI, the pipeline holds several prepped
    # batches in HBM at once — the one searching, the queued ones, and
    # the one the parked worker holds (prefetch_depth + 2 in flight) —
    # so each batch gets only its share of the budget. The budget is PER
    # DEVICE: a DM-sharded batch splits across the mesh, so k chips
    # admit k x the spectra per dispatch (the per-shard slice of each
    # chip stays inside its own HBM share)
    hbm = int(knobs.env_float("PYPULSAR_TPU_ACCEL_HBM"))
    inflight = prefetch_depth + 2 if prefetch_depth > 0 else 1
    # spectral: prep already happened (the slice's resident planes), so
    # a batch holds only its gathered rows — no per-batch prep cap
    unit = (min(batch, max(1, ndm * ((hbm // inflight) // (24 * T))))
            if device_prep and not spectral else batch)
    # the batch cap lands on the compile plane's bucket ladder (floor:
    # it bounds HBM) so full dispatch batches reuse one executable
    # across nearby geometries; tails pad UP to the ladder in prep()
    unit = bucket_floor(unit)
    if ndm > 1:
        # dispatch batches stay whole device multiples; short tails pad
        # by replicating the last row (dropped after the search)
        unit = max(ndm, (unit // ndm) * ndm)
    schedule = deredden_schedule(T // 2 + 1)
    n_searched = 0
    n_failed = 0
    fallbacks = 0

    # round 24: with the batch broker on, every batched search below
    # SUBMITS to the fleet coalescing plane instead of dispatching
    # directly — same-key batches from concurrent observations fuse
    # into one device dispatch (parallel/broker.py, byte-identical
    # demux). PYPULSAR_TPU_BROKER=0 leaves bk None and every dispatch
    # takes exactly the pre-round-24 path.
    bk = broker_mod.get_broker() if broker_mod.enabled() else None
    bk_party = ("accel", broker_mod.device_scope(dev_ids))
    bk_tag = os.path.basename(outbase) or outbase
    # fused batches stop growing at one full-HBM dispatch (~24 B/sample
    # per prepped spectrum); accel_search_batch still self-slices, so
    # the cap bounds host concat cost, not correctness
    bk_budget = max(int(unit),
                    ndm * max(1, int(hbm) // (24 * max(int(T), 1))))

    for d0 in range(0, D, slice_dms):
        dsl = slice(d0, min(d0 + slice_dms, D))
        sl_todo = [i for i in todo if dsl.start <= i < dsl.stop]
        if not sl_todo and not write_dats:
            continue
        series = re_pl = im_pl = None
        if spectral:
            from pypulsar_tpu.parallel.specfuse import fused_spectra_slice

            fused = fused_spectra_slice(
                reader, dms[dsl], schedule=schedule, downsamp=downsamp,
                nsub=nsub, group_size=group_size, rfimask=rfimask,
                engine=engine, chunk_payload=chunk_payload, mesh=mesh,
                verbose=verbose)
            re_pl, im_pl, dt_eff = fused["re"], fused["im"], fused["dt_eff"]
        else:
            series, dt_eff = stream_series(
                reader, dms[dsl], downsamp=downsamp, nsub=nsub,
                group_size=group_size, rfimask=rfimask, engine=engine,
                chunk_payload=chunk_payload,
                dat_outbase=outbase if write_dats else None,
                mesh=mesh, verbose=verbose)
        faultinject.trip("accel.after_stream")  # kill-point (journal test)
        T_sec = T * dt_eff

        def groups():
            for g0 in range(0, len(sl_todo), unit):
                yield sl_todo[g0:g0 + unit]

        def prep(idxs):
            """Worker-side half of the pipeline: gather the batch rows
            and dispatch the device prep while the PREVIOUS batch is
            still searching (its result a device-resident plane tuple
            the search consumes without a host round trip). Exceptions
            (a failed device dispatch) travel as values — raised on the
            worker they would abort the whole run instead of degrading
            this one batch to the serial fallback. Under a mesh the
            rows pad to a whole device multiple by REPLICATING the last
            row — replication (not zeros) keeps every shard's numerics
            on real data shapes, and the padded results drop before the
            writers, so padding cannot change any artifact byte.

            Spectral mode: the slice's spectra are ALREADY prepped and
            device-resident — the worker only gathers the batch's rows
            of the planes (a device gather, never a host round trip),
            padding by the same last-row replication."""
            try:
                prep_attrs = {"batch": len(idxs)}
                if dev_ids is not None:
                    prep_attrs["dev"] = dev_ids
                if spectral:
                    import jax.numpy as jnp

                    loc = np.asarray([i - d0 for i in idxs],
                                     dtype=np.int32)
                    with telemetry.span("accel_prep_fused", **prep_attrs):
                        rre, rim = re_pl[loc], im_pl[loc]
                        pad = (bucket_rows(rre.shape[0], multiple=ndm)
                               - rre.shape[0])
                        if pad:
                            note_bucket_pad(rre.shape[0],
                                            rre.shape[0] + pad)
                            rre = jnp.concatenate(
                                [rre, jnp.repeat(rre[-1:], pad, axis=0)])
                            rim = jnp.concatenate(
                                [rim, jnp.repeat(rim[-1:], pad, axis=0)])
                        return idxs, (rre, rim), None
                rows = np.ascontiguousarray(series[[i - d0 for i in idxs]])
                pad = bucket_rows(rows.shape[0], multiple=ndm) - rows.shape[0]
                if pad:
                    note_bucket_pad(rows.shape[0], rows.shape[0] + pad)
                    rows = np.concatenate(
                        [rows, np.repeat(rows[-1:], pad, axis=0)])
                with telemetry.span("accel_prep_device" if device_prep
                                    else "accel_prep_host",
                                    **prep_attrs):
                    payload = (prep_spectra_batch(rows, schedule,
                                                  mesh=mesh)
                               if device_prep
                               else _host_prep_rows(rows, schedule))
            except Exception as e:  # noqa: BLE001 - consumer decides
                return idxs, None, e
            return idxs, payload, None

        if prefetch_depth > 0:
            from pypulsar_tpu.parallel.prefetch import prefetch

            source = prefetch(groups(), depth=prefetch_depth,
                              name="accel.pipe", transform=prep,
                              retries=2)
        else:  # --accel-prefetch 0: inline, single-threaded debugging
            source = (prep(g) for g in groups())
        def search_halved(payload, n):
            """The batched dispatch under the OOM-adaptive policy: a
            RESOURCE_EXHAUSTED halves the batch (per-spectrum results
            are independent, so the halves concatenate bit-identically);
            any other failure — or an OOM that persists at batch 1 —
            propagates to the serial-fallback handler below. ``n`` is
            the PADDED batch under a mesh (a whole device multiple;
            min_size keeps halves on it), and the caller slices the
            result back to the real trials."""
            def run(lo, hi):
                faultinject.trip("accel.batch_dispatch")
                part = (tuple(p[lo:hi] for p in payload)
                        if isinstance(payload, tuple) else payload[lo:hi])
                return accel_search_batch(part, T_sec, config,
                                          mesh_devices=ndm if ndm > 1
                                          else 0, devices=mesh_devs)

            parts = halving_dispatch(run, n, min_size=ndm,
                                     what="accel.batch")
            return [c for _, _, cands in parts for c in cands]

        def _bk_key(pl):
            """Exact coalescing key for one submitted batch: per-row
            plane geometry + the science config + (inside dispatch_key)
            device scope and the accel knob digest. Two observations
            fuse only when the fused rows would hit the same compiled
            executable family as their solo dispatches."""
            if isinstance(pl, tuple):
                geom = ("planes",) + tuple(
                    (tuple(int(s) for s in p.shape[1:]), str(p.dtype))
                    for p in pl)
            else:
                arr = np.asarray(pl)
                geom = ("hostfft", tuple(int(s) for s in arr.shape[1:]),
                        str(arr.dtype))
            return broker_mod.dispatch_key(
                "accel",
                (int(T), repr(float(T_sec)), int(ndm)) + geom,
                (repr(config),), dev_ids)

        def _bk_dispatch(pl, n):
            """The broker's fused (or solo) dispatch: re-bucket the
            fused row count (members are bucket-padded individually, so
            a solo batch is already on the ladder and pads zero rows —
            byte- and dispatch-identical to the un-brokered call) and
            run the same OOM-halving search the direct path runs."""
            m = bucket_rows(n, multiple=ndm)
            if m > n:
                note_bucket_pad(n, m)
                if isinstance(pl, tuple):
                    import jax.numpy as jnp

                    pl = tuple(jnp.concatenate(
                        [p, jnp.repeat(p[-1:], m - n, axis=0)])
                        for p in pl)
                else:
                    pl = np.concatenate(
                        [pl, np.repeat(pl[-1:], m - n, axis=0)])
            return search_halved(pl, m)[:n]

        for idxs, payload, prep_err in source:
            try:
                if prep_err is not None:
                    raise prep_err
                n_padded = (len(payload[0])
                            if isinstance(payload, tuple)
                            else len(payload))
                search_attrs = {"batch": len(idxs)}
                if dev_ids is not None:
                    search_attrs["dev"] = dev_ids
                with telemetry.span("accel_search", aggregate=False,
                                    **search_attrs):
                    # padded replicas (mesh batches round up to a device
                    # multiple) searched then DROPPED: zip(idxs, ...)
                    # below stops at the real trials
                    if bk is None:
                        all_cands = search_halved(payload, n_padded)
                    else:
                        all_cands = bk.submit(
                            _bk_key(payload), bk_party, payload,
                            n_padded, tag=bk_tag,
                            concat=_broker_concat_rows,
                            dispatch=_bk_dispatch,
                            demux=lambda out, lo, hi: out[lo:hi],
                            budget_rows=bk_budget)
            except Exception as e:  # noqa: BLE001 - poison-spectrum
                if health.no_degrade(e):
                    # watchdog interrupts, chip-indicting and injected
                    # faults escalate to the stage retry (lease
                    # reclaim / device strike) instead of degrading
                    raise
                # contract of the batched CLI: degrade to per-spectrum
                # serial host-prep searches, never fail the whole batch
                fallbacks += 1
                telemetry.counter("accel.serial_fallbacks")
                telemetry.event("accel.batch_serial_fallback",
                                n=len(idxs), kind="stream",
                                error=type(e).__name__)
                print(f"# streamed batch of {len(idxs)} failed "
                      f"({type(e).__name__}: {e}); retrying serially")
                all_cands = []
                # still recorded as accel_search time: the bench derives
                # cells/s from this span's total, and an unspanned
                # fallback would make a degraded run look faster
                with telemetry.span("accel_search", aggregate=False,
                                    batch=len(idxs), fallback=True):
                    for i in idxs:
                        # one poison spectrum fails ALONE (no .cand
                        # written, so a skip_existing restart retries
                        # it), never the rest of the run — the batched
                        # CLI's contract. Spectral mode falls back on
                        # the fused spectrum itself (pulled to host for
                        # the serial search): there is no time series
                        # to host-prep, and the fused spectrum is the
                        # run's prep provenance
                        try:
                            if spectral:
                                fft1 = (np.asarray(re_pl[i - d0])
                                        + 1j * np.asarray(im_pl[i - d0])
                                        ).astype(np.complex64)
                            else:
                                fft1 = _host_prep_rows(
                                    series[i - d0:i - d0 + 1],
                                    schedule)[0]
                            all_cands.append(accel_search(
                                fft1, T_sec, config))
                        except Exception as e1:  # noqa: BLE001
                            if health.no_degrade(e1):
                                raise  # see the batch handler above
                            all_cands.append(None)
                            n_failed += 1
                            print(f"# trial DM{dms[i]:.2f} FAILED "
                                  f"serially ({type(e1).__name__}: "
                                  f"{e1})")
            for i, cands in zip(idxs, all_cands):
                if cands is None:
                    continue
                faultinject.trip("accel.before_cand_write")  # kill-point
                with telemetry.span("accel_write"):
                    write_candfiles(names[i][0], names[i][1], cands,
                                    T_sec, max_cands)
                faultinject.trip("accel.after_cand_write")  # kill-point
                if journal is not None:
                    journal.done(units[i], [names[i][0], names[i][1]])
                    faultinject.trip("accel.after_journal")  # kill-point
                n_searched += 1
            telemetry.counter("accel.stream_batches")
            if dev_ids is not None:
                for d in dev_ids:
                    telemetry.counter(f"device{d}.accel.stream_batches")
            if verbose:
                print(f"# searched trials {idxs[0]}..{idxs[-1]} "
                      f"({n_searched}/{len(todo)})")
        # free the slice buffer (host series or device planes) before
        # the next pass
        del series, re_pl, im_pl

    if journal is not None:
        journal.note(event="accel_stream_done", n_searched=n_searched,
                     n_skipped=n_skipped, n_failed=n_failed)
        if own_journal:
            journal.close()
    return {"n_searched": n_searched, "n_skipped": n_skipped,
            "n_failed": n_failed, "serial_fallbacks": fallbacks,
            "cand_paths": [n[0] for n in names]}
