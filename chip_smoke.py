#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the survey chain starts on the chip.

One process, one device owner: generates a pointing from ``--seed`` with
``tools/make_synthetic_fil.py`` (in-process), runs the normal ``survey``
entry point over it (``pypulsar_tpu.cli.survey.main`` — the code
``python -m pypulsar_tpu.cli survey`` runs): RFI mask -> DM sweep +
acceleration search -> sift -> batched fold -> profile SNR, then checks

  (a) the injected pulsar comes out of the chain (sifted candidate, .pfd,
      _snr.json);
  (b) each device stage agrees with the plain NumPy twin already in the
      tree on a window of the same file at full width, outside any timing;
  (c) no fallback fired and the device path is the one that ran (telemetry
      counters, resolved engine, Pallas kernel in the lowered chunk program,
      arrays on ``tpu`` devices).

The LAST line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
everything else (sizes, cuts, walls, counters, findings) is on earlier
lines. Exit code is non-zero, and ``"ok"`` false, whenever a phase fails
or JAX offers no TPU.

``--chips 4`` runs ONLY the multi-chip path and what it is compared with:
one observation through ``survey --devices 4 --gang auto`` against the same
observation on ``--devices 1``; ``--fleet`` adds four short observations
fleet-parallel on the four chips.

Widths are never cut: 1024 channels, 64 us, 300 MHz at 1500 MHz, zmax 50,
numharm 8, the default 2^18 FFT chunk, engine ``auto``, default boxcar
widths. Depth may be: every cut from ``FULL`` is printed on a ``reduced:``
line with its reason.
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import json
import math
import os
import shutil
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# -- the repo's one supported telescope geometry, at full width (never cut)
NCHAN, TSAMP, FCH1, BW = 1024, 64e-6, 1500.0, 300.0
ZMAX, NUMHARM = 50, 8
# -- the injected pulsar (tools/make_synthetic_fil.py defaults)
INJ_DM, INJ_PERIOD, INJ_WIDTH = 70.0, 4096, 8

# -- depth: what the issue asks for, and what this run takes of it
FULL = {"nsamp": 1 << 22, "nbits": 4, "numdms": 64, "lodm": 0.0,
        "dmstep": 2.0}
SIZES = dict(FULL)
# the four-chip comparison may cut the pointing, never the widths
SIZES_CHIPS4 = dict(FULL, nsamp=1 << 20)
WHY_CHIPS4 = {"nsamp": "two whole chains (4-chip gang and its 1-chip "
                       "comparison) in one call at four times the "
                       "chip-minutes"}
FLEET_NSAMP = 1 << 19  # --fleet: four short observations

# -- stage-twin window and the contracts each stage is held to
WINDOW = 1 << 16        # samples of the same file, all 1024 channels
TWIN_NPART = 32
TOL = {
    "mask": 2e-3,    # tests/test_rfifind.py: maxpow rtol vs the f64 twin
    "sweep": 2e-6,   # README "Golden parity": relative SNR
    "prep": 2e-5,    # tests/test_accelsearch.py: of the largest amplitude
    "accel": 2e-5,   # summed matched power at the detected grid cell
    "fold": 1e-5,    # tests/test_fold_pipeline.py: of the largest profile
    "tables": 2e-6,  # 4-chip vs 1-chip sigma / power / SNR, relative
}
# folded-profile SNR the recovered pulsar must exceed at the FULL pointing
# length; scaled by sqrt(length) for a cut one (radiometer). Measured on the
# chip: 256 at 2^22, 83 at 2^20, 45-58 at 2^19 samples (which harmonic the
# search ranks first moves it); a noise-only fold scores a few.
SNR_FLOOR = 60.0

NATIVE_LIB = os.path.join(REPO, "pypulsar_tpu", "native", "libpsrcodec.so")

# fallbacks that would let the run pass without the device path: all zero
FALLBACK_COUNTERS = ("fold.numpy_fallbacks", "accel.serial_fallbacks",
                     "compile.aot_fallback", "resilience.oom_backoffs")
FALLBACK_EVENTS = ("resilience.oom_backoff", "survey.stage_retry",
                   "survey.device_evicted", "mesh.device_quarantined")


def say(msg: str) -> None:
    print(msg, flush=True)


class PhaseFailed(Exception):
    """A check did not hold; the message says which and by how much."""


# ---------------------------------------------------------------------------
# device gate


def device_record() -> dict:
    """The device as JAX reports it — the three keys of the last line."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(n: int) -> dict:
    """The device record, or PhaseFailed when JAX offers no TPU or fewer
    than ``n`` chips (no modulo wrap of leases onto chip 0)."""
    dev = device_record()
    if dev["platform"] != "tpu":
        raise PhaseFailed(f"JAX offers platform {dev['platform']!r}, not a "
                          f"TPU: nothing here is a device result")
    if dev["count"] < n:
        raise PhaseFailed(f"{n} chips asked for, JAX offers "
                          f"{dev['count']}")
    return dev


# ---------------------------------------------------------------------------
# phases


def print_banner() -> None:
    """JAX version and the compile cache in use, set up before the first
    compile so every program of the run lands in it."""
    import jax

    from pypulsar_tpu.compile import configure_persistent_cache

    say(f"jax {jax.__version__}; compile cache: "
        f"{configure_persistent_cache()} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")


def print_sizes(sizes: dict, why: dict) -> None:
    say(f"sizes: {NCHAN} channels, {TSAMP * 1e6:g} us, {BW:g} MHz at "
        f"{FCH1:g} MHz, {sizes['nbits']}-bit, {sizes['nsamp']} samples "
        f"({sizes['nsamp'] * TSAMP:.0f} s), {sizes['numdms']} DM trials "
        f"from {sizes['lodm']:g} step {sizes['dmstep']:g}, accel zmax "
        f"{ZMAX} numharm {NUMHARM}, chunk/engine/widths default")
    cuts = [k for k in FULL if sizes[k] != FULL[k]]
    if not cuts:
        say("reduced: none")
    for k in cuts:
        say(f"reduced: {k} {FULL[k]} -> {sizes[k]} "
            f"({why.get(k, 'no reason given')})")


def phase_native() -> bool:
    """Host codec: reported, not gated. The git-ignored .so is removed
    first (what `make clean` does), so what loads was built on this
    machine from the committed .cpp files."""
    if os.path.exists(NATIVE_LIB):
        os.remove(NATIVE_LIB)
    from pypulsar_tpu import native

    ok = bool(native.available())
    say(f"native: pypulsar_tpu.native.available() = {ok} "
        f"(built here from codec.cpp + prefetch.cpp: "
        f"{os.path.exists(NATIVE_LIB)})")
    return ok


def make_input(workdir: str, seed: int, sizes: dict, tag: str = "") -> str:
    """One pointing from the seed, through the repo's generator."""
    tools = os.path.join(REPO, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import make_synthetic_fil

    fn = os.path.join(
        workdir, f"psr{tag}_s{seed}_{NCHAN}x{sizes['nsamp']}_"
                 f"{sizes['nbits']}bit.fil")
    want = sizes["nsamp"] * NCHAN * sizes["nbits"] // 8
    if os.path.exists(fn) and os.path.getsize(fn) > want:
        say(f"input: reusing {fn}")
        return fn
    t0 = time.perf_counter()
    make_synthetic_fil.main([
        "--out", fn, "--nchan", str(NCHAN), "--tsamp", repr(TSAMP),
        "--duration", repr(sizes["nsamp"] * TSAMP), "--fch1", repr(FCH1),
        "--bw", repr(BW), "--dm", repr(INJ_DM),
        "--period-samples", str(INJ_PERIOD), "--width", str(INJ_WIDTH),
        "--nbits", str(sizes["nbits"]), "--seed", str(seed),
        # 16 MB blocks stay in cache: 2-3x the default's write rate
        "--blocks-per-write", "4"])
    say(f"input: {fn} ({os.path.getsize(fn) / 1e9:.2f} GB) generated in "
        f"{time.perf_counter() - t0:.1f}s")
    return fn


def survey_argv(fils, outdir: str, sizes: dict, devices: int = 1,
                gang: str = "auto") -> list:
    return [*fils, "-o", outdir, "--telemetry-dir",
            os.path.join(outdir, "tlm"), "--devices", str(devices),
            "--gang", gang, "--lodm", repr(sizes["lodm"]),
            "--dmstep", repr(sizes["dmstep"]),
            "--numdms", str(sizes["numdms"]),
            "--accel-zmax", str(ZMAX), "--accel-numharm", str(NUMHARM)]


def run_survey(fils, outdir: str, sizes: dict, devices: int = 1,
               gang: str = "auto") -> float:
    """The chain, through the entry point a user calls. Returns wall
    seconds; a non-zero exit is a failed phase."""
    from pypulsar_tpu.cli import survey

    shutil.rmtree(outdir, ignore_errors=True)
    argv = survey_argv(fils, outdir, sizes, devices, gang)
    say(f"survey: python -m pypulsar_tpu.cli survey {' '.join(argv)}")
    t0 = time.perf_counter()
    rc = survey.main(argv)
    wall = time.perf_counter() - t0
    if rc:
        raise PhaseFailed(f"survey exited {rc}")
    return wall


def telemetry_summary(outdir: str):
    """Fleet roll-up of the run's traces (what `tlmsum` prints)."""
    from pypulsar_tpu.obs.summarize import (
        combine_summaries,
        load_records,
        summarize,
    )

    paths = sorted(glob.glob(os.path.join(outdir, "tlm", "*.jsonl")))
    if not paths:
        raise PhaseFailed(f"no telemetry under {outdir}/tlm")
    # the fleet trace carries the process-wide counters; the per-
    # observation traces repeat its stage spans
    fleet = [p for p in paths if os.path.basename(p).startswith("fleet")]
    return combine_summaries([summarize(load_records(p)) for p in fleet])


def print_walls(label: str, wall: float, summ) -> None:
    from pypulsar_tpu.compile import persistent_cache_dir

    stages = {k.split(".")[-1]: round(v[0], 2)
              for k, v in summ.stages.items()
              if k.startswith("survey.stage.")}
    say(f"{label}: wall {wall:.1f}s, stages {json.dumps(stages)}")
    say(f"{label}: compile.ms {summ.counters.get('compile.ms', 0.0):.0f} "
        f"({summ.counters.get('compile.ms', 0.0) / 1e3:.1f}s in "
        f"{int(summ.counters.get('compile.cache_miss', 0))} plane "
        f"compiles, {int(summ.counters.get('compile.persistent_hit', 0))} "
        f"already in the persistent cache); cache directory "
        f"{persistent_cache_dir()}")


# -- (a) the injected pulsar comes out of the chain -------------------------


def _harmonic_of(period: float, nsamp: int, max_harm: int = 64):
    """(a, b) when the candidate's Fourier bin r = T/period sits within
    half a bin of a/b times the injected fundamental's bin (b <= 4,
    a/b <= max_harm), else None. The injected pulse is 8 samples of a
    4096-sample period: hundreds of harmonics carry equal power, so which
    group of eight the search ranks first is the noise's choice — but a
    harmonic it must be, to half a bin."""
    r = nsamp * TSAMP / period
    r0 = nsamp / INJ_PERIOD
    for b in (1, 2, 3, 4):
        a = int(round(r * b / r0))
        if a >= 1 and a <= max_harm * b and math.gcd(a, b) == 1 \
                and abs(r * b - a * r0) < 0.5:
            return a, b
    return None


def check_recovery(outdir: str, stem: str, sizes: dict) -> None:
    from pypulsar_tpu.io.accelcands import parse_candlist

    base = os.path.join(outdir, stem)
    cands = parse_candlist(base + ".accelcands")
    if not cands:
        raise PhaseFailed("sift kept no candidate")
    i_best = max(range(len(cands)), key=lambda i: float(cands[i].sigma))
    best = cands[i_best]
    inj_p = INJ_PERIOD * TSAMP
    ratio = _harmonic_of(float(best.period), sizes["nsamp"])
    say(f"recovered: best of {len(cands)} sifted candidates: DM "
        f"{float(best.dm):.2f}, P {float(best.period) * 1e3:.6f} ms, sigma "
        f"{float(best.sigma):.1f}, numharm {int(best.numharm)} (injected "
        f"DM {INJ_DM:g}, P {inj_p * 1e3:.6f} ms)")
    if abs(float(best.dm) - INJ_DM) > sizes["dmstep"]:
        raise PhaseFailed(f"best candidate DM {float(best.dm)} is more "
                          f"than one step from {INJ_DM}")
    if ratio is None:
        raise PhaseFailed(f"best candidate period {float(best.period)} s "
                          f"is no harmonic of {inj_p} s to half a bin")
    pfds = glob.glob(f"{base}_cand{i_best:04d}_*.pfd")
    if len(pfds) != 1:
        raise PhaseFailed(f"expected one .pfd for cand{i_best:04d}, found "
                          f"{pfds}")
    with open(base + "_snr.json") as f:
        rows = json.load(f)
    row = [r for r in rows
           if os.path.basename(r["pfd"]) == os.path.basename(pfds[0])]
    if not row or row[0].get("snr") is None:
        raise PhaseFailed(f"no SNR row for {pfds[0]} in {stem}_snr.json")
    snr = float(row[0]["snr"])
    floor = SNR_FLOOR * math.sqrt(sizes["nsamp"] / FULL["nsamp"])
    say(f"recovered: frequency = injected x {ratio[0]}/{ratio[1]} to half a "
        f"Fourier bin; "
        f"{os.path.basename(pfds[0])} folded SNR {snr:.1f} (floor "
        f"{floor:.1f}); {len(rows)} archives in {stem}_snr.json")
    if not snr > floor:
        raise PhaseFailed(f"folded SNR {snr} is under the floor {floor}")


# -- (b) each device stage against its NumPy twin ---------------------------


def read_window(fil: str):
    """(data[C, T] float32 high frequency first, freqs[C]) of the first
    WINDOW samples of ``fil`` at full width."""
    from pypulsar_tpu.io.filterbank import FilterbankFile

    fb = FilterbankFile(fil)
    try:
        spec = fb.get_spectra(0, min(WINDOW, fb.number_of_samples))
    finally:
        fb.close()
    return (np.asarray(spec.data, dtype=np.float32),
            np.asarray(spec.freqs, dtype=np.float64))


def _on_tpu(*arrays) -> bool:
    return all(d.platform == "tpu" for a in arrays for d in a.devices())


def _ref_sweep_snr(data, plan, T):
    """f64 twin of the sweep's detection statistic from ops/numpy_ref:
    the plan's exact integer shifts per channel, per-channel baseline
    removed first, end-of-data at baseline (the sweep_stream contract),
    SNR = (max window sum - w*mean) / (sqrt(w)*std) over the payload."""
    from pypulsar_tpu.ops import numpy_ref

    C = data.shape[0]
    per = C // plan.nsub
    W = max(plan.widths)
    pad = T + W + plan.max_total_shift
    padded = np.zeros((C, pad))
    padded[:, :T] = data - data.mean(axis=1, keepdims=True)
    snr = np.zeros((plan.n_trials, len(plan.widths)))
    series = np.zeros((plan.n_trials, T + W))
    for d in range(plan.n_trials):
        g, t = divmod(d, plan.group_size)
        bins = plan.stage1_bins[g] + np.repeat(plan.stage2_bins[g, t], per)
        ts = numpy_ref.dedispersed_timeseries(padded, bins)[:T + W]
        series[d] = ts
        mean = ts[:T].mean()
        std = ts[:T].std()
        cs = np.concatenate([[0.0], np.cumsum(ts)])
        for wi, w in enumerate(plan.widths):
            box = cs[w:w + T] - cs[:T]
            snr[d, wi] = (box.max() - w * mean) / (math.sqrt(w) * std)
    return snr, series


def _ref_summed_power(fft, r_top: float, z_top: float, H: int, cfg):
    """f64 twin of one (r, z) cell of the harmonic-summed matched power:
    each subharmonic b/H correlated directly (no FFT) against the in-tree
    analytic response (fourier/zresponse.z_response), windowed and unit-
    energy normalized exactly as the search's template banks are."""
    from pypulsar_tpu.fourier.zresponse import z_halfwidth, z_response

    # bins below zero are the conjugate reflection (a real input's bin -k
    # is conj(bin k)), as the search pads its spectrum
    front = max(z_halfwidth(z, cfg.min_halfwidth) for z in cfg.zs) + 1
    ext = np.concatenate([np.conj(fft[1:front + 1][::-1]), fft])
    total = 0.0
    for b in range(1, H + 1):
        rho = b / H
        half = int(math.floor(2.0 * rho * r_top + 0.5))
        r_int, frac = half // 2, 0.5 * (half % 2)
        z_b = z_top * rho
        hw = max(z_halfwidth(z * rho, cfg.min_halfwidth) for z in cfg.zs)
        k = np.arange(-hw, hw, dtype=np.float64)
        resp = z_response(z_b, k - frac + z_b / 2.0)
        row = np.conj(resp) / math.sqrt(np.sum(np.abs(resp) ** 2))
        lo = front + r_int - hw
        total += abs(np.sum(ext[lo:lo + 2 * hw] * row)) ** 2
    return total


def check_twins(fil: str, sizes: dict) -> dict:
    """Worst deviation per device stage on a window of ``fil`` at full
    width; every array a stage returns must live on a tpu device."""
    import jax.numpy as jnp

    from pypulsar_tpu.core.spectra import Spectra
    from pypulsar_tpu.fold.engine import (
        fold_parts_batch,
        fold_parts_batch_numpy,
        phase_to_bins,
    )
    from pypulsar_tpu.fourier import numpy_ref as fourier_ref
    from pypulsar_tpu.fourier.accelsearch import (
        AccelSearchConfig,
        accel_search_batch,
    )
    from pypulsar_tpu.fourier.kernels import prep_spectra_batch
    from pypulsar_tpu.ops.rfifind import block_stats, block_stats_numpy
    from pypulsar_tpu.parallel.sweep import (
        choose_group_size,
        make_sweep_plan,
        resolve_engine,
        sweep_chunk,
    )

    data, freqs = read_window(fil)
    T = data.shape[1]
    dev, placed = {}, {}

    # mask stage: per-interval block statistics
    pts = max(T // 8, 2)
    nint = T // pts
    out = block_stats(data[:, :nint * pts], pts)
    placed["mask"] = _on_tpu(*out)
    ref = block_stats_numpy(data[:, :nint * pts].astype(np.float64), pts)
    dev["mask"] = max(
        float(np.max(np.abs(np.asarray(o) - r) / np.maximum(np.abs(r), 1.0)))
        for o, r in zip(out, ref))

    # sweep stage: dedispersion + boxcar detection, four trials bracketing
    # the injection, the survey's own plan derivation
    dms = INJ_DM + sizes["dmstep"] * np.arange(-1, 3)
    group = choose_group_size(dms, freqs, TSAMP, 64)
    plan = make_sweep_plan(dms, freqs, TSAMP, nsub=64, group_size=group)
    W = max(plan.widths)
    need = T + W + plan.max_total_shift
    padded = np.zeros((data.shape[0], need), np.float32)
    padded[:, :T] = data - data.mean(axis=1, keepdims=True)
    out = sweep_chunk(jnp.asarray(padded), jnp.asarray(plan.stage1_bins),
                      jnp.asarray(plan.stage2_bins), plan.nsub, T + W,
                      plan.max_shift2, tuple(plan.widths), T,
                      engine=resolve_engine("auto"))
    placed["sweep"] = _on_tpu(*out)
    s, ss, mb, _ab = (np.asarray(x, dtype=np.float64) for x in out)
    mean = s / T
    std = np.sqrt(np.maximum(ss / T - mean ** 2, 0.0))
    ws = np.asarray(plan.widths, dtype=np.float64)
    snr_dev = (mb - ws * mean[:, None]) / (np.sqrt(ws) * std[:, None])
    snr_ref, series = _ref_sweep_snr(data.astype(np.float64), plan, T)
    dev["sweep"] = float(np.max(np.abs(snr_dev - snr_ref)
                                / np.maximum(np.abs(snr_ref), 1.0)))
    say(f"twin sweep: best window SNR {snr_ref.max():.2f} at DM "
        f"{dms[int(np.argmax(snr_ref.max(axis=1)))]:g}")

    # accel stage, part 1: spectrum prep (rfft + deredden) of the twin's
    # own dedispersed series at the injected DM and its neighbour
    rows = series[1:3, :T].astype(np.float32)
    re, im = prep_spectra_batch(rows)
    placed["prep"] = _on_tpu(re, im)
    spec_dev = np.asarray(re, np.float64) + 1j * np.asarray(im, np.float64)
    worst = 0.0
    for row, got in zip(rows.astype(np.float64), spec_dev):
        want = fourier_ref.deredden(np.fft.rfft(row - row.mean()))
        worst = max(worst, float(np.max(np.abs(got[1:] - want[1:]))
                                 / np.max(np.abs(want[1:]))))
    dev["prep"] = worst

    # accel stage, part 2: the search itself at the survey's depth; the
    # harmonic-summed power of its best candidate against the direct sum
    cfg = AccelSearchConfig(zmax=float(ZMAX), dz=2.0, numharm=NUMHARM,
                            sigma_min=2.0)
    cands = accel_search_batch((re, im), T * TSAMP, cfg)[0]
    if not cands:
        raise PhaseFailed("the window's accel search found nothing")
    best = max(cands, key=lambda c: c.sigma)
    H = best.numharm
    r_top = round(2.0 * best.r * H) / 2.0
    z_top = round(best.z * H / cfg.dz) * cfg.dz
    want = _ref_summed_power(spec_dev[0], r_top, z_top, H, cfg)
    dev["accel"] = abs(best.power - want) / want
    say(f"twin accel: best window candidate r {best.r:.3f} z {best.z:.3f} "
        f"numharm {H} power {best.power:.2f} (direct sum {want:.2f}), "
        f"sigma {best.sigma:.1f}")

    # fold stage: the injected period and its half, batched
    npart = TWIN_NPART
    t = np.arange(T, dtype=np.float64) * TSAMP
    periods = (INJ_PERIOD * TSAMP, INJ_PERIOD * TSAMP / 2.0)
    bin_idx = np.stack([phase_to_bins(t / p, 64) for p in periods])
    out = fold_parts_batch(rows[0], bin_idx.astype(np.int32), 64, npart)
    placed["fold"] = _on_tpu(*out)
    want, _counts = fold_parts_batch_numpy(rows[0], bin_idx, 64, npart)
    dev["fold"] = float(np.max(np.abs(np.asarray(out[0], np.float64) - want))
                        / np.max(np.abs(want)))

    bad = []
    for stage in ("mask", "sweep", "prep", "accel", "fold"):
        ok = dev[stage] <= TOL[stage] and placed.get(stage, True)
        say(f"twin {stage}: worst deviation {dev[stage]:.3e} (contract "
            f"{TOL[stage]:g}), outputs on tpu: {placed.get(stage, '-')}"
            f"{'' if ok else '  <-- FAIL'}")
        if not ok:
            bad.append(stage)
    if bad:
        raise PhaseFailed(f"stage twins out of contract or off the chip: "
                          f"{bad}")
    return dev


# -- (c) nothing fell back; the device path is the one that ran -------------


def check_device_path() -> None:
    """The engine `auto` resolves to, and the Pallas kernel in the lowered
    chunk program (`tpu_custom_call`)."""
    import jax
    import jax.numpy as jnp

    from pypulsar_tpu.parallel.sweep import (
        DEFAULT_WIDTHS,
        make_sweep_plan,
        resolve_engine,
        sweep_chunk,
    )

    engine = resolve_engine("auto")
    freqs = FCH1 - (BW / NCHAN) * np.arange(NCHAN)
    plan = make_sweep_plan(INJ_DM + np.arange(2.0), freqs, TSAMP, nsub=64,
                           group_size=2)
    T = 1 << 12
    W = max(DEFAULT_WIDTHS)
    need = T + W + plan.max_total_shift
    text = jax.jit(lambda d, s1, s2: sweep_chunk(
        d, s1, s2, plan.nsub, T + W, plan.max_shift2, DEFAULT_WIDTHS, T,
        engine=engine)).lower(
            jax.ShapeDtypeStruct((NCHAN, need), jnp.float32),
            jnp.asarray(plan.stage1_bins),
            jnp.asarray(plan.stage2_bins)).as_text()
    pallas = "tpu_custom_call" in text
    say(f"device path: sweep engine auto -> {engine}; tpu_custom_call "
        f"(Pallas boxcar) in the lowered chunk program: {pallas}")
    if engine != "fourier" or not pallas:
        raise PhaseFailed("the chunk program is not the TPU one "
                          "(fourier engine + Pallas boxcar)")


def check_no_fallback(summ, gate_compile: bool = True) -> None:
    counts = {k: summ.counters.get(k, 0) for k in FALLBACK_COUNTERS}
    counts.update({k: summ.events.get(k, 0) for k in FALLBACK_EVENTS})
    say(f"fallbacks: {json.dumps(counts)}")
    gated = {k: v for k, v in counts.items()
             if gate_compile or k != "compile.aot_fallback"}
    fired = {k: v for k, v in gated.items() if v}
    if fired:
        raise PhaseFailed(f"a fallback fired: {fired}")


# ---------------------------------------------------------------------------
# the two runs


class Checks:
    """Run every check even after one fails (a chip call is too dear to
    learn one fault at a time); `finish` fails the phase if any did."""

    def __init__(self):
        self.failed = []

    def run(self, name, fn, *args, **kwargs):
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kwargs)
        except PhaseFailed as e:
            self.failed.append(name)
            say(f"check {name}: FAILED: {e}")
            return None
        except Exception as e:  # noqa: BLE001 - recorded, run goes on
            traceback.print_exc()
            self.failed.append(name)
            say(f"check {name}: FAILED: raised {type(e).__name__}: "
                f"{str(e)[:300]}")
            return None
        say(f"check {name}: ok ({time.perf_counter() - t0:.1f}s)")
        return out

    def finish(self):
        if self.failed:
            raise PhaseFailed(f"checks failed: {self.failed}")


def run_one_chip(args) -> None:
    print_banner()
    print_sizes(SIZES, {})
    phase_native()
    fil = make_input(args.workdir, args.seed, SIZES)
    stem = os.path.splitext(os.path.basename(fil))[0]
    outdir = os.path.join(args.workdir, "out")
    checks = Checks()
    wall = checks.run("survey", run_survey, [fil], outdir, SIZES)
    if wall is not None:
        summ = telemetry_summary(outdir)
        print_walls("run", wall, summ)
        checks.run("recovery", check_recovery, outdir, stem, SIZES)
        checks.run("no-fallback", check_no_fallback, summ)
    checks.run("device-path", check_device_path)
    checks.run("twins", check_twins, fil, SIZES)
    checks.finish()


# -- --chips 4 ---------------------------------------------------------------


def _sha(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for blk in iter(lambda: f.read(1 << 22), b""):
            h.update(blk)
    return h.hexdigest()


def _artifacts(outdir: str, stem: str) -> dict:
    """{name relative to the outdir: path} of the chain's artifacts (not
    manifests, journals or traces, which record placement and time)."""
    keep = (".cands", ".dat", ".inf", ".cand", ".txtcand", ".accelcands",
            ".pfd", "_snr.json", ".mask")
    return {os.path.basename(p): p
            for p in glob.glob(os.path.join(outdir, stem + "*"))
            if p.endswith(keep)}


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b), 1e-300)


def compare_tables(out_a: str, out_b: str, stem: str) -> None:
    """4-chip vs 1-chip under the science contract: the same (DM, r, z,
    numharm) candidates, sigma / power / folded SNR within TOL['tables'].
    Byte identity of every artifact is printed, not gated."""
    from pypulsar_tpu.io.prestocand import read_rzwcands

    a, b = _artifacts(out_a, stem), _artifacts(out_b, stem)
    if sorted(a) != sorted(b):
        raise PhaseFailed(f"artifact sets differ: only 4-chip "
                          f"{sorted(set(a) - set(b))[:5]}, only 1-chip "
                          f"{sorted(set(b) - set(a))[:5]}")
    worst = {"sigma": 0.0, "power": 0.0, "r": 0.0, "z": 0.0, "snr": 0.0}
    n_cands = 0
    for name in sorted(n for n in a if n.endswith(".cand")):
        ca, cb = read_rzwcands(a[name]), read_rzwcands(b[name])
        if len(ca) != len(cb):
            raise PhaseFailed(f"{name}: {len(ca)} vs {len(cb)} candidates")
        for x, y in zip(ca, cb):
            # r and z are sub-cell refined floats: the same candidate is
            # the same grid cell (half a bin, one dz step)
            if x.locpow != y.locpow or abs(x.r - y.r) > 0.25 \
                    or abs(x.z - y.z) > 1.0:
                raise PhaseFailed(f"{name}: candidate sets differ at r "
                                  f"{x.r} / {y.r}")
            worst["sigma"] = max(worst["sigma"], _rel(x.sig, y.sig))
            worst["power"] = max(worst["power"], _rel(x.pow, y.pow))
            worst["r"] = max(worst["r"], abs(x.r - y.r))
            worst["z"] = max(worst["z"], abs(x.z - y.z))
            n_cands += 1
    with open(a[stem + "_snr.json"]) as f:
        ra = {os.path.basename(r["pfd"]): r["snr"] for r in json.load(f)}
    with open(b[stem + "_snr.json"]) as f:
        rb = {os.path.basename(r["pfd"]): r["snr"] for r in json.load(f)}
    if sorted(ra) != sorted(rb):
        raise PhaseFailed("folded archive sets differ")
    for k in ra:
        if (ra[k] is None) != (rb[k] is None):
            raise PhaseFailed(f"{k}: SNR present in one run only")
        if ra[k] is not None:
            worst["snr"] = max(worst["snr"], _rel(ra[k], rb[k]))
    say(f"tables: {n_cands} per-trial candidates and {len(ra)} folded "
        f"archives matched; worst relative difference sigma "
        f"{worst['sigma']:.3e}, power {worst['power']:.3e}, folded SNR "
        f"{worst['snr']:.3e} (contract {TOL['tables']:g}); worst |dr| "
        f"{worst['r']:.3e} bins, |dz| {worst['z']:.3e}")
    by_kind = {}
    for name in a:
        kind = name[name.rindex("."):] if not name.endswith("_snr.json") \
            else "_snr.json"
        same = _sha(a[name]) == _sha(b[name])
        if kind == "_snr.json" and not same:
            # rows carry their outdir in the pfd path: compare without it
            same = ra == rb
        ent = by_kind.setdefault(kind, [0, 0])
        ent[0] += int(same)
        ent[1] += 1
    ident = sum(v[0] for v in by_kind.values())
    total = sum(v[1] for v in by_kind.values())
    say(f"byte identity 4-chip vs 1-chip (not gated; ROADMAP D0): "
        f"{ident}/{total} artifacts identical, by kind "
        f"{json.dumps({k: f'{v[0]}/{v[1]}' for k, v in sorted(by_kind.items())})}")
    if max(worst["sigma"], worst["power"], worst["snr"]) > TOL["tables"]:
        raise PhaseFailed("4-chip and 1-chip tables disagree beyond the "
                          "science contract")


def check_four_devices_worked(outdir: str, summ, gang: bool) -> None:
    """All four real device ids carry work. A gang shows it in the leaf
    device spans of the sharded sweep and accel search (tlmsum's
    per-device roll-up); a fleet of 1-chip leases in what the scheduler
    leased: seconds by chip (`survey.lease_chip_s.chip<N>`), and a sweep
    lease on every chip (the `chips` of the `survey.lease` spans)."""
    from pypulsar_tpu.obs.summarize import load_records

    busy = {int(d): round(v[0], 2) for d, v in summ.device_busy.items()}
    prefix = "survey.lease_chip_s.chip"
    leased = {int(k[len(prefix):]): round(v, 2)
              for k, v in summ.counters.items() if k.startswith(prefix)}
    swept: set = set()
    for path in glob.glob(os.path.join(outdir, "tlm", "fleet*.jsonl")):
        for rec in load_records(path):
            attrs = rec.get("attrs") or {}
            if rec.get("name") == "survey.lease" \
                    and attrs.get("stage") == "sweep":
                swept.update(int(d) for d in attrs.get("chips") or ())
    say(f"per-device roll-up (tlmsum): leaf device-span seconds by "
        f"device id {json.dumps(busy)}; seconds leased by chip "
        f"{json.dumps(dict(sorted(leased.items())))}; chips that held a "
        f"sweep lease {sorted(swept)}")
    seen = busy if gang else leased
    if sorted(seen) != [0, 1, 2, 3] or not all(seen.values()):
        raise PhaseFailed(f"not all four device ids carried work: {seen}")
    if not gang and sorted(swept) != [0, 1, 2, 3]:
        raise PhaseFailed(f"a chip never held a sweep lease: {sorted(swept)}")


def check_no_compile(summ) -> None:
    """A pass over shapes and chips an earlier pass of this process ran:
    nothing is built, at the plane's door or at JAX's."""
    counts = {k: int(summ.counters.get(k, 0))
              for k in ("compile.cache_miss", "jit.compiles",
                        "compile.chip_load")}
    say(f"second pass: {json.dumps(counts)}")
    if counts["compile.cache_miss"] or counts["jit.compiles"]:
        raise PhaseFailed(f"a warm pass compiled: {counts}")


def check_sharded_intermediates(fil: str, sizes: dict) -> None:
    """The gang's intermediates really are spread over four chips: the
    sharded series chunk and the sharded prep planes, on a window."""
    import jax.numpy as jnp

    from pypulsar_tpu.fourier.kernels import prep_spectra_batch
    from pypulsar_tpu.parallel.mesh import gang_mesh
    from pypulsar_tpu.parallel.sweep import (
        _mesh_pad_groups,
        choose_group_size,
        make_sharded_series_chunk,
        make_sweep_plan,
        resolve_engine,
    )

    mesh = gang_mesh(4)
    data, freqs = read_window(fil)
    T = data.shape[1]
    dms = sizes["lodm"] + sizes["dmstep"] * np.arange(8)
    group = choose_group_size(dms, freqs, TSAMP, 64)
    plan = make_sweep_plan(dms, freqs, TSAMP, nsub=64, group_size=group,
                           pad_groups_to=_mesh_pad_groups(len(dms), group,
                                                          mesh))
    need = T + plan.max_total_shift
    padded = np.zeros((len(freqs), need), np.float32)
    padded[:, :T] = data
    fn = make_sharded_series_chunk(mesh, plan.nsub, T, plan.max_shift2,
                                   engine=resolve_engine("auto"))
    series = fn(jnp.asarray(padded), jnp.asarray(plan.stage1_bins),
                jnp.asarray(plan.stage2_bins))
    re, _im = prep_spectra_batch(series[:8], mesh=mesh)
    n_series = len(series.sharding.device_set)
    n_prep = len(re.sharding.device_set)
    say(f"sharded intermediates: series chunk {tuple(series.shape)} on "
        f"{n_series} devices, prepped planes {tuple(re.shape)} on "
        f"{n_prep} devices")
    if n_series != 4 or n_prep != 4:
        raise PhaseFailed("a sharded intermediate is not on four devices")


def run_four_chips(args) -> None:
    print_banner()
    sizes = SIZES_CHIPS4
    print_sizes(sizes, WHY_CHIPS4)
    fil = make_input(args.workdir, args.seed, sizes)
    stem = os.path.splitext(os.path.basename(fil))[0]
    out4 = os.path.join(args.workdir, "out_gang4")
    out1 = os.path.join(args.workdir, "out_1chip")
    checks = Checks()
    wall4 = checks.run("survey-gang4", run_survey, [fil], out4, sizes,
                       devices=4, gang="auto")
    wall1 = checks.run("survey-1chip", run_survey, [fil], out1, sizes,
                       devices=1)
    if wall4 is not None:
        summ4 = telemetry_summary(out4)
        print_walls("gang of 4", wall4, summ4)
        checks.run("recovery", check_recovery, out4, stem, sizes)
        checks.run("four-devices", check_four_devices_worked, out4, summ4,
                   gang=True)
        checks.run("no-fallback-gang4", check_no_fallback, summ4,
                   gate_compile=False)
    if wall1 is not None:
        summ1 = telemetry_summary(out1)
        print_walls("1 chip", wall1, summ1)
        checks.run("no-fallback-1chip", check_no_fallback, summ1)
    if wall4 is not None and wall1 is not None:
        checks.run("tables", compare_tables, out4, out1, stem)
    checks.run("sharded-intermediates", check_sharded_intermediates, fil,
               sizes)
    if args.fleet:
        fsizes = dict(sizes, nsamp=FLEET_NSAMP)
        fils = [make_input(args.workdir, args.seed + 1 + i, fsizes,
                           tag=f"_fleet{i}") for i in range(4)]
        outf = os.path.join(args.workdir, "out_fleet4")
        wallf = checks.run("survey-fleet4", run_survey, fils, outf, fsizes,
                           devices=4, gang="auto")
        if wallf is not None:
            summf = telemetry_summary(outf)
            print_walls("fleet of 4 on 4 chips", wallf, summf)
            checks.run("four-devices-fleet", check_four_devices_worked,
                       outf, summf, gang=False)
            checks.run("no-fallback-fleet4", check_no_fallback, summf,
                       gate_compile=False)
            for f in fils:
                checks.run("recovery-fleet", check_recovery, outf,
                           os.path.splitext(os.path.basename(f))[0], fsizes)
            # the same fleet again: whichever chips the leases fall on,
            # every program is there
            outf2 = os.path.join(args.workdir, "out_fleet4_again")
            if checks.run("survey-fleet4-again", run_survey, fils, outf2,
                          fsizes, devices=4, gang="auto") is not None:
                checks.run("no-compile-fleet4-again", check_no_compile,
                           telemetry_summary(outf2))
    checks.finish()


# ---------------------------------------------------------------------------


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated pointing (default 0)")
    ap.add_argument("--workdir",
                    default=os.path.join(REPO, "chip_smoke_work"),
                    help="inputs, outputs and per-stage telemetry "
                         "(default: chip_smoke_work/ in the checkout, "
                         "git-ignored)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the multi-chip path and its 1-chip "
                         "comparison (needs four chips)")
    ap.add_argument("--fleet", action="store_true",
                    help="with --chips 4: also four short observations "
                         "fleet-parallel on the four chips")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    t0 = time.perf_counter()
    device = None
    ok = False
    try:
        device = require_chips(args.chips)
        os.makedirs(args.workdir, exist_ok=True)
        if args.chips == 4:
            run_four_chips(args)
        else:
            run_one_chip(args)
        ok = True
    except PhaseFailed as e:
        say(f"FAILED: {e}")
    except (Exception, SystemExit):  # the last line must still say so
        traceback.print_exc()
        say("FAILED: a phase raised (traceback on stderr)")
    say(f"total wall {time.perf_counter() - t0:.1f}s")
    if device is None:
        try:
            device = device_record()
        except Exception:  # noqa: BLE001 - no backend at all
            device = {"platform": None, "kind": None, "count": 0}
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
