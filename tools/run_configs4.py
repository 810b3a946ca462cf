"""configs[4] END-TO-END on chip (VERDICT r4 item 1 / r5 item 1): measure
the chain

    900-s window of the north-star file
      -> cli sweep --write-dats  (streamed two-stage writer, 512 DMs)
      -> cli accelsearch --batch (shared template banks, batched stages)
      -> cli sift

or, with --stream (round 6, the record path), the PIPELINED chain

    900-s window -> cli sweep --accel-search  (dedispersed series stream
      straight into the batched search: no .dat write + re-read, prep of
      batch N+1 overlapped with the search of batch N) -> cli sift

as one timed run with the per-stage wall split, and verify the injected
pulsar (P=262.144 ms => f0=3.814697 Hz at DM 70) comes out of the sift.
Writes BENCH_r06_configs4.json. Every stage is a child CLI process that
owns the chip alone, one after another; this parent computes baselines
with NumPy only and never runs a JAX operation. ``--ab-stream`` additionally runs
the classic .dat chain on the same window and records both walls plus
whether the sifted tables match (the handoff's parity evidence at the
production scale).

Reference surface: formats/prestofft.py:76-195 + bin/plot_accelcands.py:
50-104 (the reference defers the search itself to PRESTO accelsearch on
one core; BASELINE configs[4]).

Usage: python tools/run_configs4.py [--stream] [--trials 512]
           [--duration 900] [--downsamp 4] [--keep]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--fil", default=os.path.join(REPO, "data",
                                                  "northstar_1hr.fil"))
    ap.add_argument("--trials", type=int, default=512)
    ap.add_argument("--duration", type=float, default=900.0)
    ap.add_argument("--dm-max", type=float, default=500.0)
    ap.add_argument("--downsamp", type=int, default=4,
                    help="dedispersed-series downsampling before the "
                         "accel search (256 us at the north-star's 64 us "
                         "raw rate: the benched N=2^21-scale spectrum)")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--stream", action="store_true",
                    help="round-6 pipelined path: ONE sweep invocation "
                         "streams the dedispersed series straight into "
                         "the batched accel search (--accel-search) — "
                         "no per-DM .dat write + re-read (745.9 s of "
                         "the round-5 chain)")
    ap.add_argument("--ab-stream", action="store_true",
                    help="with --stream: afterwards run the classic "
                         ".dat chain on the same window and record both "
                         "walls + sift parity in the JSON")
    ap.add_argument("--device-prep", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="device-side rfft + deredden for the accel "
                         "stage (default ON for --batch >= 2 since "
                         "round 6 under the matched-candidate contract; "
                         "--no-device-prep restores host prep)")
    ap.add_argument("--zmax", type=float, default=200.0)
    ap.add_argument("--coarse-dz", type=float, default=0.0,
                    help="coarse-to-fine z preselection step for the "
                         "accelsearch stage (cli accelsearch --coarse-dz; "
                         "0 = single pass). Used for the A/B record")
    ap.add_argument("--ab-coarse", type=float, default=0.0, metavar="DZ",
                    help="after the primary accelsearch stage, re-run "
                         "JUST that stage on the same .dats with "
                         "--coarse-dz DZ and record the A/B walls plus "
                         "whether the re-sifted candidates match "
                         "(VERDICT r4 item 1 stretch evidence at zero "
                         "extra sweep cost)")
    ap.add_argument("--workdir", default=os.path.join(REPO, "data",
                                                      "configs4"))
    ap.add_argument("--keep", action="store_true",
                    help="keep the .dat/.cand intermediates")
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "BENCH_r06_configs4.json"))
    ap.add_argument("--allow-miss", action="store_true",
                    help="exit 0 even when the injected pulsar is not "
                         "recovered (toy-scale rehearsals on other files)")
    return ap.parse_args(argv)


def slice_window(fil: str, out: str, seconds: float) -> int:
    """First ``seconds`` of a .fil as a standalone file (byte copy:
    header + whole spectra). When the source already IS the window
    (a file generated at exactly --duration), reuse it in place —
    no 14-GB copy, no double disk footprint."""
    from pypulsar_tpu.io.filterbank import FilterbankFile

    fb = FilterbankFile(fil)
    nsamp = min(int(round(seconds / fb.tsamp)), fb.number_of_samples)
    total = fb.number_of_samples
    if os.path.abspath(out) == os.path.abspath(fil):
        # the source IS the window artifact (re-run against a kept
        # window.fil): never remove it; slicing onto itself is a user error
        fb.close()
        if nsamp < total:
            raise ValueError(f"--fil and the window path are the same file "
                             f"({out}); cannot slice it onto itself")
        return nsamp
    if os.path.lexists(out):
        os.remove(out)  # never open through a stale symlink from a prior run
    if nsamp == fb.number_of_samples:
        fb.close()
        os.symlink(os.path.abspath(fil), out)
        return nsamp
    nbytes = nsamp * fb.bytes_per_spectrum
    with open(fil, "rb") as src, open(out, "wb") as dst:
        dst.write(src.read(fb.header_size))
        copied = 0
        while copied < nbytes:
            buf = src.read(min(1 << 24, nbytes - copied))
            if not buf:
                break
            dst.write(buf)
            copied += len(buf)
    fb.close()
    return nsamp


def run_stage(name, argv, log, env_extra=None):
    print(f"## stage {name}: {' '.join(argv)}", flush=True)
    env = None
    if env_extra:
        env = dict(os.environ, **env_extra)
    t0 = time.perf_counter()
    with open(log, "w") as lf:
        rc = subprocess.call(argv, stdout=lf, stderr=subprocess.STDOUT,
                             env=env)
    el = time.perf_counter() - t0
    if rc != 0:
        tail = open(log).read()[-3000:]
        raise RuntimeError(f"stage {name} failed rc={rc}:\n{tail}")
    print(f"## stage {name}: {el:.1f}s", flush=True)
    return el


def _span_seconds(jsonl: str) -> dict:
    """Per-span-name wall totals from a telemetry trace — the streamed
    chain is ONE CLI stage, so its internal sweep/prep/search split comes
    from the recorded spans (incl. noagg wrapper spans)."""
    from pypulsar_tpu.obs.summarize import load_records

    tot = {}
    for rec in load_records(jsonl):
        if rec.get("type") == "span":
            name = rec.get("name", "?")
            tot[name] = tot.get(name, 0.0) + float(rec.get("dur", 0.0))
    # round ONCE: per-record rounding floors sub-50ms spans to zero (a
    # toy-scale accel_search total would collapse to the 1e-9 guard)
    return {k: round(v, 3) for k, v in tot.items()}


def main(argv=None):
    a = parse_args(argv)
    if a.device_prep and a.batch < 2:
        raise SystemExit("--device-prep only takes effect on the batched "
                         "accelsearch path; use --batch >= 2")
    if a.device_prep is None:  # auto: on for the grouped path, like the CLI
        a.device_prep = a.batch >= 2
    if a.stream and (a.coarse_dz > 0 or a.ab_coarse > 0):
        raise SystemExit("--coarse-dz/--ab-coarse are classic-chain "
                         "options (the handoff runs single-pass)")
    if a.ab_stream and not a.stream:
        raise SystemExit("--ab-stream requires --stream")
    os.makedirs(a.workdir, exist_ok=True)
    base = os.path.join(a.workdir, "c4")
    win_fil = os.path.join(a.workdir, "window.fil")
    stages = {}

    t_all = time.perf_counter()
    t0 = time.perf_counter()
    nsamp = slice_window(a.fil, win_fil, a.duration)
    stages["slice_window"] = round(time.perf_counter() - t0, 1)
    from pypulsar_tpu.io.filterbank import FilterbankFile

    _fb = FilterbankFile(win_fil)
    nchan, nbits, tsamp = _fb.nchans, _fb.nbits, float(_fb.tsamp)
    _fb.close()
    # actual covered span: the input can be SHORTER than the requested
    # window (slice_window clamps to the file), and every derived number
    # (trials/s, projections) must be read against the real coverage
    covered = nsamp * tsamp
    if covered < a.duration - 0.5 * tsamp:
        print(f"## WARNING: input covers only {covered:.1f}s of the "
              f"requested --duration {a.duration:.0f}s window; the "
              f"recorded metrics describe the shorter span",
              file=sys.stderr)
    print(f"## window: {nsamp} samples ({covered:.1f}s of the requested "
          f"{a.duration:.0f}s), {nchan} chans {nbits}-bit -> {win_fil}")

    dmstep = a.dm_max / max(a.trials - 1, 1)
    sweep_base_argv = [
        sys.executable, "-m", "pypulsar_tpu.cli.sweep", win_fil,
        "-o", base, "--lodm", "0", "--dmstep", f"{dmstep:.6f}",
        "--numdms", str(a.trials), "--downsamp", str(a.downsamp),
        "-s", "64", "--group-size", "32", "--threshold", "8"]
    stream_tlm = os.path.join(a.workdir, "stream_tlm.jsonl")
    stream_spans = None
    if a.stream:
        # ONE invocation: sweep detection + dedispersed series streamed
        # straight into the batched accel search (no .dat round trip);
        # the internal split comes from the telemetry trace
        stream_argv = sweep_base_argv + [
            "--accel-search", "--accel-zmax", str(int(a.zmax)),
            "--accel-dz", "2", "--accel-numharm", "8",
            "--accel-sigma", "2", "--accel-batch", str(a.batch),
            "--telemetry", stream_tlm]
        if not a.device_prep:
            stream_argv += ["--no-accel-device-prep"]
        stages["sweep_accel_stream"] = round(run_stage(
            "sweep+accel-stream", stream_argv,
            os.path.join(a.workdir, "stream.log")), 1)
        stream_spans = _span_seconds(stream_tlm)
        print(f"## stream spans: {stream_spans}")
    else:
        # always the STREAMED .dat writer (prepsubband semantics — what
        # the full-scale window uses anyway, and the handoff's parity
        # partner), so toy-scale rehearsals measure the same path
        stages["sweep_write_dats"] = round(run_stage(
            "sweep+dats", sweep_base_argv + ["--write-dats"],
            os.path.join(a.workdir, "sweep.log"),
            env_extra={"PYPULSAR_TPU_DATS_RESIDENT_LIMIT": "0"}), 1)

        dats = sorted(glob.glob(f"{base}_DM*.dat"))
        assert len(dats) == a.trials, (len(dats), a.trials)
        accel_argv = [sys.executable, "-m", "pypulsar_tpu.cli.accelsearch",
                      *dats, "--batch", str(a.batch),
                      "-z", str(int(a.zmax)), "--dz", "2", "-n", "8",
                      "-s", "2"]
        if a.coarse_dz > 0:
            accel_argv += ["--coarse-dz", str(a.coarse_dz)]
        if not a.device_prep:
            accel_argv += ["--no-device-prep"]
        stages["accelsearch_batch"] = round(run_stage(
            "accelsearch", accel_argv,
            os.path.join(a.workdir, "accel.log")), 1)

    cands = sorted(glob.glob(f"{base}_DM*_ACCEL_{int(a.zmax)}.cand"))
    assert cands, "no .cand outputs"
    sifted = base + ".sifted"
    stages["sift"] = round(run_stage(
        "sift",
        [sys.executable, "-m", "pypulsar_tpu.cli.sift", *cands,
         "-o", sifted, "-s", "4"],
        os.path.join(a.workdir, "sift.log")), 1)
    wall = time.perf_counter() - t_all

    # --- recovery check: the injected pulsar (or a harmonic) in the sift
    from pypulsar_tpu.io.accelcands import parse_candlist

    p0 = 4096 * 64e-6  # injected period 262.144 ms
    best = None
    for c in parse_candlist(sifted):
        for h in (1, 2, 3, 4, 8):
            if (abs(c.period * h - p0) < 0.01 * p0
                    and abs(c.dm - 70.0) < 5.0):
                if best is None or c.sigma > best["sigma"]:
                    best = {"dm": c.dm, "sigma": c.sigma,
                            "period_s": c.period, "harmonic": h,
                            "snr": c.snr}
    print(f"## injected pulsar recovery: {best}")

    # --- optional A/B: the coarse-to-fine accel stage on the SAME .dats
    ab = None
    if a.ab_coarse > 0:
        if a.coarse_dz > 0:
            raise SystemExit("--ab-coarse needs a single-pass primary run "
                             "(drop --coarse-dz)")
        for fn in cands + [sifted]:
            shutil.move(fn, fn + ".single")
        stages["accelsearch_batch_coarse"] = round(run_stage(
            "accelsearch-coarse",
            accel_argv + ["--coarse-dz", str(a.ab_coarse)],
            os.path.join(a.workdir, "accel_coarse.log")), 1)
        stages["sift_coarse"] = round(run_stage(
            "sift-coarse",
            [sys.executable, "-m", "pypulsar_tpu.cli.sift", *cands,
             "-o", sifted, "-s", "4"],
            os.path.join(a.workdir, "sift_coarse.log")), 1)
        with open(sifted + ".single", "rb") as f1, open(sifted, "rb") as f2:
            identical = f1.read() == f2.read()
        ab = {
            "coarse_dz": a.ab_coarse,
            "accel_wall_single": stages["accelsearch_batch"],
            "accel_wall_coarse": stages["accelsearch_batch_coarse"],
            "speedup": round(stages["accelsearch_batch"]
                             / max(stages["accelsearch_batch_coarse"],
                                   1e-9), 2),
            "sift_identical": identical,
        }
        print(f"## coarse-to-fine A/B: {ab}")

    # --- optional A/B: the classic .dat chain on the same window
    ab_stream = None
    if a.ab_stream:
        for fn in cands + [sifted]:
            shutil.move(fn, fn + ".stream")
        # the classic chain's timings live INSIDE the A/B record, not in
        # the streamed record's stage_seconds (whose sum must match the
        # reported wall)
        dat_stages = {}
        dat_stages["sweep_write_dats"] = round(run_stage(
            "sweep+dats", sweep_base_argv + ["--write-dats"],
            os.path.join(a.workdir, "sweep_dat.log"),
            env_extra={"PYPULSAR_TPU_DATS_RESIDENT_LIMIT": "0"}), 1)
        dats = sorted(glob.glob(f"{base}_DM*.dat"))
        dat_accel_argv = [sys.executable, "-m",
                          "pypulsar_tpu.cli.accelsearch", *dats,
                          "--batch", str(a.batch), "-z", str(int(a.zmax)),
                          "--dz", "2", "-n", "8", "-s", "2"]
        if not a.device_prep:
            dat_accel_argv += ["--no-device-prep"]
        dat_stages["accelsearch_batch"] = round(run_stage(
            "accelsearch", dat_accel_argv,
            os.path.join(a.workdir, "accel_dat.log")), 1)
        dat_stages["sift"] = round(run_stage(
            "sift-dat",
            [sys.executable, "-m", "pypulsar_tpu.cli.sift", *cands,
             "-o", sifted, "-s", "4"],
            os.path.join(a.workdir, "sift_dat.log")), 1)
        with open(sifted + ".stream", "rb") as f1, open(sifted, "rb") as f2:
            identical = f1.read() == f2.read()
        dat_wall = sum(dat_stages.values())
        stream_wall = stages["sweep_accel_stream"] + stages["sift"]
        ab_stream = {
            "stream_wall": round(stream_wall, 1),
            "dat_chain_wall": round(dat_wall, 1),
            "speedup": round(dat_wall / max(stream_wall, 1e-9), 2),
            "sift_identical": identical,
            "dat_stage_seconds": dat_stages,
        }
        print(f"## stream-vs-dat A/B: {ab_stream}")

    # --- (r, z) cell accounting at the searched geometry (bench run_accel
    # formula) x trials / accel wall. The streamed chain has no separate
    # accel CLI stage, so its search wall comes from the recorded
    # accel_search spans (device dispatch + result drain; prep runs
    # overlapped on the pipeline thread and is reported separately)
    from pypulsar_tpu.fourier.accelsearch import AccelSearchConfig
    from pypulsar_tpu.fourier.zresponse import template_bank

    n_ds = nsamp // a.downsamp
    N = n_ds // 2
    T = n_ds * tsamp * a.downsamp
    cfg = AccelSearchConfig(zmax=a.zmax, dz=2.0, numharm=8, sigma_min=2.0)
    Z = len(cfg.zs)
    rlo = max(int(np.ceil(cfg.flo * T)), 1)
    cells = sum(2 * Z * max((N - 1) - H * rlo, 0) for H in cfg.stages)
    if a.stream:
        accel_wall = max(stream_spans.get("accel_search", 0.0), 1e-9)
    else:
        accel_wall = stages["accelsearch_batch"]
    cells_per_sec = cells * a.trials / accel_wall

    # single-core NumPy baseline for the search stage: one stage-1
    # segment's correlations with np.fft (the same generous baseline
    # bench.py run_accel measures), scaled linearly to the full count
    segw = cfg.seg_width
    tb, hw = template_bank(cfg.zs, numbetween=2)
    L = 1
    while L < segw + 4 * hw:
        L <<= 1
    padded = np.zeros((tb.shape[0], L), np.complex128)
    padded[:, : tb.shape[1]] = tb
    rev = np.zeros_like(padded)
    rev[:, 0] = padded[:, 0]
    rev[:, 1:] = padded[:, :0:-1]
    tf = np.fft.fft(rev, axis=1).astype(np.complex64)
    rng = np.random.RandomState(0)
    seg = (rng.standard_normal(L) + 1j * rng.standard_normal(L)) \
        .astype(np.complex64)

    def one_rep():
        tb0 = time.perf_counter()
        sl = np.fft.fft(seg)
        corr = np.fft.ifft(sl[None, :] * tf, axis=1)
        _ = (np.abs(corr) ** 2).astype(np.float32)
        return time.perf_counter() - tb0

    # the round-5 baseline protocol (bench.numpy_baseline): >=5
    # loadavg-gated reps + pinned-calibration cross-check
    import bench as bench_mod

    bl = bench_mod.numpy_baseline(one_rep)
    bl_cells_per_sec = (2 * Z * segw) / bl["seconds"]
    vs_baseline = cells_per_sec / bl_cells_per_sec

    # linear-extrapolation spot check (VERDICT r5 item 7): the same twin
    # on a 10x larger slice; ratio ~1 validates the scaling model behind
    # every scaled-baseline figure in the bench JSONs
    segs10 = [(rng.standard_normal(L) + 1j * rng.standard_normal(L))
              .astype(np.complex64) for _ in range(10)]

    def ten_rep():
        tb0 = time.perf_counter()
        for s10 in segs10:
            sl = np.fft.fft(s10)
            corr = np.fft.ifft(sl[None, :] * tf, axis=1)
            _ = (np.abs(corr) ** 2).astype(np.float32)
        return time.perf_counter() - tb0

    scale = bench_mod.baseline_scale_check(one_rep, ten_rep, factor=10)

    # per-spectrum fields keep the BENCH_r05 meaning (the ACCEL stage
    # per trial, comparable round over round): accel_wall is the
    # accelsearch CLI stage classically and the recorded accel_search
    # span total under --stream. The streamed chain's combined stage is
    # reported separately as stream_stage_per_spectrum_seconds.
    chain_stage = accel_wall
    rec = {
        "metric": "configs4_end_to_end_seconds",
        "value": round(wall, 1),
        "unit": (f"wall seconds, {a.duration:.0f}s x {nchan}-chan "
                 f"{nbits}-bit window -> "
                 + (f"sweep --accel-search (streamed handoff, "
                    f"ds={a.downsamp}, batch {a.batch}"
                    if a.stream else
                    f"sweep(+streamed .dats, ds={a.downsamp}) -> "
                    f"accelsearch --batch {a.batch}")
                 + f" (zmax={a.zmax:.0f}, "
                 f"dz=2, H<=8, N={N} bins x {a.trials} trials"
                 + (f", coarse-dz={a.coarse_dz:g} prepass"
                    if a.coarse_dz > 0 else "")
                 + (", device-prep" if a.device_prep else ", host-prep")
                 + ") -> sift; stages are child CLI processes on JAX's "
                   "default backend, one at a time"),
        "vs_baseline": round(vs_baseline, 2),
        "numpy_cells_per_sec": round(bl_cells_per_sec, 1),
        **{k: v for k, v in bl.items() if k != "seconds"},
        **scale,
        "trials": a.trials,
        "covered_seconds": round(covered, 1),
        "requested_seconds": round(a.duration, 1),
        "streamed_handoff": a.stream,
        "coarse_dz": a.coarse_dz,
        "device_prep": a.device_prep,
        "wall_seconds": round(wall, 1),
        "stage_seconds": stages,
        **({"stream_span_seconds": stream_spans} if stream_spans else {}),
        "spectrum_bins": N,
        "cells_per_spectrum": cells,
        "accel_search_wall_seconds": round(accel_wall, 1),
        "cells_per_sec": round(cells_per_sec, 1),
        "injected_recovered": best,
        **({"ab_coarse": ab} if ab else {}),
        **({"ab_stream": ab_stream} if ab_stream else {}),
        "per_spectrum_seconds": round(chain_stage / a.trials, 2),
        "projection_4096_trials_hours": round(
            4096 * chain_stage / a.trials / 3600.0, 2),
        **({"stream_stage_per_spectrum_seconds": round(
            stages["sweep_accel_stream"] / a.trials, 2)}
           if a.stream else {}),
    }
    with open(a.out, "w") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec))
    if not a.keep:
        shutil.rmtree(a.workdir, ignore_errors=True)
    if best is None and not a.allow_miss:
        print("## FAIL: injected pulsar NOT recovered by the sift",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
