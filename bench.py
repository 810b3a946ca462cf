#!/usr/bin/env python
"""Benchmark: DM-trials/sec of the sweep engine vs single-core NumPy.

Metric (BASELINE.md): DM-trials/sec on a 1024-channel filterbank at 64 us
sampling; one "DM trial" = dedispersing + boxcar-detecting the full segment at
one DM. ``vs_baseline`` is the speedup over a single-core NumPy implementation
doing the reference's brute-force per-channel-roll dedispersion
(reference formats/spectra.py:229-260 semantics) with the same detection step,
measured on a time slice and a trial subset and scaled linearly (NumPy cost is
linear in both; the scaling is stated in the JSON).

HBM budgeting (round-3 fix: BENCH_r02 OOM'd the chip): the dataset is
device-resident only up to a byte budget derived from the accelerator's HBM
(``memory_stats()["bytes_limit"]`` of the device the run is on); the chunk
payload is sized for a power-of-two FFT length, the streaming dispatch depth
(max_pending) is computed from the leftover budget, and a RESOURCE_EXHAUSTED
retry halves the dataset until the run fits. The measured configuration is
always recorded in the JSON.

One process, one device owner: ``main()`` runs the selected mode in this
interpreter (a parent that touched JAX would hold the chip against a child).
Every record names the device it ran on (``platform``, ``device_kind``,
``device_count``). A mode whose headline value is a time, a rate or a ratio
of wall clocks is a DEVICE METRIC: without a TPU it prints
``{"ok": false, ...}`` and exits non-zero — a CPU number is never written
under a device metric's name. The structural harnesses (``HARNESS_MODES``:
parity fractions, dispatch counters, fault recovery) assert their gates on
whatever backend JAX offers and say which in the record; the ones that start
several JAX processes (``CPU_FLEET_MODES``) always run on the CPU backend.
A mode that raises exits non-zero with its traceback; nothing is
substituted.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Usage: python bench.py [--quick] [--trials D] [--nsamp T] [--nchan C]
                       [--engine auto|gather|fourier] [--ab]
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from pypulsar_tpu.tune import knobs

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``. A kind
# that is not in the table is an error, not a default: a roofline share
# against another chip's peak is a wrong number.
# "TPU v5 lite": Google Cloud documentation, "TPU v5e" (16 GB HBM2e at
# 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8).
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12},
}

# Modes whose gates are structural (byte parity, counters, recovery) and
# whose headline value is not a time, rate or wall ratio: runnable on any
# backend, recorded with the backend's name. Every other mode needs a TPU.
HARNESS_MODES = ("broker", "candplane", "chaos", "race", "corruption")
# Harnesses that start several JAX processes (a killed-and-restarted
# daemon, a multi-host fleet, the cross-process compile-cache leg). A chip
# belongs to one process at a time, so these always run on the CPU backend:
# main() pins JAX_PLATFORMS=cpu before JAX is imported and the children
# inherit it.
CPU_FLEET_MODES = ("daemon_soak", "multihost", "compile")


def device_peaks(device_kind: str) -> dict:
    """The peaks row for ``device_kind``; an unknown kind raises."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"sourced row to bench.DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)})") from None


def device_hbm_bytes(dev) -> float:
    """The device's own HBM size, as its allocator reports it."""
    stats = dev.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(
            f"{dev} reports no memory_stats()['bytes_limit']; the HBM "
            f"budget cannot be derived from the device")
    return float(stats["bytes_limit"])


def device_record() -> dict:
    """(platform, device_kind, device_count) as JAX reports them — the
    three keys every record carries."""
    devs = acquire_backend()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small shapes for smoke tests")
    ap.add_argument("--trials", type=int, default=None, help="number of DM trials")
    ap.add_argument("--nchan", type=int, default=None)
    ap.add_argument("--nsamp", type=int, default=None)
    ap.add_argument("--dm-max", type=float, default=500.0)
    ap.add_argument("--engine", default="auto",
                    help="sweep chunk engine: auto|gather|fourier")
    ap.add_argument("--tune", action="store_true",
                    help="auto-tuning A/B (round 17): bounded search vs "
                         "hand-picked defaults at >=2 geometries, "
                         "cache-hit reuse gate, science-invariance "
                         "byte check (BENCH_r12_tune.json)")
    ap.add_argument("--tune-trials", type=int, default=None,
                    help="trial budget per stage search (default: the "
                         "PYPULSAR_TPU_TUNE_TRIALS knob)")
    ap.add_argument("--compile", action="store_true",
                    help="compilation-plane A/B (round 22): cold vs "
                         "warm compile counters over 3 toy geometries, "
                         "bucket-ladder collapse, cross-process "
                         "persistent-cache hits, and the fleet "
                         "warm-pool precompile overlap "
                         "(BENCH_r17_compile.json)")
    ap.add_argument("--baseline-trials", type=int, default=None,
                    help="NumPy trials to actually run before extrapolating")
    ap.add_argument("--profile", action="store_true",
                    help="print a per-stage timing breakdown to stderr")
    ap.add_argument("--ab", action="store_true",
                    help="run the kernel A/B comparison table instead of the "
                         "headline benchmark")
    ap.add_argument("--accel", action="store_true",
                    help="benchmark the acceleration-search engine "
                         "(configs[4]) instead of the DM sweep")
    ap.add_argument("--batch", type=int, default=None,
                    help="with --accel: also measure the BATCHED search "
                         "(this many spectra against the shared template "
                         "bank in one dispatch a chunk)")
    ap.add_argument("--spectral", action="store_true",
                    help="with --accel: run the round-10 spectral-fusion "
                         "pipeline A/B instead of the raw engine bench — "
                         "the SAME toy pulsar through all three handoff "
                         "paths (.dat round trip, streamed, --spectral "
                         "fused) plus the opt-in decimate regime, with "
                         "sift parity asserted and the per-trial "
                         "transform counts taken from the telemetry "
                         "counters (BENCH_r10_specfuse.json)")
    ap.add_argument("--fold", action="store_true",
                    help="benchmark the folding engine (configs[3]) "
                         "instead of the DM sweep")
    ap.add_argument("--waterfall", action="store_true",
                    help="benchmark the single-DM waterfall path "
                         "(configs[0]) instead of the DM sweep")
    ap.add_argument("--survey", action="store_true",
                    help="A/B the survey orchestrator (pypulsar_tpu."
                         "survey) against the serial per-observation "
                         "chain on a 4-observation toy fleet — the "
                         "round-9 host/device-overlap measurement")
    ap.add_argument("--devices", type=int, default=1,
                    help="with --survey: also run the orchestrator with "
                         "this many device leases (gang auto), the "
                         "round-11 multi-chip leg — artifacts byte-"
                         "checked against BOTH the serial chain and the "
                         "1-device orchestrated run. Needs that many "
                         "JAX devices (CPU recipe: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--broker", action="store_true",
                    help="A/B the round-24 batch broker: a 4-observation\n"
                         "same-geometry toy fleet brokered (batch lanes +\n"
                         "cross-obs fused dispatches) vs PYPULSAR_TPU_BROKER=0\n"
                         "per-obs dispatch, gated on structural counters\n"
                         "(coalesce factor, dispatch collapse, compile misses)\n"
                         "+ byte parity + validated-resume-zero")
    ap.add_argument("--candplane", action="store_true",
                    help="A/B the round-25 candidate data plane: the same\n"
                         "synthetic pulsar observed at 3 epochs (plus per-\n"
                         "epoch noise) run through the fleet scheduler with\n"
                         "the candidate store ON vs PYPULSAR_TPU_CANDSTORE=0,\n"
                         "byte-parity on per-obs artifacts, cross-epoch sift\n"
                         "duplicate reduction measured, kill -9 + resume\n"
                         "exactly-once and pre/post-compaction query identity\n"
                         "asserted (BENCH_r20_candplane.json)")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="A/B the round-21 observability plane on a toy "
                         "sweep->accel fleet: instrumentation-off vs "
                         "flight-recorder-only vs full telemetry, "
                         "candidates byte-checked identical and the "
                         "full overhead asserted <= 5% (OBS_rXX.json)")
    ap.add_argument("--chaos", action="store_true",
                    help="run a toy fleet under seeded probabilistic "
                         "fault chaos (kills + OOMs + IO errors + hangs "
                         "+ device faults sprayed across every "
                         "registered fault point), resume until it "
                         "completes, and ASSERT byte-parity against a "
                         "clean run — the fleet-health acceptance "
                         "measurement (CHAOS_rXX.json)")
    ap.add_argument("--daemon-soak", action="store_true",
                    help="run the round-23 streaming-daemon soak: a "
                         "multi-tenant overload storm (bulk flood past "
                         "the accept queue bound, chaos sprayed over "
                         "the admission edge, a corrupt ingest file, a "
                         "SIGKILL'd+restarted --daemon subprocess, a "
                         "SIGTERM drain), with balanced books, bulk-"
                         "only shedding, a trace-reconstructible shed "
                         "trail and byte-parity vs a batch reference "
                         "all ASSERTED (SOAK_rXX.json)")
    ap.add_argument("--multihost", action="store_true",
                    help="run the round-18 multi-host fleet harness: a "
                         "4-obs, 3-process CPU fleet coordinated through "
                         "the shared-directory plane (fenced lease "
                         "takeover), first CLEAN (A/B vs the 1-host "
                         "serial chain), then with one host SIGKILL'd "
                         "mid-sweep — survivors must ADOPT its "
                         "observation, every artifact must be "
                         "byte-identical to the serial run, and a final "
                         "no-fault resume must re-run ZERO stages "
                         "(BENCH_r13_multihost.json + HOSTCHAOS_r01.json)")
    ap.add_argument("--hostchaos-out", default="HOSTCHAOS_r01.json",
                    metavar="PATH",
                    help="with --multihost: where the host-kill chaos "
                         "record lands (default HOSTCHAOS_r01.json)")
    ap.add_argument("--trace-out", default="OBS_trace_r01.json",
                    metavar="PATH",
                    help="with --multihost: where the tlmtrace-stitched "
                         "Perfetto/Chrome-trace JSON of the host-kill "
                         "leg lands — the adoption is visible as a lane "
                         "handover on one trace_id (default "
                         "OBS_trace_r01.json; empty string disables)")
    ap.add_argument("--race", action="store_true",
                    help="seeded interleaving stress harness (psrrace): "
                         "a toy fleet on 2 in-process hosts + a leaving "
                         "ghost, claim/adopt + watchdog hang-interrupt "
                         "+ prefetch concurrently, setswitchinterval "
                         "cranked and seeded pauses injected at every "
                         "tracked lock boundary under "
                         "PYPULSAR_TPU_LOCKDEP=strict; asserts "
                         "byte-identical artifacts and zero lockdep "
                         "order violations per seed (RACE_rXX.json)")
    ap.add_argument("--race-seeds", type=int, default=2,
                    help="with --race: how many interleaving seeds to "
                         "run (default 2)")
    ap.add_argument("--chaos-seed", type=int, default=1,
                    help="with --chaos: the chaos seed (default 1)")
    ap.add_argument("--chaos-rate", type=float, default=None,
                    help="with --chaos: per-(point,hit) fault "
                         "probability (default 0.015, --quick 0.01)")
    ap.add_argument("--corruption", action="store_true",
                    help="run a toy fleet over INPUTS corrupted with "
                         "every data-fault kind (truncate, bitflip, "
                         "dropblock, NaN-burst, garbage header) plus one "
                         "clean control, assert the fleet completes "
                         "(degraded or data-quarantined per "
                         "--max-bad-frac policy, zero crashes), the "
                         "control's artifacts stay byte-identical to a "
                         "clean run, and the reader fuzz harness is "
                         "100%% clean — the data-integrity acceptance "
                         "measurement (CORRUPT_rXX.json)")
    ap.add_argument("--corruption-seed", type=int, default=1,
                    help="with --corruption: corruption + fuzz seed "
                         "(default 1)")
    ap.add_argument("--prepass", action="store_true",
                    help="benchmark the zero-DM + spectrogram + detrend "
                         "prepass (configs[1]) instead of the DM sweep")
    ap.add_argument("--stream", default=None, metavar="FIL",
                    help="run the north-star STREAMED sweep over this "
                         "on-disk filterbank (I/O included in the metric). "
                         "With no mode flags, bench.py auto-selects this "
                         "mode when data/northstar_1hr.fil exists")
    ap.add_argument("--stream-window", type=float, default=None,
                    metavar="SECONDS",
                    help="bound the streamed sweep to the first SECONDS of "
                         "the file (0 = whole file). The auto-selected "
                         "stream mode defaults to $BENCH_STREAM_WINDOW_S "
                         "or 900 so an unattended bench run stays under "
                         "~15 min; an explicit --stream defaults to the "
                         "whole file. The full-hour measured run is in "
                         "BENCHNOTES.md / BENCH_r04_full_stream.json")
    from pypulsar_tpu.obs.telemetry import add_telemetry_flag

    add_telemetry_flag(
        ap, what="spans + counters of the measured run; the final totals "
                 "also land in the JSON record's extras")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write the final JSON record to this file "
                         "(how the driver lands BENCH_rXX_*.json rows, "
                         "e.g. --waterfall --out BENCH_r06_waterfall.json)")
    return ap.parse_args(argv)


def acquire_backend():
    """The backend's device list. Raises what JAX raises: a backend that
    cannot be asked is an error, never a reason to measure elsewhere."""
    import jax

    return jax.devices()  # psrlint: ignore[PL002] -- raw inventory for the record's device keys, below the lease registry


def budget_shapes(C, T_req, plan, hbm_bytes):
    """(T, chunk_payload, n_fft, max_pending) fitting the HBM budget.

    Accounting: device dataset C*T*4; each in-flight chunk buffer C*n*4
    (padded to the FFT length); one executable workspace ~3 chunk buffers
    (rfft output + fused intermediates); 25% headroom for the allocator.
    """
    from pypulsar_tpu.parallel.sweep import default_chunk_payload

    payload = default_chunk_payload(plan)
    n = payload + plan.min_overlap  # round-5 chunk-length A/B, BENCHNOTES
    budget = 0.75 * hbm_bytes
    chunk_bytes = 4 * C * n
    workspace = 3 * chunk_bytes
    avail = budget - workspace - 2 * chunk_bytes  # >= 2 chunks in flight
    # charge the dataset TWICE: the resident path's compiled program holds
    # the input and its tail-padded working copy concurrently
    T = int(min(T_req, avail // (2 * 4 * C)))
    T = max(T, payload)
    max_pending = int((budget - workspace - 2 * 4 * C * T) // chunk_bytes)
    max_pending = max(1, min(4, max_pending))
    return T, payload, n, max_pending


def sweep_bytes(plan, C, T, payload, n, engine):
    """Analytic HBM traffic of the full sweep (dominant streams only)."""
    G, g, S = plan.n_groups, plan.group_size, plan.nsub
    D = G * g
    W = max(plan.widths)
    nchunks = -(-T // payload)
    F = n // 2 + 1
    out_len = payload + W
    if engine == "fourier":
        per_chunk = (
            4 * C * n + 8 * C * F  # rfft read + write
            + G * (8 * C * F + 8 * S * F)  # stage1 read X per group + write
            + 8 * D * S * F + 8 * D * F  # stage2 read + write
            + 8 * D * F + 4 * D * n  # irfft read + write
            + 2 * 4 * D * out_len  # boxcar read + stats
        )
    else:
        L1 = out_len + plan.max_shift2
        per_chunk = 4 * (G * C * L1 + G * S * L1 + D * S * out_len
                         + 2 * D * out_len)
    return per_chunk * nchunks


# ---------------------------------------------------------------------------
# NumPy-baseline measurement protocol (VERDICT r4 item 5). The host is a
# shared 1-core box whose speed varies >2x run to run; a baseline of record
# needs (a) >=5 repetitions with the median + spread recorded, (b) a
# loadavg gate with sleep-retry before each rep, (c) warn-and-rerun when
# the spread still exceeds 1.3x, and (d) a cross-check against a PINNED
# calibration workload so "the host was slow today" is detected even when
# the reps agree with each other.
# ---------------------------------------------------------------------------

# Pinned seconds for _cal_workload() measured on this host near-idle
# (loadavg 0.04, min of 5 = 0.123 s, reps 0.123-0.148; 2026-07-30,
# round 5). A bench-time measurement slower than ~1.3x this means the
# HOST is contended and every numpy baseline in that run is suspect.
NUMPY_CAL_SECONDS = 0.123


def _cal_workload():
    """Fixed single-core probe: dedisperse+boxcar of a [256, 65536] f64
    array (the baseline's own inner-loop math at a pinned shape). Data
    generation is excluded from the timing."""
    from pypulsar_tpu.ops import numpy_ref

    rng = np.random.RandomState(7)
    data = rng.standard_normal((256, 1 << 16))
    freqs = 1500.0 - np.arange(256.0)
    bins = numpy_ref.bin_delays(150.0, freqs, 64e-6)
    t0 = time.perf_counter()
    ts = numpy_ref.dedispersed_timeseries(data, bins)
    numpy_ref.boxcar_snr(ts, (1, 2, 4, 8, 16, 32))
    return time.perf_counter() - t0


def _loadavg() -> float:
    try:
        return os.getloadavg()[0]
    except (OSError, AttributeError):
        return -1.0


def wait_for_idle(gate: float = None, max_wait: float = 180.0) -> float:
    """Sleep-retry until 1-min loadavg < ``gate`` (default 0.5, override
    BENCH_LOADAVG_GATE); give up after ``max_wait`` s and proceed with a
    warning. Returns the loadavg seen when proceeding."""
    if gate is None:
        gate = float(os.environ.get("BENCH_LOADAVG_GATE", 0.5))
    deadline = time.monotonic() + max_wait
    load = _loadavg()
    while load >= gate and time.monotonic() < deadline:
        time.sleep(5.0)
        load = _loadavg()
    if load >= gate:
        print(f"# WARNING: loadavg {load:.2f} still >= {gate} after "
              f"{max_wait:.0f}s wait; baseline reps may be contended",
              file=sys.stderr)
    return load


def numpy_baseline(rep_fn, reps: int = 5, spread_limit: float = 1.3):
    """Measure a single-core NumPy baseline under the round-5 protocol.

    ``rep_fn()`` runs one full repetition and returns its seconds. The
    loadavg gate runs before EACH rep; if the spread of the first
    ``reps`` exceeds ``spread_limit`` the whole set is re-run once and
    the median is taken over all recorded reps. A calibration probe
    (min of 3 ``_cal_workload`` runs) is compared against the pinned
    idle-host value: ``cal_ratio`` > ~1.3 flags a host that is slow
    across the board. Returns a dict of the protocol's evidence fields.
    """
    all_reps = []

    def one_round():
        # full gate before the round; between reps only a short check —
        # a multi-second rep pushes the 1-min loadavg over the gate with
        # its OWN decaying footprint, and sleeping 180 s per rep to wait
        # out ourselves would stall the bench for nothing
        wait_for_idle()
        for i in range(reps):
            if i:
                wait_for_idle(max_wait=15.0)
            all_reps.append(rep_fn())

    one_round()
    spread = max(all_reps) / min(all_reps)
    reran = False
    used = all_reps
    if spread > spread_limit:
        print(f"# numpy baseline spread {spread:.2f}x > {spread_limit}x; "
              f"re-running the rep set", file=sys.stderr)
        reran = True
        one_round()
        # the rerun replaces the contended round: judge the spread AND
        # take the median over the second round alone (pooling the two
        # populations would skew the median while the spread field looks
        # clean); every recorded rep still lands in the JSON
        used = all_reps[reps:]
        spread = max(used) / min(used)
        if spread > spread_limit:
            print(f"# WARNING: spread {spread:.2f}x persists after rerun "
                  f"(load {_loadavg():.2f}); median of the rerun used",
                  file=sys.stderr)
    cal = min(_cal_workload() for _ in range(3))
    cal_ratio = (cal / NUMPY_CAL_SECONDS) if NUMPY_CAL_SECONDS else -1.0
    if cal_ratio > 1.3:
        print(f"# WARNING: host calibration {cal:.3f}s is "
              f"{cal_ratio:.2f}x the pinned idle value "
              f"({NUMPY_CAL_SECONDS:.3f}s) - numpy baselines this run "
              f"are inflated by host contention", file=sys.stderr)
    return {
        "seconds": float(np.median(used)),
        "numpy_seconds_reps": [round(r, 3) for r in all_reps],
        "numpy_rep_spread": round(spread, 3),
        "numpy_reps_reran": reran,
        "host_loadavg": round(_loadavg(), 2),
        "host_cal_seconds": round(cal, 4),
        "host_cal_ratio": round(cal_ratio, 3),
    }


def baseline_scale_check(small_rep, large_rep, factor: int = 10,
                         reps: int = 5):
    """Spot-check of the linear-extrapolation model behind every scaled
    NumPy baseline (VERDICT r5 item 7): time the twin on a ``factor``-x
    larger slice and report ``t_large / (factor * t_small)`` — ~1.0 means
    the extrapolation is sound; drift past ~±20% flags cache-size or
    allocator effects the scaling model misses. Loadavg-gated like the
    rep protocol; min-of-reps on both sides (the ratio wants the
    uncontended floor of each, not medians of different noise)."""
    wait_for_idle()
    t_small = min(small_rep() for _ in range(reps))
    t_large = min(large_rep() for _ in range(reps))
    ratio = t_large / (factor * t_small)
    if not 0.8 <= ratio <= 1.2:
        print(f"# WARNING: baseline_scale_check {ratio:.3f} outside "
              f"±20% - the linearly scaled baseline figures carry a "
              f"model error of that size", file=sys.stderr)
    return {
        "baseline_scale_check": round(ratio, 3),
        "baseline_scale_factor": factor,
        "baseline_scale_small_seconds": round(t_small, 4),
        "baseline_scale_large_seconds": round(t_large, 4),
    }


def run_benchmark(args):
    if args.quick:
        C = args.nchan or 128
        T_req = args.nsamp or 1 << 15
        D = args.trials or 64
        nb = args.baseline_trials or 2
        nsub, group = 32, 16
    else:
        C = args.nchan or 1024
        T_req = args.nsamp or 1 << 21  # ~134 s at 64 us
        D = args.trials or 1024
        nb = args.baseline_trials or 4
        nsub, group = 64, 32

    devs = acquire_backend()

    import jax
    import jax.numpy as jnp
    from pypulsar_tpu.core.spectra import Spectra
    from pypulsar_tpu.ops import numpy_ref
    from pypulsar_tpu.parallel import (
        choose_group_size,
        make_sweep_plan,
        sweep_spectra,
    )
    from pypulsar_tpu.parallel.sweep import resolve_engine, sweep_resident

    dt = 64e-6
    dev = devs[0]
    engine = resolve_engine(args.engine)

    freqs = (1500.0 - 300.0 / C * np.arange(C)).astype(np.float64)
    dms = np.linspace(0.0, args.dm_max, D)
    # stage-1 group from the smearing bound: dense trial grids afford
    # larger groups (measured 25% faster at g=64, BENCHNOTES.md)
    group = max(group, choose_group_size(dms, freqs, dt, nsub))
    plan = make_sweep_plan(dms, freqs, dt, nsub=nsub, group_size=group)
    if args.quick:
        from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len

        T, chunk, max_pending = T_req, min(T_req, 1 << 14), 2
        if plan.min_overlap >= chunk:
            chunk = fourier_chunk_len(plan.min_overlap * 2)
        n_fft = fourier_chunk_len(chunk + plan.min_overlap)
    else:
        T, chunk, n_fft, max_pending = budget_shapes(
            C, T_req, plan, device_hbm_bytes(dev))
        T = max((T // chunk) * chunk, chunk)  # whole chunks: single-dispatch path
    print(f"# device: {dev}, engine={engine}, C={C} chans, T={T} samples "
          f"({T*dt:.0f}s), D={D} trials 0-{args.dm_max}, chunk={chunk}, "
          f"max_pending={max_pending}", file=sys.stderr)

    def measure(T):
        # generate the dataset directly on device: the measured quantity is
        # the sweep engine, not the host->device transfer rate
        key = jax.random.PRNGKey(0)
        data = jax.random.normal(key, (C, T), dtype=jnp.float32)
        float(jnp.sum(data[0, :8]))  # force materialization
        spec = Spectra(freqs, dt, data)
        # single-dispatch whole-sweep program
        resident = T % chunk == 0
        def run():
            if resident:
                return sweep_resident(spec, dms, nsub=nsub,
                                      group_size=group, chunk_payload=chunk,
                                      engine=engine)
            return sweep_spectra(spec, dms, nsub=nsub, group_size=group,
                                 chunk_payload=chunk, engine=engine,
                                 max_pending=max_pending)
        if resident:
            run()  # compile + execute the real program once (cached runner)
        else:
            # streamed path: warm only the per-shape compiles on slices
            warm_lens = {min(T, chunk)}
            if T > chunk and T % chunk:
                warm_lens.add(T % chunk)
            for wl in warm_lens:
                warm = Spectra(freqs, dt, data[:, :wl])
                sweep_spectra(warm, dms, nsub=nsub, group_size=group,
                              chunk_payload=chunk, engine=engine,
                              max_pending=max_pending)
        if args.profile:
            from pypulsar_tpu.utils.profiling import stage_report

            profile_ctx = stage_report(file=sys.stderr)
        else:
            import contextlib

            profile_ctx = contextlib.nullcontext()
        with profile_ctx:
            # best of 2: a one-chip machine shares its host's cores, so
            # single host-clock readings spread
            jax_time = float("inf")
            for _ in range(1 if args.profile else 2):
                t0 = time.perf_counter()
                res = run()
                jax_time = min(jax_time, time.perf_counter() - t0)
        return res, jax_time

    res = None
    for attempt in range(6):
        try:
            res, jax_time = measure(T)
            break
        except Exception as e:  # noqa: BLE001 - OOM shrinks and retries
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            if T // 2 >= chunk:
                T = max(((T // 2) // chunk) * chunk, chunk)  # whole chunks
                print(f"# RESOURCE_EXHAUSTED; halving dataset to T={T}",
                      file=sys.stderr)
            elif n_fft // 2 > plan.min_overlap:
                # dataset is already one chunk: shrink the chunk itself
                n_fft //= 2
                chunk = n_fft - plan.min_overlap
                T = min(T, max(chunk, T // 2))
                print(f"# RESOURCE_EXHAUSTED; shrinking chunk to {chunk} "
                      f"(n_fft={n_fft})", file=sys.stderr)
            else:
                raise
    if res is None:
        raise RuntimeError("dataset would not fit on device at any size")
    trials_per_sec = D / jax_time

    # --- NumPy single-core baseline: reference-style brute force ---
    # Round-5 protocol (numpy_baseline): >=5 loadavg-gated reps, median +
    # spread + pinned-calibration cross-check recorded. Single
    # measurements have twice recorded contended-host outliers that
    # flipped vs_baseline by 2-11x.
    bl_T = min(T, 1 << 17)  # slice; scale linearly
    rng = np.random.RandomState(1)
    bl_data = rng.standard_normal((C, bl_T))  # same distribution; cost is data-independent

    def one_rep():
        t0 = time.perf_counter()
        for dm in dms[:: max(1, D // nb)][:nb]:
            bins = numpy_ref.bin_delays(dm, freqs, dt)
            ts = numpy_ref.dedispersed_timeseries(bl_data, bins)
            numpy_ref.boxcar_snr(ts, plan.widths)
        return time.perf_counter() - t0

    bl = numpy_baseline(one_rep)
    bl_time = bl["seconds"]
    bl_trials_per_sec = nb / (bl_time * (T / bl_T))
    speedup = trials_per_sec / bl_trials_per_sec

    # --- bandwidth accounting vs the HBM roofline ---
    nbytes = sweep_bytes(plan, C, T, chunk, n_fft, engine)
    hbm_frac = (nbytes / jax_time
                / device_peaks(dev.device_kind)["hbm_bytes_per_s"])

    # --- north-star extrapolation: same trials/s formula at 1 hr ---
    T_1hr = int(3600.0 / dt)
    trials_1hr = trials_per_sec * T / T_1hr

    print(f"# jax: {jax_time:.3f}s for {D} trials; numpy: {bl_time:.3f}s for "
          f"{nb} trials on {bl_T/T:.3f} of data; best cand: {res.best(1)[0]}",
          file=sys.stderr)
    print(f"# analytic HBM traffic {nbytes/1e9:.0f} GB -> "
          f"{nbytes/jax_time/1e9:.0f} GB/s ({hbm_frac*100:.0f}% of v5e "
          f"roofline); 1-hr extrapolation {trials_1hr:.1f} trials/s",
          file=sys.stderr)
    unit = (f"DM-trials/s ({C}-chan, {T*dt:.0f}s @ 64us, nsub={nsub}, "
            f"engine={engine}, best of 2 runs; numpy baseline median of "
            f"{len(bl['numpy_seconds_reps'])} loadavg-gated reps on "
            f"{bl_T/T:.2f} of the data x {nb}/{D} trials, scaled linearly)")
    return {
        "metric": "dm_trials_per_sec",
        "value": round(trials_per_sec, 2),
        "unit": unit,
        "vs_baseline": round(speedup, 2),
        "jax_seconds": round(jax_time, 3),
        "numpy_seconds_measured": round(bl_time, 3),
        **{k: v for k, v in bl.items() if k != "seconds"},
        "numpy_trials_measured": nb,
        "numpy_slice_frac": round(bl_T / T, 4),
        "hbm_frac": round(hbm_frac, 4),
        "hbm_gbps": round(nbytes / jax_time / 1e9, 1),
        "trials_per_sec_1hr_extrapolated": round(trials_1hr, 2),
        "nsamp": T,
        "engine": engine,
        "path": "resident" if T % chunk == 0 else "streamed",
        # SNR parity contract (VERDICT r3 item 7): engine=gather is the
        # bit-exact-SNR reference formulation; the fourier engine agrees
        # to the stated relative tolerance (FFT f32 rounding), asserted
        # by tests/test_sweep.py::test_fourier_engine_snr_tolerance.
        # Emitted only when the measured engine is the toleranced one.
        **({"snr_parity": "gather=bit-exact reference; fourier toleranced",
            "fourier_snr_rel_tol": 2e-6} if engine == "fourier" else {}),
    }


def run_ab(args):
    """Kernel A/B table (VERDICT r2 item 3): full-chunk engines + boxcar
    backends, timed on the live backend. Results land in BENCHNOTES.md."""
    acquire_backend()
    import jax
    import jax.numpy as jnp
    from functools import partial
    from pypulsar_tpu.ops.pallas_kernels import boxcar_stats
    from pypulsar_tpu.parallel import make_sweep_plan
    from pypulsar_tpu.parallel.sweep import sweep_chunk

    C, D = args.nchan or 1024, args.trials or 1024
    nsub, group = 64, 32
    dt = 64e-6
    freqs = (1500.0 - 300.0 / C * np.arange(C)).astype(np.float64)
    dms = np.linspace(0.0, args.dm_max, D)
    plan = make_sweep_plan(dms, freqs, dt, nsub=nsub, group_size=group)
    n = 1 << 17
    W = max(plan.widths)
    chunk = n - plan.min_overlap
    out_len = chunk + W
    need = out_len + plan.max_shift2 + plan.max_shift1
    key = jax.random.PRNGKey(0)
    data = jax.random.normal(key, (C, need), dtype=jnp.float32)
    s1 = jnp.asarray(plan.stage1_bins)
    s2 = jnp.asarray(plan.stage2_bins)
    float(jnp.sum(data[0, :8]))

    def force(out):
        return float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0])

    results = {}
    for engine in ("fourier", "gather"):
        try:
            fn = lambda: sweep_chunk(data, s1, s2, plan.nsub, out_len,
                                     plan.max_shift2, plan.widths, chunk,
                                     engine=engine)
            force(fn())
            t0 = time.perf_counter()
            force(fn())
            el = time.perf_counter() - t0
            results[f"chunk-{engine}"] = round(el, 4)
            print(f"# chunk-{engine:8s} {el*1e3:9.1f} ms "
                  f"({D / el:.1f} trials/s per chunk)", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - record and keep going
            results[f"chunk-{engine}"] = f"FAILED: {type(e).__name__}"
            print(f"# chunk-{engine} FAILED: {type(e).__name__}: "
                  f"{str(e)[:200]}", file=sys.stderr)
    # two-stage geometry variants (fourier engine): stage1 traffic scales
    # as (D/group)*C*F and stage2 as D*nsub*F — the sweet spot is chip-
    # dependent, so record a small grid
    for nsub2, group2 in ((64, 64), (32, 32), (128, 32)):
        try:
            plan2 = make_sweep_plan(dms, freqs, dt, nsub=nsub2,
                                    group_size=group2)
            chunk2 = n - plan2.min_overlap
            out_len2 = chunk2 + W
            need2 = out_len2 + plan2.max_shift2 + plan2.max_shift1
            data2 = jax.random.normal(key, (C, need2), dtype=jnp.float32)
            s1b = jnp.asarray(plan2.stage1_bins)
            s2b = jnp.asarray(plan2.stage2_bins)
            fn = lambda: sweep_chunk(data2, s1b, s2b, plan2.nsub, out_len2,
                                     plan2.max_shift2, plan2.widths, chunk2,
                                     engine="fourier")
            force(fn())
            t0 = time.perf_counter()
            force(fn())
            el = time.perf_counter() - t0
            results[f"fourier-s{nsub2}g{group2}"] = round(el, 4)
            print(f"# fourier nsub={nsub2} group={group2}: {el*1e3:9.1f} ms",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            results[f"fourier-s{nsub2}g{group2}"] = (
                f"FAILED: {type(e).__name__}")
            print(f"# fourier-s{nsub2}g{group2} FAILED: "
                  f"{type(e).__name__}: {str(e)[:200]}", file=sys.stderr)

    ts = jax.random.normal(key, (256, out_len), dtype=jnp.float32)
    float(ts[0, 0])
    for be in ("pallas", "lax"):
        try:
            fn = partial(boxcar_stats, ts, plan.widths, chunk, backend=be)
            force(fn())
            t0 = time.perf_counter()
            force(fn())
            results[f"boxcar-{be}"] = round(time.perf_counter() - t0, 4)
        except Exception as e:  # noqa: BLE001
            results[f"boxcar-{be}"] = f"FAILED: {type(e).__name__}"
    fourier_t = results.get("chunk-fourier", 0.0)
    return {
        "metric": "kernel_ab_seconds",
        # "value" must stay numeric whatever failed (the one-JSON-line
        # contract); string FAILED markers live in the extras only
        "value": fourier_t if isinstance(fourier_t, float) else 0.0,
        "unit": "s per 1024-trial chunk (see extras)",
        "vs_baseline": 0.0,
        **results,
    }


class _WindowedFilterbank:
    """FilterbankFile proxy bounded to the first ``nsamp`` samples, so an
    unattended bench run can measure the streamed path on a time window
    without losing the native prefetcher (iter_blocks passes ``end``
    through to PrefetchReader)."""

    BLOCK_ITER_ARRAYS = True

    def __init__(self, fb, nsamp: int):
        self._fb = fb
        self.number_of_samples = int(nsamp)

    @property
    def nspec(self):
        return self.number_of_samples

    @property
    def obs_duration(self):
        return self.number_of_samples * float(self._fb.tsamp)

    def __getattr__(self, name):
        return getattr(self._fb, name)

    def iter_blocks(self, block_size, overlap=0, **kw):
        kw.setdefault("end", self.number_of_samples)
        return self._fb.iter_blocks(block_size, overlap, **kw)


def run_stream(args):
    """North-star streamed sweep (VERDICT r3 item 1): a real on-disk
    filterbank through the native prefetcher + sweep_stream on the live
    chip, checkpointing on, HOST I/O INCLUDED in the measured wall time.

    The record's ``path`` field is "streamed" and its extras carry the
    per-stage wall breakdown (block_source = disk wait + host->device
    ship; device_wait = un-overlapped device time) plus a synchronous
    per-chunk compute probe, so the compute-vs-transfer overlap fraction
    is measured, not assumed."""
    acquire_backend()
    import jax
    import jax.numpy as jnp
    from pypulsar_tpu.io.filterbank import FilterbankFile
    from pypulsar_tpu.ops import numpy_ref
    from pypulsar_tpu.parallel import choose_group_size, make_sweep_plan
    from pypulsar_tpu.parallel.staged import sweep_flat
    from pypulsar_tpu.parallel.sweep import resolve_engine, sweep_chunk
    from pypulsar_tpu.utils import profiling

    fb = FilterbankFile(args.stream)
    C, dt = fb.nchans, float(fb.tsamp)
    file_T = int(fb.number_of_samples)
    window = getattr(args, "stream_window", None)
    T = file_T if not window else min(file_T, int(round(window / dt)))
    if T < file_T:
        fb = _WindowedFilterbank(fb, T)
    freqs = np.asarray(fb.frequencies, dtype=np.float64)
    D = args.trials or 4096
    dms = np.linspace(0.0, args.dm_max, D)
    engine = resolve_engine(args.engine)
    nsub = 64
    group = choose_group_size(dms, freqs, dt, nsub)
    plan = make_sweep_plan(dms, freqs, dt, nsub=nsub, group_size=group)
    from pypulsar_tpu.parallel.sweep import default_chunk_payload

    payload = default_chunk_payload(plan)
    file_gb = file_T * C * fb.nbits / 8 / 1e9
    streamed_gb = T * C * fb.nbits / 8 / 1e9
    nchunks = -(-T // payload)
    print(f"# streamed: {args.stream} C={C} T={T} of {file_T} "
          f"({T*dt:.0f}s of {file_T*dt:.0f}s; streaming {streamed_gb:.1f} "
          f"of {file_gb:.1f} GB {fb.nbits}-bit on disk) D={D} trials, "
          f"payload={payload}, {nchunks} chunks, engine={engine}",
          file=sys.stderr)

    # Synchronous pure-compute probe at the streamed shapes — run BEFORE
    # the timed stream so it doubles as the compile warm-up (the chunk
    # program jit-caches on these exact shapes). nchunks of these
    # estimates total device compute; compared against the profiled
    # device_wait it yields the fraction of compute hidden behind I/O.
    W = max(plan.widths)
    out_len = payload + W
    need = out_len + plan.max_shift2 + plan.max_shift1
    datap = jax.random.normal(jax.random.PRNGKey(0), (C, need),
                              dtype=jnp.float32)
    float(jnp.sum(datap[0, :4]))
    s1 = jnp.asarray(plan.stage1_bins)
    s2 = jnp.asarray(plan.stage2_bins)

    def one_chunk(stat_len=payload):
        out = sweep_chunk(datap, s1, s2, plan.nsub, out_len, plan.max_shift2,
                          plan.widths, stat_len, engine=engine)
        return float(jnp.asarray(out[0]).ravel()[0])

    one_chunk()  # compile at the streamed shapes
    t1 = time.perf_counter()
    one_chunk()
    chunk_s = time.perf_counter() - t1
    tail_stat = T - (nchunks - 1) * payload
    if 0 < tail_stat < payload:
        one_chunk(tail_stat)  # the tail chunk's distinct static stat_len
    del datap

    # one-block transfer probe: synchronous host->device ship of a real
    # block at the streamed dtype — nchunks of these estimates the wire
    # leg of the wall time
    raw0 = fb._read_raw_block(0, min(payload + plan.min_overlap, T))
    t1 = time.perf_counter()
    d0 = jax.device_put(np.ascontiguousarray(raw0))
    d0.block_until_ready()
    ship_s = time.perf_counter() - t1
    del d0, raw0
    print(f"# probes (and warm-up): compute {chunk_s*1e3:.0f} ms/chunk, "
          f"ship {ship_s*1e3:.0f} ms/block "
          f"({(payload + plan.min_overlap) * C * fb.nbits / 8 / ship_s / 1e6:.0f}"
          f" MB/s)", file=sys.stderr)

    # fresh checkpoint: a stale file from a killed run would silently
    # resume mid-file and inflate the trials/s of record
    ckpt = args.stream + ".ckpt.npz"
    for stale in (ckpt, ckpt + ".tmp.npz"):
        if os.path.exists(stale):
            os.remove(stale)
    t0 = time.perf_counter()
    with profiling.stage_report(file=sys.stderr) as rep:
        staged = sweep_flat(fb, dms, nsub=nsub, group_size=group,
                            chunk_payload=payload, engine=engine,
                            checkpoint_path=ckpt, checkpoint_every=32)
    wall = time.perf_counter() - t0
    totals = rep.totals()
    trials_per_sec = D / wall
    best = staged.best(1)[0]
    print(f"# wall {wall:.1f}s = {trials_per_sec:.2f} DM-trials/s over the "
          f"{T*dt:.0f}s file, I/O included; best: {best}", file=sys.stderr)

    # overlap accounting: with compute and transfer fully serialized the
    # wall would be est_compute + est_transfer; fully overlapped it would
    # be max() of them — report the fraction of the smaller leg hidden
    est_compute = chunk_s * nchunks
    est_transfer = ship_s * nchunks
    dev_wait = totals.get("device_wait+accumulate", 0.0)
    blk_src = totals.get("block_source", 0.0)
    smaller = min(est_compute, est_transfer)
    overlap = (max(0.0, min(1.0, (est_compute + est_transfer - wall)
                            / smaller)) if smaller > 0 else 0.0)
    print(f"# est compute {est_compute:.0f}s + est transfer "
          f"{est_transfer:.0f}s vs wall {wall:.0f}s -> {overlap*100:.0f}% "
          f"of the smaller leg overlapped (device_wait {dev_wait:.0f}s, "
          f"block_source {blk_src:.0f}s)", file=sys.stderr)

    # numpy single-core baseline on a real slice of this file (reference
    # brute-force semantics; round-5 protocol: >=5 loadavg-gated reps +
    # pinned-calibration cross-check, cf. numpy_baseline)
    bl_T = min(T, 1 << 17)
    nb = args.baseline_trials or 4
    bl_data = np.ascontiguousarray(fb.get_samples(0, bl_T).T
                                   ).astype(np.float64)

    def one_rep():
        tb = time.perf_counter()
        for dm in dms[:: max(1, D // nb)][:nb]:
            bins = numpy_ref.bin_delays(dm, freqs, dt)
            ts = numpy_ref.dedispersed_timeseries(bl_data, bins)
            numpy_ref.boxcar_snr(ts, plan.widths)
        return time.perf_counter() - tb

    bl = numpy_baseline(one_rep)
    bl_time = bl["seconds"]
    bl_trials_per_sec = nb / (bl_time * (T / bl_T))
    speedup = trials_per_sec / bl_trials_per_sec

    return {
        "metric": "dm_trials_per_sec",
        "value": round(trials_per_sec, 2),
        "unit": (f"DM-trials/s STREAMED from disk ({C}-chan, {T*dt:.0f}s"
                 + (f" window of a {file_T*dt:.0f}s" if T < file_T else "")
                 + f" {fb.nbits}-bit .fil, {streamed_gb:.1f} GB streamed, "
                 f"{D} trials, engine={engine}; wall includes disk read, "
                 f"host->device ship and checkpointing; numpy baseline "
                 f"median of {len(bl['numpy_seconds_reps'])} loadavg-gated "
                 f"reps on {bl_T/T:.4f} of the data x "
                 f"{nb}/{D} trials, scaled linearly)"),
        "vs_baseline": round(speedup, 2),
        "wall_seconds": round(wall, 1),
        "nsamp": T,
        "window_seconds": round(T * dt, 1),
        "file_seconds": round(file_T * dt, 1),
        "nchan": C,
        "file_gb": round(file_gb, 1),
        "streamed_gb": round(streamed_gb, 1),
        "nbits": fb.nbits,
        "chunks": nchunks,
        "stage_seconds": {k: round(v, 1) for k, v in totals.items()},
        "compute_per_chunk_s": round(chunk_s, 3),
        "ship_per_block_s": round(ship_s, 3),
        "est_compute_seconds": round(est_compute, 1),
        "est_transfer_seconds": round(est_transfer, 1),
        "io_overlap_frac": round(overlap, 3),
        "best_candidate": {k: (round(v, 4) if isinstance(v, float) else int(v)
                               if isinstance(v, (int, np.integer)) else v)
                           for k, v in best.items()},
        "numpy_seconds_measured": round(bl_time, 3),
        **{k: v for k, v in bl.items() if k != "seconds"},
        "engine": engine,
        "path": "streamed",
        **({"snr_parity": "gather=bit-exact reference; fourier toleranced",
            "fourier_snr_rel_tol": 2e-6} if engine == "fourier" else {}),
    }


def run_accel(args):
    """Acceleration-search throughput (BASELINE configs[4]: the reference
    defers this stage to PRESTO accelsearch on one core; our engine is
    fourier/accelsearch.py). Metric: searched (r, z) plane cells per
    second over the full harmonic ladder; baseline: the same correlation
    math in single-core NumPy (np.fft) measured on a slice of the z bank
    and one segment per stage, scaled linearly."""
    acquire_backend()
    from pypulsar_tpu.fourier.accelsearch import AccelSearchConfig, accel_search
    from pypulsar_tpu.fourier.zresponse import template_bank

    if args.quick:
        N, zmax, segw = 1 << 18, 50.0, 1 << 13
    else:
        N, zmax, segw = 1 << 21, 200.0, 1 << 14
    T = N * 128e-6
    rng = np.random.RandomState(0)
    ts = rng.standard_normal(2 * N).astype(np.float32)
    fft = (np.fft.rfft(ts) / np.sqrt(2 * N)).astype(np.complex64)[:N]
    cfg = AccelSearchConfig(zmax=zmax, dz=2.0, numharm=8, sigma_min=6.0,
                            seg_width=segw)
    Z = len(cfg.zs)

    # warm at the REAL shape (the stage runners' jit keys on the spectrum
    # length and segment count; a smaller warmup would not populate them).
    # accel_search handles the host->device transfer itself (as float
    # re/im planes, ops/transfer.py)
    accel_search(fft, T, cfg)
    t0 = time.perf_counter()
    cands = accel_search(fft, T, cfg)
    jax_time = time.perf_counter() - t0
    rlo = max(int(np.ceil(cfg.flo * T)), 1)
    # stage H searches the top-harmonic bins [H*rlo, N-1] at half-bin
    # resolution across Z drifts (fhi defaults to Nyquist here)
    cells = sum(2 * Z * max((N - 1) - H * rlo, 0) for H in cfg.stages)
    cells_per_sec = cells / jax_time

    # numpy baseline: one stage-1 segment's correlations (the engine's own
    # math with np.fft), scaled to the full cell count
    tb, hw = template_bank(cfg.zs, numbetween=2)
    L = 1
    while L < segw + 4 * hw:
        L <<= 1
    # dtype-matched to the engine (complex64) so the comparison is the
    # same math at the same precision
    padded = np.zeros((tb.shape[0], L), np.complex128)
    padded[:, : tb.shape[1]] = tb
    rev = np.zeros_like(padded)
    rev[:, 0] = padded[:, 0]
    rev[:, 1:] = padded[:, :0:-1]
    tf = np.fft.fft(rev, axis=1).astype(np.complex64)
    seg = fft[:L].astype(np.complex64)

    def _bl_rep(segments):
        t0 = time.perf_counter()
        for s in segments:
            sl = np.fft.fft(s)
            corr = np.fft.ifft(sl[None, :] * tf, axis=1)
            _ = (np.abs(corr) ** 2).astype(np.float32)
        return time.perf_counter() - t0

    bl_time = _bl_rep([seg])
    bl_cells = 2 * Z * segw  # one fundamental segment's worth
    bl_cells_per_sec = bl_cells / bl_time
    speedup = cells_per_sec / bl_cells_per_sec
    # linear-extrapolation spot check (VERDICT r5 item 7): 10 distinct
    # segments = a 10x slice of the same twin
    segs10 = [(fft[i * L // 16:i * L // 16 + L]
               if i * L // 16 + L <= len(fft) else seg).astype(np.complex64)
              for i in range(10)]
    scale_fields = baseline_scale_check(lambda: _bl_rep([seg]),
                                        lambda: _bl_rep(segs10), factor=10)

    print(f"# accel search: {jax_time:.2f}s for {cells/1e6:.0f}M cells "
          f"({len(cands)} cands); numpy slice {bl_time:.2f}s for "
          f"{bl_cells/1e6:.1f}M cells", file=sys.stderr)

    # --- batched search over the shared template bank (VERDICT r3 item 2:
    # the 4096-trial workload searches B spectra per configuration; the
    # banks are DM-independent so one dispatch a chunk serves them all).
    # OOM halves the batch and retries.
    batch_extras = {}
    value = cells_per_sec
    if args.batch and args.batch > 1:
        from pypulsar_tpu.fourier.accelsearch import accel_search_batch

        B = args.batch
        while B > 1:
            try:
                ffts = np.stack([
                    (np.fft.rfft(np.random.RandomState(100 + b)
                                 .standard_normal(2 * N)) / np.sqrt(2 * N))
                    .astype(np.complex64)[:N] for b in range(B)])
                accel_search_batch(ffts, T, cfg)  # warm at the real shape
                t0 = time.perf_counter()
                res_b = accel_search_batch(ffts, T, cfg)
                bt = time.perf_counter() - t0
                batch_cps = B * cells / bt
                batch_extras = {
                    "batch": B,
                    "batch_seconds": round(bt, 2),
                    "batch_cells_per_sec": round(batch_cps, 1),
                    "batch_vs_serial": round(batch_cps / cells_per_sec, 2),
                    "batch_cands": [len(c) for c in res_b],
                }
                value = batch_cps
                print(f"# batched x{B}: {bt:.2f}s = {batch_cps/1e6:.1f}M "
                      f"cells/s ({batch_cps/cells_per_sec:.2f}x serial)",
                      file=sys.stderr)
                break
            except Exception as e:  # noqa: BLE001 - OOM shrinks, else raise
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                B //= 2
                print(f"# batched accel RESOURCE_EXHAUSTED; retrying B={B}",
                      file=sys.stderr)

    unit = (f"(r,z) cells/s (N={N} bins, zmax={zmax:.0f}, dz=2, H<=8"
            + (f", batch={batch_extras['batch']}" if batch_extras else "")
            + "; numpy baseline from one segment x one stage, scaled "
              "linearly)")
    return {
        "metric": "accel_rz_cells_per_sec",
        "value": round(value, 1),
        "unit": unit,
        "vs_baseline": round(value / bl_cells_per_sec, 2),
        "serial_cells_per_sec": round(cells_per_sec, 1),
        "serial_vs_baseline": round(speedup, 2),
        "jax_seconds": round(jax_time, 3),
        "numpy_seconds_measured": round(bl_time, 3),
        **scale_fields,
        "n_candidates": len(cands),
        **batch_extras,
    }


def run_specfuse(args):
    """Spectral-fusion pipeline A/B (round 10 / ISSUE 10 acceptance):
    one toy pulsar observation through every sweep->accel handoff path
    under the SAME engine ('fourier', the TPU default — the decimate
    leg requires it and cross-engine series differ by design):

    - ``dat``:      sweep --write-dats (streamed writer) -> batched
                    accelsearch over the .dat files (the classic chain)
    - ``streamed``: the round-6 in-RAM handoff (irfft -> D2H -> H2D ->
                    rfft per trial)
    - ``fused``:    --spectral, stitched regime (series stays on
                    device; candidate tables asserted BYTE-identical to
                    the streamed leg, and the streamed leg to the .dat
                    leg — the full parity chain)
    - ``decimate``: --spectral + PYPULSAR_TPU_SPECFUSE_MODE=decimate
                    (zero transforms per trial; circular boundary
                    semantics, so parity is reported as measured, not
                    asserted byte-identical)

    The STRUCTURAL claim is the gate (MULTICHIP_r* methodology): the
    per-trial transform counts come from the telemetry counters
    (``specfuse.fft_pairs_elided`` = one irfft+rfft pair per trial on
    this single-chunk geometry), and the CPU-toy wall times are
    reported honestly as CPU-toy wall times."""
    acquire_backend()
    import glob as _glob
    import tempfile

    from pypulsar_tpu.obs import telemetry as _tlm

    C = 32
    T = 1 << 13 if args.quick else 1 << 15
    dtp = 5e-4
    D = 16
    freqs = 1500.0 - 4.0 * np.arange(C)
    sweep_args = ["--lodm", "0", "--dmstep", "5", "--numdms", str(D),
                  "-s", "8", "--group-size", "4", "--threshold", "8",
                  "--engine", "fourier"]
    accel_cfg = ["--accel-zmax", "20", "--accel-numharm", "2",
                 "--accel-sigma", "3", "--accel-batch", "8"]
    handoff = [*accel_cfg, "--accel-search", "--accel-only"]

    def cands(prefix):
        return {os.path.basename(f)[len(prefix):]: open(f, "rb").read()
                for f in sorted(_glob.glob(f"{prefix}_DM*_ACCEL_20.*cand"))}

    olddir = os.getcwd()
    # env knobs are pinned for the run and RESTORED after (pop would
    # clobber a user's preset; an inherited decimate mode would break
    # the stitched legs' byte-parity assertion spuriously)
    env_save = {k: os.environ.get(k) for k in
                ("PYPULSAR_TPU_DATS_RESIDENT_LIMIT",
                 "PYPULSAR_TPU_SPECFUSE_MODE")}
    with tempfile.TemporaryDirectory() as td:
        os.chdir(td)
        try:
            fil = _synth_survey_fil("psr.fil", 5, C, T, dtp, freqs,
                                    "SPECFUSE")
            from pypulsar_tpu.cli import accelsearch as cli_accel
            from pypulsar_tpu.cli import sweep as cli_sweep

            os.environ["PYPULSAR_TPU_DATS_RESIDENT_LIMIT"] = "0"
            os.environ["PYPULSAR_TPU_SPECFUSE_MODE"] = "stitch"

            # per-leg counters come from SNAPSHOT DIFFS of one shared
            # session: nested telemetry sessions reuse the outer
            # collector (the run_corruption pitfall), so per-leg trace
            # files would silently stay empty under an outer
            # --telemetry session
            with _tlm.session(tool="bench-specfuse") as tlm:
                def leg_counters(fn):
                    before = dict(tlm.counter_totals())
                    wall = fn()
                    after = tlm.counter_totals()
                    return wall, {k: v - before.get(k, 0)
                                  for k, v in after.items()
                                  if v != before.get(k, 0)}

                def run_dat(tag):
                    t0 = time.perf_counter()
                    assert cli_sweep.main([fil, "-o", tag, *sweep_args,
                                           "--write-dats"]) == 0
                    dats = sorted(_glob.glob(f"{tag}_DM*.dat"))
                    assert cli_accel.main([*dats, "--batch", "8", "-z",
                                           "20", "-n", "2", "-s", "3"]) == 0
                    return time.perf_counter() - t0

                def run_handoff(tag, extra=()):
                    def go():
                        t0 = time.perf_counter()
                        assert cli_sweep.main([fil, "-o", tag,
                                               *sweep_args, *handoff,
                                               *extra]) == 0
                        return time.perf_counter() - t0
                    return leg_counters(go)

                # each leg runs twice: the first pass compiles that
                # leg's kernels (jit caches are shared in-process), the
                # second is the measured wall — the same
                # warm-at-real-shape discipline every other bench leg
                # applies
                run_dat("wdat")
                wall_dat = run_dat("dat")
                run_handoff("wstr")
                wall_streamed, str_counters = run_handoff("str")
                run_handoff("wfus", ["--spectral"])
                wall_fused, fus_counters = run_handoff("fus",
                                                       ["--spectral"])
                os.environ["PYPULSAR_TPU_SPECFUSE_MODE"] = "decimate"
                run_handoff("wdec", ["--spectral"])
                wall_dec, dec_counters = run_handoff("dec",
                                                     ["--spectral"])

            c_dat, c_str = cands("dat"), cands("str")
            c_fus, c_dec = cands("fus"), cands("dec")
            assert c_str == c_dat, "streamed vs .dat parity broke"
            assert c_fus == c_str, "fused(stitched) vs streamed parity broke"
            dec_identical = sum(c_dec[k] == c_str[k] for k in c_str)
        finally:
            for k, v in env_save.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            os.chdir(olddir)

    pairs_elided = dec_counters.get("specfuse.fft_pairs_elided", 0)
    unit = (f"fused vs streamed vs .dat walls, CPU-toy geometry "
            f"({C}-chan x {T}-samp x {D} trials, zmax=20, H<=2, "
            f"engine=fourier); the GATE is structural: transforms/trial "
            f"from telemetry counters, sift parity asserted")
    return {
        "metric": "specfuse_ab",
        # headline: the fused stitched path vs the streamed handoff
        "value": round(wall_streamed / wall_fused, 3),
        "unit": unit,
        "wall_dat_chain_s": round(wall_dat, 2),
        "wall_streamed_s": round(wall_streamed, 2),
        "wall_fused_s": round(wall_fused, 2),
        "wall_decimate_s": round(wall_dec, 2),
        "parity": {
            "streamed_vs_dat": "byte-identical (asserted)",
            "fused_vs_streamed": "byte-identical (asserted)",
            "decimate_vs_streamed": f"{dec_identical}/{len(c_str)} tables "
                                    f"byte-identical (circular boundary "
                                    f"semantics; opt-in regime, see "
                                    f"specfuse docstring)",
        },
        "transforms_per_trial": {
            # single-chunk geometry: the streamed path pays one sweep
            # irfft + one prep rfft per trial; fused(stitched) pays the
            # same two but keeps the series on device; decimate pays 0
            "streamed": 2,
            "fused_stitched": 2,
            "fused_decimate": 0,
        },
        "fft_pairs_elided_decimate": int(pairs_elided),
        "series_bytes_kept_on_device_fused": int(
            fus_counters.get("specfuse.bytes_on_device", 0)),
        "chunks_stitched_fused": int(
            fus_counters.get("specfuse.chunks_stitched", 0)),
        "d2h_bytes": {
            "streamed": int(str_counters.get("d2h.bytes", 0)),
            "fused": int(fus_counters.get("d2h.bytes", 0)),
            "decimate": int(dec_counters.get("d2h.bytes", 0)),
        },
        "n_trials": D,
    }


def run_fold(args):
    """Folding-engine throughput (BASELINE configs[3]: polyco fold +
    profile accumulation; the reference folds one rotation at a time in
    Python, formats/datfile.py:231-275). Metric: samples folded/s into a
    [npart, nchan, nbins] archive cube (all raw channels kept — the
    .pfd-style product before subbanding) via the device scatter-add
    engine vs the single-core NumPy bincount twin."""
    acquire_backend()
    import jax.numpy as jnp
    from pypulsar_tpu.fold.engine import fold_numpy, fold_parts, phase_to_bins

    if args.quick:
        C, T = 64, 1 << 18
    else:
        # fits HBM with headroom: dataset 4 GB on the 16 GB v5e (there is
        # no streaming/retry here — a single resident cube is the measure)
        C, T = 1024, 1 << 20
    nbins, npart = 128, 64
    dt, period = 64e-6, 0.033
    # float32 generation: a float64 intermediate would double host peak
    data = np.random.default_rng(0).standard_normal((C, T),
                                                    dtype=np.float32)
    t = np.arange(T) * dt
    phase = t / period
    bin_idx = phase_to_bins(phase, nbins)
    part_len = T // npart

    dev = jnp.asarray(data)
    bi = jnp.asarray(bin_idx)
    float(dev[0, 0])

    def run():
        # whole [npart, C, nbins] cube in ONE dispatch (fold_parts), not
        # one dispatch per partition
        profs, _ = fold_parts(dev, bi, nbins, npart)
        return np.asarray(profs)

    run()  # warm
    # min-of-3: single host-clock readings spread run to run
    jax_time = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        profs = run()
        jax_time = min(jax_time, time.perf_counter() - t0)
    samples_per_sec = C * T / jax_time
    # split out the device compute from the cube's device->host pull
    # (33 MB); both are reported. kernel_time syncs on a scalar pull, so
    # it includes one dispatch's round trip — kernel_samples_per_sec is
    # a LOWER bound
    kernel_time = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        profs_dev, _ = fold_parts(dev, bi, nbins, npart)
        float(jnp.ravel(profs_dev)[0])
        kernel_time = min(kernel_time, time.perf_counter() - t0)
    kernel_samples_per_sec = C * T / kernel_time

    # fused fold + ON-DEVICE profile statistics (VERDICT r3 item 4): the
    # archive cube stays on device; what comes back to the host is per-part/
    # per-chan profiles, data moments and the bestprof chi2 grid (~KBs,
    # not 33 MB) — this is the END-TO-END path of record
    from pypulsar_tpu.fold.engine import bestprof_offsets, fold_stats

    _, off = bestprof_offsets(npart, T * dt, period, ntrial=65)
    offd = jnp.asarray(off)
    float(offd[0, 0])

    def run_fused():
        # one batched pull — per-array np.asarray syncs once per output
        # (ops/transfer.pull_host)
        from pypulsar_tpu.ops.transfer import pull_host

        return list(pull_host(*fold_stats(dev, bi, nbins, npart, offd)))

    run_fused()  # warm
    fused_time = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fused = run_fused()
        fused_time = min(fused_time, time.perf_counter() - t0)
    fused_samples_per_sec = C * T / fused_time

    # numpy twin on one partition, scaled linearly
    t0 = time.perf_counter()
    ref, _ = fold_numpy(data[:, :part_len], bin_idx[:part_len], nbins)
    bl_time = (time.perf_counter() - t0) * npart
    # zero-mean channel sums: f32 accumulation error is absolute-scale
    # (~1e-3 at these shapes), so an atol is required alongside rtol
    np.testing.assert_allclose(profs[0].sum(axis=0),
                               ref.sum(axis=0), rtol=1e-3, atol=0.5)
    np.testing.assert_allclose(fused[0][0], ref.sum(axis=0), rtol=1e-3,
                               atol=0.5)  # fused part_profs[0] twin-checked
    bl_samples_per_sec = C * T / bl_time
    speedup = fused_samples_per_sec / bl_samples_per_sec
    try:
        pipe_extras = _fold_pipeline_ab(args)
    except Exception as e:  # noqa: BLE001 - the headline must still land
        print(f"# fold pipeline A/B failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        pipe_extras = {"fold_pipe_error": f"{type(e).__name__}: {e}"}
    print(f"# fold: fused stats {fused_time:.3f}s = "
          f"{fused_samples_per_sec/1e9:.2f} Gsamp/s end-to-end "
          f"(kernel {kernel_time:.3f}s = "
          f"{kernel_samples_per_sec/1e9:.2f} Gsamp/s; full-cube pull "
          f"{jax_time:.2f}s); numpy 1/{npart} slice {bl_time/npart:.2f}s",
          file=sys.stderr)
    unit = (f"folded samples/s ({C}-chan, {T} samples, {nbins} bins, "
            f"{npart} partitions, min of 3 runs, END-TO-END through the "
            f"fused on-device stats path (profiles + moments + bestprof "
            f"chi2 pulled, cube stays on device); kernel-only and "
            f"cube-pull rates in extras; numpy baseline one partition "
            f"x{npart})")
    return {
        "metric": "fold_samples_per_sec",
        "value": round(fused_samples_per_sec, 1),
        "unit": unit,
        "vs_baseline": round(speedup, 2),
        "fused_seconds": round(fused_time, 3),
        "fused_vs_kernel": round(fused_time / kernel_time, 2),
        "cube_pull_seconds": round(jax_time, 3),
        "cube_pull_samples_per_sec": round(samples_per_sec, 1),
        "kernel_seconds": round(kernel_time, 3),
        "kernel_samples_per_sec": round(kernel_samples_per_sec, 1),
        "numpy_seconds_scaled": round(bl_time, 3),
        **pipe_extras,
    }


def _fold_pipeline_ab(args):
    """Batched candidate-fold PIPELINE A/B (the round-8 tentpole's
    acceptance measurement), two legs:

    PARITY (per-DM .dat series): ``foldbatch --datbase`` vs one
    in-process ``prepfold`` call per candidate on the same series — the
    archives must be BYTE-identical (profs + stats arrays; the batched
    one-hot fold runs the identical per-candidate contraction, so the
    f32 accumulation matches bitwise) and the derived SNRs equal.

    SPEEDUP (raw .fil): ``foldbatch <fil> --cands`` streams the
    observation ONCE (dedisperse via the sweep chunk kernel, one batched
    fold per DM group, on-device (p, pdot) refinement) vs the serial
    workflow it replaces — one ``prepfold`` call per candidate, each
    re-reading the raw file (measured on a subset and scaled linearly,
    the bench's standing baseline pattern). The serial loop runs
    in-process: a chip belongs to one process, so a fresh interpreter
    per candidate cannot be measured beside the one that holds it."""
    import tempfile

    from pypulsar_tpu.cli import foldbatch as cli_foldbatch
    from pypulsar_tpu.cli import prepfold as cli_prepfold
    from pypulsar_tpu.fold import profile_snr
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.io.datfile import write_dat
    from pypulsar_tpu.io.infodata import InfoData
    from pypulsar_tpu.io.prestopfd import PfdFile

    ndm, per_dm = 4, 8  # 32 candidates, the acceptance floor
    Np = 1 << 15 if args.quick else 1 << 16
    C, dtp = 32, 5e-4
    nbins, npart = 64, 16
    rng = np.random.default_rng(7)
    dms = [10.0 * (d + 1) for d in range(ndm)]
    cand_rows = []
    t = np.arange(Np) * dtp
    olddir = os.getcwd()
    with tempfile.TemporaryDirectory() as td:
        os.chdir(td)
        try:
            # toy observation: C-channel .fil with one dispersed pulse
            # train, plus the per-DM dedispersed .dat series the parity
            # leg folds (same noise seed per DM so the series are stable)
            for d, dm in enumerate(dms):
                base_p = 0.0517 * (1.0 + 0.13 * d)
                ts = rng.standard_normal(Np).astype(np.float32)
                ts += 3.0 * np.exp(
                    -0.5 * (((t / base_p) % 1.0 - 0.4) / 0.03) ** 2
                ).astype(np.float32)
                inf = InfoData()
                inf.epoch, inf.dt, inf.N = 55000.0, dtp, Np
                inf.telescope, inf.object = "Fake", "BENCH"
                inf.lofreq, inf.BW = 1400.0, 100.0
                inf.numchan, inf.chan_width = 1, 100.0
                inf.DM = dm
                write_dat(f"toy_DM{dm:.2f}", ts, inf)
                for j in range(per_dm):
                    cand_rows.append((base_p * (1.0 + 0.021 * j), dm))
            fildata = rng.standard_normal((Np, C)).astype(np.float32) * 2.0
            phase = (t / 0.0731) % 1.0
            fildata += 8.0 * np.exp(
                -0.5 * ((phase - 0.5) / 0.03) ** 2
            ).astype(np.float32)[:, None]
            filterbank.write_filterbank(
                "toy.fil", dict(nchans=C, tsamp=dtp, fch1=1500.0,
                                foff=-4.0, tstart=55000.0, nbits=32,
                                nifs=1, source_name="BENCH"), fildata)
            with open("cands.txt", "w") as f:
                f.writelines(f"{p!r} {dm}\n" for p, dm in cand_rows)
            n = len(cand_rows)

            # -- parity leg (.dat series, in-process both sides) --------
            t0 = time.perf_counter()
            for i, (p, dm) in enumerate(cand_rows):
                rc = cli_prepfold.main(
                    [f"toy_DM{dm:.2f}.dat", "-p", repr(p), "--dm",
                     str(dm), "-n", str(nbins), "--npart", str(npart),
                     "-o", f"serial_{i:04d}.pfd"])
                assert rc == 0
            dat_serial_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            rc = cli_foldbatch.main(
                ["--cands", "cands.txt", "--datbase", "toy", "-o", "bb",
                 "-n", str(nbins), "--npart", str(npart)])
            assert rc == 0
            dat_pipe_s = time.perf_counter() - t0

            import json as _json

            summary = _json.load(open("bb_foldbatch.json"))
            results = [r for r in summary["results"]
                       if not r.get("skipped")]
            # join on the candNNNN index encoded in the name, and fail
            # LOUDLY if any candidate is missing — a positional zip
            # would silently misalign every comparison after one
            # failed fold
            assert len(results) == len(cand_rows), (
                f"foldbatch folded {len(results)}/{len(cand_rows)}")
            identical = 0
            snr_diff = 0.0
            for res in results:
                i = int(res["name"][4:8])
                a = PfdFile(f"serial_{i:04d}.pfd")
                b = PfdFile(res["pfd"])
                if (np.array_equal(a.profs, b.profs)
                        and np.array_equal(a.stats, b.stats)):
                    identical += 1
                try:
                    sa = profile_snr.pfd_snr(a)["snr"]
                    sb = profile_snr.pfd_snr(b)["snr"]
                    snr_diff = max(snr_diff, abs(sa - sb))
                except profile_snr.OnPulseError:
                    pass  # a noise fold with no on-pulse: nothing to score

            # -- speedup leg (raw .fil) ---------------------------------
            t0 = time.perf_counter()
            rc = cli_foldbatch.main(
                ["toy.fil", "--cands", "cands.txt", "-o", "ff",
                 "-n", str(nbins), "--npart", str(npart), "-s", "8",
                 "--group-size", "4"])
            assert rc == 0
            pipe_s = time.perf_counter() - t0
            n_serial = min(6, n)  # subset, scaled linearly (cost is
            # per-invocation constant + per-sample linear, both measured)
            t0 = time.perf_counter()
            for i, (p, dm) in enumerate(cand_rows[:n_serial]):
                rc = cli_prepfold.main(
                    ["toy.fil", "-p", repr(p), "--dm", str(dm),
                     "-n", str(nbins), "--npart", str(npart),
                     "-o", f"rawi_{i:04d}.pfd"])
                assert rc == 0
            serial_s = (time.perf_counter() - t0) * (n / n_serial)

            print(f"# fold pipe A/B: raw-file serial loop "
                  f"{serial_s:.1f}s est ({n / serial_s:.2f} cand/s, "
                  f"{n_serial} in-process prepfold calls measured) vs "
                  f"streamed batched "
                  f"{pipe_s:.2f}s ({n / pipe_s:.2f} cand/s) = "
                  f"{serial_s / pipe_s:.1f}x; .dat parity leg "
                  f"{dat_serial_s / dat_pipe_s:.1f}x with {identical}/"
                  f"{n} archives byte-identical, max |dSNR| "
                  f"{snr_diff:.2e}", file=sys.stderr)
            return {
                "fold_pipe_n_cands": n,
                "fold_pipe_n_dms": ndm,
                "fold_pipe_nsamp": Np,
                "fold_pipe_nchan": C,
                "fold_pipe_cands_per_sec": round(n / pipe_s, 2),
                "fold_pipe_serial_cands_per_sec": round(n / serial_s, 3),
                "fold_pipe_speedup": round(serial_s / pipe_s, 2),
                "fold_pipe_seconds": round(pipe_s, 3),
                "fold_pipe_serial_seconds_est": round(serial_s, 2),
                "fold_pipe_serial_invocations_measured": n_serial,
                "fold_pipe_dat_speedup":
                    round(dat_serial_s / dat_pipe_s, 2),
                "fold_pipe_archives_identical": f"{identical}/{n}",
                "fold_pipe_max_snr_diff": float(snr_diff),
            }
        finally:
            os.chdir(olddir)


def _synth_survey_fil(fn, seed, C, T, dtp, freqs, src_name,
                      dm=40.0, period=0.1024, amp=10.0,
                      tstart=55000.0):
    """One synthetic pulsar filterbank for the survey/chaos harnesses
    (shared so the two A/Bs can never drift apart on the recipe).
    ``tstart`` lets the candplane A/B re-observe the same pulsar at
    several epochs; every other harness keeps the 55000.0 default."""
    import numpy as np

    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.ops import numpy_ref

    rng = np.random.RandomState(seed)
    data = rng.randn(T, C).astype(np.float32) * 2.0 + 30.0
    bins = numpy_ref.bin_delays(dm, freqs, dtp)
    for t0 in np.arange(0.01, T * dtp, period):
        s0 = int(t0 / dtp)
        for c in range(C):
            idx = s0 + bins[c]
            if idx < T:
                data[idx, c] += amp
    filterbank.write_filterbank(
        fn, dict(nchans=C, tsamp=dtp, fch1=float(freqs[0]),
                 foff=-4.0, tstart=float(tstart), nbits=32, nifs=1,
                 source_name=src_name), data)
    return fn


def run_survey(args):
    """Survey-orchestrator A/B (the round-9 tentpole's acceptance
    measurement): the SAME per-observation stage chain (rfifind-mask ->
    sweep --accel-search --write-dats -> sift -> foldbatch -> pfd_snr,
    identical in-process CLI argvs) over a 4-observation toy fleet, run
    two ways —

    - **serial**: one observation at a time, one stage at a time (the
      shell-loop workflow the orchestrator replaces);
    - **orchestrated**: the fleet scheduler, one device lease + a
      2-worker host pool, so observation B's sift/SNR summaries overlap
      observation A's device stages.

    Both legs run after a full warmup chain (jit caches hot — the A/B
    measures orchestration, not compilation). Artifacts are checked
    byte-identical across legs (.txtcand candidate tables and .pfd
    archives), so the speedup is overlap, not skipped work."""
    acquire_backend()
    import glob as _glob
    import tempfile

    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.ops import numpy_ref
    from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    n_obs = 4
    C, T, dtp = 32, (1 << 14 if args.quick
                     else 1 << 15), 5e-4
    rng_freqs = 1500.0 - 4.0 * np.arange(C)
    cfg = SurveyConfig(
        mask=True, mask_time=2.0, lodm=0.0, dmstep=10.0, numdms=8,
        nsub=8, group_size=4, threshold=8.0,
        accel_zmax=20.0, accel_numharm=2, accel_sigma=3.0, accel_batch=4,
        sift_sigma=3.0, sift_min_hits=1, fold_nbins=32, fold_npart=8)
    stages = build_dag(cfg)

    def make_obs_fil(fn, seed, dm=40.0, period=0.1024, amp=10.0):
        return _synth_survey_fil(fn, seed, C, T, dtp, rng_freqs,
                                 f"BENCH{seed}", dm=dm, period=period,
                                 amp=amp)

    def run_serial(obs_list):
        for obs in obs_list:
            for stage in stages:
                stage.execute(obs, cfg)

    with tempfile.TemporaryDirectory() as td:
        fils = [make_obs_fil(os.path.join(td, f"obs{i}.fil"), seed=11 + i,
                             period=0.1024 * (1.0 + 0.07 * i))
                for i in range(n_obs)]

        def fleet(dirname):
            out = os.path.join(td, dirname)
            os.makedirs(out, exist_ok=True)
            return [Observation(f"obs{i}", fils[i],
                                os.path.join(out, f"obs{i}"))
                    for i in range(n_obs)]

        # warmup: one full chain compiles every stage's jit programs
        run_serial(fleet("warm")[:1])

        t0 = time.perf_counter()
        run_serial(fleet("serial"))
        serial_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        result = FleetScheduler(fleet("orch"), cfg, max_host_workers=2,
                                devices=1).run()
        orch_s = time.perf_counter() - t0
        assert result.ok and len(result.ran) == n_obs * len(stages)

        # parity: the orchestrated fleet's candidate tables and archives
        # are byte-identical to the serial chain's — enforced, not just
        # reported: a speedup over divergent/missing work is not a win
        def _parity(dir_a, dir_b):
            ident = tot = 0
            for pattern in ("*_ACCEL_*.cand", "*_ACCEL_*.txtcand",
                            "*_cand*.pfd"):
                for fa in sorted(_glob.glob(os.path.join(td, dir_a,
                                                         pattern))):
                    fb = os.path.join(td, dir_b, os.path.basename(fa))
                    tot += 1
                    if (os.path.exists(fb) and open(fa, "rb").read()
                            == open(fb, "rb").read()):
                        ident += 1
            return ident, tot

        identical, total = _parity("serial", "orch")
        assert identical == total and total > 0, \
            f"orchestrated artifacts diverged: {identical}/{total}"

        # multi-chip leg (round 11): the SAME fleet with k device
        # leases + gang auto — fleet-parallel while ready device stages
        # fill the chips, gang-widened (`sweep --mesh k` over the
        # leased chips) when they would idle. Byte-parity is asserted
        # against BOTH the serial chain and the 1-device orchestrated
        # run: placement is not science
        orchk_s = None
        identical_k = total_k = None
        gang_decisions = []
        if args.devices > 1:
            import jax

            ndev = len(jax.devices())  # psrlint: ignore[PL002] -- fleet capacity check against the REAL inventory, outside any lease
            assert ndev >= args.devices, (
                f"--devices {args.devices} needs that many JAX devices, "
                f"have {ndev} (CPU recipe: XLA_FLAGS="
                f"--xla_force_host_platform_device_count=8)")
            # warm EVERY chip's jit caches, not just device 0's: stages
            # pin via jax.default_device and executables are
            # per-device, so an unwarmed chip would recompile the whole
            # chain inside the timed leg. One fleet-parallel pass warms
            # the k per-device 1-chip programs, one gang pass warms the
            # mesh-sharded (gang-width) programs
            FleetScheduler(fleet("warmk"), cfg, max_host_workers=2,
                           devices=args.devices, gang=1).run()
            FleetScheduler(fleet("warmg")[:1], cfg, max_host_workers=2,
                           devices=args.devices,
                           gang=args.devices).run()
            tlm_k = os.path.join(td, "tlm_k")
            t0 = time.perf_counter()
            result_k = FleetScheduler(
                fleet("orchk"), cfg, max_host_workers=2,
                devices=args.devices, gang="auto",
                telemetry_dir=tlm_k).run()
            orchk_s = time.perf_counter() - t0
            assert result_k.ok \
                and len(result_k.ran) == n_obs * len(stages)
            identical_k, total_k = _parity("serial", "orchk")
            assert identical_k == total_k and total_k > 0, (
                f"multi-chip artifacts diverged from the serial chain: "
                f"{identical_k}/{total_k}")
            ik, tk = _parity("orch", "orchk")
            assert ik == tk and tk > 0, (
                f"multi-chip artifacts diverged from the 1-device "
                f"orchestrated run: {ik}/{tk}")

            # the single-observation shape (the tentpole itself): a LONE
            # observation on k idle chips gang-widens (`sweep --mesh k`
            # over the leased gang) — timed against the same observation
            # through the serial 1-chip chain, artifacts byte-checked
            t0 = time.perf_counter()
            run_serial(fleet("serial1")[:1])
            serial1_s = time.perf_counter() - t0
            tlm_g = os.path.join(td, "tlm_g")
            t0 = time.perf_counter()
            result_g = FleetScheduler(
                fleet("gangk")[:1], cfg, max_host_workers=2,
                devices=args.devices, gang="auto",
                telemetry_dir=tlm_g).run()
            gang_s = time.perf_counter() - t0
            assert result_g.ok and len(result_g.ran) == len(stages)
            ig, tg = _parity("serial1", "gangk")
            assert ig == tg and tg > 0, (
                f"gang-leased artifacts diverged: {ig}/{tg}")

            # the recorded placement decisions (the obs traces carry
            # the same survey.gang_decision events the fleet trace does)
            gang_decisions_g = []
            for tdir, sink in ((tlm_k, gang_decisions),
                               (tlm_g, gang_decisions_g)):
                for p in sorted(_glob.glob(os.path.join(tdir, "*.jsonl"))):
                    for line in open(p):
                        try:
                            rec = json.loads(line)
                        except json.JSONDecodeError:
                            continue
                        if (rec.get("type") == "event"
                                and rec.get("name")
                                == "survey.gang_decision"):
                            sink.append(rec.get("attrs", {}))
            # the widening claim is about the LONE-obs leg only; the
            # fleet leg's decisions must not be able to satisfy it
            assert any(d.get("k", 1) > 1 for d in gang_decisions_g), \
                "the lone observation never gang-widened"
            gang_decisions.extend(gang_decisions_g)

    speedup = serial_s / orch_s
    print(f"# survey A/B: serial chain {serial_s:.2f}s vs orchestrated "
          f"{orch_s:.2f}s = {speedup:.2f}x ({n_obs} obs x "
          f"{len(stages)} stages, 1 device lease + 2 host workers; "
          f"{identical}/{total} artifacts byte-identical)",
          file=sys.stderr)
    unit = (f"orchestrated-fleet speedup over the serial per-observation "
            f"chain ({n_obs} toy obs x {len(stages)} stages "
            f"[mask/sweep+accel/sift/fold/snr], {C}-chan x {T}-sample "
            f"each, warm jit caches, 1 device lease + 2 host workers — "
            f"host-stage/device-stage overlap only, artifacts "
            f"byte-checked against the serial legs)")
    record = {
        "metric": "survey_fleet_speedup",
        "value": round(speedup, 3),
        "unit": unit,
        "vs_baseline": round(speedup, 3),
        "survey_n_obs": n_obs,
        "survey_n_stages": len(stages),
        "survey_serial_seconds": round(serial_s, 3),
        "survey_orchestrated_seconds": round(orch_s, 3),
        "survey_stages_run": len(result.ran),
        "survey_max_host_workers": 2,
        "survey_devices": 1,
        "survey_artifacts_identical": f"{identical}/{total}",
        "survey_nsamp": T,
        "survey_nchan": C,
    }
    if orchk_s is not None:
        speedup_k = serial_s / orchk_s
        n_gang = sum(1 for d in gang_decisions if d.get("k", 1) > 1)
        print(f"# survey multi-chip: {args.devices} device leases + gang "
              f"auto {orchk_s:.2f}s = {speedup_k:.2f}x vs serial "
              f"({orch_s / orchk_s:.2f}x vs 1-device orchestrated; "
              f"{len(gang_decisions)} placement decisions, {n_gang} "
              f"gang-widened; {identical_k}/{total_k} artifacts "
              f"byte-identical to the serial chain)", file=sys.stderr)
        print(f"# survey 1-obs gang: serial chain {serial1_s:.2f}s vs "
              f"gang x{args.devices} {gang_s:.2f}s = "
              f"{serial1_s / gang_s:.2f}x (one observation spanning "
              f"{args.devices} chips end to end, artifacts "
              f"byte-identical)", file=sys.stderr)
        record.update({
            "metric": "survey_multichip_speedup",
            "value": round(speedup_k, 3),
            "vs_baseline": round(speedup_k, 3),
            "unit": unit.replace(
                "1 device lease + 2 host workers",
                f"{args.devices} device leases (gang auto: fleet-"
                f"parallel + gang-widening onto idle chips) + 2 host "
                f"workers").replace(
                "byte-checked against the serial legs",
                "byte-checked against BOTH the serial chain and the "
                "1-device orchestrated run"),
            "survey_devices": args.devices,
            "survey_multichip_seconds": round(orchk_s, 3),
            "survey_orchestrated_1dev_speedup": round(speedup, 3),
            "survey_multichip_vs_1dev": round(orch_s / orchk_s, 3),
            "survey_multichip_artifacts_identical":
                f"{identical_k}/{total_k}",
            "survey_1obs_serial_seconds": round(serial1_s, 3),
            "survey_1obs_gang_seconds": round(gang_s, 3),
            "survey_1obs_gang_speedup": round(serial1_s / gang_s, 3),
            "survey_gang_decisions": len(gang_decisions),
            "survey_gang_widened": n_gang,
            "survey_gang_reasons": sorted(
                {d.get("reason", "?") for d in gang_decisions})[:6],
        })
        try:
            import jax

            platform = jax.devices()[0].platform  # psrlint: ignore[PL002] -- record annotation, runs after the fleet (no lease)
        except Exception:  # noqa: BLE001 - note is best-effort
            platform = "?"
        if platform == "cpu":
            record["survey_multichip_note"] = (
                "k virtual CPU devices share ONE host's cores, so "
                "multi-chip wall-clock is not expected to improve here "
                "— the record's claims are the byte-parity of every "
                "artifact at k chips and the recorded gang/fleet "
                "placement decisions; wall-clock scaling needs real "
                "chips")
    return record


def run_broker(args):
    """Batch-broker A/B (the round-24 tentpole's acceptance
    measurement): the SAME 4-observation same-geometry toy fleet
    through the fleet scheduler two ways —

    - **per-obs** (`PYPULSAR_TPU_BROKER=0`): the pre-round-24 dispatch
      tree, every observation's accel/fold batches dispatched solo;
    - **brokered**: batch lanes + the cross-observation broker
      (lane width 4, a wide coalescing window so the toy fleet always
      fuses), same-key work units from different observations merged
      into single device dispatches and demuxed back per obs.

    Each leg runs after its own full warmup pass (jit caches hot for
    THAT leg's batch shapes). The record is gated on structure, not
    wall-clock: coalesce factor >= 2, fused dispatch count <= half the
    per-obs device-dispatch count, no extra compile misses on the
    measured leg, artifacts byte-identical across legs, and a
    validated resume that re-runs zero stages."""
    acquire_backend()
    import glob as _glob
    import tempfile

    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.parallel import broker as broker_mod
    from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    n_obs = 4
    C, T, dtp = 16, (1 << 13 if args.quick
                     else 1 << 14), 5e-4
    rng_freqs = 1500.0 - 4.0 * np.arange(C)
    # no mask stage: every observation's sweep is queued at t0, so the
    # lane claim is deterministically fleet-wide instead of racing the
    # per-obs mask I/O. The sift gate is pinned HIGH so the fold stage
    # stays empty: fold-lane composition depends on which observation's
    # sift lands first (a benign scheduling race), so fold fused shapes
    # are not run-to-run reproducible and would make the zero-extra-
    # compile-miss gate flaky — fold fusion parity and fault isolation
    # are owned by tests/test_broker.py; this A/B pins the accel
    # spectrum-bank path, the fleet's hot fused dispatch.
    cfg = SurveyConfig(
        mask=False, lodm=0.0, dmstep=10.0, numdms=16, nsub=8,
        group_size=4, threshold=8.0,
        accel_zmax=20.0, accel_numharm=2, accel_sigma=3.0, accel_batch=4,
        sift_sigma=20.0, sift_min_hits=3, fold_nbins=32, fold_npart=8)
    stages = build_dag(cfg)

    with tempfile.TemporaryDirectory() as td:
        fils = [_synth_survey_fil(os.path.join(td, f"obs{i}.fil"),
                                  11 + i, C, T, dtp, rng_freqs,
                                  f"BENCH{i}",
                                  period=0.1024 * (1.0 + 0.07 * i))
                for i in range(n_obs)]

        def fleet(dirname):
            out = os.path.join(td, dirname)
            os.makedirs(out, exist_ok=True)
            return [Observation(f"obs{i}", fils[i],
                                os.path.join(out, f"obs{i}"))
                    for i in range(n_obs)]

        def leg(dirname, env):
            # ONE host worker: the lane claim is deterministic (the
            # leader finds every other same-stage task still queued and
            # claims a full 4-wide lane) instead of racing a second
            # worker for mates — the A/B pins structure, and lane mates
            # run in their own threads anyway
            old = {k: os.environ.get(k) for k in env}
            os.environ.update(env)
            try:
                broker_mod.reset()
                # warm THIS configuration's jit programs: fused batch
                # shapes differ from the per-obs ones, so each leg
                # warms its own
                FleetScheduler(fleet(dirname + "-warm"), cfg,
                               max_host_workers=1, devices=1).run()
                broker_mod.reset()
                with telemetry.session() as tlm:
                    t0 = time.perf_counter()
                    result = FleetScheduler(fleet(dirname), cfg,
                                            max_host_workers=1,
                                            devices=1).run()
                    wall = time.perf_counter() - t0
                assert result.ok \
                    and len(result.ran) == n_obs * len(stages), \
                    f"{dirname} leg failed"
                # validated resume: brokered manifests must be as
                # trustworthy as per-obs ones — a second pass over the
                # same outdirs re-runs nothing
                res2 = FleetScheduler(fleet(dirname), cfg,
                                      max_host_workers=1, devices=1,
                                      resume=True).run()
                assert res2.ok and not res2.ran, \
                    f"{dirname} resume re-ran {len(res2.ran)} stages"
                return wall, tlm.counter_totals()
            finally:
                for k, v in old.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
                broker_mod.reset()

        base_s, base_c = leg("perobs", {"PYPULSAR_TPU_BROKER": "0"})
        brk_s, brk_c = leg("brokered", {
            "PYPULSAR_TPU_BROKER": "1",
            "PYPULSAR_TPU_BROKER_LANE": "4",
            # a wide window: the toy stages are host-bound, so the A/B
            # pins coalescing STRUCTURE rather than racing the clock
            "PYPULSAR_TPU_BROKER_WAIT_MS": "30000",
            # CPU-toy stages routinely blow their chip-budget deadlines,
            # and every slo_burn would collapse the window mid-leg —
            # fused compositions would then depend on wall-clock timing
            # and the measured leg could meet batch shapes the warm leg
            # never compiled. Pressure holds have their own tests; this
            # A/B pins the deterministic party-driven composition.
            "PYPULSAR_TPU_BROKER_SLO_HOLD_S": "0",
        })

        # parity: brokered demux must hand every observation bytes
        # identical to its solo dispatches — enforced, not reported
        ident = tot = 0
        for pattern in ("*_ACCEL_*.cand", "*_ACCEL_*.txtcand",
                        "*_cand*.pfd"):
            for fa in sorted(_glob.glob(os.path.join(td, "perobs",
                                                     pattern))):
                fb = os.path.join(td, "brokered", os.path.basename(fa))
                tot += 1
                if (os.path.exists(fb) and open(fa, "rb").read()
                        == open(fb, "rb").read()):
                    ident += 1
        assert ident == tot and tot > 0, \
            f"brokered artifacts diverged: {ident}/{tot}"

    # structural gates (the perf claim a CPU toy CAN make): the broker
    # must have collapsed the device-dispatch count, not just run
    subs = brk_c.get("broker.submissions", 0)
    disp = brk_c.get("broker.dispatches", 0)
    coalesce = subs / disp if disp else 0.0
    base_disp = (base_c.get("accel.stream_batches", 0)
                 + base_c.get("fold.group_dispatches", 0))
    base_miss = int(base_c.get("compile.cache_miss", 0))
    brk_miss = int(brk_c.get("compile.cache_miss", 0))
    assert disp > 0 and coalesce >= 2.0, \
        f"coalesce factor {coalesce:.2f} < 2 ({subs} units / {disp} fused)"
    assert disp * 2 <= base_disp, (
        f"fused dispatch count did not collapse: {disp} brokered vs "
        f"{base_disp} per-obs")
    assert brk_miss <= base_miss, (
        f"brokering introduced compile misses on the measured leg: "
        f"{brk_miss} vs {base_miss}")

    collapse = base_disp / disp
    print(f"# broker A/B: per-obs {base_s:.2f}s ({int(base_disp)} device "
          f"dispatches) vs brokered {brk_s:.2f}s ({int(disp)} fused "
          f"dispatches = {collapse:.2f}x collapse, coalesce factor "
          f"{coalesce:.2f}, {int(brk_c.get('broker.fused_rows', 0))} "
          f"rows fused; {ident}/{tot} artifacts byte-identical)",
          file=sys.stderr)
    record = {
        "metric": "broker_dispatch_collapse",
        "value": round(collapse, 3),
        "unit": (f"device-dispatch collapse from cross-observation "
                 f"batch brokering ({n_obs} same-geometry toy obs x "
                 f"{len(stages)} stages, {C}-chan x {T}-sample each, "
                 f"warm jit caches per leg, 1 device lease + 1 host worker, lane "
                 f"width 4 — per-obs accel/fold device dispatches "
                 f"divided by brokered fused dispatches; artifacts "
                 f"byte-checked across legs, validated resume re-runs "
                 f"zero stages; sift gate pinned high so the fold stage "
                 f"stays empty — fold fusion parity is owned by "
                 f"tests/test_broker.py, this A/B pins the accel "
                 f"spectrum-bank path)"),
        "vs_baseline": round(collapse, 3),
        "broker_n_obs": n_obs,
        "broker_n_stages": len(stages),
        "broker_lane_width": 4,
        "broker_submissions": int(subs),
        "broker_fused_dispatches": int(disp),
        "broker_coalesce_factor": round(coalesce, 3),
        "broker_fused_rows": int(brk_c.get("broker.fused_rows", 0)),
        "broker_lane_grants": int(brk_c.get("broker.lane_grants", 0)),
        "broker_baseline_dispatches": int(base_disp),
        "broker_baseline_compile_misses": base_miss,
        "broker_compile_misses": brk_miss,
        "broker_artifacts_identical": f"{ident}/{tot}",
        "broker_resume_reran": 0,
        "broker_per_obs_seconds": round(base_s, 3),
        "broker_brokered_seconds": round(brk_s, 3),
        "broker_wall_speedup": round(base_s / brk_s, 3),
        "broker_nsamp": T,
        "broker_nchan": C,
    }
    try:
        import jax

        platform = jax.devices()[0].platform  # psrlint: ignore[PL002] -- record annotation, runs after the fleet (no lease)
    except Exception:  # noqa: BLE001 - note is best-effort
        platform = "?"
    if platform == "cpu":
        record["broker_wall_note"] = (
            "toy CPU fleet: fused dispatches save real per-dispatch "
            "launch + HBM round-trip overhead on chips, but on one "
            "host's cores the wall-clock delta is noise — this "
            "record's claims are the structural counters (dispatch "
            "collapse, coalesce factor, zero extra compile misses) "
            "and byte parity; wall-clock scaling needs real chips")
    return record


def run_candplane(args):
    """Candidate-data-plane A/B (the round-25 tentpole's acceptance
    measurement): the SAME synthetic pulsar observed at 3 epochs
    (identical P, DM; fresh noise and a fresh MJD per epoch) through
    the fleet scheduler two ways —

    - **plain** (``PYPULSAR_TPU_CANDSTORE=0``): the pre-round-25
      fleet, per-obs artifacts only, no candidate store;
    - **store**: the candidate data plane on, every terminal ``done``
      observation publishing its normalized candidates into the
      fenced append-only store under ``<outdir>/_fleet/candstore/``.

    The record is gated on structure, not wall-clock: per-obs
    artifacts byte-identical across legs (the store is a pure
    passenger), the plain leg leaves NO store directory behind, the
    cross-epoch candsift finds the pulsar in all 3 epochs and folds
    the store's records into strictly fewer clusters (the measured
    duplicate reduction), a kill -9 mid-append + re-publish leaves
    exactly-once live records (raw log keeps the torn rows; the query
    surface and the ``cands`` CLI both hide them), and every query is
    identical before and after compaction."""
    acquire_backend()
    import contextlib
    import glob as _glob
    import io
    import tempfile

    from pypulsar_tpu import candstore as candstore_mod
    from pypulsar_tpu.candstore.store import CandStore, store_dir
    from pypulsar_tpu.cli import cands as cands_cli
    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.resilience import faultinject
    from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    n_epochs = 3
    C, T, dtp = 16, (1 << 13 if args.quick
                     else 1 << 14), 5e-4
    rng_freqs = 1500.0 - 4.0 * np.arange(C)
    period, dm = 0.1024, 40.0
    # sift gate LOW (unlike --broker): the fold + snr stages must run
    # so the terminal edge has real pfd_snr rows to publish
    cfg = SurveyConfig(
        mask=False, lodm=0.0, dmstep=10.0, numdms=16, nsub=8,
        group_size=4, threshold=8.0,
        accel_zmax=20.0, accel_numharm=2, accel_sigma=3.0, accel_batch=4,
        sift_sigma=3.0, sift_min_hits=1, fold_nbins=32, fold_npart=8)
    stages = build_dag(cfg)

    with tempfile.TemporaryDirectory() as td:
        fils = [_synth_survey_fil(os.path.join(td, f"ep{i}.fil"),
                                  31 + i, C, T, dtp, rng_freqs,
                                  "CANDAB", dm=dm, period=period,
                                  tstart=55000.0 + 10.0 * i)
                for i in range(n_epochs)]

        def fleet(dirname):
            out = os.path.join(td, dirname)
            os.makedirs(out, exist_ok=True)
            return [Observation(f"ep{i}", fils[i],
                                os.path.join(out, f"ep{i}"))
                    for i in range(n_epochs)]

        def leg(dirname, env):
            old = {k: os.environ.get(k) for k in env}
            os.environ.update(env)
            try:
                with telemetry.session() as tlm:
                    t0 = time.perf_counter()
                    result = FleetScheduler(fleet(dirname), cfg,
                                            max_host_workers=1,
                                            devices=1).run()
                    wall = time.perf_counter() - t0
                assert result.ok \
                    and len(result.ran) == n_epochs * len(stages), \
                    f"{dirname} leg failed"
                return wall, tlm.counter_totals()
            finally:
                for k, v in old.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v

        plain_s, _plain_c = leg("plain", {"PYPULSAR_TPU_CANDSTORE": "0"})
        store_s, store_c = leg("store", {"PYPULSAR_TPU_CANDSTORE": "1"})

        # parity: the store is a passenger on the terminal edge —
        # per-obs artifacts must be byte-identical to the store-less run
        ident = tot = 0
        for pattern in ("*_ACCEL_*.cand", "*_ACCEL_*.txtcand",
                        "*_cand*.pfd"):
            for fa in sorted(_glob.glob(os.path.join(td, "plain",
                                                     pattern))):
                fb = os.path.join(td, "store", os.path.basename(fa))
                tot += 1
                if (os.path.exists(fb) and open(fa, "rb").read()
                        == open(fb, "rb").read()):
                    ident += 1
        assert ident == tot and tot > 0, \
            f"store leg artifacts diverged: {ident}/{tot}"
        # the snr fleet summaries embed each pfd's path (which contains
        # the leg dirname), so parity there is structural: identical
        # rows once the path field is reduced to its basename
        for i in range(n_epochs):
            legs = []
            for dirname in ("plain", "store"):
                with open(os.path.join(td, dirname,
                                       f"ep{i}_snr.json")) as f:
                    rows = json.load(f)
                legs.append([dict(r, pfd=os.path.basename(r["pfd"]))
                             for r in rows])
            assert legs[0] == legs[1], f"ep{i} snr summaries diverged"
            tot += 1
            ident += 1
        assert not os.path.exists(store_dir(os.path.join(td, "plain"))), \
            "disabled store still left a candstore directory behind"

        # the data-plane claims: 3 epochs of one pulsar fold into one
        # cluster — the duplicate reduction per-obs files cannot give
        store = CandStore(os.path.join(td, "store"))
        recs = store.records()
        n_records = len(recs)
        assert n_records >= n_epochs, \
            f"store holds {n_records} records from {n_epochs} epochs"
        clusters = candstore_mod.cross_sift(recs)
        # the cluster seeds on its strongest member, which for a bright
        # pulsar is often a harmonic — identify it harmonically, not by
        # the fundamental alone
        pulsar = [c for c in clusters
                  if candstore_mod.harmonic_ratio(c["p_s"], period,
                                                  5e-3) is not None]
        assert pulsar and pulsar[0]["n_epochs"] == n_epochs, (
            f"pulsar cluster missing or incomplete: "
            f"{[ (c['p_s'], c['n_epochs']) for c in clusters[:5] ]}")
        reduction = n_records / len(clusters)
        assert reduction > 1.0, \
            f"no duplicate reduction: {n_records} recs / {len(clusters)}"

        # queries are identical before and after compaction (the
        # snapshot is an equivalent-by-construction rewrite)
        q_near = dict(near=(period, dm), top=50)
        pre_near = store.query(**q_near)
        pre_all = store.query()
        pre_ep = store.query(epoch_range=(55005.0, 55025.0))
        store.compact()
        assert store.query(**q_near) == pre_near \
            and store.query() == pre_all \
            and store.query(epoch_range=(55005.0, 55025.0)) == pre_ep, \
            "query changed across compaction"
        assert store.status()["segments"] == 0, \
            "compaction left segments behind"

        # kill -9 mid-append + resume: the round-25 exactly-once claim.
        # Re-publish the SAME (obs, fingerprint) after an injected kill
        # tore the first attempt — the raw log keeps the torn rows, the
        # query surface shows each candidate once.
        obs_name, outbase = "ep0", os.path.join(td, "store", "ep0")
        recs0, fp = candstore_mod.normalize_obs(obs_name, outbase,
                                                fils[0])
        assert len(recs0) >= 2, "need >=2 rows for a mid-append kill"
        kdir = os.path.join(td, "killres")
        os.makedirs(kdir, exist_ok=True)
        faultinject.reset()
        faultinject.configure("kill:candstore.append:2")
        killed = False
        try:
            CandStore(kdir).publish(obs_name, recs0, fp)
        except faultinject.InjectedKill:
            killed = True
        finally:
            faultinject.reset()
        assert killed, "armed candstore.append kill never fired"
        ks = CandStore(kdir)  # the resumed host
        ks.publish(obs_name, recs0, fp)
        kstat = ks.status()
        assert kstat["records"] == len(recs0), (
            f"kill+resume not exactly-once: {kstat['records']} live "
            f"vs {len(recs0)} published")
        assert kstat["raw_records"] > kstat["records"], \
            "torn first attempt left no raw rows — kill leg proved nothing"
        # the per-obs sift keeps only the strongest harmonic, so query
        # near the strongest published row rather than the fundamental
        strongest = max((r for r in recs0
                         if isinstance(r.get("p_s"), float)
                         and isinstance(r.get("dm"), float)),
                        key=lambda r: r.get("snr") or 0.0)
        assert ks.query(near=(strongest["p_s"], strongest["dm"])), \
            "resumed store lost the pulsar"
        # ...and the same exactly-once view through the cands CLI
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cands_cli.main([kdir, "--json"])
        cli_rows = json.loads(buf.getvalue())
        assert rc == 0 and len(cli_rows) == len(recs0), \
            f"cands CLI disagrees: {len(cli_rows)} vs {len(recs0)}"

    print(f"# candplane A/B: {n_epochs} epochs -> {n_records} store "
          f"records -> {len(clusters)} clusters ({reduction:.2f}x dup "
          f"reduction, pulsar seen {pulsar[0]['n_epochs']}/{n_epochs} "
          f"epochs); {ident}/{tot} artifacts byte-identical; "
          f"kill+resume exactly-once ({kstat['raw_records']} raw -> "
          f"{kstat['records']} live); plain {plain_s:.2f}s vs store "
          f"{store_s:.2f}s", file=sys.stderr)
    record = {
        "metric": "candplane_dup_reduction",
        "value": round(reduction, 3),
        "unit": (f"cross-epoch duplicate reduction from the round-25 "
                 f"candidate data plane ({n_epochs} epochs of one "
                 f"synthetic pulsar + per-epoch noise, {C}-chan x "
                 f"{T}-sample each, full sweep->accel->sift->fold->snr "
                 f"DAG — live store records divided by candsift "
                 f"clusters; per-obs artifacts byte-checked identical "
                 f"to a PYPULSAR_TPU_CANDSTORE=0 run, kill -9 "
                 f"mid-append + re-publish asserted exactly-once, "
                 f"queries asserted identical pre/post compaction)"),
        "vs_baseline": round(reduction, 3),
        "candplane_n_epochs": n_epochs,
        "candplane_n_records": n_records,
        "candplane_n_clusters": len(clusters),
        "candplane_pulsar_epochs": int(pulsar[0]["n_epochs"]),
        "candplane_artifacts_identical": f"{ident}/{tot}",
        "candplane_publishes": int(store_c.get("candstore.publishes", 0)),
        "candplane_appended": int(store_c.get("candstore.appended", 0)),
        "candplane_killres_raw_records": int(kstat["raw_records"]),
        "candplane_killres_live_records": int(kstat["records"]),
        "candplane_query_stable_across_compaction": True,
        "candplane_plain_seconds": round(plain_s, 3),
        "candplane_store_seconds": round(store_s, 3),
        "candplane_nsamp": T,
        "candplane_nchan": C,
    }
    try:
        import jax

        platform = jax.devices()[0].platform  # psrlint: ignore[PL002] -- record annotation, runs after the fleet (no lease)
    except Exception:  # noqa: BLE001 - note is best-effort
        platform = "?"
    if platform == "cpu":
        record["candplane_wall_note"] = (
            "toy CPU fleet: the claim is structural (dup reduction, "
            "byte parity, exactly-once after kill, compaction-stable "
            "queries), not wall-clock — store overhead on the "
            "terminal edge is file appends, noise next to the DAG")
    return record


def run_chaos(args):
    """Seeded chaos harness (the fleet-health acceptance measurement):
    run a toy fleet CLEAN, then run the SAME fleet with

    - seeded probabilistic chaos (``--fault-chaos SEED:RATE``) spraying
      kills / OOMs / IO errors / hangs / device faults across every
      registered fault point, and
    - one deterministic armed fault per family on top (so every family
      provably fires regardless of what the seed happens to draw),

    resuming after every kill until the fleet completes, with the
    watchdog (heartbeat-stall detection) turning injected hangs into
    ordinary retryable failures. Then assert:

    - a final no-chaos ``--resume`` validates everything and runs ZERO
      stages (the manifests survived every torn window), and
    - every artifact is byte-identical to the clean run's — recovery
      reconstructed the exact bytes, not approximately the science.
    """
    acquire_backend()
    import glob as _glob
    import random
    import tempfile

    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.ops import numpy_ref
    from pypulsar_tpu.resilience import faultinject
    from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    seed = args.chaos_seed
    rate = args.chaos_rate if args.chaos_rate is not None \
        else (0.01 if args.quick else 0.015)
    n_obs = 3
    stall_s = 8.0
    max_rounds = 40
    C, T, dtp = 32, (1 << 13 if args.quick
                     else 1 << 14), 5e-4
    rng_freqs = 1500.0 - 4.0 * np.arange(C)
    cfg = SurveyConfig(
        mask=True, mask_time=2.0, lodm=0.0, dmstep=10.0, numdms=8,
        nsub=8, group_size=4, threshold=8.0,
        accel_zmax=20.0, accel_numharm=2, accel_sigma=3.0, accel_batch=4,
        sift_sigma=3.0, sift_min_hits=1, fold_nbins=32, fold_npart=8)
    stages = build_dag(cfg)

    def make_obs_fil(fn, seed_i, dm=40.0, period=0.1024, amp=10.0):
        return _synth_survey_fil(fn, seed_i, C, T, dtp, rng_freqs,
                                 f"CHAOS{seed_i}", dm=dm, period=period,
                                 amp=amp)

    # bound the injected hangs and a chaos-wedged prefetch consumer so
    # the harness's wall time stays bounded even when an interrupt
    # cannot land (a hang must outlive stall_s for the watchdog path to
    # be the one that ends it)
    env_save = {k: os.environ.get(k) for k in
                ("PYPULSAR_TPU_HANG_S", "PYPULSAR_TPU_PREFETCH_TIMEOUT")}
    os.environ["PYPULSAR_TPU_HANG_S"] = str(stall_s + 4.0)
    os.environ["PYPULSAR_TPU_PREFETCH_TIMEOUT"] = "15"
    try:
        with tempfile.TemporaryDirectory() as td:
            fils = [make_obs_fil(os.path.join(td, f"obs{i}.fil"),
                                 seed_i=23 + i,
                                 period=0.1024 * (1.0 + 0.07 * i))
                    for i in range(n_obs)]

            def fleet(dirname):
                out = os.path.join(td, dirname)
                os.makedirs(out, exist_ok=True)
                return [Observation(f"obs{i}", fils[i],
                                    os.path.join(out, f"obs{i}"))
                        for i in range(n_obs)]

            # clean leg (also warms every stage's jit programs, so the
            # chaos leg's stall detector never sees a cold compile)
            faultinject.reset()
            t0 = time.perf_counter()
            clean = FleetScheduler(fleet("clean"), cfg,
                                   max_host_workers=2, devices=1).run()
            clean_s = time.perf_counter() - t0
            assert clean.ok and len(clean.ran) == n_obs * len(stages)

            # chaos leg: seeded spray + one guaranteed fault per family
            # (kill in the stage_done torn window, an escaped OOM, a
            # mid-.dat-stream IO error, an in-stage hang for the
            # watchdog, a chip-indicting device fault)
            faultinject.reset()
            faultinject.configure_chaos(f"{seed}:{rate}")
            faultinject.configure(
                "kill:survey.stage_done:1,"
                "oom:accel.batch_dispatch:1,"
                "io:dats.append:2,"
                "hang:sweep.chunk_dispatch:3,"
                "device:fold.batch_dispatch:1")
            rounds = kills = timeouts = retried = quarantined = 0
            t0 = time.perf_counter()
            result = None
            while rounds < max_rounds:
                rounds += 1
                sched = FleetScheduler(
                    fleet("chaos"), cfg, max_host_workers=2, devices=1,
                    retries=2, resume=(rounds > 1), stall_s=stall_s,
                    jitter_rng=random.Random(seed + rounds))
                try:
                    result = sched.run()
                except faultinject.InjectedKill:
                    kills += 1
                    timeouts += sched.result.timeouts
                    retried += sched.result.retried
                    continue  # "the process died": restart + --resume
                timeouts += result.timeouts
                retried += result.retried
                quarantined += len(result.quarantined)
                if result.ok:
                    break
                # quarantined observations: the operator resumes them
            chaos_s = time.perf_counter() - t0
            fired = faultinject.fired_counts()
            assert result is not None and result.ok, (
                f"chaos fleet did not complete in {max_rounds} rounds "
                f"(fired: {fired})")
            for kind in ("kill", "oom", "io", "hang", "device"):
                assert fired.get(kind, 0) >= 1, (
                    f"fault family {kind!r} never fired: {fired}")
            assert timeouts >= 1, (
                "no watchdog interrupt fired — the injected hang was "
                "not recovered by the deadline/stall path")

            # chaos off: a final validated resume must run NOTHING
            faultinject.reset()
            final = FleetScheduler(fleet("chaos"), cfg,
                                   max_host_workers=2, devices=1,
                                   resume=True).run()
            assert final.ok and len(final.ran) == 0, (
                f"post-chaos manifests did not validate clean: "
                f"{len(final.ran)} stages re-ran")

            # byte-parity: the chaos run's artifacts ARE the clean
            # run's artifacts
            ident = tot = 0
            diverged = []
            for pattern in ("*_ACCEL_*.cand", "*_ACCEL_*.txtcand",
                            "*_cand*.pfd", "*.dat"):
                for fa in sorted(_glob.glob(os.path.join(td, "clean",
                                                         pattern))):
                    fb = os.path.join(td, "chaos", os.path.basename(fa))
                    tot += 1
                    if (os.path.exists(fb) and open(fa, "rb").read()
                            == open(fb, "rb").read()):
                        ident += 1
                    else:
                        diverged.append(os.path.basename(fa))
            assert ident == tot and tot > 0, (
                f"chaos artifacts diverged from clean: {ident}/{tot} "
                f"({diverged[:8]})")
    finally:
        faultinject.reset()
        for k, v in env_save.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    n_faults = sum(fired.values())
    print(f"# chaos: seed {seed} rate {rate}: {n_faults} faults "
          f"({', '.join(f'{k}={v}' for k, v in sorted(fired.items()))}) "
          f"over {rounds} round(s), {kills} kill-resumes, {timeouts} "
          f"watchdog interrupts, {retried} stage retries, {quarantined} "
          f"quarantine verdicts — fleet completed, {ident}/{tot} "
          f"artifacts byte-identical to clean ({clean_s:.1f}s clean, "
          f"{chaos_s:.1f}s under chaos)", file=sys.stderr)
    return {
        "metric": "chaos_fleet_recovery",
        "value": round(ident / max(tot, 1), 3),
        "unit": (f"fraction of artifacts byte-identical to a clean run "
                 f"after an {n_obs}-obs x {len(stages)}-stage fleet "
                 f"survived {n_faults} injected faults (seeded chaos "
                 f"{seed}:{rate} + one armed fault per family) via "
                 f"watchdog-driven retries, kill-restarts with --resume "
                 f"and quarantine-resume — asserted 1.0, plus a final "
                 f"no-chaos resume validating 0 stages re-run"),
        "vs_baseline": 1.0,
        "chaos_seed": seed,
        "chaos_rate": rate,
        "chaos_n_obs": n_obs,
        "chaos_n_stages": len(stages),
        "chaos_faults_fired": fired,
        "chaos_rounds": rounds,
        "chaos_kill_resumes": kills,
        "chaos_watchdog_interrupts": timeouts,
        "chaos_stage_retries": retried,
        "chaos_quarantine_verdicts": quarantined,
        "chaos_stall_timeout_s": stall_s,
        "chaos_artifacts_identical": f"{ident}/{tot}",
        "chaos_clean_seconds": round(clean_s, 2),
        "chaos_seconds": round(chaos_s, 2),
        "chaos_nsamp": T,
        "chaos_nchan": C,
    }


def run_daemon_soak(args):
    """Streaming-daemon soak (the round-23 acceptance measurement):
    the multi-tenant admission plane under sustained overload, measured
    three ways against ONE batch reference —

    - **reference**: the same 4-observation corpus through a plain
      batch fleet (the artifacts every later leg must reproduce
      byte-for-byte);
    - **overload**: an in-process daemon fed a gold tenant (priority 5,
      unmetered) plus a bulk tenant (burst-limited) flooding past a
      2-deep accept queue, with seeded chaos sprayed over the admission
      storm and one armed fault at each daemon ingest point
      (``daemon.arrival`` / ``daemon.admit`` / ``daemon.shed``), and a
      corrupt bulk file exercising the ingest-quarantine edge. Books
      must balance in-process, shedding must hit ONLY unaccepted bulk
      work, and the whole shed trail must reconstruct from the trace
      events alone;
    - **kill -9**: a real ``survey --daemon --watch`` subprocess
      SIGKILL'd mid-pipeline after accepting two observations, then
      restarted — the admission journal must resume the accepted work
      with ZERO re-runs of manifest-validated stages — and finally
      SIGTERM'd for a clean (rc 0) drain.

    A final no-chaos in-process resume over every accepted observation
    must run ZERO stages, and every completed artifact must be
    byte-identical to the batch reference's."""
    acquire_backend()
    import glob as _glob
    import signal
    import tempfile
    import threading

    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.resilience import faultinject
    from pypulsar_tpu.survey.daemon import (SurveyDaemon, TenantSpec,
                                            journal_path,
                                            read_tenant_status)
    from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import MANIFEST_SUFFIX, Observation

    seed = args.chaos_seed
    rate = args.chaos_rate if args.chaos_rate is not None else 0.05
    n_gold, n_bulk, queue_bound = 2, 6, 2
    C, T, dtp = 32, (1 << 13 if args.quick
                     else 1 << 14), 5e-4
    rng_freqs = 1500.0 - 4.0 * np.arange(C)
    cfg = SurveyConfig(
        mask=True, mask_time=2.0, lodm=0.0, dmstep=10.0, numdms=8,
        nsub=8, group_size=4, threshold=8.0,
        accel_zmax=20.0, accel_numharm=2, accel_sigma=3.0, accel_batch=4,
        sift_sigma=3.0, sift_min_hits=1, fold_nbins=32, fold_npart=8)
    stages = build_dag(cfg)

    def wait_for(cond, what, timeout=120.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            time.sleep(0.05)
        raise AssertionError(f"daemon soak timed out waiting for {what}")

    def accept_records(outdir):
        """(name, tenant, infile, outbase) per journaled accept, plus
        the terminal-state map — the restart/resume assertions' input."""
        accepts, terminal = {}, {}
        with open(journal_path(outdir)) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail
                if rec.get("type") == "accept":
                    accepts[rec["obs"]] = rec
                elif rec.get("type") == "terminal":
                    terminal[rec["obs"]] = rec["state"]
        return accepts, terminal

    def done_units(outdir):
        """{manifest basename: [unit, ...]} across the outdir — one
        list entry PER RECORD, so a re-run shows up as a duplicate."""
        units = {}
        for mp in sorted(_glob.glob(os.path.join(
                outdir, "*" + MANIFEST_SUFFIX))):
            rows = []
            with open(mp) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("type") == "done":
                        rows.append(rec.get("unit"))
            units[os.path.basename(mp)] = rows
        return units

    def byte_parity(ref_dir, out_dir, stems):
        ident = tot = 0
        diverged = []
        for pattern in ("*_ACCEL_*.cand", "*_ACCEL_*.txtcand",
                        "*_cand*.pfd", "*.dat"):
            for fa in sorted(_glob.glob(os.path.join(ref_dir, pattern))):
                base = os.path.basename(fa)
                if not any(base.startswith(s) for s in stems):
                    continue
                fb = os.path.join(out_dir, base)
                tot += 1
                if (os.path.exists(fb) and open(fa, "rb").read()
                        == open(fb, "rb").read()):
                    ident += 1
                else:
                    diverged.append(base)
        return ident, tot, diverged

    t_start = time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        # corpus: 2 gold (in-process leg) + 2 kobs (kill -9 leg); the
        # kobs pair lives in the subprocess's watch dir from the start
        watch2 = os.path.join(td, "watch2")
        os.makedirs(watch2)
        golds = [_synth_survey_fil(os.path.join(td, f"gold{i}.fil"),
                                   61 + i, C, T, dtp, rng_freqs,
                                   f"SOAKG{i}",
                                   period=0.1024 * (1.0 + 0.07 * i))
                 for i in range(n_gold)]
        kobs = [_synth_survey_fil(os.path.join(watch2, f"kobs{i}.fil"),
                                  71 + i, C, T, dtp, rng_freqs,
                                  f"SOAKK{i}",
                                  period=0.1024 * (1.0 + 0.09 * i))
                for i in range(2)]

        # ---- leg A: the batch reference (also warms the jit caches) --
        faultinject.reset()
        ref = os.path.join(td, "ref")
        os.makedirs(ref)
        obs_ref = ([Observation(f"gold{i}", golds[i],
                                os.path.join(ref, f"gold{i}"))
                    for i in range(n_gold)]
                   + [Observation(f"kobs{i}", kobs[i],
                                  os.path.join(ref, f"kobs{i}"))
                      for i in range(2)])
        batch = FleetScheduler(obs_ref, cfg, max_host_workers=2,
                               devices=1).run()
        assert batch.ok and len(batch.ran) == len(obs_ref) * len(stages)

        # ---- leg B: in-process overload soak under chaos spray -------
        out1 = os.path.join(td, "daemon")
        bulkdir = os.path.join(td, "bulk_incoming")
        os.makedirs(bulkdir)
        trace = os.path.join(td, "soak_trace.jsonl")
        faultinject.reset()
        # probabilistic spray over the admission storm (non-fatal kinds
        # — the kill family gets a REAL SIGKILL in leg C) plus one
        # armed fault per daemon ingest point so each provably fires
        faultinject.configure_chaos(f"{seed}:{rate}:oom+io")
        faultinject.configure("io:daemon.arrival:1,"
                              "io:daemon.admit:1,"
                              "io:daemon.shed:1")
        daemon = SurveyDaemon(
            out1, cfg, stages=stages,
            tenants=[TenantSpec("gold", priority=5, rate=0.0),
                     TenantSpec("bulk", priority=0, rate=1e-6,
                                burst=2.0)],
            watch=[(bulkdir, "bulk")],
            queue_bound=queue_bound, quiesce_s=0.2, poll_s=0.05,
            idle_exit_s=0.0, min_free_mb=0,
            max_host_workers=2, devices=1, retries=3)
        with telemetry.session(trace) as tlm:
            thread = threading.Thread(target=daemon.run,
                                      name="soak-daemon", daemon=True)
            thread.start()
            # 1. one corrupt bulk file FIRST: it absorbs the armed
            #    arrival + admit faults (watch rescan / re-pend retry),
            #    then ingest validation quarantines it — bulk's burst-2
            #    bucket is now empty, so the later flood can only shed
            corrupt = os.path.join(td, "corrupt.fil")
            with open(corrupt, "wb") as f:
                f.write(b"this is not a filterbank" * 64)
            os.replace(corrupt, os.path.join(bulkdir, "corrupt.fil"))
            wait_for(lambda: daemon.stats()["quarantined"] >= 1,
                     "corrupt bulk file to ingest-quarantine")
            # 2. gold submissions through the socket-lane API, retrying
            #    the sprayed transient ingest faults like a client would
            for fn in golds:
                for _ in range(200):
                    v, why = daemon.submit("gold", fn)
                    if v in ("accepted", "pending") or (
                            v == "error" and "already submitted" in why):
                        break
                    assert v == "error" and "transient" in why, (v, why)
                    time.sleep(0.05)
                else:
                    raise AssertionError(f"gold {fn} never admitted")
            wait_for(lambda: daemon.tenant_snapshot()["tenants"]
                     ["gold"]["accepted"] >= n_gold, "gold acceptance")
            # 3. the bulk flood: over-capacity arrivals with an empty
            #    token bucket — past the 2-deep bound they shed
            for i in range(n_bulk):
                fn = os.path.join(td, f"bulk{i}.fil")
                with open(fn, "wb") as f:
                    f.write(b"\x00" * 4096)  # never admitted: content
                    # is irrelevant, the bucket is already empty
                os.replace(fn, os.path.join(bulkdir, f"bulk{i}.fil"))
            wait_for(lambda: daemon.stats()["submitted"]
                     >= 1 + n_gold + n_bulk, "the bulk flood to arrive")
            # 4. storm over: chaos off, SIGTERM semantics — accepted
            #    work finishes, the pending remainder sheds loudly
            faultinject.configure_chaos(None)
            daemon.request_drain()
            thread.join(timeout=600)
            assert not thread.is_alive(), "daemon failed to drain"
            counters = {k: int(v) for k, v in
                        tlm.counter_totals().items()
                        if k.startswith("daemon.")}
        fired = faultinject.fired_counts()
        faultinject.reset()
        # the fleet verdict: exactly ONE quarantined observation — the
        # corrupt bulk file, stopped by ingest validation (result.ok is
        # False by design here: a quarantine IS a loud verdict)
        assert daemon.result is not None, "fleet never reported"
        q_names = sorted(daemon.result.quarantined)
        assert q_names == ["corrupt"], (
            f"unexpected quarantine set: {daemon.result.quarantined}")

        # books balance, by tenant and in aggregate
        agg = daemon.stats()
        snap = daemon.tenant_snapshot()["tenants"]
        assert agg["pending"] == 0 and agg["accepted_open"] == 0
        assert agg["submitted"] == agg["accepted"] + agg["shed"], agg
        assert agg["accepted"] == (agg["completed"]
                                   + agg["quarantined"]), agg
        assert agg["submitted"] == 1 + n_gold + n_bulk, agg
        gold_b, bulk_b = snap["gold"], snap["bulk"]
        assert (gold_b["completed"] == n_gold and gold_b["shed"] == 0
                and gold_b["quarantined"] == 0), (
            f"healthy tenant charged for bulk's overload: {gold_b}")
        assert (bulk_b["quarantined"] == 1 and bulk_b["shed"] == n_bulk
                and bulk_b["completed"] == 0), bulk_b
        # every armed daemon ingest point provably fired and was
        # absorbed (the arrival was re-seen, the admit re-pended, the
        # shed still happened)
        for point in ("arrival", "admit", "shed"):
            assert counters.get(f"daemon.{point}_faults", 0) >= 1, (
                f"daemon.{point} fault never fired: {counters}")
        assert fired.get("io", 0) >= 3, fired

        # the shed trail reconstructs from the trace alone: every
        # victim, its tenant, the reason and the queue depth at the
        # decision — and no shed ever names accepted (gold) work
        shed_evs = []
        with open(trace) as f:
            for line in f:
                rec = json.loads(line)
                if (rec.get("type") == "event"
                        and rec.get("name") == "daemon.shed"):
                    shed_evs.append(rec["attrs"])
        assert len(shed_evs) == n_bulk, shed_evs
        assert all(e["tenant"] == "bulk" and e["queue_depth"] >= 1
                   and e["reason"] for e in shed_evs), shed_evs
        n_shed_bound = sum(1 for e in shed_evs
                           if "queue full" in e["reason"])
        n_shed_drain = sum(1 for e in shed_evs
                           if "draining" in e["reason"])
        assert n_shed_bound >= 1 and n_shed_drain >= 1, shed_evs
        assert n_shed_bound + n_shed_drain == n_bulk, shed_evs

        # ---- leg C: kill -9 a REAL --daemon subprocess, restart ------
        out2 = os.path.join(td, "killdaemon")
        argv = [sys.executable, "-m", "pypulsar_tpu.cli", "survey",
                "--daemon", "-o", out2, "--watch", watch2 + ":gold",
                "--tenant", "gold:5:0:8", "--queue-bound", "8",
                "--quiesce", "0.2", "--daemon-poll", "0.05",
                "--min-free-mb", "0", "--max-host-workers", "2",
                "--retries", "2",
                "--mask-time", "2.0", "--lodm", "0.0",
                "--dmstep", "10.0", "--numdms", "8", "--nsub", "8",
                "--group-size", "4", "--threshold", "8.0",
                "--accel-zmax", "20.0", "--accel-numharm", "2",
                "--accel-sigma", "3.0", "--accel-batch", "4",
                "--sift-sigma", "3.0", "--sift-min-hits", "1",
                "--fold-nbins", "32", "--fold-npart", "8"]
        env = dict(os.environ)
        for var in ("PYPULSAR_TPU_FAULTS", "PYPULSAR_TPU_CHAOS"):
            env.pop(var, None)

        def spawn(log_name):
            log = open(os.path.join(td, log_name), "w")
            return subprocess.Popen(argv, env=env, stdout=log,
                                    stderr=subprocess.STDOUT), log

        def poll_subproc(proc, cond, what, timeout=600.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    raise AssertionError(
                        f"daemon subprocess exited rc={proc.returncode} "
                        f"while waiting for {what}")
                if cond():
                    return
                time.sleep(0.1)
            raise AssertionError(f"subprocess soak timed out on {what}")

        def tstat(key, tenant="gold"):
            st = read_tenant_status(out2)
            if not st:
                return 0
            return st.get("tenants", {}).get(tenant, {}).get(key, 0)

        proc1, log1 = spawn("kill_leg_1.log")
        try:
            # accepted + at least one manifest-validated stage, but the
            # pipeline still in flight: the interesting kill window
            poll_subproc(
                proc1,
                lambda: (tstat("accepted") >= 2
                         and sum(len(v) for v in
                                 done_units(out2).values()) >= 1),
                "2 accepts + 1 validated stage before the SIGKILL")
        finally:
            proc1.kill()  # SIGKILL: no drain, no journal close
            proc1.wait(timeout=60)
            log1.close()
        pre_kill = done_units(out2)
        n_pre = sum(len(v) for v in pre_kill.values())

        proc2, log2 = spawn("kill_leg_2.log")
        try:
            poll_subproc(
                proc2,
                lambda: (tstat("completed") >= 2
                         and (read_tenant_status(out2) or {})
                         .get("accepted_open", 1) == 0),
                "the restarted daemon to finish the adopted work")
            proc2.send_signal(signal.SIGTERM)  # the clean-drain contract
            rc2 = proc2.wait(timeout=120)
        finally:
            if proc2.poll() is None:
                proc2.kill()
                proc2.wait(timeout=60)
            log2.close()
        assert rc2 == 0, f"SIGTERM drain exited rc={rc2}"
        # zero re-runs of validated stages: every unit recorded done
        # before the SIGKILL appears EXACTLY once in the final manifest
        # (a re-run would append a duplicate done record)
        post = done_units(out2)
        assert n_pre >= 1
        for man, units in pre_kill.items():
            for unit in units:
                assert post.get(man, []).count(unit) == 1, (
                    f"{man}:{unit} re-ran after the restart")

        # ---- the cross-leg gates -------------------------------------
        # a final no-chaos resume over EVERY accepted observation (both
        # legs) validates the manifests and runs ZERO stages
        reran = 0
        for outdir in (out1, out2):
            accepts, terminal = accept_records(outdir)
            fleet = [Observation(r["obs"], r["infile"], r["outbase"])
                     for r in accepts.values()
                     if terminal.get(r["obs"]) == "done"]
            assert fleet, f"no completed accepts journaled in {outdir}"
            final = FleetScheduler(fleet, cfg, max_host_workers=2,
                                   devices=1, resume=True).run()
            assert final.ok and len(final.ran) == 0, (
                f"{outdir}: {len(final.ran)} stages re-ran on the "
                f"final resume")
            reran += len(final.ran)

        # completed artifacts byte-identical to the batch reference
        ident = tot = 0
        diverged = []
        for out_dir, stems in ((out1, ("gold",)), (out2, ("kobs",))):
            i, t, d = byte_parity(ref, out_dir, stems)
            ident, tot, diverged = ident + i, tot + t, diverged + d
        assert ident == tot and tot > 0, (
            f"soak artifacts diverged from the batch reference: "
            f"{ident}/{tot} ({diverged[:8]})")
    soak_s = time.perf_counter() - t_start

    n_faults = sum(fired.values())
    print(f"# daemon-soak: seed {seed} rate {rate}: books balanced over "
          f"{agg['submitted']} arrivals ({agg['accepted']} accepted, "
          f"{agg['shed']} shed [{n_shed_bound} bound / {n_shed_drain} "
          f"drain], {agg['quarantined']} quarantined), {n_faults} "
          f"injected faults absorbed at the ingest points, kill -9 "
          f"resumed {n_pre} pre-kill unit(s) with zero re-runs, SIGTERM "
          f"drained rc 0, {ident}/{tot} artifacts byte-identical to "
          f"batch ({soak_s:.1f}s)", file=sys.stderr)
    return {
        "metric": "daemon_soak_overload_degradation",
        "value": round(ident / max(tot, 1), 3),
        "unit": (f"fraction of streaming-daemon artifacts "
                 f"byte-identical to the batch reference after a "
                 f"multi-tenant overload soak (bulk flood past a "
                 f"{queue_bound}-deep accept queue, seeded chaos "
                 f"{seed}:{rate} over the admission storm + one armed "
                 f"fault per daemon ingest point, one ingest-"
                 f"quarantined corrupt file, a SIGKILL'd+restarted "
                 f"--daemon subprocess and a SIGTERM drain) — asserted "
                 f"1.0 with balanced books, bulk-only shedding, a "
                 f"trace-reconstructible shed trail and a final resume "
                 f"running zero stages"),
        "vs_baseline": 1.0,
        "soak_chaos_seed": seed,
        "soak_chaos_rate": rate,
        "soak_books": agg,
        "soak_tenant_books": {n: {k: b[k] for k in
                                  ("submitted", "accepted", "shed",
                                   "quarantined", "completed")}
                              for n, b in snap.items()},
        "soak_shed_events": len(shed_evs),
        "soak_shed_at_bound": n_shed_bound,
        "soak_shed_at_drain": n_shed_drain,
        "soak_faults_fired": fired,
        "soak_ingest_fault_counters": {
            k: v for k, v in counters.items() if k.endswith("_faults")},
        "soak_kill9_prekill_units": n_pre,
        "soak_kill9_reruns": 0,
        "soak_sigterm_rc": rc2,
        "soak_final_resume_reran": reran,
        "soak_artifacts_identical": f"{ident}/{tot}",
        "soak_seconds": round(soak_s, 2),
        "soak_nsamp": T,
        "soak_nchan": C,
    }


def run_obs_overhead(args):
    """Observability-plane overhead A/B (round 21's zero-overhead
    contract, measured): the SAME toy sweep->accel chain over a small
    fleet, run three ways —

    - **off**: flight recorder disabled (``PYPULSAR_TPU_OBS_FLIGHTREC=0``
      semantics via ``flightrec.configure(0)``), no telemetry session —
      the true zero-instrumentation floor;
    - **flightrec**: the always-on default — the in-memory ring records
      every span/counter, nothing hits disk;
    - **full**: flight recorder + a live ``--telemetry`` JSONL session +
      per-observation obs traces (``telemetry_dir``) — everything the
      observability plane can write.

    Each leg is min-of-``reps`` over a freshly-dirs'd fleet after a full
    warmup chain, candidates are byte-checked identical across legs
    (observability must never touch science), and the full-vs-off
    overhead is asserted <= 5% in-process — the bound ROADMAP's
    "passenger, never the payload" rule means."""
    acquire_backend()
    import glob as _glob
    import tempfile

    from pypulsar_tpu.obs import flightrec, telemetry
    from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    n_obs = 2
    # min-of-N is the noise floor: the toy chain is ~2 s, so scheduler
    # jitter is a few percent per rep — enough reps that the minima
    # compare floors, not jitter
    reps = 3 if args.quick else 5
    C, T, dtp = 32, 1 << 14, 5e-4
    rng_freqs = 1500.0 - 4.0 * np.arange(C)
    cfg = SurveyConfig(
        lodm=0.0, dmstep=10.0, numdms=8, nsub=8, group_size=4,
        threshold=8.0, accel_zmax=20.0, accel_numharm=2,
        accel_sigma=3.0, accel_batch=4)
    stages = build_dag(cfg)
    overhead_bound = 0.05

    with tempfile.TemporaryDirectory() as td:
        fils = [_synth_survey_fil(os.path.join(td, f"obs{i}.fil"),
                                  11 + i, C, T, dtp, rng_freqs,
                                  f"BENCH{i}", dm=40.0,
                                  period=0.1024 * (1.0 + 0.07 * i),
                                  amp=10.0)
                for i in range(n_obs)]

        def fleet(dirname):
            out = os.path.join(td, dirname)
            os.makedirs(out, exist_ok=True)
            return [Observation(f"obs{i}", fils[i],
                                os.path.join(out, f"obs{i}"))
                    for i in range(n_obs)]

        # warmup: one full chain compiles every stage's jit programs
        for stage in stages:
            stage.execute(fleet("warm")[0], cfg)

        def leg(name, rep, telemetry_dir=None):
            obs = fleet(f"{name}{rep}")
            t0 = time.perf_counter()
            result = FleetScheduler(obs, cfg, max_host_workers=2,
                                    devices=1,
                                    telemetry_dir=telemetry_dir).run()
            dt = time.perf_counter() - t0
            assert result.ok and len(result.ran) == n_obs * len(stages)
            return dt

        legs = {}
        try:
            # interleave reps so drift (thermal, page cache) hits all
            # three legs evenly instead of the last one measured
            for rep in range(reps):
                flightrec.configure(0)
                legs.setdefault("off", []).append(leg("off", rep))
                flightrec.configure(None)
                legs.setdefault("flightrec", []).append(leg("ring", rep))
                tlm_dir = os.path.join(td, f"tlm{rep}")
                with telemetry.session(os.path.join(td, f"full{rep}.jsonl"),
                                       tool="bench-obs"):
                    legs.setdefault("full", []).append(
                        leg("full", rep, telemetry_dir=tlm_dir))
        finally:
            flightrec.configure(None)

        # byte parity: candidates identical across all three legs
        def _parity(dir_a, dir_b):
            ident = tot = 0
            for pattern in ("*_ACCEL_*.cand", "*_ACCEL_*.txtcand"):
                for fa in sorted(_glob.glob(os.path.join(td, dir_a,
                                                         pattern))):
                    fb = os.path.join(td, dir_b, os.path.basename(fa))
                    tot += 1
                    if (os.path.exists(fb) and open(fa, "rb").read()
                            == open(fb, "rb").read()):
                        ident += 1
            return ident, tot

        ident_r, tot_r = _parity("off0", "ring0")
        ident_f, tot_f = _parity("off0", "full0")
        assert ident_r == tot_r and tot_r > 0, \
            f"flightrec leg diverged: {ident_r}/{tot_r}"
        assert ident_f == tot_f and tot_f > 0, \
            f"full-telemetry leg diverged: {ident_f}/{tot_f}"

    off_s = min(legs["off"])
    ring_s = min(legs["flightrec"])
    full_s = min(legs["full"])
    ring_frac = ring_s / off_s - 1.0
    full_frac = full_s / off_s - 1.0
    print(f"# obs overhead A/B: off {off_s:.3f}s, flightrec "
          f"{ring_s:.3f}s ({100 * ring_frac:+.1f}%), full telemetry "
          f"{full_s:.3f}s ({100 * full_frac:+.1f}%) — min of {reps} "
          f"reps, {n_obs} obs x {len(stages)} stages, "
          f"{ident_f}/{tot_f} candidates byte-identical",
          file=sys.stderr)
    assert full_frac <= overhead_bound, (
        f"observability plane costs {100 * full_frac:.1f}% "
        f"(> {100 * overhead_bound:.0f}%): the passenger is steering")
    return {
        "metric": "obs_overhead_frac",
        "value": round(full_frac, 4),
        "unit": (f"fractional wall-clock overhead of the FULL "
                 f"observability plane (flight recorder + telemetry "
                 f"session + obs traces) vs instrumentation-off on the "
                 f"toy sweep->accel fleet ({n_obs} obs x {len(stages)} "
                 f"stages, {C}-chan x {T}-sample, min of {reps} reps, "
                 f"warm jit; bound asserted <= {overhead_bound})"),
        "vs_baseline": 0.0,
        "obs_off_seconds": round(off_s, 4),
        "obs_flightrec_seconds": round(ring_s, 4),
        "obs_full_seconds": round(full_s, 4),
        "obs_flightrec_overhead_frac": round(ring_frac, 4),
        "obs_full_overhead_frac": round(full_frac, 4),
        "obs_overhead_bound": overhead_bound,
        "obs_reps": reps,
        "obs_n_obs": n_obs,
        "obs_n_stages": len(stages),
        "obs_candidates_identical": f"{ident_f}/{tot_f}",
        "obs_nsamp": T,
        "obs_nchan": C,
    }


def run_race(args):
    """Seeded interleaving stress harness (psrrace's dynamic acceptance
    measurement, round 19): run a toy fleet CLEAN (single host, no
    perturbation), then re-run the SAME fleet once per seed with every
    concurrency surface the runtime has, deliberately perturbed:

    - TWO in-process hosts coordinating through a shared FleetPlane
      (claim/adopt loops, heartbeat renewers, fenced manifests), plus a
      ghost host that claims an observation and leaves — so adoption is
      exercised every leg, not just when a race happens to produce one;
    - an armed in-stage ``hang`` outlasting ``--stall`` so the watchdog
      async-interrupt path fires (under the round-19 deferral rule: an
      interrupt is withheld while the target holds a tracked lock);
    - prefetch producers inside the real sweep stages;
    - ``sys.setswitchinterval`` cranked down per seed AND seeded
      faultinject-driven pauses at every tracked lock boundary
      (``resilience.locks.configure_race``), widening race windows by
      orders of magnitude;
    - ``PYPULSAR_TPU_LOCKDEP=strict``: ANY acquisition-order cycle
      raises instead of warning.

    Asserted per seed: the fleet completes with zero quarantines, at
    least one adoption and at least one watchdog interrupt happened,
    ZERO lockdep order violations were recorded, and every artifact is
    byte-identical to the clean run's. The committed record is
    RACE_r01.json."""
    acquire_backend()
    import glob as _glob
    import tempfile
    import threading

    from pypulsar_tpu.resilience import faultinject, locks
    from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
    from pypulsar_tpu.survey.fleet import FleetPlane
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    n_obs, n_hosts = 3, 2
    stall_s = 6.0
    seeds = list(range(1, max(1, args.race_seeds) + 1))
    C, T, dtp = 32, 1 << 13, 5e-4  # structure, not walls: always small
    rng_freqs = 1500.0 - 4.0 * np.arange(C)
    cfg = SurveyConfig(
        mask=True, mask_time=2.0, lodm=0.0, dmstep=10.0, numdms=8,
        nsub=8, group_size=4, threshold=8.0,
        accel_zmax=20.0, accel_numharm=2, accel_sigma=3.0, accel_batch=4,
        sift_sigma=3.0, sift_min_hits=1, fold_nbins=32, fold_npart=8)
    stages = build_dag(cfg)

    env_save = {k: os.environ.get(k) for k in
                ("PYPULSAR_TPU_HANG_S", "PYPULSAR_TPU_PREFETCH_TIMEOUT",
                 "PYPULSAR_TPU_LOCKDEP")}
    os.environ["PYPULSAR_TPU_HANG_S"] = str(stall_s + 4.0)
    os.environ["PYPULSAR_TPU_PREFETCH_TIMEOUT"] = "20"
    os.environ["PYPULSAR_TPU_LOCKDEP"] = "strict"
    old_si = sys.getswitchinterval()
    per_seed = []
    try:
        with tempfile.TemporaryDirectory() as td:
            fils = [_synth_survey_fil(
                os.path.join(td, f"obs{i}.fil"), 31 + i, C, T, dtp,
                rng_freqs, f"RACE{i}", dm=40.0,
                period=0.1024 * (1.0 + 0.07 * i), amp=10.0)
                for i in range(n_obs)]

            def fleet(dirname):
                out = os.path.join(td, dirname)
                os.makedirs(out, exist_ok=True)
                return [Observation(f"obs{i}", fils[i],
                                    os.path.join(out, f"obs{i}"))
                        for i in range(n_obs)]

            def parity(dirname):
                ident = tot = 0
                diverged = []
                for pattern in ("*_ACCEL_*.cand", "*_ACCEL_*.txtcand",
                                "*_cand*.pfd", "*.dat"):
                    for fa in sorted(_glob.glob(
                            os.path.join(td, "clean", pattern))):
                        fb = os.path.join(td, dirname,
                                          os.path.basename(fa))
                        tot += 1
                        if (os.path.exists(fb)
                                and open(fa, "rb").read()
                                == open(fb, "rb").read()):
                            ident += 1
                        else:
                            diverged.append(os.path.basename(fa))
                return ident, tot, diverged

            # clean reference leg (also warms every stage's jit
            # programs so the race legs' stall bound never fires on a
            # cold compile)
            faultinject.reset()
            locks.reset()
            clean = FleetScheduler(fleet("clean"), cfg,
                                   max_host_workers=2, devices=1).run()
            assert clean.ok and len(clean.ran) == n_obs * len(stages)

            for seed in seeds:
                tag = f"race{seed}"
                obs = fleet(tag)
                out = os.path.join(td, tag)
                faultinject.reset()
                locks.reset()
                locks.configure_race(seed, pause_us=150.0)
                sys.setswitchinterval(
                    (2e-6, 5e-5, 5e-6, 2e-4)[seed % 4])
                # one armed in-stage hang per leg: the watchdog
                # interrupt path must fire under perturbation, not just
                # when the seed happens to produce a stall
                faultinject.configure("hang:sweep.chunk_dispatch:3")
                # a ghost host claims an observation and LEAVES (lease
                # retired with the claim still running): adoption is
                # exercised deterministically every leg
                ghost = FleetPlane(out, host_id="ghost", lease_s=0.5,
                                   settle_s=0.0)
                ghost.register()
                ghost.claim(obs[0].name)
                ghost.close()
                results, errors = {}, {}

                def go(host_id, _obs=obs, _out=out):
                    plane = FleetPlane(_out, host_id=host_id,
                                       lease_s=1.0, settle_s=0.02,
                                       heartbeat_s=0.2)
                    try:
                        results[host_id] = FleetScheduler(
                            _obs, cfg, max_host_workers=2, devices=1,
                            retries=2, stall_s=stall_s,
                            plane=plane).run()
                    except BaseException as e:  # noqa: BLE001 - re-raised
                        errors[host_id] = e
                t0 = time.perf_counter()
                hosts = [threading.Thread(target=go, args=(f"host{h}",))
                         for h in range(n_hosts)]
                for t in hosts:
                    t.start()
                    time.sleep(0.05)
                for t in hosts:
                    t.join(timeout=600)
                wall = time.perf_counter() - t0
                sys.setswitchinterval(old_si)
                locks.configure_race(None)
                assert not errors, (
                    f"seed {seed}: host raised: "
                    f"{ {h: repr(e) for h, e in errors.items()} }")
                assert all(not t.is_alive() for t in hosts), (
                    f"seed {seed}: a host thread wedged past 600s")
                quarantined = {n: q for r in results.values()
                               for n, q in r.quarantined.items()}
                assert not quarantined, (
                    f"seed {seed}: quarantines under race stress: "
                    f"{quarantined}")
                adopted = sorted({n for r in results.values()
                                  for n in r.adopted})
                timeouts = sum(r.timeouts for r in results.values())
                assert adopted, (
                    f"seed {seed}: the ghost's claim was never adopted")
                assert timeouts >= 1, (
                    f"seed {seed}: the armed hang never produced a "
                    f"watchdog interrupt — the async-interrupt-under-"
                    f"perturbation path went uncovered")
                viol = locks.violations()
                assert not viol, (
                    f"seed {seed}: lockdep order violations: {viol}")
                ident, tot, diverged = parity(tag)
                assert ident == tot and tot > 0, (
                    f"seed {seed}: artifacts diverged from clean: "
                    f"{ident}/{tot} ({diverged[:8]})")
                # a final no-perturbation resume validates every
                # manifest and re-runs nothing
                final = FleetScheduler(fleet(tag), cfg,
                                       max_host_workers=2, devices=1,
                                       resume=True).run()
                assert final.ok and len(final.ran) == 0, (
                    f"seed {seed}: post-race resume re-ran "
                    f"{len(final.ran)} stages")
                snap = locks.snapshot()
                per_seed.append({
                    "seed": seed,
                    "switch_interval_s": (2e-6, 5e-5, 5e-6, 2e-4)[seed % 4],
                    "lock_pauses_injected": locks.race_pauses(),
                    "adopted": adopted,
                    "watchdog_interrupts": timeouts,
                    "order_violations": 0,
                    "artifacts_identical": f"{ident}/{tot}",
                    "wall_s": round(wall, 2),
                    "locks_tracked": len(snap),
                    "contentions": sum(v["contentions"]
                                       for v in snap.values()),
                })
                print(f"# race: seed {seed}: "
                      f"{per_seed[-1]['lock_pauses_injected']} lock "
                      f"pauses, {timeouts} watchdog interrupts, "
                      f"adopted {adopted}, {ident}/{tot} artifacts "
                      f"identical, 0 violations ({wall:.1f}s)",
                      file=sys.stderr)
    finally:
        sys.setswitchinterval(old_si)
        faultinject.reset()
        locks.configure_race(None)
        for k, v in env_save.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    timeouts_total = sum(p["watchdog_interrupts"] for p in per_seed)
    return {
        "metric": "race_interleaving_parity",
        "value": 1.0,
        "unit": (f"fraction of artifacts byte-identical to a clean run "
                 f"across {len(seeds)} seeded interleaving legs of a "
                 f"{n_obs}-obs x {len(stages)}-stage fleet on "
                 f"{n_hosts} in-process hosts + 1 leaving ghost "
                 f"(claim/adopt + watchdog hang-interrupt + prefetch "
                 f"concurrently, setswitchinterval cranked, seeded "
                 f"lock-boundary pauses, PYPULSAR_TPU_LOCKDEP=strict) "
                 f"— asserted 1.0 with ZERO lockdep order violations "
                 f"and a zero-stage final resume per seed"),
        "vs_baseline": 1.0,
        "race_seeds": seeds,
        "race_n_obs": n_obs,
        "race_n_hosts": n_hosts,
        "race_n_stages": len(stages),
        "race_stall_timeout_s": stall_s,
        "race_pause_us": 150.0,
        "race_watchdog_interrupts_total": timeouts_total,
        "race_per_seed": per_seed,
        "race_nsamp": T,
        "race_nchan": C,
    }


def run_multihost(args):
    """Multi-host fleet harness (the round-18 fenced-lease-takeover
    acceptance measurement): ONE survey over a 4-observation toy fleet,
    run three ways —

    - **serial**: the 1-host serial chain (the byte-parity reference);
    - **clean 3-host**: three REAL host processes (``survey --host-id
      hostN`` children, rank env grid) coordinating purely through the
      shared-directory plane (``<outdir>/_fleet``): fsync'd heartbeat
      leases, fencing-token'd claims, no coordinator service;
    - **host-kill chaos**: the same 3-host fleet, but host0 is parked
      mid-sweep by an armed in-stage hang and then SIGKILL'd (the real
      signal — no finally blocks, no heartbeat retirement, the lease
      just goes silent). Survivors must detect the death past
      ``PYPULSAR_TPU_HOST_LEASE_S``, ADOPT the orphaned observation,
      resume it from its manifest, and finish the fleet.

    Asserted, not just reported: the kill leg's final artifact set is
    byte-identical to the serial run, at least one adoption event fired,
    the victim really died by signal, and a final no-fault single-host
    ``--resume`` over the kill leg's outdir re-runs ZERO stages. The
    wall-clock A/B is a CPU toy (hosts share one machine's cores) — the
    committed claims are the adoption/fencing/parity structure.

    Round-21 observability riders: the clean leg's host0 runs with
    ``--status-port 0`` and this process scrapes the LIVE
    ``/status.json`` + Prometheus ``/metrics`` mid-fleet; the kill
    leg's traces are fed through ``tlmtrace --check`` (no dangling
    parent_ids even across a SIGKILL'd host) and stitched into the
    committed Perfetto JSON (``--trace-out``), with the adoption
    asserted visible as a lane handover on one trace_id."""
    acquire_backend()
    import glob as _glob
    import re
    import signal
    import tempfile
    import urllib.request

    from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    n_obs, n_hosts = 4, 3
    lease_s = 3.0
    C, T, dtp = 32, (1 << 13 if args.quick
                     else 1 << 14), 5e-4
    rng_freqs = 1500.0 - 4.0 * np.arange(C)
    cfg = SurveyConfig(
        mask=True, mask_time=2.0, lodm=0.0, dmstep=10.0, numdms=8,
        nsub=8, group_size=4, threshold=8.0,
        accel_zmax=20.0, accel_numharm=2, accel_sigma=3.0, accel_batch=4,
        sift_sigma=3.0, sift_min_hits=1, fold_nbins=32, fold_npart=8)
    stages = build_dag(cfg)
    # the SAME knobs as CLI flags — the children must run the identical
    # chain or the byte-parity assert (and the final resume's
    # fingerprint match) would be vacuous
    flags = ["--mask-time", "2.0", "--lodm", "0.0", "--dmstep", "10.0",
             "--numdms", "8", "-s", "8", "--group-size", "4",
             "--threshold", "8.0", "--accel-zmax", "20.0",
             "--accel-dz", "2.0", "--accel-numharm", "2",
             "--accel-sigma", "3.0", "--accel-batch", "4",
             "--sift-sigma", "3.0", "--sift-min-hits", "1",
             "--fold-nbins", "32", "--fold-npart", "8"]
    repo_root = os.path.dirname(os.path.abspath(__file__))

    def spawn_host(rank, fils, outdir, tlmdir, logdir, extra_env=None,
                   extra_flags=None):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = (repo_root + os.pathsep
                             + env.get("PYTHONPATH", "")).rstrip(
                                 os.pathsep)
        env["PYPULSAR_TPU_HOST_LEASE_S"] = str(lease_s)
        env["PYPULSAR_TPU_NUM_PROCESSES"] = str(n_hosts)
        env["PYPULSAR_TPU_PROCESS_ID"] = str(rank)
        env.update(extra_env or {})
        log = open(os.path.join(logdir, f"host{rank}.log"), "w")
        proc = subprocess.Popen(
            [sys.executable, "-m", "pypulsar_tpu.cli", "survey",
             *fils, "-o", outdir, *flags, "--host-id", f"host{rank}",
             "--telemetry-dir", tlmdir, *(extra_flags or [])],
            env=env, stdout=log, stderr=subprocess.STDOUT)
        proc._log = log  # closed on wait below
        proc._logpath = log.name
        return proc

    def wait_hosts(procs, timeout=900):
        codes = []
        for proc in procs:
            try:
                codes.append(proc.wait(timeout=timeout))
            finally:
                proc._log.close()
        return codes

    def adoption_events(tlmdir):
        out = []
        for p in sorted(_glob.glob(os.path.join(tlmdir, "*.jsonl"))):
            for line in open(p):
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if (rec.get("type") == "event"
                        and rec.get("name") == "survey.obs_adopted"
                        and (rec.get("attrs") or {}).get("obs")):
                    # the plane-emitted flavor only (host+obs+token);
                    # the per-obs trace echoes a hostless twin that
                    # would double-count the same adoption
                    out.append(rec["attrs"])
        return out

    def parity(td, dir_a, dir_b):
        ident = tot = 0
        diverged = []
        for pattern in (".cands", "_DM*_ACCEL_*.cand",
                        "_DM*_ACCEL_*.txtcand", "_DM*.dat",
                        ".accelcands", "_cand*.pfd"):
            for fa in sorted(_glob.glob(os.path.join(td, dir_a,
                                                     "*" + pattern))):
                fb = os.path.join(td, dir_b, os.path.basename(fa))
                tot += 1
                if (os.path.exists(fb) and open(fa, "rb").read()
                        == open(fb, "rb").read()):
                    ident += 1
                else:
                    diverged.append(os.path.basename(fa))
        return ident, tot, diverged

    with tempfile.TemporaryDirectory() as td:
        fils = [_synth_survey_fil(os.path.join(td, f"obs{i}.fil"), 31 + i,
                                  C, T, dtp, rng_freqs, f"MH{i}",
                                  period=0.1024 * (1.0 + 0.07 * i))
                for i in range(n_obs)]

        def fleet(dirname):
            out = os.path.join(td, dirname)
            os.makedirs(out, exist_ok=True)
            return out, [Observation(f"obs{i}", fils[i],
                                     os.path.join(out, f"obs{i}"))
                         for i in range(n_obs)]

        # leg 0 — serial 1-host reference (also the timing baseline)
        sdir, sobs = fleet("serial")
        t0 = time.perf_counter()
        for obs in sobs:
            for stage in stages:
                stage.execute(obs, cfg)
        serial_s = time.perf_counter() - t0
        print(f"# multihost: serial 1-host reference {serial_s:.1f}s",
              file=sys.stderr)

        # leg 1 — clean 3-host fleet (subprocess hosts, cold jit caches:
        # the wall includes per-host compile, stated in the record).
        # host0 carries the round-21 endpoint smoke: --status-port 0
        # binds a free port, and while the fleet is LIVE we scrape both
        # /status.json and the Prometheus /metrics from this process.
        mdir, mobs = fleet("mh")
        mtlm = os.path.join(td, "mh_tlm")
        t0 = time.perf_counter()
        procs = [spawn_host(r, fils, mdir, mtlm, td,
                            extra_flags=(["--status-port", "0"]
                                         if r == 0 else None))
                 for r in range(n_hosts)]
        status_url = None
        url_re = re.compile(r"live status at (http://[^/\s]+)/status\.json")
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline and status_url is None:
            if procs[0].poll() is not None:
                break  # host0 already exited — the asserts below will say
            try:
                m = url_re.search(open(procs[0]._logpath).read())
            except OSError:
                m = None
            if m:
                status_url = m.group(1)
            else:
                time.sleep(0.2)
        assert status_url, "host0 never announced its --status-port URL"
        # the server lives for host0's whole scheduler run, so these
        # fetches hit a LIVE endpoint — but observation rows only
        # appear once the first manifests land, a moment after the
        # claims, so poll the snapshot until they do
        snap = None
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            try:
                snap = json.loads(urllib.request.urlopen(
                    status_url + "/status.json", timeout=15).read())
            except OSError:
                snap = None
            if snap and snap.get("rows"):
                break
            if procs[0].poll() is not None:
                break  # host0 done: the server is gone with it
            time.sleep(0.5)
        assert snap and snap.get("rows"), \
            f"live /status.json never grew observation rows: {snap}"
        assert all(r.get("state") for r in snap["rows"])
        metrics = urllib.request.urlopen(
            status_url + "/metrics", timeout=15).read().decode()
        assert "pypulsar_obs_state" in metrics, \
            f"live /metrics missing obs_state gauges:\n{metrics[:400]}"
        print(f"# multihost: live endpoint smoke OK — {status_url} "
              f"served {len(snap['rows'])} status rows + "
              f"{sum(1 for ln in metrics.splitlines() if ln and not ln.startswith('#'))} "
              f"Prometheus samples mid-fleet", file=sys.stderr)
        codes = wait_hosts(procs)
        mh_s = time.perf_counter() - t0
        assert codes == [0] * n_hosts, \
            f"clean multihost leg exit codes {codes}"
        ident, tot, diverged = parity(td, "serial", "mh")
        assert ident == tot and tot > 0, (
            f"clean 3-host artifacts diverged from serial: {ident}/{tot}"
            f" ({diverged[:8]})")
        print(f"# multihost: clean 3-host fleet {mh_s:.1f}s, {ident}/"
              f"{tot} artifacts byte-identical to serial",
              file=sys.stderr)

        # leg 2 — HOST-KILL CHAOS: park host0 mid-sweep (armed in-stage
        # hang, bound far beyond the leg), then SIGKILL it once the
        # hang provably fired (its per-record-flushed fleet trace shows
        # resilience.fault_injected). No finally blocks run: the lease
        # just goes silent, which is exactly what survivors must detect.
        kdir, kobs = fleet("kill")
        ktlm = os.path.join(td, "kill_tlm")
        t0 = time.perf_counter()
        victim = spawn_host(0, fils, kdir, ktlm, td, extra_env={
            "PYPULSAR_TPU_FAULTS": "hang:sweep.chunk_dispatch:1",
            "PYPULSAR_TPU_HANG_S": "600"})
        survivors = [spawn_host(r, fils, kdir, ktlm, td)
                     for r in range(1, n_hosts)]
        vtrace = os.path.join(ktlm, "fleet.host0.jsonl")
        deadline = time.monotonic() + 300
        parked = False
        while time.monotonic() < deadline:
            if victim.poll() is not None:
                break  # died early — the log will say why
            try:
                parked = "resilience.fault_injected" in open(vtrace).read()
            except OSError:
                parked = False
            if parked:
                break
            time.sleep(0.25)
        assert parked, "victim never reached the armed mid-sweep hang"
        os.kill(victim.pid, signal.SIGKILL)
        vcode = victim.wait(timeout=60)
        victim._log.close()
        kcodes = wait_hosts(survivors)
        kill_s = time.perf_counter() - t0
        assert vcode == -signal.SIGKILL, \
            f"victim exit {vcode}, expected -SIGKILL"
        assert kcodes == [0] * (n_hosts - 1), \
            f"survivor exit codes {kcodes}"
        adoptions = adoption_events(ktlm)
        assert adoptions, "no survey.obs_adopted event fired"
        assert all(a.get("adopted_from") == "host0" for a in adoptions)
        ident_k, tot_k, diverged_k = parity(td, "serial", "kill")
        assert ident_k == tot_k and tot_k > 0, (
            f"post-kill artifacts diverged from serial: "
            f"{ident_k}/{tot_k} ({diverged_k[:8]})")

        # the round-21 trace smoke: tlmtrace over EVERYTHING the kill
        # leg wrote (per-host fleet traces, per-obs traces, postmortem
        # capsules). --check must come back clean — the victim's torn
        # tail (children of the stage span it never got to flush) is
        # tolerated because the adoption receipt proves the murder,
        # but any OTHER dangling parent_id fails — and the stitched
        # Perfetto JSON must show the adoption as a LANE HANDOVER:
        # spans of one trace_id on both the victim's and an adopter's
        # host lane. That stitched file is the committed OBS_trace
        # artifact (--trace-out).
        from pypulsar_tpu.cli import tlmtrace as _tlmtrace
        trace_files = sorted(_glob.glob(os.path.join(ktlm, "*.jsonl")))
        trace_files += sorted(_glob.glob(
            os.path.join(kdir, "_fleet", "postmortem", "*.json")))
        assert _tlmtrace.main(["--check", *trace_files]) == 0, \
            "tlmtrace --check found dangling parent_ids after host kill"
        trace_dst = (os.path.abspath(args.trace_out) if args.trace_out
                     else os.path.join(td, "kill.trace.json"))
        assert _tlmtrace.main([*trace_files, "-o", trace_dst]) == 0
        with open(trace_dst) as f:
            doc = json.load(f)
        lanes_by_trace = {}
        for ev in doc["traceEvents"]:
            a = ev.get("args") or {}
            if a.get("trace_id") and a.get("host"):
                lanes_by_trace.setdefault(
                    a["trace_id"], set()).add(a["host"])
        trace_by_obs = {o: t for t, o
                        in doc["otherData"]["traces"].items()}
        adopters = {str(a.get("host")) for a in adoptions}
        handover = {}
        for obs_name in sorted({str(a.get("obs")) for a in adoptions}):
            tid = trace_by_obs.get(obs_name)
            assert tid, f"adopted obs {obs_name} has no stitched trace"
            handover[obs_name] = sorted(lanes_by_trace.get(tid, ()))
        assert any("host0" in lanes and set(lanes) & adopters
                   for lanes in handover.values()), (
            f"no adopted trace spans both the victim's and an "
            f"adopter's lane: {handover} (adopters {adopters})")
        n_trace_ev = len(doc["traceEvents"])
        n_trace_hosts = len(doc["otherData"]["hosts"])
        print(f"# multihost: tlmtrace --check clean over "
              f"{len(trace_files)} file(s); stitched {n_trace_ev} "
              f"events / {n_trace_hosts} host lanes -> {trace_dst} — "
              f"adoption lane handover {handover}", file=sys.stderr)

        # the acceptance tail: a final no-fault single-host resume over
        # the kill leg's outdir validates every manifest and runs NOTHING
        final = FleetScheduler(kobs, cfg, resume=True).run()
        assert final.ok and len(final.ran) == 0, (
            f"final resume re-ran {len(final.ran)} stages: {final.ran}")
        resume_skipped = len(final.skipped)

    speedup = serial_s / mh_s
    n_adopt = len(adoptions)
    print(f"# multihost: host-kill leg {kill_s:.1f}s — victim SIGKILL'd "
          f"mid-sweep, {n_adopt} adoption(s) by "
          f"{sorted({a.get('host') for a in adoptions})}, "
          f"{ident_k}/{tot_k} artifacts byte-identical to serial, final "
          f"resume ran 0 / skipped {resume_skipped} stages",
          file=sys.stderr)
    hostchaos = {
        "metric": "multihost_kill_recovery",
        "value": round(ident_k / max(tot_k, 1), 3),
        "unit": (f"fraction of artifacts byte-identical to the 1-host "
                 f"serial run after a {n_obs}-obs x {n_hosts}-process "
                 f"CPU fleet had host0 SIGKILL'd mid-sweep (parked by "
                 f"an armed in-stage hang, killed by real SIGKILL, "
                 f"lease silent past {lease_s}s) and survivors adopted "
                 f"its observation via the fenced lease plane — "
                 f"asserted 1.0, plus a final no-fault resume "
                 f"validating 0 stages re-run"),
        "vs_baseline": 1.0,
        "multihost_n_obs": n_obs,
        "multihost_n_hosts": n_hosts,
        "multihost_lease_s": lease_s,
        "multihost_victim": "host0",
        "multihost_victim_exit": vcode,
        "multihost_kill_point": "hang:sweep.chunk_dispatch:1 + SIGKILL",
        "multihost_adoptions": n_adopt,
        "multihost_adopters": sorted({str(a.get("host"))
                                      for a in adoptions}),
        "multihost_adopted_obs": sorted({str(a.get("obs", "?"))
                                         for a in adoptions}),
        "multihost_artifacts_identical": f"{ident_k}/{tot_k}",
        "multihost_kill_leg_seconds": round(kill_s, 2),
        "multihost_final_resume_ran": 0,
        "multihost_final_resume_skipped": resume_skipped,
        "multihost_trace_out": (os.path.basename(args.trace_out)
                                if args.trace_out else None),
        "multihost_trace_events": n_trace_ev,
        "multihost_trace_host_lanes": n_trace_hosts,
        "multihost_trace_handover": {k: list(v)
                                     for k, v in handover.items()},
        "multihost_status_endpoint_rows": len(snap["rows"]),
        "multihost_nsamp": T,
        "multihost_nchan": C,
    }
    if args.hostchaos_out:
        with open(args.hostchaos_out, "w") as f:
            json.dump(hostchaos, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# multihost: host-kill chaos record -> "
              f"{args.hostchaos_out}", file=sys.stderr)
    return {
        "metric": "multihost_fleet_parity",
        "value": round((ident + ident_k) / max(tot + tot_k, 1), 3),
        "unit": (f"fraction of artifacts byte-identical to the 1-host "
                 f"serial chain across BOTH multi-host legs (clean "
                 f"{n_hosts}-process fleet + host-kill/adoption leg; "
                 f"{n_obs} toy obs x {len(stages)} stages, {C}-chan x "
                 f"{T}-sample each) — asserted 1.0. Wall clocks are "
                 f"recorded but NOT the claim on this CPU toy: host "
                 f"processes are cold (each child pays its own jax "
                 f"import + jit compile inside the timed leg) and all "
                 f"hosts share one machine's cores; the committed "
                 f"claims are plane coordination, fenced adoption "
                 f"(detail in "
                 f"{os.path.basename(args.hostchaos_out or 'HOSTCHAOS')}"
                 f") and byte parity"),
        "vs_baseline": 1.0,
        "multihost_cold_fleet_speedup": round(speedup, 3),
        "multihost_n_obs": n_obs,
        "multihost_n_hosts": n_hosts,
        "multihost_serial_seconds": round(serial_s, 2),
        "multihost_fleet_seconds": round(mh_s, 2),
        "multihost_artifacts_identical": f"{ident}/{tot}",
        "multihost_kill_leg": {
            k: hostchaos[k] for k in
            ("multihost_adoptions", "multihost_adopters",
             "multihost_victim_exit", "multihost_artifacts_identical",
             "multihost_final_resume_ran", "multihost_kill_leg_seconds")},
        "multihost_lease_s": lease_s,
        "multihost_nsamp": T,
        "multihost_nchan": C,
    }


def run_corruption(args):
    """Corruption-chaos harness (the round-13 data-integrity acceptance
    measurement): run a toy fleet CLEAN over pristine inputs, then run
    the SAME fleet over copies corrupted with every data-fault kind
    (one kind per observation, plus one untouched control):

    - ``nanburst`` / ``bitflip`` / ``dropblock`` payload damage must be
      scrubbed by the dataguard (NaNs zero-filled on device, counted in
      ``data.*`` telemetry) and the observation completes DEGRADED;
    - ``truncate`` must salvage the valid prefix (reported in the
      manifest's data-quality note) and complete degraded — its
      missing fraction sits below the --max-bad-frac bar;
    - ``header`` garbage must be caught at INGEST (DataFormatError)
      and the observation data-quarantined (reason ``"data"``) without
      burning a single device stage.

    Then assert: zero crashes/hangs (the scheduler returns), exactly
    the header observation quarantined, the clean CONTROL observation's
    artifacts byte-identical to the clean run's, a no-op validated
    resume, and — the committed fuzz receipt — N seeded reader-fuzz
    mutations per format with a 100% parse-or-DataFormatError outcome.
    """
    acquire_backend()
    import glob as _glob
    import shutil
    import tempfile

    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.resilience import dataguard
    from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation, status_rows

    seed = args.corruption_seed
    fuzz_n = 500
    C, T, dtp = 32, (1 << 13 if args.quick
                     else 1 << 14), 5e-4
    rng_freqs = 1500.0 - 4.0 * np.arange(C)
    cfg = SurveyConfig(
        mask=True, mask_time=2.0, lodm=0.0, dmstep=10.0, numdms=8,
        nsub=8, group_size=4, threshold=8.0,
        accel_zmax=20.0, accel_numharm=2, accel_sigma=3.0, accel_batch=4,
        sift_sigma=3.0, sift_min_hits=1, fold_nbins=32, fold_npart=8)
    stages = build_dag(cfg)
    kinds = ["nanburst", "bitflip", "dropblock", "truncate", "header"]
    n_obs = 1 + len(kinds)  # obs0 = clean control

    def _counter_totals():
        cur = telemetry.current()
        return dict(cur.counter_totals()) if cur is not None else {}

    with tempfile.TemporaryDirectory() as td:
        fils = [_synth_survey_fil(os.path.join(td, f"obs{i}.fil"),
                                  31 + i, C, T, dtp, rng_freqs,
                                  f"CORR{i}",
                                  period=0.1024 * (1.0 + 0.07 * i))
                for i in range(n_obs)]

        def fleet(dirname, files):
            out = os.path.join(td, dirname)
            os.makedirs(out, exist_ok=True)
            return [Observation(f"obs{i}", files[i],
                                os.path.join(out, f"obs{i}"))
                    for i in range(len(files))]

        # clean leg over pristine inputs (also warms every jit cache)
        t0 = time.perf_counter()
        clean = FleetScheduler(fleet("clean", fils), cfg,
                               max_host_workers=2, devices=1).run()
        clean_s = time.perf_counter() - t0
        assert clean.ok and len(clean.ran) == n_obs * len(stages)

        # corrupted copies: obs0 untouched, obs1..n one fault kind each
        # (the ONE corruption code path tools/tests share)
        corr = [os.path.join(td, f"corr_obs{i}.fil")
                for i in range(n_obs)]
        corruption = {}
        for i, (src, dst) in enumerate(zip(fils, corr)):
            shutil.copy(src, dst)
            if i > 0:
                desc = dataguard.corrupt_file(dst, kinds[i - 1],
                                              seed=seed + i)
                corruption[f"obs{i}"] = {
                    k: v for k, v in desc.items() if k != "path"}

        t0 = time.perf_counter()
        corr_obs = fleet("corr", corr)
        # an in-memory telemetry session (nested sessions reuse the
        # outer one) guarantees the data.* counters are live — the
        # scrub receipt below is an acceptance assertion, not a nice-
        # to-have
        with telemetry.session(tool="bench-corruption"):
            base = _counter_totals()
            result = FleetScheduler(corr_obs, cfg, max_host_workers=2,
                                    devices=1).run()
            counters = _counter_totals()
        corr_s = time.perf_counter() - t0
        scrubbed = (counters.get("data.nonfinite_cells", 0)
                    - base.get("data.nonfinite_cells", 0))
        cells = (counters.get("data.cells", 0)
                 - base.get("data.cells", 0))

        # verdicts: exactly the header observation is DATA-quarantined;
        # every other observation (incl. the salvaged truncation)
        # completed — degraded, not dead
        header_obs = f"obs{1 + kinds.index('header')}"
        assert set(result.quarantined) == {header_obs}, (
            f"unexpected quarantine set: {result.quarantined}")
        q = result.quarantined[header_obs]
        assert q.get("reason") == "data" and q["stage"] == "ingest", q
        assert len(result.ran) == (n_obs - 1) * len(stages), (
            f"degraded observations did not complete: "
            f"{len(result.ran)} stages ran")
        # the NaN burst provably hit the scrub (masked fraction is the
        # telemetry receipt the gate test pins down)
        assert scrubbed > 0, "nanburst was never scrubbed on device"

        # the truncated observation's manifest carries its salvage story
        rows = {r["obs"]: r for r in status_rows(
            [o.manifest for o in corr_obs])}
        trunc_obs = f"obs{1 + kinds.index('truncate')}"
        dq = rows[trunc_obs].get("data_quality") or {}
        assert (dq.get("salvage") or {}).get("missing_samples", 0) > 0, (
            f"truncation salvage not reported: {dq}")
        bad_fracs = {o: (rows[o].get("data_quality") or {}).get(
            "bad_frac") for o in rows}

        # byte-parity of the UNCORRUPTED observation: the control's
        # whole artifact chain must match the clean run exactly —
        # asserted, not just reported
        ident = tot = 0
        diverged = []
        for pattern in ("obs0*_ACCEL_*.cand", "obs0*_ACCEL_*.txtcand",
                        "obs0*_cand*.pfd", "obs0*.dat", "obs0*.cands"):
            for fa in sorted(_glob.glob(os.path.join(td, "clean",
                                                     pattern))):
                fb = os.path.join(td, "corr", os.path.basename(fa))
                tot += 1
                if (os.path.exists(fb) and open(fa, "rb").read()
                        == open(fb, "rb").read()):
                    ident += 1
                else:
                    diverged.append(os.path.basename(fa))
        assert ident == tot and tot > 0, (
            f"control-observation artifacts diverged: {ident}/{tot} "
            f"({diverged[:8]})")
        # the SNR summary embeds the run's outdir in each row's pfd
        # path, so compare ROWS with the path normalized to its
        # basename — every measured value must still match exactly
        def _snr_rows(d):
            with open(os.path.join(td, d, "obs0_snr.json")) as f:
                rows_ = json.load(f)
            for r in rows_:
                r["pfd"] = os.path.basename(r["pfd"])
            return rows_

        snr_clean, snr_corr = _snr_rows("clean"), _snr_rows("corr")
        assert snr_clean == snr_corr and snr_clean, (
            "control-observation SNR rows diverged")
        tot += 1
        ident += 1

        # a validated resume re-runs NOTHING (the degraded runs'
        # manifests are trustworthy) and re-issues only the data verdict
        final = FleetScheduler(fleet("corr", corr), cfg,
                               max_host_workers=2, devices=1,
                               resume=True).run()
        assert len(final.ran) == 0, (
            f"post-corruption resume re-ran {len(final.ran)} stages")
        assert set(final.quarantined) == {header_obs}

    # the committed fuzz receipt: N seeded mutations per format, 100%
    # parse-or-DataFormatError (never a hang or a raw codec exception)
    fuzz = {}
    with tempfile.TemporaryDirectory() as fz:
        for fmt in ("filterbank", "psrfits", "dat"):
            counts, failures = dataguard.run_reader_fuzz(
                fmt, fuzz_n, seed, os.path.join(fz, fmt))
            assert not failures, (
                f"reader fuzz contract violated for {fmt}: "
                f"{failures[:5]}")
            fuzz[fmt] = counts

    n_kinds = len(kinds)
    print(f"# corruption: {n_kinds} fault kinds over {n_obs - 1} "
          f"observations + 1 control — fleet completed "
          f"({len(result.ran)} stages, 1 data quarantine at ingest, "
          f"{scrubbed} non-finite cells scrubbed on device), control "
          f"{ident}/{tot} artifacts byte-identical to clean "
          f"({clean_s:.1f}s clean, {corr_s:.1f}s corrupted); reader "
          f"fuzz {fuzz_n}x3 formats 100% clean", file=sys.stderr)
    return {
        "metric": "corruption_fleet_integrity",
        "value": round(ident / max(tot, 1), 3),
        "unit": (f"fraction of the uncorrupted control observation's "
                 f"artifacts byte-identical to a clean run after a "
                 f"{n_obs}-obs x {len(stages)}-stage fleet ingested "
                 f"inputs corrupted with {n_kinds} data-fault kinds "
                 f"({'+'.join(kinds)}) — asserted 1.0, with the fleet "
                 f"completing degraded (salvaged truncation, on-device "
                 f"NaN scrub) or data-quarantined (garbage header at "
                 f"ingest, reason 'data') and a validated resume "
                 f"re-running zero stages; plus {fuzz_n} seeded reader-"
                 f"fuzz mutations per format, 100% clean-error-or-"
                 f"salvage"),
        "vs_baseline": 1.0,
        "corruption_seed": seed,
        "corruption_kinds": kinds,
        "corruption_by_obs": corruption,
        "corruption_n_obs": n_obs,
        "corruption_n_stages": len(stages),
        "corruption_stages_run": len(result.ran),
        "corruption_data_quarantines": sorted(result.quarantined),
        "corruption_bad_fracs": bad_fracs,
        "corruption_nonfinite_cells_scrubbed": int(scrubbed),
        "corruption_cells_checked": int(cells),
        "corruption_control_artifacts_identical": f"{ident}/{tot}",
        "corruption_fuzz_n_per_format": fuzz_n,
        "corruption_fuzz_outcomes": fuzz,
        "corruption_clean_seconds": round(clean_s, 2),
        "corruption_seconds": round(corr_s, 2),
        "corruption_nsamp": T,
        "corruption_nchan": C,
    }


def run_waterfall(args):
    """Single-DM waterfall path (BASELINE configs[0]: waterfaller.py
    dedisperse + downsample + scale on a 10 s, 256-chan filterbank —
    reference bin/waterfaller.py:189-208 over the per-channel-roll
    Spectra path formats/spectra.py:229-260). The device pipeline is the
    same ops the CLI waterfaller uses (ops/kernels.py dedisperse /
    downsample / scaled), fused into one jitted program; the baseline is
    the NumPy twin of the identical pipeline."""
    acquire_backend()
    import jax
    import jax.numpy as jnp
    from pypulsar_tpu.ops import kernels, numpy_ref

    C, dt, dm, factor = 256, 64e-6, 100.0, 16
    T = int(round(10.0 / dt))  # 10 s
    if args.quick:
        T = 1 << 15
    freqs = (1500.0 - 300.0 / C * np.arange(C)).astype(np.float64)
    rng = np.random.RandomState(3)
    data = rng.standard_normal((C, T)).astype(np.float32)
    host_bins = numpy_ref.bin_delays(dm, freqs, dt)

    from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len

    n_shift = fourier_chunk_len(T + int(np.abs(host_bins).max()))

    def _pipe(d, bins):
        # the same op the Spectra/waterfaller path runs: auto backend
        # (fourier on TPU) with the host-known static shift bound
        ded = kernels.shift_channels(d, bins, n_fft=n_shift)
        return kernels.scaled(kernels.downsample(ded, factor))

    pipeline = jax.jit(_pipe)

    dev = jnp.asarray(data)
    binsd = jnp.asarray(host_bins)
    out = pipeline(dev, binsd)  # compile + warm
    float(jnp.ravel(out)[0])
    # COLD: one synced dispatch — the interactive waterfaller latency,
    # dispatch + sync turnaround included (VERDICT r5 item 6 asks for the
    # steady-state number NEXT TO it, not instead of it)
    cold_time = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = pipeline(dev, binsd)
        float(jnp.ravel(out)[0])
        cold_time = min(cold_time, time.perf_counter() - t0)
    cold_samples_per_sec = C * T / cold_time
    # repeat-dispatch amortized (the r5 measurement): k dispatches, one
    # sync — dispatch latency amortizes but each program is still one
    # 10-s window
    k = 10
    jax_time = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(k):
            out = pipeline(dev, binsd)
        float(jnp.ravel(out)[0])
        jax_time = min(jax_time, (time.perf_counter() - t0) / k)
    samples_per_sec = C * T / jax_time
    # STEADY STATE: a BATCH of windows through one vmapped program (the
    # repeat-window survey shape — amortizes dispatch AND the per-program
    # fixed overhead over B windows; compile excluded)
    B = 4 if args.quick else 16
    pipelineB = jax.jit(jax.vmap(_pipe, in_axes=(0, None)))
    devB = jnp.asarray(np.broadcast_to(data, (B, C, T)).copy())
    outB = pipelineB(devB, binsd)  # compile + warm
    float(jnp.ravel(outB)[0])
    steady_time = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        outB = pipelineB(devB, binsd)
        float(jnp.ravel(outB)[0])
        steady_time = min(steady_time, time.perf_counter() - t0)
    steady_samples_per_sec = B * C * T / steady_time

    # parity: the device product IS the NumPy twin's product
    ref = numpy_ref.scaled(numpy_ref.downsample(
        numpy_ref.shift_channels(data, host_bins), factor))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)

    def one_rep():
        t0 = time.perf_counter()
        numpy_ref.scaled(numpy_ref.downsample(
            numpy_ref.shift_channels(data, host_bins), factor))
        return time.perf_counter() - t0

    bl = numpy_baseline(one_rep)
    bl_samples_per_sec = C * T / bl["seconds"]
    speedup = steady_samples_per_sec / bl_samples_per_sec
    print(f"# waterfall: cold {cold_time*1e3:.1f} ms, amortized "
          f"{jax_time*1e3:.1f} ms/pipeline, steady x{B} "
          f"{steady_time*1e3:.1f} ms = {steady_samples_per_sec/1e9:.2f} "
          f"Gsamp/s; numpy {bl['seconds']:.3f}s", file=sys.stderr)
    unit = (f"waterfalled samples/s STEADY-STATE ({C}-chan, {T*dt:.1f}s @ "
            f"64us, dm={dm}, downsamp={factor}; one vmapped program over "
            f"{B} windows, best of 3, compile excluded; cold single-"
            f"dispatch and x{k} repeat-dispatch rates in extras; numpy "
            f"twin baseline, round-5 protocol)")
    return {
        "metric": "waterfall_samples_per_sec",
        "value": round(steady_samples_per_sec, 1),
        "unit": unit,
        "vs_baseline": round(speedup, 2),
        "steady_batch_windows": B,
        "steady_seconds_per_batch": round(steady_time, 4),
        "cold_seconds": round(cold_time, 4),
        "cold_samples_per_sec": round(cold_samples_per_sec, 1),
        "cold_vs_baseline": round(cold_samples_per_sec
                                  / bl_samples_per_sec, 2),
        "dispatch_amortized_seconds": round(jax_time, 4),
        "dispatch_amortized_samples_per_sec": round(samples_per_sec, 1),
        "dispatch_amortized_vs_baseline": round(samples_per_sec
                                                / bl_samples_per_sec, 2),
        "numpy_seconds_measured": round(bl["seconds"], 3),
        **{k2: v for k2, v in bl.items() if k2 != "seconds"},
    }


def run_prepass(args):
    """RFI/detrend prepass (BASELINE configs[1]: zero_dm_filter.py +
    spectrogram.py + mydetrend on a 60 s filterbank — reference
    bin/zero_dm_filter.py:30-50, bin/spectrogram.py:17-37,
    utils/mydetrend.py:65-107). Device pipeline, one jitted program:
    per-sample zero-DM filter -> channel-summed timeseries -> block
    power spectrogram (power-of-two block: non-pow2 FFTs lower to dense
    DFT matmuls on this platform, BENCHNOTES) -> batched WLS detrend of
    the log-power rows (utils/detrend._detrend_blocks_jit, the same
    kernel detrend_blocks wraps)."""
    acquire_backend()
    import jax
    import jax.numpy as jnp
    from pypulsar_tpu.fourier.kernels import spectrogram
    from pypulsar_tpu.ops import kernels
    from pypulsar_tpu.ops import numpy_ref
    from pypulsar_tpu.fourier import numpy_ref as fnumpy_ref
    from pypulsar_tpu.utils import detrend as detrend_mod

    C, dt, spb = 1024, 64e-6, 1 << 14  # ~1.05 s spectra blocks
    T = (int(round(60.0 / dt)) // spb) * spb  # 60 s, whole blocks
    if args.quick:
        C, spb = 128, 1 << 12
        T = 8 * spb

    @jax.jit
    def pipeline(d):
        # zero_dm_filter's product is the whole CLEANED filterbank; the
        # abs-sum checksum forces all C x T output cells to materialize
        # (XLA would otherwise dead-code-eliminate every channel but the
        # one the spectrogram reads). The spectrogram+detrend leg runs on
        # a cleaned channel timeseries (the reference spectrogram.py
        # consumes a timeseries; the zero-DM sum itself is identically 0)
        zdm = kernels.zero_dm(d)
        checksum = jnp.sum(jnp.abs(zdm))
        spec = spectrogram(zdm[0], spb)  # [B, spb//2+1]
        y = jnp.log10(jnp.maximum(spec, 1e-30))
        x = jnp.broadcast_to(
            jnp.arange(y.shape[1], dtype=jnp.float32), y.shape)
        keep = jnp.ones(y.shape, dtype=bool)
        return checksum, detrend_mod._detrend_blocks_jit(y, x, keep, 1)

    # generate on device: the measured quantity is the prepass, not the
    # 3.8 GB host->device ship
    key = jax.random.PRNGKey(5)
    dev = jax.random.normal(key, (C, T), dtype=jnp.float32)
    cks, out = pipeline(dev)
    float(cks)
    jax_time = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        cks, out = pipeline(dev)
        float(cks)  # sync on the checksum: the full cleaned product ran
        jax_time = min(jax_time, time.perf_counter() - t0)
    samples_per_sec = C * T / jax_time

    # numpy twin baseline on a slice (cost linear in T), pulled from the
    # device so both paths see identical data; parity-check the device
    # pipeline at the slice shape against the twin
    nblk = 4
    bl_T = nblk * spb
    bl_data = np.asarray(dev[:, :bl_T]).astype(np.float64)

    def numpy_prepass(d):
        zdm = numpy_ref.zero_dm(d)
        checksum = np.abs(zdm).sum()
        spec = fnumpy_ref.spectrogram(zdm[0], spb)
        y = np.log10(np.maximum(spec, 1e-30))
        return checksum, np.stack([detrend_mod.old_detrend(row, order=1)
                                   for row in y])

    ref_cks, ref = numpy_prepass(bl_data)
    got_cks, got = pipeline(jnp.asarray(bl_data, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(got), ref, rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(float(got_cks), ref_cks, rtol=1e-3)

    def one_rep():
        t0 = time.perf_counter()
        numpy_prepass(bl_data)
        return time.perf_counter() - t0

    bl = numpy_baseline(one_rep)
    bl_samples_per_sec = C * bl_T / bl["seconds"]
    speedup = samples_per_sec / bl_samples_per_sec
    print(f"# prepass: {jax_time*1e3:.1f} ms = "
          f"{samples_per_sec/1e9:.2f} Gsamp/s ({T//spb} spectra blocks); "
          f"numpy {bl['seconds']:.3f}s on {bl_T/T:.3f} of the data",
          file=sys.stderr)
    unit = (f"prepassed samples/s ({C}-chan, {T*dt:.0f}s @ 64us, zero-DM "
            f"+ {spb}-sample spectrogram + order-1 WLS detrend, one fused "
            f"program, best of 3; numpy twin baseline on {bl_T/T:.3f} of "
            f"the data scaled linearly, round-5 protocol)")
    return {
        "metric": "prepass_samples_per_sec",
        "value": round(samples_per_sec, 1),
        "unit": unit,
        "vs_baseline": round(speedup, 2),
        "jax_seconds": round(jax_time, 4),
        "numpy_seconds_measured": round(bl["seconds"], 3),
        "numpy_slice_frac": round(bl_T / T, 4),
        **{k: v for k, v in bl.items() if k != "seconds"},
    }


def run_tune(args):
    """Auto-tuning A/B (round 17, BENCH_r12_tune.json).

    Per geometry (>=2), per searchable stage (sweep, accel):

    1. **search leg** — ``tune.autotune(force_search=True)`` against a
       fresh cache: the coordinate-descent searcher times the REAL
       stage dispatches (tune/stages.py) at that geometry. Gates:
       trials <= the declared budget (the bounded-cost guarantee) and
       tuned wall <= hand-picked-baseline wall * 1.05 (the searcher
       starts FROM the baseline, so it can only tie-or-win; the 5%
       allows timer noise on ties). Walls here are CPU-toy numbers
       (labeled, per the PR 10 convention) — the STRUCTURAL claims are
       the gates.
    2. **reuse leg** — a second consult at the SAME key must run ZERO
       trials and bump ``tune.cache_hit`` (counter-snapshot diff of the
       shared telemetry session).

    Then one **science-invariance leg**: the sweep->accel chain over a
    synthetic pulsar under two different tuned configs from the legal
    search domain — candidate tables must be BYTE-identical (tuning
    moves throughput knobs, never results; asserted, not reported).
    """
    import glob
    import shutil
    import tempfile

    from pypulsar_tpu import tune
    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.tune import knobs
    from pypulsar_tpu.tune.stages import accel_measure, sweep_measure

    workdir = tempfile.mkdtemp(prefix="bench_tune_")
    saved_env = {k: os.environ.get(k)
                 for k in ("PYPULSAR_TPU_TUNE", "PYPULSAR_TPU_TUNE_CACHE",
                           "PYPULSAR_TPU_SWEEP_CHUNK",
                           "PYPULSAR_TPU_ACCEL_BATCH",
                           "PYPULSAR_TPU_ACCEL_HBM",
                           "PYPULSAR_TPU_DATS_RESIDENT_LIMIT")}
    for k in saved_env:
        os.environ.pop(k, None)
    knobs.clear_tuned()
    budget = args.tune_trials or max(
        1, knobs.env_int("PYPULSAR_TPU_TUNE_TRIALS"))
    if args.quick:
        geometries = [(32, 1 << 14), (64, 1 << 15)]
        ndm, nspec = 16, 8
    else:
        geometries = [(64, 1 << 16), (128, 1 << 17)]
        ndm, nspec = 32, 16
    from pypulsar_tpu.parallel.mesh import lease_devices
    from pypulsar_tpu.parallel.sweep import resolve_engine

    engine = resolve_engine(args.engine)
    dev = lease_devices()[0]
    on_tpu = getattr(dev, "platform", "cpu") == "tpu"
    record = {
        "metric": "tune_ab", "unit": "see legs",
        "engine": engine, "backend": str(dev.device_kind
                                         if hasattr(dev, "device_kind")
                                         else dev.platform),
        "trial_budget": budget,
        "wall_label": ("real-chip walls" if on_tpu else
                       "CPU-toy walls (structural gates are the claim: "
                       "bounded trials + cache-hit reuse + invariance)"),
        "geometries": [],
    }
    try:
        cache_fn = os.path.join(workdir, "tune.json")
        os.environ["PYPULSAR_TPU_TUNE_CACHE"] = cache_fn
        cache = tune.TuneCache(cache_fn)
        with telemetry.session() as tlm:
            for nchan, nsamp in geometries:
                geo = {"nchan": nchan, "nsamp": nsamp, "stages": {}}
                for stage in ("sweep", "accel"):
                    knobs.clear_tuned()
                    if stage == "sweep":
                        measure = sweep_measure(nchan, nsamp, ndm=ndm,
                                                engine=engine)
                        key_kw = dict(nchan=nchan, nsamp=nsamp,
                                      engine=engine)
                    else:
                        measure = accel_measure(min(nsamp, 1 << 15),
                                                zmax=20, numharm=2,
                                                nspec=nspec)
                        key_kw = dict(nsamp=min(nsamp, 1 << 15), zmax=20)
                    c0 = dict(tlm.counter_totals())
                    tune.autotune(stage, measure=measure, cache=cache,
                                  budget=budget, force_search=True,
                                  verbose=True, **key_kw)
                    c1 = dict(tlm.counter_totals())
                    trials = c1.get("tune.trials", 0) - c0.get(
                        "tune.trials", 0)
                    ent = cache.lookup(tune.make_key(stage, **key_kw))
                    meta = ent["meta"]
                    assert trials <= budget, \
                        f"{stage}: {trials} trials > budget {budget}"
                    assert meta["best_s"] <= meta["baseline_s"] * 1.05, \
                        f"{stage}: tuned {meta['best_s']} slower than " \
                        f"hand-picked baseline {meta['baseline_s']}"
                    # reuse leg: same key, zero trials, cache_hit bumps
                    knobs.clear_tuned()
                    c2 = dict(tlm.counter_totals())
                    applied = tune.apply_cached(stage, cache=cache,
                                                **key_kw)
                    c3 = dict(tlm.counter_totals())
                    assert c3.get("tune.trials", 0) == c2.get(
                        "tune.trials", 0), "reuse ran trials"
                    hits = c3.get("tune.cache_hit", 0) - c2.get(
                        "tune.cache_hit", 0)
                    assert hits == 1, f"no cache hit on reuse ({hits})"
                    geo["stages"][stage] = {
                        "n_trials": int(trials),
                        "baseline_s": meta["baseline_s"],
                        "tuned_s": meta["best_s"],
                        "speedup": meta["speedup"],
                        "tuned_config": ent["config"],
                        "reapplied_config": applied,
                        "second_run_trials": 0,
                        "second_run_cache_hit": True,
                    }
                    print(f"# tune[{stage}] @ ({nchan}, {nsamp}): "
                          f"{meta['baseline_s']:.4f}s -> "
                          f"{meta['best_s']:.4f}s "
                          f"({meta['speedup']:.2f}x, {trials} trials, "
                          f"reuse=hit)")
                record["geometries"].append(geo)
            # compile.* rides along (round 22): tuned-config changes
            # key fresh executables, so the search cost includes them
            record["telemetry_counters"] = {
                k: round(v, 1) for k, v in
                sorted(tlm.counter_totals().items())
                if k.startswith(("tune.", "compile."))}
        # ---- science-invariance leg (gather engine: the CPU default
        # whose chunk domain is byte-invariant; fourier's tuned configs
        # never carry the chunk, enforced by variant_engines) ----
        knobs.clear_tuned()
        os.environ["PYPULSAR_TPU_DATS_RESIDENT_LIMIT"] = "0"
        C, T = (32, 1 << 13) if args.quick else (32, 1 << 14)
        freqs = (1500.0 - 4.0 * np.arange(C)).astype(np.float64)
        fil = _synth_survey_fil(os.path.join(workdir, "psr.fil"), 5, C,
                                T, 5e-4, freqs, "PSR_TUNE")
        from pypulsar_tpu.cli import sweep as cli_sweep

        cfgs = [{"PYPULSAR_TPU_SWEEP_CHUNK": 4096,
                 "PYPULSAR_TPU_ACCEL_BATCH": 4,
                 "PYPULSAR_TPU_ACCEL_HBM": 2e9},
                {"PYPULSAR_TPU_SWEEP_CHUNK": 8192,
                 "PYPULSAR_TPU_ACCEL_BATCH": 8,
                 "PYPULSAR_TPU_ACCEL_HBM": 8e9}]
        arts = []
        for i, cfg in enumerate(cfgs):
            sub = os.path.join(workdir, f"leg{i}")
            os.makedirs(sub)
            base = os.path.join(sub, "x")
            knobs.clear_tuned()
            knobs.apply_tuned(cfg)
            try:
                rc = cli_sweep.main(
                    [fil, "-o", base, "--lodm", "0", "--dmstep", "10",
                     "--numdms", "8", "-s", "8", "--group-size", "4",
                     "--threshold", "8", "--engine", "gather",
                     "--write-dats", "--accel-search", "--accel-zmax",
                     "20", "--accel-numharm", "2", "--accel-sigma",
                     "3"])
                assert rc == 0, f"invariance leg {i} rc={rc}"
            finally:
                knobs.clear_tuned()
            leg = {}
            for pat in ("_DM*.cand", "_DM*.txtcand", ".cands"):
                for fn in sorted(glob.glob(base + pat)):
                    with open(fn, "rb") as f:
                        leg[os.path.basename(fn)] = f.read()
            arts.append(leg)
        assert arts[0] and set(arts[0]) == set(arts[1])
        diffs = [k for k in arts[0] if arts[0][k] != arts[1][k]]
        assert not diffs, f"tuned configs changed science: {diffs}"
        record["invariance"] = {
            "engine": "gather",
            "configs": cfgs,
            "artifacts_compared": len(arts[0]),
            "byte_identical": True,
        }
        print(f"# invariance: {len(arts[0])} artifacts byte-identical "
              f"across tuned configs (gather)")
        record["value"] = float(record["geometries"][-1]["stages"]
                                ["accel"]["speedup"])
        return record
    finally:
        knobs.clear_tuned()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(workdir, ignore_errors=True)


def run_compile(args):
    """Compilation-plane A/B (round 22, BENCH_r17_compile.json).

    Four legs; every claim is STRUCTURAL (compile-counter deltas, byte
    parity, span overlap) — walls are CPU-toy numbers unless a real
    chip is attached, labeled per the PR 10 convention:

    1. **cold vs warm** — the in-process CLI sweep at 3 toy
       geometries, each run twice into separate outdirs. The cold pass
       compiles; the warm pass at the SAME geometry must show
       ``compile.cache_miss == 0`` (the never-compile-twice gate) and
       byte-identical candidate tables.
    2. **bucket collapse** — two fold candidate-batch sizes (10 and
       12) land on ONE ``{2^k} U {3*2^k}`` ladder rung, so the second
       warm compiles nothing (the mixed-geometry headline, on the axis
       bucketing actually owns — the DM-range statics of a sweep are
       time-axis geometry, which is never padded). A bucketing-off
       rerun (``PYPULSAR_TPU_COMPILE_BUCKETS=0``) of geometry 2 must
       be byte-identical — padding is execution policy, never science.
    3. **persistent cross-process** — a child interpreter pointed at
       the same ``JAX_COMPILATION_CACHE_DIR`` reruns geometry 1: its
       (process-cold) compiles must probe as ``compile.persistent_hit``
       and its artifacts must match the parent's bytes.
    4. **warm-pool overlap** — a 3-observation fleet with per-obs
       channel counts (a mixed-geometry fleet) and the scheduler warm
       pool on: some observation's ``survey.precompile`` span must
       overlap ANOTHER observation's device-stage span in the fleet
       trace — precompile rides spare host cycles, off the critical
       path.
    """
    acquire_backend()
    import glob as _glob
    import shutil
    import tempfile

    from pypulsar_tpu.cli import sweep as cli_sweep
    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.parallel.mesh import lease_devices
    from pypulsar_tpu.parallel.sweep import resolve_engine

    # a FRESH persistent cache: the cold legs must actually compile.
    # JAX reads JAX_COMPILATION_CACHE_DIR when it is imported, so main()
    # points it at an empty directory before that; the child inherits it.
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir or os.path.exists(cache_dir) and os.listdir(cache_dir):
        raise RuntimeError(
            "--compile needs JAX_COMPILATION_CACHE_DIR pointing at an "
            "empty directory before jax is imported (bench.main() sets "
            f"it); got {cache_dir!r}")
    workdir = tempfile.mkdtemp(prefix="bench_compile_")
    saved_env = {k: os.environ.get(k)
                 for k in ("PYPULSAR_TPU_COMPILE_BUCKETS",)}
    os.environ.pop("PYPULSAR_TPU_COMPILE_BUCKETS", None)

    engine = resolve_engine(args.engine)
    dev = lease_devices()[0]
    on_tpu = getattr(dev, "platform", "cpu") == "tpu"
    C, T, dtp = 32, (1 << 13 if args.quick
                     else 1 << 14), 5e-4
    freqs = (1500.0 - 4.0 * np.arange(C)).astype(np.float64)
    record = {
        "metric": "compile_plane_ab", "unit": "see legs",
        "engine": engine,
        "backend": str(dev.device_kind if hasattr(dev, "device_kind")
                       else dev.platform),
        "wall_label": ("real-chip walls" if on_tpu else
                       "CPU-toy walls (structural gates are the claim: "
                       "zero warm-leg compiles + bucket collapse + "
                       "persistent cross-process hits + byte parity + "
                       "precompile span overlap)"),
        "geometries": [],
    }
    _DELTA_KEYS = ("compile.cache_miss", "compile.cache_hit",
                   "compile.persistent_hit", "compile.aot_fallback",
                   "compile.bucket_pad_rows", "compile.ms")

    def sweep_argv(base, numdms):
        return [fil, "-o", base, "--lodm", "0", "--dmstep", "10",
                "--numdms", str(numdms), "-s", "8", "--group-size", "4",
                "--threshold", "8", "--engine", engine]

    def read_arts(outdir):
        arts = {}
        for fn in sorted(_glob.glob(os.path.join(outdir, "x*"))):
            with open(fn, "rb") as f:
                arts[os.path.basename(fn)] = f.read()
        return arts

    try:
        fil = _synth_survey_fil(os.path.join(workdir, "psr.fil"), 7, C,
                                T, dtp, freqs, "PSR_COMPILE")
        geometries = [{"name": "g1", "numdms": 8},
                      {"name": "g2", "numdms": 10},
                      {"name": "g3", "numdms": 12}]
        cold_wall = warm_wall = 0.0
        g1_arts = g2_arts = None
        with telemetry.session() as tlm:
            for geo in geometries:
                legs = {}
                arts = {}
                for leg in ("cold", "warm"):
                    outdir = os.path.join(workdir,
                                          f"{geo['name']}_{leg}")
                    os.makedirs(outdir)
                    base = os.path.join(outdir, "x")
                    c0 = dict(tlm.counter_totals())
                    t0 = time.perf_counter()
                    rc = cli_sweep.main(sweep_argv(base, geo["numdms"]))
                    wall = time.perf_counter() - t0
                    c1 = dict(tlm.counter_totals())
                    assert rc == 0, f"{geo['name']} {leg} leg rc={rc}"
                    legs[leg] = {"wall_s": round(wall, 3)}
                    legs[leg].update(
                        {k: round(c1.get(k, 0) - c0.get(k, 0), 1)
                         for k in _DELTA_KEYS})
                    arts[leg] = read_arts(outdir)
                # the warm-leg contract: a previously-seen geometry
                # never compiles on the critical path
                assert legs["warm"]["compile.cache_miss"] == 0, \
                    f"{geo['name']}: warm leg compiled " \
                    f"({legs['warm']['compile.cache_miss']} misses)"
                assert legs["warm"]["compile.cache_hit"] >= 1, \
                    f"{geo['name']}: warm leg never hit the registry"
                assert arts["cold"] and arts["cold"] == arts["warm"], \
                    f"{geo['name']}: cold/warm artifacts diverged"
                if geo["name"] == "g1":
                    g1_arts = arts["cold"]
                if geo["name"] == "g2":
                    g2_arts = arts["cold"]
                cold_wall += legs["cold"]["wall_s"]
                warm_wall += legs["warm"]["wall_s"]
                print(f"# compile[{geo['name']}] numdms="
                      f"{geo['numdms']}: cold "
                      f"{legs['cold']['compile.cache_miss']:.0f} "
                      f"compiles ({legs['cold']['compile.ms']:.0f} ms), "
                      f"warm 0 compiles / "
                      f"{legs['warm']['compile.cache_hit']:.0f} hits, "
                      f"{len(arts['cold'])} artifacts byte-identical",
                      file=sys.stderr)
                record["geometries"].append(
                    dict(geo, legs=legs,
                         artifacts_identical=len(arts["cold"])))
        # ---- bucket-collapse leg: two candidate-batch sizes, one
        # ladder rung, zero second compiles (through the production
        # warm-pool entry point) ----
        import pypulsar_tpu.fold.engine  # noqa: F401 - registers warmer
        from pypulsar_tpu.compile import bucket_rows, warm_stage

        fold_geo = dict(n_samples=T, downsamp=1, fold_nbins=32,
                        fold_npart=8)
        assert bucket_rows(10) == bucket_rows(12) == 12
        with telemetry.session() as tlm:
            n1 = warm_stage("fold", fold_batch=10, **fold_geo)
            c_mid = dict(tlm.counter_totals())
            n2 = warm_stage("fold", fold_batch=12, **fold_geo)
            c_end = dict(tlm.counter_totals())
        assert n1 >= 1, "first fold warm compiled nothing"
        assert n2 == 0 and (c_end.get("compile.cache_miss", 0)
                            == c_mid.get("compile.cache_miss", 0)), (
            "bucket ladder failed to collapse fold batches 10 and 12 "
            "onto one executable")
        record["bucket_collapse"] = {
            "axis": "fold candidate batch", "batch_sizes": [10, 12],
            "ladder_rows": 12, "first_warm_compiles": int(n1),
            "second_warm_compiles": 0}
        print("# compile[collapse]: fold batches 10 and 12 -> one "
              "12-row executable (second warm compiled nothing)",
              file=sys.stderr)

        # bucketing is runtime policy, not science: geometry 2 with the
        # ladder off is byte-identical (its unpadded shapes may compile)
        os.environ["PYPULSAR_TPU_COMPILE_BUCKETS"] = "0"
        try:
            outdir = os.path.join(workdir, "g2_nobuckets")
            os.makedirs(outdir)
            rc = cli_sweep.main(sweep_argv(os.path.join(outdir, "x"), 10))
            assert rc == 0, f"no-buckets leg rc={rc}"
            nb_arts = read_arts(outdir)
        finally:
            os.environ.pop("PYPULSAR_TPU_COMPILE_BUCKETS", None)
        assert nb_arts == g2_arts, \
            "bucketing changed artifact bytes (science regression)"
        record["bucket_invariance"] = {
            "geometry": "g2", "artifacts_compared": len(nb_arts),
            "byte_identical": True}

        # ---- persistent cross-process leg ----
        child_dir = os.path.join(workdir, "child")
        os.makedirs(child_dir)
        child_argv = sweep_argv(os.path.join(child_dir, "x"), 8)
        child_src = (
            "import json, sys\n"
            "from pypulsar_tpu.obs import telemetry\n"
            "from pypulsar_tpu.cli import sweep as cli_sweep\n"
            "with telemetry.session() as tlm:\n"
            "    rc = cli_sweep.main(%r)\n"
            "    print('COMPILE_TOTALS '"
            " + json.dumps(tlm.counter_totals()))\n"
            "sys.exit(rc)\n" % (child_argv,))
        env = dict(os.environ)
        env["PYTHONPATH"] = (
            os.path.dirname(os.path.abspath(__file__)) + os.pathsep
            + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
        proc = subprocess.run([sys.executable, "-c", child_src], env=env,
                              capture_output=True, text=True,
                              timeout=1800)
        assert proc.returncode == 0, \
            f"persistent-cache child rc={proc.returncode}: " \
            f"{proc.stderr[-2000:]}"
        totals = json.loads(
            [ln for ln in proc.stdout.splitlines()
             if ln.startswith("COMPILE_TOTALS ")][-1]
            [len("COMPILE_TOTALS "):])
        assert totals.get("compile.persistent_hit", 0) >= 1, (
            f"child process saw no persistent-cache hits "
            f"({ {k: v for k, v in totals.items() if k.startswith('compile.')} })")
        assert read_arts(child_dir) == g1_arts, \
            "cross-process artifacts diverged"
        record["persistent_cross_process"] = {
            "cache_dir_shared": True,
            "child_persistent_hits":
                int(totals.get("compile.persistent_hit", 0)),
            "child_compiles": int(totals.get("compile.cache_miss", 0)),
            "artifacts_identical": len(g1_arts),
        }
        print(f"# compile[persistent]: child process "
              f"{int(totals.get('compile.persistent_hit', 0))} "
              f"persistent hit(s) over "
              f"{int(totals.get('compile.cache_miss', 0))} compiles, "
              f"{len(g1_arts)} artifacts byte-identical",
              file=sys.stderr)

        # ---- warm-pool overlap leg (a mixed-geometry fleet) ----
        from pypulsar_tpu.survey.dag import SurveyConfig, build_dag
        from pypulsar_tpu.survey.scheduler import FleetScheduler
        from pypulsar_tpu.survey.state import Observation

        n_obs = 3
        cfg = SurveyConfig(
            mask=False, lodm=0.0, dmstep=10.0, numdms=8, nsub=8,
            group_size=4, threshold=8.0, accel_zmax=20.0,
            accel_numharm=2, accel_sigma=3.0, accel_batch=4,
            sift_sigma=3.0, sift_min_hits=1, fold_nbins=32,
            fold_npart=8)
        stages = build_dag(cfg)
        # per-obs channel counts: each observation's geometry keys its
        # own executables, so every precompile does real work
        fleet_out = os.path.join(workdir, "fleet")
        os.makedirs(fleet_out)
        obs = []
        for i, Ci in enumerate((24, 32, 48)):
            fi = _synth_survey_fil(
                os.path.join(workdir, f"obs{i}.fil"), 11 + i, Ci, T,
                dtp, 1500.0 - 4.0 * np.arange(Ci), f"CMP{i}",
                period=0.1024 * (1.0 + 0.07 * i))
            obs.append(Observation(f"obs{i}", fi,
                                   os.path.join(fleet_out, f"obs{i}")))
        tlm_dir = os.path.join(workdir, "tlm")
        with telemetry.session() as tlm:
            result = FleetScheduler(obs, cfg, max_host_workers=2,
                                    devices=1,
                                    telemetry_dir=tlm_dir).run()
            fleet_totals = dict(tlm.counter_totals())
        assert result.ok and len(result.ran) == n_obs * len(stages), \
            f"fleet failed: ran {len(result.ran)}, " \
            f"failed {result.failed}"
        assert fleet_totals.get("survey.precompiled", 0) >= 1, \
            "warm pool precompiled nothing"
        # device-LANE stages by declaration ("dev" span attrs only
        # appear at devices>1, where stages pin explicitly)
        dev_names = {f"survey.stage.{s.name}" for s in stages
                     if s.device_bound}
        pre_spans, dev_spans = [], []
        for p in sorted(_glob.glob(os.path.join(tlm_dir, "*.jsonl"))):
            o = os.path.basename(p)[:-len(".jsonl")]
            with open(p) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if rec.get("type") != "span":
                        continue
                    t0, t1 = rec.get("t", 0), \
                        rec.get("t", 0) + rec.get("dur", 0)
                    if rec.get("name") == "survey.precompile":
                        pre_spans.append((o, t0, t1))
                    elif rec.get("name") in dev_names:
                        dev_spans.append((o, t0, t1, rec["name"]))
        overlaps = [
            {"precompile_obs": po, "device_obs": do, "device_span": dn,
             "overlap_s": round(min(p1, d1) - max(p0, d0), 3)}
            for (po, p0, p1) in pre_spans
            for (do, d0, d1, dn) in dev_spans
            if po != do and p0 < d1 and d0 < p1]
        assert overlaps, (
            f"no survey.precompile span overlapped another "
            f"observation's device span (precompile spans: "
            f"{pre_spans}; device spans: {dev_spans[:6]})")
        best = max(overlaps, key=lambda d: d["overlap_s"])
        record["warm_pool"] = {
            "n_obs": n_obs,
            "nchan_per_obs": [24, 32, 48],
            "precompiled_executables":
                int(fleet_totals.get("survey.precompiled", 0)),
            "precompile_spans": len(pre_spans),
            "off_critical_path_overlaps": len(overlaps),
            "example_overlap": best,
        }
        print(f"# compile[warm-pool]: {len(pre_spans)} precompile "
              f"span(s), {len(overlaps)} overlap(s) with another "
              f"observation's device span (best {best['overlap_s']}s: "
              f"{best['precompile_obs']} warmed during "
              f"{best['device_obs']}'s {best['device_span']})",
              file=sys.stderr)

        record["value"] = round(cold_wall / max(warm_wall, 1e-9), 3)
        record["vs_baseline"] = record["value"]
        record["unit"] = (
            "cold-vs-warm wall ratio across 3 toy geometries (the "
            "structural gates are the claim: warm legs compile "
            "nothing, the bucket ladder collapses nearby DM counts "
            "onto one executable, a second process hits the shared "
            "persistent cache byte-identically, and fleet precompile "
            "overlaps another observation's device work)")
        return record
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(workdir, ignore_errors=True)
        shutil.rmtree(cache_dir, ignore_errors=True)


DEFAULT_STREAM_FIL = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "northstar_1hr.fil")


def _emit_record(args, record) -> None:
    """Print the final JSON record and, with --out, write the identical
    line to the file."""
    line = json.dumps(record)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)


def _run_mode(args):
    if args.tune:
        return run_tune(args)
    if args.compile:
        return run_compile(args)
    if args.ab:
        return run_ab(args)
    if args.accel and args.spectral:
        return run_specfuse(args)
    if args.accel:
        return run_accel(args)
    if args.fold:
        return run_fold(args)
    if args.waterfall:
        return run_waterfall(args)
    if args.obs_overhead:
        return run_obs_overhead(args)
    if args.survey:
        return run_survey(args)
    if args.broker:
        return run_broker(args)
    if args.candplane:
        return run_candplane(args)
    if args.multihost:
        return run_multihost(args)
    if args.race:
        return run_race(args)
    if args.chaos:
        return run_chaos(args)
    if args.daemon_soak:
        return run_daemon_soak(args)
    if args.corruption:
        return run_corruption(args)
    if args.prepass:
        return run_prepass(args)
    if args.stream:
        return run_stream(args)
    return run_benchmark(args)


def main(argv=None) -> int:
    args = parse_args(argv)
    if (args.stream is None
            and not (args.quick or args.ab or args.accel or args.fold
                     or args.waterfall or args.prepass or args.survey
                     or args.broker or args.candplane
                     or args.chaos or args.corruption or args.tune
                     or args.compile or args.multihost or args.race
                     or args.obs_overhead or args.daemon_soak
                     or args.nsamp or args.nchan)
            and os.path.exists(DEFAULT_STREAM_FIL)):
        # the north-star workload exists on disk: measure THAT (streamed,
        # I/O included) rather than the device-resident 71-s segment.
        # Unattended runs bound the window so the bench stays ~15 min
        args.stream = DEFAULT_STREAM_FIL
        if args.stream_window is None:
            args.stream_window = float(
                os.environ.get("BENCH_STREAM_WINDOW_S", 900.0))
    if any(getattr(args, m) for m in CPU_FLEET_MODES):
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.compile:
        import tempfile

        os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
            prefix="bench_compile_xla_")
    device = device_record()
    harness = any(getattr(args, m)
                  for m in HARNESS_MODES + CPU_FLEET_MODES)
    if not harness and device["platform"] != "tpu":
        # a device metric has no value without the device: say so and
        # fail — never a CPU number under the metric's name
        print(json.dumps({
            "ok": False,
            "error": "this mode reports a device metric and needs a TPU; "
                     f"JAX offers platform {device['platform']!r}",
            **device}))
        return 1
    # With --telemetry the whole measured run records an obs trace whose
    # final counter totals (H2D/D2H bytes, chunks dispatched, pipeline
    # depth) land in the JSON extras — byte-level evidence alongside the
    # wall-clock metric. A mode that raises propagates: non-zero exit,
    # no record.
    from pypulsar_tpu.obs import telemetry

    with telemetry.session_from_flag(args.telemetry, tool="bench") as tlm:
        record = _run_mode(args)
        if tlm is not None:
            record["telemetry_jsonl"] = args.telemetry
            record["telemetry_counters"] = {
                k: round(v, 1) for k, v in
                sorted(tlm.counter_totals().items())}
            gauges = tlm.gauge_values()
            if gauges:
                record["telemetry_gauges"] = gauges
    record.update(device)
    _emit_record(args, record)
    return 0


if __name__ == "__main__":
    sys.exit(main())
