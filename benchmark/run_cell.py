#!/usr/bin/env python3
"""Run one benchmark cell once: set-up, one measured window, the check.

    python3 benchmark/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, which owns the chip. A cell is data: ``workloads/<cell>.json``
names a configuration (``configs/<config>.json``) and an entry driver
(``entries/<entry>.py``); each metric it reports is a reader of its own
(``metrics/<metric>.py``). Nothing here knows a cell, a configuration or a
metric by name. ``README.md`` says how a later PR adds one.

Set-up (``setup_s``, process start to window start): imports, the host
codec, the input generated from ``--seed``, the compile cache, and one whole
step as warm-up so that every shape of the cell is compiled or read from
the cache. Window: steps back to back, closed loop, one in flight, each
into a fresh output directory; no step starts after ``--seconds`` and the
one in flight finishes. ``--trace 1`` runs the same loop under
``jax.profiler`` and the program's telemetry and reports the per-layer
metrics; ``--trace 0`` turns on neither and reports the end-to-end ones.

After the window: the device's memory peak is read, then the entry's check
compares what the timed steps wrote with the plain reference
(``reference/``). The last line of standard output is the result object;
the numbers compared, each beside its limit, are the last lines of
standard error and the last key of that object.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

WORK = os.path.join(HERE, "work")


def say(msg: str) -> None:
    print(msg, flush=True)


class Refused(Exception):
    """The run cannot be a measurement: wrong device, missing file."""


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(workload, config, rehearsal). A rehearsal workload lives under
    ``tests/`` only, is listed nowhere, and alone may run off the chip."""
    for sub, rehearsal in (("workloads", False), ("tests/workloads", True)):
        path = os.path.join(HERE, sub, name + ".json")
        if os.path.exists(path):
            wl = load_json(sub, name + ".json")
            sub_cfg = "tests/configs" if rehearsal else "configs"
            return wl, load_json(sub_cfg, wl["config"] + ".json"), rehearsal
    raise Refused(f"no workload file for {name!r} under benchmark/workloads")


def device_gate(chips: int, rehearsal: bool):
    """The device as JAX reports it and its peaks row. Anything but a TPU
    of a kind in ``peaks.json`` with enough chips refuses the run."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    peaks = load_json("peaks.json")["devices"]
    if rehearsal:
        return dev, peaks.get(dev["kind"])
    if dev["platform"] != "tpu":
        raise Refused(f"JAX offers platform {dev['platform']!r}, not a TPU")
    if dev["kind"] not in peaks:
        raise Refused(f"device kind {dev['kind']!r} has no row in "
                      f"benchmark/peaks.json (known: {sorted(peaks)})")
    if dev["count"] < chips:
        raise Refused(f"{chips} chips asked for, JAX offers {dev['count']}")
    return dev, peaks[dev["kind"]]


def memory_peak_bytes(chips: int):
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_metric(name: str):
    return importlib.import_module(f"metrics.{name.replace('-', '_')}")


class Cell:
    """What an entry driver and the metric readers are handed."""

    def __init__(self, name, wl, cfg, seed, trace, rehearsal, peaks=None):
        self.name, self.wl, self.cfg = name, wl, cfg
        self.seed, self.trace, self.rehearsal = seed, trace, rehearsal
        self.peaks = peaks     # the device's row of peaks.json
        self.workdir = os.path.join(WORK, name)
        self.infile = None
        self.injected = None
        self.steps = []        # one dict per step of the window
        self.warmup = None
        self.window_s = None
        self.sky_s_per_step = None
        self.setup_s = None
        self.entry = None      # the entry driver's module
        self.trace_summary = None
        self.telemetry = None  # reduced spans and counters of the window


def run_step(cell: Cell, entry, k, tag: str, annotate: bool):
    """One step through the entry into a fresh directory; returns its
    record (walls on the host clock, what it wrote, its exit code)."""
    outdir = os.path.join(cell.workdir, "out", f"{tag}{k:04d}")
    shutil.rmtree(outdir, ignore_errors=True)
    os.makedirs(outdir)
    rec = {"k": k, "outdir": outdir, "rc": None, "t_unix": time.time()}
    ctx = None
    if annotate:
        import jax

        ctx = jax.profiler.TraceAnnotation("bench.step", step=k)
        ctx.__enter__()
    rec["t0"] = time.perf_counter()
    try:
        rec["rc"] = int(entry.run(cell, outdir, telemetry=annotate) or 0)
    except (Exception, SystemExit):  # a failed step is counted, not fatal
        traceback.print_exc()
        rec["rc"] = -1
    rec["t1"] = time.perf_counter()
    if ctx is not None:
        ctx.__exit__(None, None, None)
    rec["wall"] = rec["t1"] - rec["t0"]
    return rec


def measure(cell: Cell, entry, seconds: float):
    """The window. Returns the profiler's log directory when traced."""
    logdir = None
    if cell.trace:
        import jax

        logdir = os.path.join(cell.workdir, "profile")
        shutil.rmtree(logdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans come from annotations
        opts.host_tracer_level = 1
        jax.profiler.start_trace(logdir, profiler_options=opts)
    t_first = time.perf_counter()
    k = 0
    try:
        while True:
            cell.steps.append(run_step(cell, entry, k, "step", cell.trace))
            k += 1
            if time.perf_counter() - t_first >= seconds:
                break
    finally:
        if cell.trace:
            import jax

            jax.profiler.stop_trace()
    cell.window_s = cell.steps[-1]["t1"] - t_first
    return logdir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="after the check, also read the control (the "
                         "reference one precision down, in the program's "
                         "place) and print its numbers on earlier lines; "
                         "the driver's runs never set it")
    args = ap.parse_args(argv)

    try:
        wl, cfg, rehearsal = load_cell(args.workload)
        chips = int(wl["chips"])
        device, peaks = device_gate(chips, rehearsal)
        import pypulsar_tpu  # noqa: F401 - the system under test
    except (Refused, ImportError, OSError, RuntimeError) as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 2

    cell = Cell(args.workload, wl, cfg, args.seed, bool(args.trace),
                rehearsal, peaks)
    say(f"cell {cell.name}: config {wl['config']}, entry {wl['entry']}, "
        f"seed {args.seed}, window {args.seconds:g}s, trace {args.trace}, "
        f"device {json.dumps(device)}")
    for key in cfg.get("reduced", []):
        say(f"reduced: {key} (source: "
            f"{cfg.get('source_values', {}).get(key)}, here: "
            f"{cfg.get(key)})")

    # -- set-up ------------------------------------------------------------
    from pypulsar_tpu import native
    from pypulsar_tpu.compile import configure_persistent_cache

    say(f"native.available() = {bool(native.available())}; compile cache: "
        f"{configure_persistent_cache()}")
    entry = cell.entry = importlib.import_module(f"entries.{wl['entry']}")
    shutil.rmtree(cell.workdir, ignore_errors=True)
    os.makedirs(cell.workdir)
    t = time.perf_counter()
    entry.prepare(cell)
    say(f"input: {cell.infile} ({os.path.getsize(cell.infile) / 1e6:.1f} MB)"
        f" in {time.perf_counter() - t:.2f}s; one step is "
        f"{cell.sky_s_per_step:.4f} s of sky")
    cell.warmup = run_step(cell, entry, 0, "warm", False)
    say(f"warm-up step: {cell.warmup['wall']:.3f}s, rc {cell.warmup['rc']}")
    cell.setup_s = time.perf_counter() - _T0

    # -- the window ----------------------------------------------------------
    logdir = measure(cell, entry, args.seconds)
    walls = [s["wall"] for s in cell.steps]
    say(f"window: {len(walls)} steps in {cell.window_s:.3f}s; step walls "
        f"min {min(walls):.3f} max {max(walls):.3f}")
    device["memory_peak_bytes"] = memory_peak_bytes(chips)

    # -- the check, then the reductions ---------------------------------------
    failed = sum(1 for s in cell.steps if s["rc"])
    t = time.perf_counter()
    try:
        compared = entry.check(cell)
    except Exception:  # noqa: BLE001 - a check that cannot run has failed
        traceback.print_exc()
        compared = [("check_ran", 1.0, 0.0)]
    say(f"check: {time.perf_counter() - t:.2f}s")
    if args.control:
        t = time.perf_counter()
        name = wl["check"]["control"]
        for n, v, lim in entry.check(cell, control=name):
            say(f"control {name} {n}: {v:.6g} (limit {lim:g})")
        say(f"control: {time.perf_counter() - t:.2f}s")
    if cell.trace:
        import trace_reduce

        t = time.perf_counter()
        cell.telemetry = trace_reduce.read_telemetry(
            [p for s in cell.steps for p in entry.telemetry_files(s)])
        cell.trace_summary = trace_reduce.reduce_logdir(
            logdir, chips=chips, stage_spans=cell.telemetry["stage_spans"])
        shutil.rmtree(logdir, ignore_errors=True)
        device["busy_s"] = cell.trace_summary["busy_s"]
        device["window_s"] = cell.trace_summary["window_s"]
        fb = entry.fallbacks(cell)
        say(f"fallback counters: {json.dumps(fb)}")
        compared.append(("fallbacks", float(sum(fb.values())), 0.0))
        say(f"trace reduced in {time.perf_counter() - t:.2f}s: busy "
            f"{device['busy_s']:.3f}s of {device['window_s']:.3f}s; device "
            f"seconds by program: "
            f"{json.dumps(cell.trace_summary['program_seconds'])}")

    metrics = {}
    for name in wl["per_layer" if cell.trace else "end_to_end"]:
        mod = load_metric(name)
        value = mod.read(cell)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": mod.UNIT}

    # a number that could not be read (NaN) is over any limit; the line
    # has to stay strict JSON, so it is written as a very large number
    compared = [(n, float(v) if math.isfinite(v) else 1e300, float(lim))
                for n, v, lim in compared]
    correct = failed == 0 and all(v <= lim for _, v, lim in compared)
    result = {"correct": bool(correct), "attempted": len(cell.steps),
              "failed": failed, "metrics": metrics, "device": device}
    if cell.trace:
        result["breakdown"] = cell.trace_summary["breakdown"]
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, v, lim in compared}
    sys.stdout.flush()
    for n, v, lim in compared:
        print(f"compared {n}: {v:.6g} (limit {lim:g})"
              f"{'' if v <= lim else '  <-- OVER'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
