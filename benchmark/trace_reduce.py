"""From the profiler's trace and the program's telemetry to numbers.

The trace gives, today, only what XLA names itself: per device a line of
operations, each with a start and a duration. This module reads

- ``busy_s``: the union of the intervals in which an operation ran on the
  device, inside the traced window, averaged over the chips used;
- per-operation totals under the names the trace prints;
- the longest idle gaps, each attributed to the harness's own
  ``bench.step`` annotation it falls in and, where the program's telemetry
  has stage spans, to the stage that was running.

The xplane file is read with ``jax.profiler.ProfileData`` into plain lists
first (``load_xplane``); everything after that works on those lists, so the
tests drive it from a small recorded trace kept as JSON.
"""

from __future__ import annotations

import glob
import json
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINES = ("XLA Ops",)
PROGRAMS_LINE = "XLA Modules"
STEP_ANNOTATION = "bench.step"


def load_xplane(path: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ..]},
    "annotations": [[name, start_ns, dur_ns, step], ..]}.

    Device operations are the events of a TPU plane's "XLA Ops" line. Off
    the chip (the CPU rehearsal) the host plane's events that carry an
    ``hlo_op`` stat stand in, so that the same reduction can be rehearsed;
    such a run is never reported as a device's."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, programs, annotations, host_ops = {}, {}, [], []
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if is_dev:
                into = (devices if line.name in OPS_LINES else
                        programs if line.name == PROGRAMS_LINE else None)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        [short_name(ev.name), float(ev.start_ns),
                         float(ev.duration_ns)] for ev in line.events)
                continue
            for ev in line.events:
                if ev.name == STEP_ANNOTATION:
                    step = dict(ev.stats).get("step")
                    annotations.append([ev.name, float(ev.start_ns),
                                        float(ev.duration_ns), step])
                elif ev.duration_ns > 0 and "hlo_op" in dict(ev.stats):
                    host_ops.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    if not devices and host_ops:
        devices["/host:CPU (rehearsal)"] = host_ops
    return {"devices": devices, "programs": programs,
            "annotations": annotations}


def short_name(name: str) -> str:
    """An operation's name as the trace prints it, cut before its HLO
    text ("%fusion.3 = f32[..] fusion(..)" -> "%fusion.3") and before a
    program's fingerprint ("jit_step(123)" -> "jit_step")."""
    name = name.split(" = ", 1)[0]
    if name.endswith(")") and "(" in name:
        name = name[:name.rindex("(")]
    return name[:80]


def busy_union(events, lo=None, hi=None):
    """(busy_ns, merged intervals) of [name, start, dur] events clipped to
    [lo, hi]."""
    spans = []
    for _, start, dur in events:
        a, b = start, start + dur
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b > a:
            spans.append((a, b))
    spans.sort()
    merged = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def op_totals(events, lo=None, hi=None):
    totals = {}
    for name, start, dur in events:
        a = start if lo is None else max(start, lo)
        b = start + dur if hi is None else min(start + dur, hi)
        if b > a:
            totals[name] = totals.get(name, 0.0) + (b - a)
    return totals


def idle_gaps(merged, lo, hi):
    """[(start, end)] of the stretches of [lo, hi] no interval covers."""
    gaps, cursor = [], lo
    for a, b in merged:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def _label(mid_ns, annotations, stage_spans):
    """What the host was doing at ``mid_ns``: which step and, where the
    telemetry knows, which stage."""
    for _, start, dur, step in annotations:
        if start <= mid_ns <= start + dur:
            label = f"step {step}"
            for k, name, off, sdur in stage_spans:
                if k == step and off <= (mid_ns - start) / 1e9 <= off + sdur:
                    return f"{label} {name}"
            return label
    return "between steps"


def reduce_trace(trace: dict, chips: int = 1, stage_spans=()) -> dict:
    """The summary ``run_cell`` prints. The window is the span of the
    ``bench.step`` annotations (first start to last end)."""
    ann = sorted(trace["annotations"], key=lambda a: a[1])
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device operations")
    if ann:
        lo, hi = ann[0][1], max(a[1] + a[2] for a in ann)
    else:
        evs = [e for d in devices.values() for e in d]
        lo = min(e[1] for e in evs)
        hi = max(e[1] + e[2] for e in evs)
    busy, totals, progs, gaps = [], {}, {}, []
    for plane in sorted(devices)[:max(chips, 1)]:
        b, merged = busy_union(devices[plane], lo, hi)
        busy.append(b)
        for name, ns in op_totals(devices[plane], lo, hi).items():
            totals[name] = totals.get(name, 0.0) + ns
        for name, ns in op_totals(trace.get("programs", {}).get(plane, []),
                                  lo, hi).items():
            progs[name] = progs.get(name, 0.0) + ns
        gaps += idle_gaps(merged, lo, hi)
    # a gap that runs across a step or stage boundary is split there
    edges = sorted({e for _, start, dur, _ in ann
                    for e in (start, start + dur)}
                   | {start + 1e9 * t for _, start, _, step in ann
                      for k, _, off, sdur in stage_spans if k == step
                      for t in (off, off + sdur)})
    by_label = {}
    for a, b in gaps:
        cuts = [a] + [e for e in edges if a < e < b] + [b]
        for c, d in zip(cuts, cuts[1:]):
            label = _label(0.5 * (c + d), ann, stage_spans)
            by_label[label] = by_label.get(label, 0.0) + (d - c)
    n = len(busy)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "n_device_planes": n,
        "op_seconds": {k: v / n / 1e9 for k, v in totals.items()},
        "program_seconds": {k: v / n / 1e9 for k, v in sorted(
            progs.items(), key=lambda kv: -kv[1])},
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in top],
            "idle_gaps": [[k, v / n / 1e9] for k, v in idle],
        },
    }


def reduce_logdir(logdir: str, chips: int = 1, stage_spans=()) -> dict:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return reduce_trace(load_xplane(paths[-1]), chips, stage_spans)


# -- the program's telemetry (JSONL, one session per file) -------------------


def read_telemetry(paths) -> dict:
    """Counters and events summed over the files, span seconds by name,
    and the ``survey.stage.*`` spans as (file index, stage, offset from the
    session start in seconds, duration)."""
    counters, events, spans, stage_spans = {}, {}, {}, []
    for k, path in enumerate(paths):
        if not os.path.exists(path):
            continue
        final = None
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                kind = rec.get("type")
                if kind == "span":
                    ent = spans.setdefault(rec["name"], [0.0, 0])
                    ent[0] += float(rec["dur"])
                    ent[1] += 1
                    if rec["name"].startswith("survey.stage."):
                        stage_spans.append(
                            (k, rec["name"].split(".")[-1],
                             float(rec["t"]), float(rec["dur"])))
                elif kind == "counters" and not rec.get("partial"):
                    final = rec
        if final is not None:
            for name, v in final.get("counters", {}).items():
                counters[name] = counters.get(name, 0) + v
            for name, v in final.get("events", {}).items():
                events[name] = events.get(name, 0) + v
    return {"counters": counters, "events": events, "spans": spans,
            "stage_spans": stage_spans, "n_files": len(paths)}
