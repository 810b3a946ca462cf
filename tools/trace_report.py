#!/usr/bin/env python3
"""Read one profiler trace (``.xplane.pb``) the way a builder needs it.

    python tools/trace_report.py TRACE.xplane.pb [--root NAME ...] [--json OUT]

With the telemetry's seam to the profiler (``obs/telemetry.py``: every live
span is also a ``jax.profiler.TraceAnnotation``) one trace holds the
program's spans, the runtime's own host events and the device's operations
on one clock. This prints

- the programs of the "XLA Modules" line with their device seconds;
- device seconds by name scope (``<layer>.<kernel>``, the rule
  ``PlaneJit`` and the kernels follow), exclusive of nested operations;
- for each ``--root`` span name: the tree of what ran inside it on its own
  thread, seconds and calls by path, with the share each level covers;
- the device's idle time inside the window of the ``bench.step``
  annotations, by the innermost program span the main thread was in.

The file is decoded here (protobuf wire format, the few XSpace messages),
so the event metadata's stats -- where the device plane keeps an
operation's name scope -- are read too, which ``jax.profiler.ProfileData``
does not expose. No JAX, no TensorFlow.
"""

from __future__ import annotations

import argparse
import gzip
import json
import re
import struct
import sys

STEP = "bench.step"


# -- protobuf wire format ------------------------------------------------------

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) pairs of one message; length-delimited values
    stay memoryviews, 64/32-bit ones are raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 1:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif wt == 5:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield key >> 3, v


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names):
    """One XStat -> (name, value)."""
    name = value = None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key = val = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def load(path: str) -> list:
    """[{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns,
    stats], ..]}]}]; ``stats`` holds the event's own and its metadata's."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for f, pbuf in _fields(space):
        if f != 1:
            continue
        name, lines, ev_meta, stat_names = "", [], {}, {}
        for pf, v in _fields(pbuf):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                k, m = _map_entry(v)
                ev_meta[k] = m
            elif pf == 5:
                k, m = _map_entry(v)
                for mf, mv in _fields(m):
                    if mf == 2:
                        stat_names[k] = bytes(mv).decode()
        metas = {}
        for k, m in ev_meta.items():
            mname, mstats = "", {}
            for mf, mv in _fields(m):
                if mf == 2:
                    mname = bytes(mv).decode("utf-8", "replace")
                elif mf == 5:
                    sn, sv = _stat(mv, stat_names)
                    mstats[sn] = sv
            metas[k] = (mname, mstats)
        out_lines = []
        for lbuf in lines:
            lname, t0, events = "", 0, []
            for lf, v in _fields(lbuf):
                if lf == 2:
                    lname = bytes(v).decode()
                elif lf == 3:
                    t0 = _signed(v)
                elif lf == 4:
                    events.append(v)
            evs = []
            for ebuf in events:
                mid = off = dur = 0
                stats = None
                for ef, v in _fields(ebuf):
                    if ef == 1:
                        mid = v
                    elif ef == 2:
                        off = _signed(v)
                    elif ef == 3:
                        dur = _signed(v)
                    elif ef == 4:
                        sn, sv = _stat(v, stat_names)
                        stats = stats or {}
                        stats[sn] = sv
                mname, mstats = metas.get(mid, (str(mid), {}))
                if mstats:
                    stats = {**mstats, **(stats or {})}
                evs.append([mname, t0 + off / 1e3, dur / 1e3, stats or {}])
            evs.sort(key=lambda e: (e[1], -e[2]))
            out_lines.append({"name": lname, "events": evs})
        planes.append({"name": name, "lines": out_lines})
    return planes


# -- reductions ------------------------------------------------------------------

def nest(events):
    """Events of ONE line (sorted by start, longer first) ->
    ([(event, path of enclosing names)], [(a_ns, b_ns, path, stats)]): each
    event with its ancestors, and the line cut into the stretches in which
    one event was the innermost (its exclusive time; its path, its stats). An event that
    outlives the one it starts in (a TraceMe entered inside a traced Python
    frame) hangs from the nearest event that holds all of it."""
    placed, segs, stack, cur = [], [], [], None  # stack: (end, path, stats)

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, path, stats = stack.pop()
            if end > cur:
                segs.append((cur, end, path, stats))
                cur = end

    for ev in events:
        start, end = ev[1], ev[1] + ev[2]
        close_until(start)
        while stack and stack[-1][0] < end:  # crossed, not enclosing
            _, path, stats = stack.pop()
            if start > cur:
                segs.append((cur, start, path, stats))
                cur = start
        if stack and start > cur:
            segs.append((cur, start) + stack[-1][1:])
        if cur is None or start > cur or not stack:
            cur = start
        path = (stack[-1][1] if stack else ()) + (ev[0],)
        placed.append((ev, path[:-1]))
        stack.append((end, path, ev[3]))
    close_until(float("inf"))
    return placed, segs


_RUNTIME = re.compile(r"[(:]|^[A-Z]|^shard_args$|^\$")


def is_program_span(name: str) -> bool:
    """The program's spans are lower-case dotted or underscored words; the
    runtime's own host events are C++ or ``Name(...)`` shaped."""
    return not _RUNTIME.search(name)


def device_lines(planes):
    for p in planes:
        if p["name"].startswith("/device:TPU:"):
            yield p, {ln["name"]: ln["events"] for ln in p["lines"]}


def host_lines(planes):
    for p in planes:
        if p["name"].startswith("/host:CPU"):
            for ln in p["lines"]:
                yield ln


_SCOPE = re.compile(r"^[a-z][a-z0-9_]*\.[A-Za-z0-9_.+]+$")
# a scope entered inside a transformed function is printed wrapped in the
# transform: accel.correlate under vmap is "vmap(accel.correlate)"
_TRANSFORMED = re.compile(r"^[a-z_]+\((.*)\)$")


def _unwrap(part: str) -> str:
    while (m := _TRANSFORMED.match(part)):
        part = m.group(1)
    return part


def scope_of(stats: dict):
    """(program scope, innermost kernel scope) from whichever stat holds the
    operation's ``jit(f)/scope/.../primitive`` path; (None, None) if none."""
    for v in stats.values():
        if isinstance(v, str) and "/" in v and v.startswith(("jit(", "pjit(")):
            parts = [p for p in map(_unwrap, v.split("/")[1:-1])
                     if _SCOPE.match(p)]
            if parts:
                return parts[0], parts[-1]
            return v.split("/")[0], None
    return None, None


def by_scope(planes, lo=None, hi=None):
    """Exclusive device seconds by (program scope, kernel scope), and the
    operations no scope was found for, by name."""
    scoped, bare = {}, {}
    for _, lines in device_lines(planes):
        for a, b, path, stats in nest(lines.get("XLA Ops", []))[1]:
            if lo is not None:
                a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            prog, kern = scope_of(stats)
            if prog is None:
                key = path[-1].split(" = ")[0]
                bare[key] = bare.get(key, 0.0) + (b - a) / 1e9
            else:
                key = (prog, kern or "-")
                scoped[key] = scoped.get(key, 0.0) + (b - a) / 1e9
    return scoped, bare


def modules(planes):
    out = {}
    for _, lines in device_lines(planes):
        for name, _s, dur, _st in lines.get("XLA Modules", []):
            name = re.sub(r"\(\d+\)$", "", name)
            ent = out.setdefault(name, [0.0, 0])
            ent[0] += dur / 1e9
            ent[1] += 1
    return out


def tree(planes, root: str, depth: int = 4):
    """{path: [seconds, calls, self seconds]} under every ``root`` event, on
    the line (thread) that holds it; paths start at the root."""
    out = {}
    for ln in host_lines(planes):
        if not any(e[0] == root for e in ln["events"]):
            continue
        placed, segs = nest(ln["events"])
        for ev, path in placed:
            full = path + (ev[0],)
            if root in full:
                rel = full[full.index(root):]
                if len(rel) <= depth:
                    ent = out.setdefault(rel, [0.0, 0, 0.0])
                    ent[0] += ev[2] / 1e9
                    ent[1] += 1
        for a, b, path, _stats in segs:
            if root in path:
                rel = path[path.index(root):][:depth]
                if rel in out:
                    out[rel][2] += (b - a) / 1e9
    return out


def window(planes):
    steps = [e for ln in host_lines(planes) for e in ln["events"]
             if e[0] == STEP]
    if not steps:
        return None, None
    return min(e[1] for e in steps), max(e[1] + e[2] for e in steps)


def idle_gaps(planes, lo, hi):
    busy = []
    for _, lines in device_lines(planes):
        for _n, s, d, _st in lines.get("XLA Ops", []):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                if busy and a <= busy[-1][1]:
                    busy[-1][1] = max(busy[-1][1], b)
                else:
                    busy.append([a, b])
        break  # one chip
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def idle_by_span(planes):
    """Device-idle seconds inside the window of the ``bench.step``
    annotations, for every host thread that holds a program span: by that
    thread's innermost program span, and by its innermost host event of any
    kind. Threads overlap (the ship-ahead worker reads while the main
    thread waits on it), so each thread's table sums to the idle time."""
    lo, hi = window(planes)
    if lo is None:
        return [], None
    gaps = idle_gaps(planes, lo, hi)
    threads = []
    for ln in host_lines(planes):
        if not any(is_program_span(e[0]) and e[1] < hi and e[1] + e[2] > lo
                   for e in ln["events"]) or any(
                "hlo_op" in e[3] for e in ln["events"][:50]):
            continue  # no span of the program's, or XLA:CPU's op threads
        segs = nest(ln["events"])[1]
        prog, anyk = {}, {}

        def add(a, b, path):
            inner = path[-1] if path else "(no host event)"
            p = next((n for n in reversed(path) if is_program_span(n)),
                     "(outside any span)")
            prog[p] = prog.get(p, 0.0) + (b - a) / 1e9
            anyk[inner] = anyk.get(inner, 0.0) + (b - a) / 1e9

        i = 0
        for a, b in gaps:
            cur = a
            while i < len(segs) and segs[i][1] <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < b:
                sa, sb, path, _stats = segs[j]
                sa, sb = max(sa, a), min(sb, b)
                if sa > cur:
                    add(cur, sa, ())
                if sb > sa:
                    add(sa, sb, path)
                    cur = max(cur, sb)
                j += 1
            if b > cur:
                add(cur, b, ())
        names = {e[0] for e in ln["events"] if is_program_span(e[0])}
        threads.append({
            "thread": ln["name"], "main": STEP in names,
            "by_program_span": sorted(prog.items(), key=lambda kv: -kv[1]),
            "by_innermost_event": sorted(
                anyk.items(), key=lambda kv: -kv[1])[:12]})
    threads.sort(key=lambda t: (not t["main"], -sum(
        v for k, v in t["by_program_span"] if k != "(outside any span)")))
    idle = sum(b - a for a, b in gaps) / 1e9
    return threads, {"window_s": (hi - lo) / 1e9, "idle_s": idle}


def report(path: str, roots=(), depth: int = 4) -> dict:
    planes = load(path)
    lo, hi = window(planes)
    scoped, bare = by_scope(planes, lo, hi)
    threads, win = idle_by_span(planes)
    return {
        "modules": {k: v for k, v in sorted(
            modules(planes).items(), key=lambda kv: -kv[1][0])},
        "device_s_by_scope": [[list(k), v] for k, v in sorted(
            scoped.items(), key=lambda kv: -kv[1])],
        "device_s_unscoped": sorted(bare.items(), key=lambda kv: -kv[1])[:12],
        "window": win,
        "idle_by_thread": threads,
        "trees": {r: [["/".join(k), *v] for k, v in sorted(
            tree(planes, r, depth).items())] for r in roots},
        "device_lines": {p["name"]: {n: len(e) for n, e in ls.items()}
                         for p, ls in device_lines(planes)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--root", action="append", default=[])
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    rep = report(args.trace, args.root, args.depth)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=1)
    w = rep["window"]
    if w:
        print(f"window {w['window_s']:.3f}s, device idle {w['idle_s']:.3f}s")
    print("programs (device s, runs):")
    for k, (s, n) in rep["modules"].items():
        print(f"  {k:<44s} {s:9.3f} {n:6d}")
    print("device s by scope (exclusive):")
    for (p, k), s in rep["device_s_by_scope"]:
        print(f"  {p:<34s} {k:<24s} {s:9.3f}")
    for k, s in rep["device_s_unscoped"]:
        print(f"  (unscoped) {k:<48s} {s:9.3f}")
    for t in rep["idle_by_thread"]:
        print(f"device idle s on thread {t['thread']!r}"
              f"{' (holds bench.step)' if t['main'] else ''}, by its "
              f"innermost program span:")
        for k, s in t["by_program_span"]:
            print(f"  {k:<34s} {s:9.3f}")
        print("  ... and by its innermost host event of any kind:")
        for k, s in t["by_innermost_event"]:
            print(f"    {k:<48s} {s:9.3f}")
    for r, rows in rep["trees"].items():
        print(f"tree under {r} (path, seconds, calls, self seconds):")
        for pth, s, n, self_s in rows:
            d = pth.count("/")
            print(f"  {'  ' * d}{pth.split('/')[-1]:<{46 - 2 * d}s}"
                  f" {s:9.3f} {n:6d} {self_s:9.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
