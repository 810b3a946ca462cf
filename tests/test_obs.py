"""Tests for the obs telemetry subsystem: span nesting/attributes,
counters/gauges/events, JSONL round-trip through tlmsum, the zero-overhead
inactive path, device snapshots on CPU-only backends, and the hot-path
instrumentation (sweep chunk records, H2D/D2H byte accounting)."""

import json

import numpy as np
import pytest

from pypulsar_tpu.obs import summarize, telemetry


def _read_jsonl(path):
    return [json.loads(line) for line in open(path) if line.strip()]


# ---------------------------------------------------------------------------
# core collector
# ---------------------------------------------------------------------------


def test_inactive_is_noop():
    from pypulsar_tpu.obs import flightrec

    assert not telemetry.is_active()
    assert telemetry.current() is None
    flightrec.configure(0)  # recorder off: the truly-zero-overhead path
    try:
        with telemetry.span("x", a=1) as sp:
            assert sp is None  # inactive: nothing collected
        telemetry.counter("c", 5)
        telemetry.gauge("g", 2.0)
        telemetry.event("e", detail="ignored")
        telemetry.record_span("x", 1.0)
    finally:
        flightrec.configure(None)  # back to the env-resolved default
    assert telemetry.device_snapshot() is None
    assert not telemetry.is_active()  # nothing leaked a session


def test_inactive_span_feeds_flight_recorder():
    """With no session but the (default-on) flight recorder enabled,
    span() yields a live handle and the record lands in the ring —
    round 21's always-on crash context."""
    from pypulsar_tpu.obs import flightrec

    assert not telemetry.is_active()
    flightrec.configure(8)
    try:
        flightrec.clear()
        with telemetry.span("ring.x", a=1) as sp:
            assert sp is not None  # ring handle, attrs attachable
            sp.set(rows=3)
        recs = flightrec.snapshot()
        spans = [r for r in recs if r.get("type") == "span"
                 and r.get("name") == "ring.x"]
        assert len(spans) == 1
        assert spans[0]["attrs"] == {"a": 1, "rows": 3}
        assert "tw" in spans[0]  # wall-stamped for cross-host alignment
    finally:
        flightrec.clear()
        flightrec.configure(None)
    assert not telemetry.is_active()


def test_span_nesting_attrs_and_jsonl(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with telemetry.session(path, tool="test") as tlm:
        assert telemetry.is_active()
        with telemetry.span("outer", kind="a"):
            with telemetry.span("inner", n=3) as sp:
                sp.set(rows=7)  # attrs attachable mid-flight
        with telemetry.span("outer"):
            pass
        assert tlm.stages["outer"][1] == 2
        assert tlm.stages["inner"][1] == 1
    assert not telemetry.is_active()
    recs = _read_jsonl(path)
    assert recs[0]["type"] == "meta" and recs[0]["tool"] == "test"
    spans = [r for r in recs if r["type"] == "span"]
    inner = next(r for r in spans if r["name"] == "inner")
    outers = [r for r in spans if r["name"] == "outer"]
    assert inner["parent"] == "outer"
    assert inner["depth"] == 1
    assert inner["attrs"] == {"n": 3, "rows": 7}
    assert len(outers) == 2
    assert all("parent" not in r for r in outers)
    # the first outer span encloses inner, so its duration dominates
    assert max(r["dur"] for r in outers) >= inner["dur"]
    assert recs[-1]["type"] == "end" and recs[-1]["wall"] > 0
    # end-of-run flushes carry the aggregates
    stages = next(r for r in recs if r["type"] == "stages")["stages"]
    assert stages["outer"][1] == 2


def test_counters_gauges_events(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with telemetry.session(path) as tlm:
        telemetry.counter("h2d.bytes", 100)
        telemetry.counter("h2d.bytes", 150)
        telemetry.counter("chunks")
        telemetry.gauge("depth", 2)
        telemetry.gauge("depth", 5)
        telemetry.gauge("depth", 3)
        telemetry.event("fallback", n=4, error="RuntimeError")
        assert tlm.counter_totals() == {"h2d.bytes": 250, "chunks": 1}
        assert tlm.gauge_values()["depth"] == {"last": 3, "max": 5}
    recs = _read_jsonl(path)
    ev = next(r for r in recs if r["type"] == "event")
    assert ev["name"] == "fallback"
    assert ev["attrs"] == {"n": 4, "error": "RuntimeError"}
    counters = next(r for r in recs if r["type"] == "counters")
    assert counters["counters"]["h2d.bytes"] == 250
    assert counters["gauges"]["depth"]["max"] == 5
    assert counters["events"]["fallback"] == 1


def test_nested_session_reuses_outer(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with telemetry.session(path) as outer:
        with telemetry.session(str(tmp_path / "ignored.jsonl")) as inner:
            assert inner is outer  # one trace per process
            telemetry.counter("c")
        assert telemetry.is_active()  # inner exit must not close outer
        assert outer.counter_totals() == {"c": 1}
    assert not telemetry.is_active()
    assert not (tmp_path / "ignored.jsonl").exists()


def test_session_from_flag_none_is_inactive():
    with telemetry.session_from_flag(None) as tlm:
        assert tlm is None
        assert not telemetry.is_active()


def test_device_snapshot_cpu_only(tmp_path):
    """Snapshots must work (not raise) on a backend with no memory_stats
    — the CPU-only guard of the issue's acceptance criteria."""
    import jax

    jax.devices()  # ensure the backend exists
    path = str(tmp_path / "t.jsonl")
    with telemetry.session(path):
        devs = telemetry.device_snapshot(tag="probe")
    assert isinstance(devs, list) and devs
    assert devs[0]["platform"] == "cpu"
    recs = _read_jsonl(path)
    tags = [r["tag"] for r in recs if r["type"] == "device"]
    assert "probe" in tags and "session_end" in tags


def test_threaded_counters_race_free(tmp_path):
    import threading

    with telemetry.session() as tlm:
        def work():
            for _ in range(1000):
                telemetry.counter("n")

        ts = [threading.Thread(target=work) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert tlm.counter_totals()["n"] == 4000


# ---------------------------------------------------------------------------
# hot-path instrumentation
# ---------------------------------------------------------------------------


@pytest.fixture
def small_sweep_trace(tmp_path):
    """Run a tiny chunked sweep under a telemetry session; returns
    (jsonl path, counter totals, gauge values)."""
    from pypulsar_tpu.core.spectra import Spectra
    from pypulsar_tpu.parallel import sweep_spectra

    rng = np.random.RandomState(0)
    freqs = 1500.0 - 2.0 * np.arange(32)
    spec = Spectra(freqs, 1e-3, rng.randn(32, 4096).astype(np.float32))
    path = str(tmp_path / "sweep.jsonl")
    with telemetry.session(path, tool="sweep-test") as tlm:
        sweep_spectra(spec, np.linspace(0, 50, 8), nsub=8, group_size=4,
                      chunk_payload=1024)
        counters = tlm.counter_totals()
        gauges = tlm.gauge_values()
    return path, counters, gauges


def test_sweep_stream_chunk_records(small_sweep_trace):
    path, counters, gauges = small_sweep_trace
    assert counters["sweep.chunks"] == 4  # 4096 / 1024
    assert counters["sweep.payload_samples"] == 4096
    assert counters["sweep.trials_completed"] == 8
    assert counters["d2h.bytes"] > 0 and counters["d2h.pulls"] >= 1
    assert gauges["sweep.pending_depth"]["max"] >= 1
    recs = _read_jsonl(path)
    chunk_events = [r for r in recs
                    if r["type"] == "event" and r["name"] == "sweep.chunk"]
    assert len(chunk_events) == 4
    starts = [e["attrs"]["start"] for e in chunk_events]
    assert starts == [0, 1024, 2048, 3072]
    assert all(e["attrs"]["stat_len"] == 1024 for e in chunk_events)
    assert all(e["attrs"]["pending"] >= 1 for e in chunk_events)
    span_names = {r["name"] for r in recs if r["type"] == "span"}
    assert {"dispatch_sweep_chunk", "device_wait+accumulate"} <= span_names


def test_staged_sweep_step_span(tmp_path):
    """sweep_flat wraps each DDstep in a sweep_step span carrying the
    step geometry. (Spectra data is device-resident from construction,
    so no H2D is — correctly — accounted on this path; the streamed
    reader path is covered by test_ship_ahead_counts_h2d_bytes.)"""
    from pypulsar_tpu.core.spectra import Spectra
    from pypulsar_tpu.parallel.staged import sweep_flat

    rng = np.random.RandomState(1)
    freqs = 1500.0 - 4.0 * np.arange(16)
    spec = Spectra(freqs, 1e-3, rng.randn(16, 2048).astype(np.float32))
    path = str(tmp_path / "flat.jsonl")
    with telemetry.session(path) as tlm:
        sweep_flat(spec, np.linspace(0, 30, 4), nsub=8, group_size=2,
                   chunk_payload=512)
        assert tlm.counter_totals()["sweep.chunks"] == 4
    recs = _read_jsonl(path)
    steps = [r for r in recs if r["type"] == "span"
             and r["name"] == "sweep_step"]
    assert len(steps) == 1
    assert steps[0]["attrs"]["n_trials"] == 4


def test_ship_ahead_counts_h2d_bytes():
    """The streamed reader path's background host->device ship accounts
    every shipped block's bytes (the wire is the measured streamed-sweep
    ceiling — the counter is the evidence trail)."""
    from pypulsar_tpu.parallel.staged import _ship_ahead

    blocks = [(0, np.zeros((128, 64), np.uint8)),
              (128, np.zeros((128, 64), np.uint8))]
    with telemetry.session() as tlm:
        out = list(_ship_ahead(iter(blocks)))
        assert tlm.counter_totals()["h2d.bytes"] == 2 * 128 * 64
    assert [pos for pos, _ in out] == [0, 128]


def test_fold_engine_counters():
    from pypulsar_tpu.fold.engine import fold_bins

    data = np.random.RandomState(2).randn(4, 256).astype(np.float32)
    bins = (np.arange(256) % 16).astype(np.int32)
    with telemetry.session() as tlm:
        fold_bins(data, bins, 16)
        assert tlm.counter_totals()["fold.samples"] == 4 * 256
        assert "fold_bins" in tlm.stages


def test_rfifind_intervals_counter():
    from pypulsar_tpu.ops.rfifind import rfifind

    rng = np.random.RandomState(3)
    data = rng.randn(8, 2048).astype(np.float32)
    with telemetry.session() as tlm:
        rfifind(data, dt=1e-3, time=0.256)
        counters = tlm.counter_totals()
    assert counters["rfifind.intervals"] == 8  # 2048 / 256
    assert counters["d2h.bytes"] > 0


# ---------------------------------------------------------------------------
# tlmsum round-trip
# ---------------------------------------------------------------------------


def test_tlmsum_roundtrip(small_sweep_trace, capsys):
    path, counters, _ = small_sweep_trace
    from pypulsar_tpu.cli.__main__ import main as cli_main

    assert cli_main(["tlmsum", path]) == 0
    out = capsys.readouterr().out
    # per-stage wall breakdown
    assert "stage breakdown" in out
    assert "dispatch_sweep_chunk" in out and "%" in out
    # transfer byte totals and chunk counts (acceptance criteria)
    assert "d2h.bytes" in out
    assert "sweep.chunks" in out
    assert "sweep.pending_depth" in out
    assert "device snapshot" in out


def test_incremental_counter_flush(tmp_path, monkeypatch):
    """Counter totals flush incrementally (piggybacked on events) so a
    killed run's trace still answers 'where did the bytes go' even
    though close() never wrote the final counters record."""
    monkeypatch.setattr(telemetry, "COUNTER_FLUSH_INTERVAL", 0.0)
    path = str(tmp_path / "t.jsonl")
    with telemetry.session(path):
        telemetry.counter("h2d.bytes", 111)
        telemetry.event("sweep.chunk", start=0)
        telemetry.counter("h2d.bytes", 222)
        telemetry.event("sweep.chunk", start=1)
        # simulate the kill: drop everything after the incremental records
        lines_mid_run = open(path).read().splitlines()
    kept = [ln for ln in lines_mid_run]
    trunc = str(tmp_path / "killed.jsonl")
    open(trunc, "w").write("\n".join(kept) + "\n")
    partials = [json.loads(ln) for ln in kept
                if json.loads(ln)["type"] == "counters"]
    assert partials and all(p.get("partial") for p in partials)
    s = summarize.summarize(summarize.load_records(trunc))
    assert s.counters["h2d.bytes"] == 333  # last partial flush wins


def test_tlmsum_autotuning_rollup(tmp_path, capsys):
    """The round-17 tune.* telemetry contract gets its own tlmsum
    roll-up: trials/hit/miss counters plus the winning config per stage
    from the tune.winner (search) and tune.applied (cache-hit) event
    attrs — and a trace without tune records renders no such section."""
    path = str(tmp_path / "tune.jsonl")
    with telemetry.session(path, tool="sweep"):
        telemetry.counter("tune.trials", 7)
        telemetry.counter("tune.cache_miss", 1)
        telemetry.counter("tune.cache_hit", 2)
        telemetry.event("tune.winner", stage="sweep",
                        config={"PYPULSAR_TPU_SWEEP_CHUNK": 131072},
                        n_trials=7, baseline_s=0.9, best_s=0.7)
        telemetry.event("tune.applied", stage="accel",
                        config={"PYPULSAR_TPU_ACCEL_BATCH": 8})
    from pypulsar_tpu.obs.summarize import main as tlmsum_main

    assert tlmsum_main([path]) == 0
    out = capsys.readouterr().out
    line = [ln for ln in out.splitlines() if "auto-tuning" in ln]
    assert line, out
    assert "trials=7" in line[0]
    assert "cache hits=2" in line[0]
    assert "cache misses=1" in line[0]
    sweep = [ln for ln in out.splitlines() if "SWEEP_CHUNK=131072" in ln]
    assert sweep and "7 trials" in sweep[0], out
    accel = [ln for ln in out.splitlines() if "ACCEL_BATCH=8" in ln]
    assert accel, out

    plain = str(tmp_path / "plain.jsonl")
    with telemetry.session(plain, tool="sweep"):
        telemetry.counter("sweep.chunks", 1)
    assert tlmsum_main([plain]) == 0
    assert "auto-tuning" not in capsys.readouterr().out


def test_tlmsum_truncated_trace(small_sweep_trace, capsys):
    """A killed run's trace (no end-of-run flush records) still
    summarizes from the incremental span/event records."""
    path, _, _ = small_sweep_trace
    lines = open(path).read().splitlines()
    kept = [ln for ln in lines
            if json.loads(ln)["type"] not in ("counters", "stages", "end")]
    trunc = path + ".trunc"
    with open(trunc, "w") as f:
        f.write("\n".join(kept) + "\n" + '{"type": "span", "na')  # torn line
    s = summarize.summarize(summarize.load_records(trunc))
    assert s.wall > 0
    assert "dispatch_sweep_chunk" in s.stages
    assert s.events.get("sweep.chunk") == 4
    from pypulsar_tpu.obs.summarize import main as tlmsum_main

    assert tlmsum_main([trunc]) == 0
    assert "dispatch_sweep_chunk" in capsys.readouterr().out


def test_tlmsum_multi_trace_fleet_rollup(tmp_path, capsys):
    """tlmsum over several traces (paths or a quoted glob) renders one
    section per trace plus a combined fleet roll-up with summed stage
    seconds/calls, counters and events — the survey orchestrator's
    --telemetry-dir consumer. The single-file contract is unchanged (no
    section headers)."""
    import glob as _glob

    for i in range(2):
        path = str(tmp_path / f"obs{i}.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps({"type": "meta", "tool": "survey-obs",
                                "obs": f"obs{i}"}) + "\n")
            f.write(json.dumps({"type": "span", "name": "survey.stage.x",
                                "t": 0.0, "dur": 1.0 + i}) + "\n")
            f.write(json.dumps({"type": "counters",
                                "counters": {"h2d.bytes": 100.0 * (i + 1),
                                             "sweep.chunks": 3.0},
                                "gauges": {"g": {"last": i, "max": i + 1}},
                                "events": {"e": 2}}) + "\n")
            f.write(json.dumps({"type": "end", "wall": 2.0}) + "\n")
    from pypulsar_tpu.obs.summarize import (
        combine_summaries,
        load_records,
        main as tlmsum_main,
    )

    paths = sorted(str(p) for p in _glob.glob(str(tmp_path / "obs*.jsonl")))
    assert tlmsum_main(paths) == 0
    out = capsys.readouterr().out
    assert out.count("# ===== trace:") == 2
    assert "# ===== fleet roll-up: 2 traces =====" in out
    # combined totals: counters summed, walls summed, stage calls summed
    combined = combine_summaries(
        [summarize.summarize(load_records(p)) for p in paths])
    assert combined.counters["h2d.bytes"] == 300.0
    assert combined.counters["sweep.chunks"] == 6.0
    assert combined.events["e"] == 4
    assert combined.wall == 4.0
    assert combined.stages["survey.stage.x"] == [3.0, 2]
    assert combined.gauges["g"]["max"] == 2
    # quoted-glob form expands (the CLI surface the survey docs show)
    assert tlmsum_main([str(tmp_path / "obs*.jsonl")]) == 0
    assert "fleet roll-up" in capsys.readouterr().out
    # single-file behavior unchanged: no section headers
    assert tlmsum_main([paths[0]]) == 0
    assert "=====" not in capsys.readouterr().out
    # one unreadable path among several: others still render, rc 1
    assert tlmsum_main([paths[0], str(tmp_path / "missing.jsonl")]) == 1


# ---------------------------------------------------------------------------
# the seam to the profiler, the jit.* counters, the leaves (PR 24)
# ---------------------------------------------------------------------------


def _host_events(logdir):
    """{name: [(line index, start_ns, end_ns, stats)]} of the host plane of
    the one trace under ``logdir``."""
    import glob
    import warnings

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(logdir) + "/plugins/profile/*/*.xplane.pb")
    out = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = ProfileData.from_file(path)
        for plane in data.planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for li, line in enumerate(plane.lines):
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (li, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return out


def test_spans_land_in_the_profilers_trace(tmp_path):
    """A span inside a session under jax.profiler.trace is an event of the
    SAME .xplane.pb as the operations: scalar attributes as stats (those
    attached mid-flight too), nested inside its parent on one clock, a
    worker thread's span on a line of its own."""
    import threading

    import jax
    import jax.numpy as jnp

    def work():
        with telemetry.span("seam.worker", bytes=10):
            pass

    with jax.profiler.trace(str(tmp_path)):
        with telemetry.session():
            with telemetry.span("seam.outer", a=1, tag="s", skipped=[1, 2]):
                with telemetry.span("seam.inner", k=2.5) as sp:
                    jnp.ones(8).sum().block_until_ready()
                    sp.set(rows=7)
                t = threading.Thread(target=work)
                t.start()
                t.join()
    ev = _host_events(tmp_path)
    (outer,), (inner,), (worker,) = (ev["seam.outer"], ev["seam.inner"],
                                     ev["seam.worker"])
    assert outer[3] == {"a": 1, "tag": "s"}  # scalars only
    assert inner[3] == {"k": 2.5, "rows": 7}
    assert worker[3] == {"bytes": 10}
    assert outer[0] == inner[0] and outer[1] <= inner[1] <= inner[2] <= outer[2]
    assert worker[0] != outer[0]
    assert outer[1] <= worker[1] <= worker[2] <= outer[2]  # one clock


def test_off_path_is_unchanged_and_obs_imports_no_jax():
    """No session, recorder off: span() is still the shared null context;
    and no module of obs/ imports jax as it loads (the seam and the device
    snapshot reach it only once something else has: sys.modules)."""
    import ast
    import glob
    import os

    from pypulsar_tpu.obs import flightrec

    flightrec.configure(0)
    try:
        assert telemetry.span("x", a=1) is telemetry._NULL_SPAN
    finally:
        flightrec.configure(None)
    obs_dir = os.path.dirname(telemetry.__file__)
    for path in glob.glob(os.path.join(obs_dir, "*.py")):
        for node in ast.parse(open(path).read()).body:  # module level
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            assert not any(n == "jax" or n.startswith("jax.")
                           for n in names), (path, names)


def test_jit_compiles_counted_at_the_source(tmp_path):
    """A fresh plain-jit function inside a session raises jit.compiles by
    one (with an event on the timeline) and a second call by none; outside
    a session nothing is recorded; the JSONL states the counter even at 0."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones(16)  # the constant's own program compiles out here
    f = jax.jit(lambda v: (v * 3.0 - 1.0).sum())
    path = str(tmp_path / "jit.jsonl")
    with telemetry.session(path) as tlm:
        before = tlm.counter_totals().get("jit.compiles", 0)
        f(x).block_until_ready()
        once = tlm.counter_totals()
        f(x).block_until_ready()
        twice = tlm.counter_totals()
        assert tlm.event_counts.get("jit.compile", 0) == 1
    assert once["jit.compiles"] - before == 1
    assert twice["jit.compiles"] == once["jit.compiles"]
    assert once["jit.compile_ms"] > 0 and once["jit.trace_ms"] > 0
    ev = next(r for r in _read_jsonl(path)
              if r["type"] == "event" and r["name"] == "jit.compile")
    assert ev["attrs"]["ms"] > 0 and ev["t"] >= 0
    g = jax.jit(lambda v: (v * 5.0).sum())
    g(x).block_until_ready()  # no session: the listener returns at once
    quiet = str(tmp_path / "quiet.jsonl")
    with telemetry.session(quiet) as tlm:
        g(x).block_until_ready()
        assert "jit.compiles" not in tlm.counter_totals()
    final = [r for r in _read_jsonl(quiet) if r["type"] == "counters"][-1]
    assert final["counters"]["jit.compiles"] == 0


def test_ship_and_pull_record_span_and_bytes(tmp_path):
    from pypulsar_tpu.ops import transfer

    import jax.numpy as jnp

    host = np.arange(12, dtype=np.uint8).reshape(3, 4)
    path = str(tmp_path / "xfer.jsonl")
    with telemetry.session(path) as tlm:
        dev = transfer.ship(host)                    # native dtype: 12 B
        wide = transfer.ship(host, jnp.float32)      # cast on the host: 48 B
        again = transfer.ship(dev, jnp.float32)      # on device: not a ship
        a, b = transfer.pull_host(dev, wide)
        totals = tlm.counter_totals()
    assert dev.dtype == jnp.uint8 and wide.dtype == jnp.float32
    assert again.dtype == jnp.float32
    np.testing.assert_array_equal(a, host)
    np.testing.assert_array_equal(b, host.astype(np.float32))
    assert totals["h2d.bytes"] == 12 + 48
    assert totals["d2h.bytes"] == 12 + 48 and totals["d2h.pulls"] == 1
    spans = [r for r in _read_jsonl(path) if r["type"] == "span"]
    ships = [r for r in spans if r["name"] == "h2d.ship"]
    assert [r["attrs"]["bytes"] for r in ships] == [12, 48]
    (pull,) = [r for r in spans if r["name"] == "d2h.pull"]
    assert pull["attrs"] == {"bytes": 60, "arrays": 2}
    assert all(r.get("noagg") for r in ships + [pull])  # sink-only
    # no session: same values, nothing recorded
    np.testing.assert_array_equal(
        transfer.pull_host(transfer.ship(host))[0], host)


def _span_paths(path):
    """{span name: set of parent names} of one telemetry JSONL."""
    out = {}
    for r in _read_jsonl(path):
        if r["type"] == "span":
            out.setdefault(r["name"], set()).add(r.get("parent"))
    return out


@pytest.fixture(scope="module")
def toy_fil(tmp_path_factory):
    from pypulsar_tpu.io.filterbank import write_filterbank

    rng = np.random.RandomState(24)
    nchan, nsamp = 16, 4096
    data = (rng.randn(nsamp, nchan) * 8 + 64).clip(0, 255).astype(np.uint8)
    fn = str(tmp_path_factory.mktemp("leaves") / "toy.fil")
    write_filterbank(fn, dict(fch1=1500.0, foff=-4.0, nchans=nchan,
                              tsamp=1e-3, nbits=8), data)
    return fn


def _open_by_rfifind(fil, tmp_path):
    from pypulsar_tpu.cli import rfifind as cli_rfifind

    tlm = str(tmp_path / "tlm.jsonl")
    assert cli_rfifind.main([fil, "-o", str(tmp_path / "toy"),
                             "-t", "0.512", "--telemetry", tlm]) == 0
    return tlm, 1


def _open_by_sweep(fil, tmp_path):
    from pypulsar_tpu.cli import sweep as cli_sweep

    tlm = str(tmp_path / "tlm.jsonl")
    assert cli_sweep.main([fil, "-o", str(tmp_path / "toy"),
                           "--lodm", "0", "--dmstep", "5", "--numdms", "4",
                           "-s", "8", "--chunk", "1024",
                           "--telemetry", tlm]) == 0
    return tlm, 1


def test_cli_rfifind_holds_every_leaf_under_its_root(toy_fil, tmp_path):
    tlm, _ = _open_by_rfifind(toy_fil, tmp_path)
    paths = _span_paths(tlm)
    assert paths["cli.rfifind"] == {None}
    for leaf in ("io.open", "io.read", "rfifind.stage_block", "h2d.ship",
                 "rfifind.ingest", "rfifind_block_stats", "rfifind.clip",
                 "rfifind.write"):
        assert paths[leaf] == {"cli.rfifind"}, (leaf, paths.get(leaf))
    assert paths["d2h.pull"] == {"rfifind_block_stats"}
    final = [r for r in _read_jsonl(tlm) if r["type"] == "counters"][-1]
    assert final["counters"]["io.bytes_read"] == 16 * 4096  # as on disk
    assert final["counters"]["h2d.bytes"] == 16 * 4096  # as on disk too
    assert final["counters"]["rfifind.raw_blocks"] == 1  # one read
    blocks = [r for r in _read_jsonl(tlm) if r["type"] == "span"
              and r["name"] == "rfifind.stage_block"]
    assert all(r["attrs"]["bytes"] >= 0 for r in blocks)


def test_rfifind_clip_span_says_how_much_work_it_did(tmp_path):
    """``rfifind.clip`` carries the passes of its loop, the line
    statistics it computed and the table's cells: one loud block takes a
    second pass, which redoes that block's lines and no others."""
    from pypulsar_tpu.cli import rfifind as cli_rfifind
    from pypulsar_tpu.io.filterbank import write_filterbank

    rng = np.random.RandomState(35)
    nchan, nint, pts = 16, 8, 512
    data = (rng.randn(nint * pts, nchan) * 8 + 64).clip(0, 255)
    data[3 * pts:4 * pts, 5] += 120  # one (interval, channel) block
    fn = str(tmp_path / "loud.fil")
    write_filterbank(fn, dict(fch1=1500.0, foff=-4.0, nchans=nchan,
                              tsamp=1e-3, nbits=8), data.astype(np.uint8))
    tlm = str(tmp_path / "tlm.jsonl")
    assert cli_rfifind.main([fn, "-o", str(tmp_path / "loud"),
                             "-t", "0.512", "--telemetry", tlm]) == 0
    (clip,) = [r for r in _read_jsonl(tlm) if r["type"] == "span"
               and r["name"] == "rfifind.clip"]
    attrs = clip["attrs"]
    assert attrs["cells"] == nint * nchan
    every = 2 * (nint + nchan)  # mean and std, along both axes
    assert attrs["passes"] >= 2
    assert every < attrs["lines"] < attrs["passes"] * every


def test_cli_sweep_holds_every_leaf_under_its_root(toy_fil, tmp_path):
    tlm, _ = _open_by_sweep(toy_fil, tmp_path)
    paths = _span_paths(tlm)
    assert paths["cli.sweep"] == {None}
    assert paths["io.open"] == paths["sweep.plan"] == {"cli.sweep"}
    assert paths["sweep.finalize"] == {"sweep_step", "cli.sweep"}
    assert paths["sweep.write"] == {"cli.sweep"}
    assert paths["sweep_step"] == {"cli.sweep"}
    assert paths["d2h.pull"] == {"device_wait+accumulate"}
    # the reads and ships run on the ship-ahead worker: roots of its thread
    assert paths["io.read"] == {None} and paths["h2d.ship"] == {None}
    for name in ("block_source", "host_to_device", "dispatch_sweep_chunk",
                 "device_wait+accumulate"):  # the loop's stages keep their names
        assert paths[name] == {"sweep_step"}
    recs = _read_jsonl(tlm)
    final = [r for r in recs if r["type"] == "counters"][-1]["counters"]
    assert final["io.bytes_read"] == final["h2d.bytes"] > 0  # 8-bit, native
    (write,) = [r for r in recs if r["type"] == "span"
                and r["name"] == "sweep.write"]
    assert write["attrs"]["rows"] >= 0


def _open_by_warm_pool(fil, tmp_path):
    """The survey's warm pool reads each observation's geometry through
    the same opener, on its own thread: its open is in the trace too."""
    from pypulsar_tpu.survey.dag import SurveyConfig
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    obs = [Observation(f"toy{i}", fil, str(tmp_path / f"toy{i}"))
           for i in range(2)]
    sched = FleetScheduler(obs, SurveyConfig(numdms=4))
    tlm = str(tmp_path / "tlm.jsonl")
    with telemetry.session(tlm):
        for i in range(len(obs)):
            geo = sched._obs_geometry(i)
            assert geo["n_samples"] == 4096 and len(geo["freqs"]) == 16
    return tlm, len(obs)


@pytest.mark.parametrize("opens", [_open_by_rfifind, _open_by_sweep,
                                   _open_by_warm_pool],
                         ids=["rfifind", "sweep", "warm_pool"])
def test_every_open_is_one_io_open_span(toy_fil, tmp_path, opens):
    """Exactly one ``io.open`` span per file opened, the opener's own,
    with the bytes it read to decide the format: 16 for a SIGPROC file
    (never a pass over the file), summed in ``io.sniff_bytes``."""
    tlm, n_opened = opens(toy_fil, tmp_path)
    recs = _read_jsonl(tlm)
    spans = [r for r in recs if r["type"] == "span"
             and r["name"] == "io.open"]
    assert len(spans) == n_opened
    assert all(r["attrs"] == {"format": "sigproc", "sniff_bytes": 16}
               for r in spans)
    final = [r for r in recs if r["type"] == "counters"][-1]["counters"]
    assert final["io.sniff_bytes"] == 16 * n_opened


def test_tlmsum_totals_disk_bytes_beside_the_wire(toy_fil, tmp_path):
    import io

    tlm, _ = _open_by_sweep(toy_fil, tmp_path)
    out = io.StringIO()
    summarize.render(summarize.summarize(summarize.load_records(tlm)), out)
    text = out.getvalue()
    totals = text.split("# transfer totals:")[1].split("# counters:")[0]
    for name in ("io.sniff_bytes", "io.bytes_read", "h2d.bytes",
                 "d2h.bytes"):
        assert name in totals, (name, totals)


def test_trace_report_reads_spans_and_attrs_from_the_xplane(tmp_path):
    """tools/trace_report.py decodes the .xplane.pb itself: same events as
    jax's own reader, the span tree with exclusive seconds, and the
    name-scope path of an operation taken from whichever stat holds it."""
    import glob
    import importlib.util
    import os

    import jax
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)

    with jax.profiler.trace(str(tmp_path)):
        with telemetry.session():
            with telemetry.span("bench.step", step=0):
                with telemetry.span("tr.root", n=3):
                    with telemetry.span("tr.leaf", bytes=5):
                        jnp.ones(8).sum().block_until_ready()
                    with telemetry.span("tr.leaf", bytes=6):
                        pass
    (path,) = glob.glob(str(tmp_path) + "/plugins/profile/*/*.xplane.pb")
    planes = tr.load(path)
    mine = {name: evs for name, evs in _host_events(tmp_path).items()
            if name.startswith("tr.")}
    got = [e for p in planes if p["name"].startswith("/host:CPU")
           for ln in p["lines"] for e in ln["events"]
           if e[0].startswith("tr.")]
    assert sorted(e[0] for e in got) == ["tr.leaf", "tr.leaf", "tr.root"]
    root = next(e for e in got if e[0] == "tr.root")
    assert root[3] == {"n": 3}
    assert abs(root[1] - mine["tr.root"][0][1]) < 1e3  # same clock, ns
    assert sorted(e[3]["bytes"] for e in got if e[0] == "tr.leaf") == [5, 6]
    tree = tr.tree(planes, "tr.root", depth=2)
    total, calls, self_s = tree[("tr.root",)]
    leaf_total, leaf_calls, _ = tree[("tr.root", "tr.leaf")]
    assert calls == 1 and leaf_calls == 2
    assert total == pytest.approx(root[2] / 1e9)
    assert 0 <= self_s <= total and leaf_total <= total
    assert tr.window(planes)[0] is not None  # the bench.step annotation
    assert tr.is_program_span("rfifind.stage_block")
    assert tr.is_program_span("device_wait+accumulate")
    assert not tr.is_program_span("PjitFunction(jit(_ingest_tc))")
    assert not tr.is_program_span("TpuClient::LinearizeIntoImpl")
    assert tr.scope_of({"tf_op": "jit(accel_stage)/accel.accel_stage/while/"
                                 "body/accel.correlate/fft"}) == (
        "accel.accel_stage", "accel.correlate")
    # a scope inside a vmapped body is printed wrapped in the transform
    assert tr.scope_of({"tf_op": "jit(accel_stage_batch)/accel.accel_stage_"
                                 "batch/while/body/closed_call/"
                                 "vmap(accel.harmonic_sum)/gather"}) == (
        "accel.accel_stage_batch", "accel.harmonic_sum")
    assert tr.scope_of({"tf_op": "jit(f)/vmap(jit(_where))/select_n"}) == (
        "jit(f)", None)
    assert tr.scope_of({"device_offset_ps": 5}) == (None, None)
