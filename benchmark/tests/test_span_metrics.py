"""The four span and counter readers that came with the telemetry's seam to
the profiler: on a small recorded JSONL (one toy ``cli.sweep`` batch, the
spans they read and the final counters kept), on a run that predates the
spans, and end to end through the rehearsal cell that lists them."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURE = os.path.join(BENCH, "tests", "fixtures", "spans_stream.jsonl")
READERS = ("read_mb_per_s", "h2d_s_per_step", "d2h_s_per_step",
           "backend_compiles_in_window")


def _cell(telemetry, steps=2):
    return types.SimpleNamespace(
        telemetry=telemetry, steps=[{"rc": 0}] * steps + [{"rc": 1}])


def _read(name, cell):
    mod = importlib.import_module(f"metrics.{name}")
    assert isinstance(mod.UNIT, str) and " " not in mod.UNIT
    return mod.read(cell)


def test_readers_on_the_recorded_batch():
    import trace_reduce

    tlm = trace_reduce.read_telemetry([FIXTURE, FIXTURE])  # two steps
    spans, counters = tlm["spans"], tlm["counters"]
    assert spans["io.read"][1] == 10 and spans["h2d.ship"][1] == 8
    cell = _cell(tlm)
    assert _read("read_mb_per_s", cell) == pytest.approx(
        counters["io.bytes_read"] / 1e6 / spans["io.read"][0])
    assert _read("read_mb_per_s", cell) == pytest.approx(
        1280128 / 1e6 / 0.01501, rel=1e-6)
    # span seconds over COMPLETED steps: the failed third step is left out
    assert _read("h2d_s_per_step", cell) == pytest.approx(0.001571)
    assert _read("d2h_s_per_step", cell) == pytest.approx(0.003547)
    assert _read("backend_compiles_in_window", cell) == 14.0


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_where_there_is_nothing_to_read(name):
    """An untraced run, a run of a program without the spans (the parent
    commit), a window with no completed step: None, never a 0, no raise."""
    assert _read(name, _cell(None)) is None
    old = {"counters": {"compile.cache_miss": 0, "h2d.bytes": 5},
           "events": {}, "spans": {"survey.stage.mask": [1.0, 1]},
           "stage_spans": [], "n_files": 1}
    assert _read(name, _cell(old)) is None
    if name != "backend_compiles_in_window":
        import trace_reduce

        tlm = trace_reduce.read_telemetry([FIXTURE])
        if name != "read_mb_per_s":
            assert _read(name, _cell(tlm, steps=0)) is None


def test_a_program_that_counts_compiles_reads_zero_not_none():
    zero = {"counters": {"jit.compiles": 0}, "events": {}, "spans": {},
            "stage_spans": [], "n_files": 1}
    assert _read("backend_compiles_in_window", _cell(zero)) == 0.0


def test_rehearsal_cell_reports_all_four():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload",
         "rehearsal.stream-spans", "--seed", "3000000024", "--seconds", "2",
         "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = result["metrics"]
    assert set(READERS) <= set(metrics), sorted(metrics)
    assert metrics["read_mb_per_s"]["value"] > 0
    assert metrics["h2d_s_per_step"]["value"] > 0
    assert metrics["d2h_s_per_step"]["value"] > 0
    assert metrics["backend_compiles_in_window"]["value"] == 0
    # the accepted metrics of the cell it was copied from are still there
    assert {"device_idle_pct", "compiles_in_window",
            "batch_median_s"} <= set(metrics)


def test_spans_workloads_add_only_the_four_names():
    for new, old in (("rehearsal.stream-spans", "rehearsal.stream"),
                     ("rehearsal.search-spans", "rehearsal.search")):
        a, b = (json.load(open(os.path.join(
            BENCH, "tests", "workloads", n + ".json"))) for n in (new, old))
        assert a["per_layer"] == b["per_layer"] + list(READERS)
        for key in ("config", "entry", "chips", "traffic", "argv",
                    "end_to_end", "check"):
            assert a[key] == b[key], key
