"""The fleet cell's files: the three lane readers on a recorded JSONL (one
toy ``survey beam0..3 --devices 4 --gang auto`` step on four virtual CPU
devices: the lease, observation and stage spans, the gang decisions, the
final counters kept), on a run that predates the spans and counters (the
parent commit), and end to end through the rehearsal cell
``rehearsal.fleet4`` that lists them."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURE = os.path.join(BENCH, "tests", "fixtures", "lease_fleet4.jsonl")
READERS = ("fleet_lanes_used", "obs_wall_s", "chip_lease_min_pct")


def _cell(telemetry, steps=2, path=FIXTURE, chips=4):
    entry = types.SimpleNamespace(telemetry_files=lambda step: [path])
    return types.SimpleNamespace(
        telemetry=telemetry, entry=entry, wl={"chips": chips},
        steps=[{"rc": 0}] * steps + [{"rc": 1}])


def _read(name, cell):
    mod = importlib.import_module(f"metrics.{name}")
    assert isinstance(mod.UNIT, str) and " " not in mod.UNIT
    return mod.read(cell)


def test_readers_on_the_recorded_step():
    import trace_reduce

    tlm = trace_reduce.read_telemetry([FIXTURE, FIXTURE])  # two steps
    c = tlm["counters"]
    assert tlm["spans"]["survey.lease"][1] == 24  # 3 stages x 4 beams, twice
    assert tlm["spans"]["survey.obs"][1] == 8
    cell = _cell(tlm)
    assert _read("fleet_lanes_used", cell) == 4.0
    with open(FIXTURE) as f:
        recs = [json.loads(line) for line in f]
    walls = [r["dur"] for r in recs if r.get("name") == "survey.obs"]
    assert len(walls) == 4
    assert _read("obs_wall_s", cell) == pytest.approx(sum(walls) / 4)
    # the least-leased chip of the recorded step is chip 0
    by_chip = [c[f"survey.lease_chip_s.chip{n}"] for n in range(4)]
    assert min(by_chip) == by_chip[0]
    assert _read("chip_lease_min_pct", cell) == pytest.approx(
        100.0 * by_chip[0] / (c["survey.pool_chip_s"] / 4))
    assert _read("chip_lease_min_pct", cell) == pytest.approx(
        100.0 * 9.3380201899854 / (48.240209944022354 / 4))
    # by chip and by stage the same leased seconds
    assert sum(by_chip) == pytest.approx(c["survey.lease_chip_s"])


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_where_there_is_nothing_to_read(name, tmp_path):
    """An untraced run; a program without ``survey.obs`` and the per-chip
    counters (the parent commit); no telemetry file at all: None, no
    raise. (The parent does record ``survey.lease`` with its chips, so
    ``fleet_lanes_used`` reads there too: next test.)"""
    assert _read(name, _cell(None)) is None
    old = {"counters": {"compile.cache_miss": 0, "survey.stages_run": 20,
                        "survey.lease_chip_s": 40.0,
                        "survey.lease_chip_s.sweep": 36.0,
                        "survey.pool_chip_s": 48.0},
           "events": {"survey.gang_decision": 12},
           "spans": {"survey.stage.sweep": [36.0, 4]},
           "stage_spans": [], "n_files": 1}
    bare = tmp_path / "fleet.jsonl"  # a lease without its chips, a torn line
    bare.write_text('{"type": "span", "name": "survey.lease", "dur": 1.0, '
                    '"attrs": {"stage": "sweep"}}\n'
                    '{"type": "span", "name": "survey.lease", "at')
    assert _read(name, _cell(old, path=str(bare))) is None
    assert _read(name, _cell(old, path=str(tmp_path / "none"))) is None


def _leases(path, *steps):
    """One file per step; each step a list of (stage, chips) leases."""
    paths = []
    for i, leases in enumerate(steps):
        p = path / f"step{i}.jsonl"
        p.write_text("".join(json.dumps(
            {"type": "span", "name": "survey.lease", "dur": 1.0,
             "attrs": {"stage": stage, "k": len(chips), "chips": chips}})
            + "\n" for stage, chips in leases))
        paths.append(str(p))
    return paths


def test_fleet_lanes_used_is_the_narrowest_step_of_the_window(tmp_path):
    """Four sweeps on four chips in one step; in the next a leader took
    two neighbours' sweeps onto its chip (two sweep leases, two chips):
    the window reads 2. Mask and fold leases do not count."""
    wide = [("mask", [n]) for n in range(4)] + [
        ("sweep", [n]) for n in range(4)]
    narrow = [("mask", [n]) for n in range(4)] + [
        ("sweep", [0]), ("sweep", [3]), ("fold", [1]), ("fold", [2])]
    paths = iter(_leases(tmp_path, wide, narrow))
    cell = _cell({"counters": {}}, steps=2)
    cell.entry.telemetry_files = lambda step: [next(paths)]
    assert _read("fleet_lanes_used", cell) == 2.0
    # a gang's one lease over four chips is four chips that swept
    (gang,) = _leases(tmp_path, [("sweep", [0, 1, 2, 3])])
    assert _read("fleet_lanes_used",
                 _cell({"counters": {}}, steps=1, path=gang)) == 4.0


def test_a_chip_no_lease_fell_on_reads_zero():
    tlm = {"counters": {"survey.pool_chip_s": 40.0,
                        "survey.lease_chip_s.chip0": 9.0,
                        "survey.lease_chip_s.chip1": 5.0,
                        "survey.lease_chip_s.chip3": 8.0},
           "spans": {}, "events": {}, "stage_spans": [], "n_files": 1}
    assert _read("chip_lease_min_pct", _cell(tlm)) == 0.0
    tlm["counters"]["survey.lease_chip_s.chip2"] = 2.5
    assert _read("chip_lease_min_pct", _cell(tlm)) == pytest.approx(25.0)


def _rehearse(seed, trace, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload",
         "rehearsal.fleet4", "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc


@pytest.mark.parametrize("seed", [3000000026, 17])
def test_rehearsal_fleet_cell_end_to_end(seed):
    """The new cell's files off the chip: four toy beams through one
    ``cli.survey.main`` call, every beam compared with its own float64
    reference under the search cell's limits, no beam missing, no step of
    the window compiling, every beam's sweep on a chip of its own."""
    result, proc = _rehearse(seed, 1)
    assert result["correct"] is True and result["failed"] == 0
    assert "check_ran" not in result["compared"]
    assert result["compared"]["fallbacks"]["value"] == 0
    assert result["compared"]["beams_missing"] == {"value": 0.0, "limit": 0.0}
    assert result["device"]["count"] == 4
    metrics = result["metrics"]
    assert set(READERS) <= set(metrics), sorted(metrics)
    assert metrics["fleet_lanes_used"]["value"] == 4
    assert metrics["compiles_in_window"]["value"] == 0
    assert 0 < metrics["chip_lease_min_pct"]["value"] <= 100
    assert metrics["obs_wall_s"]["value"] > 0
    assert "device_idle_pct" in metrics
    # one step is the four beams' sky, and all four were recovered
    assert "one step is 16.7772 s of sky" in proc.stdout  # 4 x 2^16 x 64 us
    assert proc.stdout.count("recovered: best of") >= 4
    for b in range(4):
        assert f"beam{b}_s{seed + b}" in proc.stdout


def test_rehearsal_fleet_cell_untraced_and_its_control():
    """Untraced: the end-to-end metrics alone. And the control (the
    reference in bfloat16 in the program's place, beam by beam) reads
    over the limits."""
    result, proc = _rehearse(3000000027, 0, "--control", "1")
    assert result["correct"] is True
    assert {"sky_s_per_s", "setup_s"} <= set(result["metrics"])
    assert not set(READERS) & set(result["metrics"])
    control = {}
    for line in proc.stdout.splitlines():
        if line.startswith("control bfloat16 "):
            name, rest = line[len("control bfloat16 "):].split(": ", 1)
            value, limit = rest.split(" (limit ")
            control[name] = (float(value), float(limit.rstrip(")")))
    assert control["beams_missing"] == (0.0, 0.0)
    over = {n for n, (v, lim) in control.items() if v > lim}
    assert {"dat_series", "snr_abs", "mask_stats"} <= over, control


_AS_THE_PARENT = """
import sys
sys.path[:0] = [{bench!r}, {root!r}]
from pypulsar_tpu.compile import plane

plane._chips_share_entries = lambda: False  # the parent: a key a chip
import run_cell
sys.exit(run_cell.main(["--workload", "rehearsal.fleet4", "--seed", "5",
                        "--seconds", "2", "--trace", sys.argv[1]]))
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_entry_refuses_a_program_that_compiles_again_on_every_chip(trace):
    """What the parent commit does with these files laid over it (the
    driver's check read its six runs 11% apart: steps of its window
    compile, at random): a one-line reason and exit code 2 within the
    set-up, before any input is made, traced and untraced."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _AS_THE_PARENT.format(bench=BENCH, root=ROOT),
         str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    refused = [line for line in proc.stderr.splitlines()
               if line.startswith("refused: ")]
    assert len(refused) == 1 and "compile.cache_miss" in refused[0]
    assert "input:" not in proc.stdout and "Traceback" not in proc.stderr


def test_a_missing_beam_fails_the_step_and_is_counted(tmp_path):
    from entries import survey_fleet

    cell = types.SimpleNamespace(
        infiles=[str(tmp_path / f"beam{b}_s{5 + b}.fil") for b in range(4)],
        wl={"argv": ["{infiles}", "-o", "{outdir}", "--numdms",
                     "{dm_trials}"]}, cfg={"dm_trials": 64})
    seen = []

    def fake_main(argv):
        seen.append(argv)
        for b in (0, 1, 3):  # beam 2 writes nothing
            open(survey_fleet._snr_json(cell, b, str(tmp_path)), "w").close()
        return 0

    held, survey_fleet.survey.cli_main = survey_fleet.survey.cli_main, fake_main
    try:
        assert survey_fleet.run(cell, str(tmp_path), telemetry=True) == 1
    finally:
        survey_fleet.survey.cli_main = held
    assert seen == [cell.infiles + ["-o", str(tmp_path), "--numdms", "64",
                                    "--telemetry-dir",
                                    str(tmp_path / "tlm")]]


def test_fleet_workload_is_the_gang_cell_but_for_the_fleet():
    a, g, s = (json.load(open(os.path.join(BENCH, "workloads", n + ".json")))
               for n in ("htru-hilat.fleet4", "htru-hilat.gang4",
                         "htru-hilat.search"))
    # the search cell's comparisons and limits, a beam at a time, plus the
    # exact count of beams that wrote nothing
    limits = dict(a["check"]["limits"])
    assert limits.pop("beams_missing") == 0
    assert limits == s["check"]["limits"]
    assert {k: v for k, v in a["check"].items() if k != "limits"} == {
        k: v for k, v in s["check"].items() if k != "limits"}
    assert a["end_to_end"] == g["end_to_end"] == ["sky_s_per_s", "setup_s"]
    assert a["traffic"]["injection"] == g["traffic"]["injection"]
    assert a["traffic"]["rfi"] == g["traffic"]["rfi"]
    # the gang cell's argv, with every beam where its one input stands
    assert a["argv"] == ["{infiles}"] + g["argv"][1:]
    assert g["argv"][0] == "{infile}"
    assert (a["config"], a["entry"], a["chips"]) == (
        "htru-hilat-fleet4", "survey_fleet", 4)
    assert a["per_layer"][:3] == g["per_layer"][:3]
    assert a["per_layer"][3:] == list(READERS)
    ca, cg = (json.load(open(os.path.join(BENCH, "configs", n + ".json")))
              for n in ("htru-hilat-fleet4", "htru-hilat-host4"))
    differ = {k for k in set(ca) | set(cg) if ca.get(k) != cg.get(k)}
    assert differ == {"name", "source", "deployment", "dm_trials", "beams",
                      "guarantees", "assumed", "reduced", "source_values"}
    assert (ca["dm_trials"], ca["beams"]) == (64, 4)
    assert ca["reduced"] == ["nsamp", "dm_trials", "beams"]
    assert set(ca["source_values"]) == set(ca["reduced"])
    assert len(ca["source"]) <= 200
    # 64 trials from 40 step 2 hold the injected DM 70 and end at 166
    assert ca["dm_lo"] + ca["dm_step"] * (ca["dm_trials"] - 1) == 166.0
    top = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cfg,) = [c for c in top["configs"] if c["name"] == "htru-hilat-fleet4"]
    assert cfg["source"] == ca["source"] and cfg["reduced"] == ca["reduced"]
    (wl,) = [w for w in top["workloads"] if w["name"] == "htru-hilat.fleet4"]
    assert wl["why"] == a["why"] and wl["chips"] == 4
    assert top["workloads"][-1] is wl and top["configs"][-1] is cfg
    assert [m["name"] for m in top["per_layer"][-3:]] == list(READERS)
    for m in top["per_layer"][-3:]:
        assert m["workloads"] == ["htru-hilat.fleet4"]
        assert m["layer"] == "fleet scheduler"
        assert importlib.import_module(
            f"metrics.{m['name']}").UNIT == m["unit"]


def test_rehearsal_fleet_cell_is_files_only():
    """``test_harness.test_rehearsal_cell_is_files_only``'s rule for the
    cell this file brings: nothing outside tests/ names it."""
    names = ("rehearsal.fleet4", "rehearsal-fleet4")
    hits = []
    for dirpath, _dirs, files in os.walk(BENCH):
        if "work" in dirpath.split(os.sep) or "__pycache__" in dirpath:
            continue
        for fn in files:
            path = os.path.join(dirpath, fn)
            with open(path, errors="replace") as f:
                text = f.read()
            if any(n in text for n in names) and "tests" not in \
                    os.path.relpath(path, BENCH).split(os.sep):
                hits.append(path)
    assert hits == []
