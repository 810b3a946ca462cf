"""The gang-leased cell's files: the three lease readers on a recorded
JSONL (one toy ``survey --devices 4 --gang auto`` step on four virtual CPU
devices: the lease and stage spans, the gang decisions, the final counters
kept), on a run that predates the counters, and end to end through the
rehearsal cell ``rehearsal.gang4`` that lists them."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURE = os.path.join(BENCH, "tests", "fixtures", "lease_gang4.jsonl")
READERS = ("gang_chips_used", "lease_idle_pct", "sweep_chip_s_per_obs")


def _cell(telemetry, steps=2, path=FIXTURE):
    """A window of ``steps`` completed steps and a failed one, each with
    ``path`` as its telemetry file."""
    entry = types.SimpleNamespace(telemetry_files=lambda step: [path])
    return types.SimpleNamespace(
        telemetry=telemetry, entry=entry,
        steps=[{"rc": 0}] * steps + [{"rc": 1}])


def _read(name, cell):
    mod = importlib.import_module(f"metrics.{name}")
    assert isinstance(mod.UNIT, str) and " " not in mod.UNIT
    return mod.read(cell)


def test_readers_on_the_recorded_step():
    import trace_reduce

    tlm = trace_reduce.read_telemetry([FIXTURE, FIXTURE])  # two steps
    c = tlm["counters"]
    assert tlm["spans"]["survey.lease"][1] == 6  # mask, sweep, fold, twice
    cell = _cell(tlm)
    assert _read("gang_chips_used", cell) == 4.0
    assert _read("lease_idle_pct", cell) == pytest.approx(
        100.0 * (1.0 - c["survey.lease_chip_s"] / c["survey.pool_chip_s"]))
    assert _read("lease_idle_pct", cell) == pytest.approx(
        100.0 * (1.0 - 27.43124970898498 / 29.71271577605512))
    # leased chip-seconds over COMPLETED steps: the failed third is left out
    assert _read("sweep_chip_s_per_obs", cell) == pytest.approx(
        26.92163829598576)
    # four chips for the lease's wall, to the few lines between span and
    # counter
    with open(FIXTURE) as f:
        recs = [json.loads(line) for line in f]
    (lease,) = [r for r in recs if r.get("name") == "survey.lease"
                and r["attrs"]["stage"] == "sweep"]
    assert lease["attrs"]["k"] == 4
    assert 4 * lease["dur"] == pytest.approx(26.92163829598576, rel=0.01)


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_where_there_is_nothing_to_read(name, tmp_path):
    """An untraced run, a run of a program without the counters (the
    parent commit), a window with no completed step: None, no raise."""
    assert _read(name, _cell(None)) is None
    old = {"counters": {"compile.cache_miss": 0, "survey.stages_run": 5},
           "events": {"survey.gang_decision": 3},
           "spans": {"survey.stage.sweep": [1.0, 1]},
           "stage_spans": [], "n_files": 1}
    bare = tmp_path / "fleet.jsonl"  # decisions without their k, a torn line
    bare.write_text('{"type": "event", "name": "survey.gang_decision", '
                    '"attrs": {"stage": "sweep"}}\n'
                    '{"type": "event", "name": "survey.gang_decision", "at')
    assert _read(name, _cell(old, path=str(bare))) is None
    assert _read(name, _cell(old, path=str(tmp_path / "none"))) is None
    if name == "sweep_chip_s_per_obs":
        import trace_reduce

        tlm = trace_reduce.read_telemetry([FIXTURE])
        assert _read(name, _cell(tlm, steps=0)) is None


def _decisions(path, *ks):
    path.write_text("".join(json.dumps(
        {"type": "event", "name": "survey.gang_decision",
         "attrs": {"stage": "sweep", "k": k, "chips": list(range(k))}})
        + "\n" for k in ks))
    return str(path)


def test_one_chip_step_reads_one_chip_and_a_full_pool(tmp_path):
    one = {"counters": {"device0.dedisperse.chunks": 3,
                        "survey.lease_chip_s": 5.0,
                        "survey.lease_chip_s.sweep": 4.0,
                        "survey.pool_chip_s": 5.0},
           "events": {}, "spans": {}, "stage_spans": [], "n_files": 1}
    cell = _cell(one, path=_decisions(tmp_path / "fleet.jsonl", 1, 1, 1))
    assert _read("gang_chips_used", cell) == 1.0
    assert _read("lease_idle_pct", cell) == 0.0
    assert _read("sweep_chip_s_per_obs", cell) == 2.0


def test_gang_chips_used_is_the_narrowest_step_of_the_window(tmp_path):
    """The widest grant of a step is its gang; a step the scheduler ran
    on fewer chips (an eviction, a gang judged not worth it) shows."""
    wide = _decisions(tmp_path / "wide.jsonl", 1, 4, 1)
    narrow = _decisions(tmp_path / "narrow.jsonl", 1, 2, 1)
    paths = iter([wide, narrow])
    cell = _cell({"counters": {}}, steps=2)
    cell.entry.telemetry_files = lambda step: [next(paths)]
    assert _read("gang_chips_used", cell) == 2.0


def _rehearse(seed, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload",
         "rehearsal.gang4", "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc


@pytest.mark.parametrize("seed", [3000000026, 821159908, 17])
def test_rehearsal_gang_cell_end_to_end(seed):
    """The new cell's files off the chip: the entry's probe lets this
    program through, the gang runs, the accepted check holds every number
    of ``htru-hilat.search``'s check to the same limits."""
    result, proc = _rehearse(seed, 1)
    assert result["correct"] is True and result["failed"] == 0
    assert "check_ran" not in result["compared"]
    assert result["compared"]["fallbacks"]["value"] == 0
    assert result["device"]["count"] == 4
    metrics = result["metrics"]
    assert set(READERS) <= set(metrics), sorted(metrics)
    assert metrics["gang_chips_used"]["value"] == 4
    assert 0 <= metrics["lease_idle_pct"]["value"] < 100
    assert metrics["sweep_chip_s_per_obs"]["value"] > 0
    assert {"device_idle_pct", "compiles_in_window"} <= set(metrics)
    assert "gang x4 on chips [0, 1, 2, 3]" in proc.stdout


def test_rehearsal_gang_cell_untraced_reports_the_end_to_end_metrics():
    result, _ = _rehearse(3000000027, 0)
    assert result["correct"] is True
    assert {"sky_s_per_s", "setup_s"} <= set(result["metrics"])
    assert not set(READERS) & set(result["metrics"])


_AS_THE_PARENT = """
import sys
sys.path[:0] = [{bench!r}, {root!r}]
from pypulsar_tpu.compile import plane

keyed = plane._leaf_key


def parent_leaf_key(x):  # the parent commit's rule for a sharded batch
    if hasattr(x, "devices") and callable(x.devices) \\
            and len(x.devices()) != 1:
        raise plane._Unkeyable("multi-device input")
    return keyed(x)


plane._leaf_key = parent_leaf_key
import run_cell
sys.exit(run_cell.main(["--workload", "rehearsal.gang4", "--seed", "5",
                        "--seconds", "2", "--trace", sys.argv[1]]))
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_entry_refuses_a_program_whose_plane_cannot_key_a_sharded_batch(
        trace):
    """What the parent commit does with these files laid over it: a
    one-line reason and exit code 2 within the set-up, before any input
    is made, traced and untraced."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _AS_THE_PARENT.format(bench=BENCH, root=ROOT),
         str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    refused = [line for line in proc.stderr.splitlines()
               if line.startswith("refused: ")]
    assert len(refused) == 1 and "compile.aot_fallback" in refused[0]
    assert "input:" not in proc.stdout and "Traceback" not in proc.stderr


def test_entry_hands_on_the_survey_entry_unchanged():
    from entries import survey, survey_gang

    for name in ("run", "telemetry_files", "fallbacks", "work"):
        assert getattr(survey_gang, name) is getattr(survey, name), name
    # the check is entry ``survey``'s own, run with this entry's reference
    # and put back as it was, whatever the check does
    seen = []

    def fake_check(cell, control=None):
        seen.append(survey.Reference)
        raise RuntimeError("as a check that cannot run")

    held, kept = survey.check, survey.Reference
    survey.check = fake_check
    try:
        with pytest.raises(RuntimeError):
            survey_gang.check(None)
    finally:
        survey.check = held
    assert seen == [survey_gang.Reference]
    assert survey.Reference is kept
    assert issubclass(survey_gang.Reference, survey.Reference)


def test_reference_reaches_a_candidate_at_the_spectrum_end():
    """A candidate the search reports within a template's half-width of
    Nyquist: the accepted ``summed_power`` cannot slice its window there;
    this entry's spectrum goes on in zeros, as the search's own does, and
    gives the search's power. Away from the end nothing changes."""
    import numpy as np

    from entries import survey_gang
    from pypulsar_tpu.fourier.accelsearch import (AccelSearchConfig,
                                                  accel_search)
    from reference import accel

    n, zmax, dz = 1 << 13, 20.0, 2.0
    series = np.random.default_rng(7).standard_normal(n)
    ref = survey_gang.Reference.__new__(survey_gang.Reference)
    ref.cfg, ref.series, ref._spectra = {"zmax": zmax, "dz": dz}, [series], {}
    plain = accel.spectrum(series)
    padded = ref.spectrum(0)
    reach = accel.halfwidth(zmax) + 1
    assert len(padded) == len(plain) + reach
    assert np.array_equal(padded[:len(plain)], plain)
    assert not padded[len(plain):].any()
    T = n * 64e-6
    cands = accel_search(
        plain.astype(np.complex64), T,
        AccelSearchConfig(zmax=zmax, dz=dz, numharm=4, sigma_min=-8.0,
                          flo=(n // 2 - 300) / T / 4, seg_width=1 << 10))
    edge = [c for c in cands
            if c.r * c.numharm > len(plain) - accel.halfwidth(zmax)]
    inner = [c for c in cands
             if c.r * c.numharm < len(plain) - 2 * reach]
    assert edge and inner
    for c in edge[:6]:
        with pytest.raises(ValueError):
            accel.summed_power(plain, c.r, c.z, c.numharm, zmax, dz)
        want = ref.power(0, c.r, c.z, c.numharm)
        assert abs(c.power - want) / want < 1e-5
    for c in inner[:6]:
        assert ref.power(0, c.r, c.z, c.numharm) == accel.summed_power(
            plain, c.r, c.z, c.numharm, zmax, dz)


def test_gang_workload_is_the_search_cell_but_for_the_gang():
    a, b = (json.load(open(os.path.join(BENCH, "workloads", n + ".json")))
            for n in ("htru-hilat.gang4", "htru-hilat.search"))
    # the search cell's comparisons and limits, on a larger sample of the
    # trial grid: 12 of the 16 trials lie anywhere, so every chip's quarter
    # of the sharded sweep is compared (the search cell's four all lie
    # within 12 trials of the injection: chip 0's quarter)
    assert a["check"]["limits"] == b["check"]["limits"]
    assert {k: v for k, v in a["check"].items() if k != "sample_trials"} == {
        k: v for k, v in b["check"].items() if k != "sample_trials"}
    assert a["check"]["sample_trials"] == 16
    assert a["end_to_end"] == b["end_to_end"]
    assert a["traffic"]["injection"] == b["traffic"]["injection"]
    assert a["traffic"]["rfi"] == b["traffic"]["rfi"]
    assert a["traffic"]["loop"] == b["traffic"]["loop"]
    i = b["argv"].index("--devices")
    assert a["argv"] == (b["argv"][:i] + ["--devices", "4", "--gang", "auto"]
                         + b["argv"][i + 2:])
    assert (a["config"], a["entry"], a["chips"]) == (
        "htru-hilat-host4", "survey_gang", 4)
    ca, cb = (json.load(open(os.path.join(BENCH, "configs", n + ".json")))
              for n in ("htru-hilat-host4", "htru-hilat"))
    differ = {k for k in set(ca) | set(cb) if ca.get(k) != cb.get(k)}
    assert differ == {"name", "deployment", "dm_trials", "guarantees",
                      "assumed", "source_values"}
    assert ca["dm_trials"] == 256 and ca["reduced"] == ["nsamp", "dm_trials"]


def test_rehearsal_gang_cell_is_files_only():
    """``test_harness.test_rehearsal_cell_is_files_only``'s rule for the
    cell this file brings: nothing outside tests/ names it."""
    names = ("rehearsal.gang4", "rehearsal-gang4")
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
