"""The harness end to end on the CPU: the rehearsal cells (added as files
under tests/ alone), the device gate, the control and the planted faults."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _cli(*argv, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), *argv],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900)


def test_rehearsal_cell_is_files_only():
    """The rehearsal cells are one workload file and one configuration
    file each, under tests/; no code or data of the benchmark names them
    (README.md shows how to run one)."""
    names = ("rehearsal.stream", "rehearsal.search", "rehearsal-chain")
    hits = []
    for dirpath, _dirs, files in os.walk(BENCH):
        if "work" in dirpath.split(os.sep) or "__pycache__" in dirpath:
            continue
        for fn in files:
            path = os.path.join(dirpath, fn)
            if fn == "README.md":
                continue
            with open(path, errors="replace") as f:
                text = f.read()
            if any(n in text for n in names) and "tests" not in \
                    os.path.relpath(path, BENCH).split(os.sep):
                hits.append(path)
    assert hits == []
    listed = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert not any(w["name"].startswith("rehearsal")
                   for w in listed["workloads"])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_stream_as_the_driver_runs_it(trace):
    proc = _cli("--workload", "rehearsal.stream", "--seed", "3000000001",
                "--seconds", "2", "--trace", str(trace))
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"  # named, never a device's
    want = ({"device_idle_pct", "compiles_in_window", "batch_median_s"}
            if trace else {"sky_s_per_s", "batch_p95_s", "setup_s"})
    assert want <= set(result["metrics"])
    # a roofline share needs a chip's peaks: off the chip it stays silent
    assert "kernel_roofline_pct" not in result["metrics"]
    if trace:
        assert result["device"]["busy_s"] > 0
        assert result["compared"]["fallbacks"]["value"] == 0
        assert len(result["breakdown"]["device_ops"]) <= 10
    for line in proc.stderr.strip().splitlines()[-2:]:
        assert line.startswith("compared ")


@pytest.mark.parametrize("cell", ["apertif-rt.stream", "htru-hilat.search"])
def test_listed_cell_refuses_the_cpu(cell):
    proc = _cli("--workload", cell, "--seed", "1", "--seconds", "1")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_unknown_device_kind_is_refused(monkeypatch):
    import run_cell

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    import jax

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(run_cell.Refused, match="no row"):
        run_cell.device_gate(1, rehearsal=False)


def test_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and benchmark/: no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("work", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run_cell.py", "--workload",
         "rehearsal.stream", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# -- the control: the reference one precision down has to fail ---------------


def _cell(name, seed, run_cell):
    wl, cfg, rehearsal = run_cell.load_cell(name)
    cell = run_cell.Cell(name, wl, cfg, seed, False, rehearsal)
    return cell


@pytest.mark.parametrize("seed", [11, 3000000007, 2147483653])
def test_control_fails_the_sweep_cell(seed):
    import importlib

    import run_cell

    cell = _cell("rehearsal.stream", seed, run_cell)
    entry = importlib.import_module("entries.sweep")
    os.makedirs(cell.workdir, exist_ok=True)
    entry.prepare(cell)
    numbers = entry.check(cell, control=cell.wl["check"]["control"])
    over = [n for n, v, lim in numbers if not v <= lim]
    assert "snr_abs" in over, numbers


def test_control_fails_the_chain_cell(run_cell_main):
    """One run of the chain, then the control in the program's place."""
    import importlib

    import run_cell

    rc, result = run_cell_main("--workload", "rehearsal.search", "--seed",
                               "3000000002", "--seconds", "1")
    assert rc == 0 and result["correct"] is True, result
    cell = _cell("rehearsal.search", 3000000002, run_cell)
    entry = importlib.import_module("entries.survey")
    entry.prepare(cell)
    cell.steps = [{"rc": 0, "outdir": os.path.join(
        cell.workdir, "out", "step0000")}]
    # prepare() rewrote the same input from the same seed
    numbers = dict((n, (v, lim)) for n, v, lim in
                   entry.check(cell, control="bfloat16"))
    # at 64 channels of 2-bit samples every channel sum is a small whole
    # number that bfloat16 holds exactly, so the series, and the spectra
    # and profiles made from them, read 0 here; at the cells' 1024 channels
    # they do not (PERF.md gives the control's readings at full size)
    for name in ("snr_abs", "mask_stats"):
        v, lim = numbers[name]
        assert v > 3 * lim, (name, v, lim)


# -- planted faults: the rest of a run sees ``correct`` come out false -------


def _break_cands(path, how):
    lines = open(path).read().splitlines(keepends=True)
    head = [ln for ln in lines if ln.startswith("#")]
    rows = [ln for ln in lines if not ln.startswith("#")]
    if how == "half_left_out":
        # the half of the DM grid that holds the injected pulsar (DM 70)
        # was never searched
        rows = [ln for ln in rows if float(ln.split()[0]) < 70.0]
    elif how == "answer_altered":
        p = rows[len(rows) // 2].split()
        p[1] = f"{float(p[1]) + 0.05:.3f}"
        rows[len(rows) // 2] = " ".join(p) + "\n"
    with open(path, "w") as f:
        f.writelines(head + rows)


@pytest.mark.parametrize("how", ["half_left_out", "answer_altered"])
def test_sweep_fault_is_caught(how, run_cell_main, monkeypatch):
    from entries import sweep as entry

    real = entry.cli_main

    def broken(argv):
        rc = real(argv)
        _break_cands(argv[argv.index("-o") + 1] + ".cands", how)
        return rc

    monkeypatch.setattr(entry, "cli_main", broken)
    rc, result = run_cell_main("--workload", "rehearsal.stream", "--seed",
                               "3000000003", "--seconds", "1")
    assert rc == 0 and result["correct"] is False, result
    bad = {n for n, c in result["compared"].items()
           if not c["value"] <= c["limit"]}
    assert bad & {"rows_off", "snr_abs"}


def _break_chain(outdir, how):
    import glob

    if how == "half_left_out":
        _break_cands(glob.glob(os.path.join(outdir, "*.cands"))[0], how)
    elif how == "series_altered":
        for path in glob.glob(os.path.join(outdir, "*_DM*.dat")):
            d = np.fromfile(path, "<f4")
            d[len(d) // 3] += 1.0
            d.tofile(path)
    elif how == "profile_altered":
        for path in glob.glob(os.path.join(outdir, "*_cand000*.pfd")):
            raw = bytearray(open(path, "rb").read())
            # the last doubles of the file are stats; profiles sit before
            off = len(raw) - 32 * 7 * 8 - 64 * 8
            val = np.frombuffer(raw[off:off + 8], "<f8")[0]
            raw[off:off + 8] = np.float64(val * 1.01 + 1.0).tobytes()
            open(path, "wb").write(bytes(raw))
    elif how == "candidate_altered":
        for path in glob.glob(os.path.join(outdir, "*_ACCEL_*.cand")):
            from reference import accel

            recs = accel.read_cands(path).copy()
            if len(recs):
                recs["pow"][0] *= 1.01
                recs.tofile(path)


@pytest.mark.parametrize("how", ["half_left_out", "series_altered",
                                 "profile_altered", "candidate_altered"])
def test_chain_fault_is_caught(how, run_cell_main, monkeypatch):
    from entries import survey as entry

    real = entry.cli_main

    def broken(argv):
        rc = real(argv)
        _break_chain(argv[argv.index("-o") + 1], how)
        return rc

    monkeypatch.setattr(entry, "cli_main", broken)
    rc, result = run_cell_main("--workload", "rehearsal.search", "--seed",
                               "3000000004", "--seconds", "1")
    assert rc == 0 and result["correct"] is False, result


def test_benchmark_json_agrees_with_the_files():
    """Every listed configuration, cell and metric has its file, and each
    cell's own metric lists are the ones ``BENCHMARK.json`` implies."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = json.load(f)
    for c in listed["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(k in cfg["source_values"] for k in c["reduced"])
    for kind in ("end_to_end", "per_layer"):
        for m in listed[kind]:
            assert os.path.exists(os.path.join(
                BENCH, "metrics", m["name"] + ".py")), m["name"]
    for w in listed["workloads"]:
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            wl = json.load(f)
        assert (wl["config"], wl["chips"], wl["why"]) == \
            (w["config"], w["chips"], w["why"])
        for kind in ("end_to_end", "per_layer"):
            want = [m["name"] for m in listed[kind]
                    if w["name"] in m.get("workloads", [w["name"]])]
            assert sorted(wl[kind]) == sorted(want), (w["name"], kind)
