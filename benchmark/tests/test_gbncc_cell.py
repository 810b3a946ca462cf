"""The GBNCC cell's files: its CPU rehearsal ``rehearsal.gbncc`` (many
channels at 350 MHz, DM step 0.01, the millisecond injection; files under
``tests/`` alone) end to end with the control over its limits and one
planted fault, and the two readers that came with the cell on a recorded
traced chip run (``fixtures/gbncc_search_v5e.json``: the counters and the
device seconds by program of one ``gbncc-350.search`` window)."""

import importlib
import json
import os
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURE = os.path.join(BENCH, "tests", "fixtures", "gbncc_search_v5e.json")
SEED = 3000000034


def _read(name, cell):
    mod = importlib.import_module(f"metrics.{name}")
    assert isinstance(mod.UNIT, str) and " " not in mod.UNIT
    return mod.read(cell)


def _recorded(steps=None):
    with open(FIXTURE) as f:
        rec = json.load(f)
    with open(os.path.join(BENCH, "configs", "gbncc-350.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"][rec["device_kind"]]
    n = rec["steps"] if steps is None else steps
    cell = types.SimpleNamespace(
        cfg=cfg, peaks=peaks, injected={"nsamp": rec["nsamp"]},
        steps=[{"rc": 0}] * n + [{"rc": 1}],
        telemetry={"counters": rec["counters"], "events": {}, "spans": {}},
        trace_summary={"busy_s": rec["busy_s"], "window_s": rec["window_s"],
                       "program_seconds": rec["program_seconds"]})
    return rec, cell


def test_readers_on_the_recorded_window():
    import counts

    rec, cell = _recorded()
    c = rec["counters"]
    assert _read("chunk_payload_pct", cell) == pytest.approx(
        100.0 * c["sweep.payload_samples"] / c["sweep.chunk_samples"])
    # every step: 262144 samples of sky in 5 transforms of 2^16
    assert c["sweep.chunk_samples"] == rec["steps"] * 5 * 65536
    assert _read("chunk_payload_pct", cell) == pytest.approx(80.0)
    # the share by hand: bytes govern (1.07 GB of input at a byte a sample
    # and 32 float32 series of 2^18 samples, at 819 GB/s), the boxcar's and
    # the tree's operations are microseconds at 197 Tflop/s
    n, peaks = rec["nsamp"], cell.peaks
    dd = (n * 4096 + 4.0 * n * 32) / peaks["hbm_bytes_per_s"]
    box = 2.0 * n * 32 * 6 / peaks["flops_per_s"]
    device_s = (rec["program_seconds"]["jit__sweep_chunk_jit"]
                + rec["program_seconds"]["jit__dedisperse_series_jit"])
    want = 100.0 * rec["steps"] * (dd + box) / device_s
    got = _read("dedisp_roofline_pct", cell)
    assert got == pytest.approx(want, rel=1e-9)
    assert 0.0 < got < 100.0
    # and both are what that run's result line held
    for name in ("dedisp_roofline_pct", "chunk_payload_pct"):
        assert _read(name, cell) == pytest.approx(rec["reported"][name])
    least, _ = counts.least_seconds(
        {"d": counts.dedispersion(nchan=4096, nsamp=n, nbits=8, trials=32,
                                  keep_series=True),
         "b": counts.boxcar(nsamp=n, trials=32, widths=6)}, peaks)
    assert least == pytest.approx(dd + box)


@pytest.mark.parametrize("name", ["chunk_payload_pct",
                                  "dedisp_roofline_pct"])
def test_readers_are_silent_where_there_is_nothing_to_read(name):
    """An untraced run, a program without the counter (the parent
    commit), a trace without the chunk programs, a run off the chip:
    None, never a 0, no raise."""
    _, cell = _recorded()
    cell.telemetry, cell.trace_summary = None, None
    assert _read(name, cell) is None
    _, cell = _recorded()
    del cell.telemetry["counters"]["sweep.chunk_samples"]
    cell.trace_summary["program_seconds"] = {"jit_accel_stage_batch": 3.0}
    assert _read(name, cell) is None
    _, cell = _recorded()
    cell.peaks = None
    if name == "dedisp_roofline_pct":
        assert _read(name, cell) is None


def test_rehearsal_is_files_only_and_of_the_cell_s_shape():
    names = ("rehearsal.gbncc", "rehearsal-gbncc")
    hits = []
    for dirpath, _dirs, files in os.walk(BENCH):
        parts = os.path.relpath(dirpath, BENCH).split(os.sep)
        if "work" in parts or "__pycache__" in parts or "tests" in parts:
            continue
        for fn in files:
            with open(os.path.join(dirpath, fn), errors="replace") as f:
                if any(n in f.read() for n in names):
                    hits.append(os.path.join(dirpath, fn))
    assert hits == []
    with open(os.path.join(BENCH, "workloads", "gbncc-350.search.json")) as f:
        cell = json.load(f)
    with open(os.path.join(BENCH, "tests", "workloads",
                           "rehearsal.gbncc.json")) as f:
        toy = json.load(f)
    for key in ("entry", "argv", "check", "end_to_end", "per_layer"):
        assert toy[key] == cell[key], key
    assert toy["traffic"]["injection"] == cell["traffic"]["injection"]
    with open(os.path.join(BENCH, "configs", "gbncc-350.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "tests", "configs",
                           "rehearsal-gbncc.json")) as f:
        toy_cfg = json.load(f)
    for key in ("bw", "tsamp", "nbits", "dm_step", "nsub", "widths",
                "numharm"):
        assert toy_cfg[key] == cfg[key], key
    # the injected trial sits on the grid, where the search has to put it
    for c in (cfg, toy_cfg):
        k = round((30.0 - c["dm_lo"]) / c["dm_step"])
        assert 0 < k < c["dm_trials"] - 1
        assert f"{c['dm_lo'] + c['dm_step'] * k:.2f}" == "30.00"


def test_entry_refuses_a_program_without_the_planner(monkeypatch, capfd):
    """The parent of PR 34: exit code 2 at once, before any input is made
    (with entry ``survey`` it printed a result with no step completed
    after 7.5 minutes on the chip, exit code 0)."""
    import sys

    import run_cell
    from pypulsar_tpu import plan

    monkeypatch.delattr(plan, "lengths", raising=False)
    monkeypatch.setitem(sys.modules, "pypulsar_tpu.plan.lengths", None)
    with pytest.raises(SystemExit) as e:
        run_cell.main(["--workload", "rehearsal.gbncc", "--seed", "7",
                       "--seconds", "1"])
    assert e.value.code == 2
    out, err = capfd.readouterr()
    assert "refused: this program plans no block or chunk length" in err
    assert "input:" not in out and not out.strip().endswith("}")
    assert not any(fn.endswith(".fil") for fn in os.listdir(
        os.path.join(BENCH, "work", "rehearsal.gbncc")))


def _run(run_cell_main, *extra):
    return run_cell_main("--workload", "rehearsal.gbncc", "--seed",
                         str(SEED), "--seconds", "1", *extra)


def test_rehearsal_as_the_driver_runs_it_then_the_control(run_cell_main):
    import run_cell

    rc, result = _run(run_cell_main, "--trace", "1")
    assert rc == 0 and result["correct"] is True, result
    assert result["compared"]["not_recovered"]["value"] == 0
    assert result["compared"]["fallbacks"]["value"] == 0
    m = result["metrics"]
    assert {"device_idle_pct", "compiles_in_window",
            "chunk_payload_pct"} <= set(m)
    assert 0 < m["chunk_payload_pct"]["value"] <= 100
    # a roofline share needs a chip's peaks: off the chip both stay silent
    assert "dedisp_roofline_pct" not in m
    assert "kernel_roofline_pct" not in m
    # the control, in the program's place for that step
    wl, cfg, rehearsal = run_cell.load_cell("rehearsal.gbncc")
    cell = run_cell.Cell("rehearsal.gbncc", wl, cfg, SEED, False, rehearsal)
    entry = importlib.import_module("entries.survey")
    entry.prepare(cell)  # the same input from the same seed
    cell.steps = [{"rc": 0, "outdir": os.path.join(
        cell.workdir, "out", "step0000")}]
    numbers = {n: (v, lim) for n, v, lim in
               entry.check(cell, control=wl["check"]["control"])}
    for name in ("mask_stats", "dat_series", "snr_abs", "accel_power",
                 "fold_profile"):
        v, lim = numbers[name]
        assert v > 3 * lim, (name, v, lim)


def test_the_neighbouring_trial_made_the_stronger_is_caught(
        run_cell_main, monkeypatch):
    """Sift's list with its best candidate moved one trial up: 30.01 is
    within no tolerance of 30.00 on a grid of step 0.01."""
    from entries import survey as entry

    real = entry.cli_main

    def broken(argv):
        rc = real(argv)
        outdir = argv[argv.index("-o") + 1]
        (path,) = [os.path.join(outdir, fn) for fn in os.listdir(outdir)
                   if fn.endswith(".accelcands")]
        lines = open(path).read().splitlines(keepends=True)
        k = next(i for i, ln in enumerate(lines)
                 if ln.strip() and ln[0] not in "# ")
        assert " 30.00 " in lines[k]
        lines[k] = lines[k].replace(" 30.00 ", " 30.01 ", 1)
        with open(path, "w") as f:
            f.writelines(lines)
        return rc

    monkeypatch.setattr(entry, "cli_main", broken)
    rc, result = _run(run_cell_main)
    assert rc == 0 and result["correct"] is False, result
    over = {n for n, c in result["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over == {"not_recovered"}, result["compared"]
