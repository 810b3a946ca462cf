"""The sweep CLI's clean-up of a killed run's tmp debris: a run marks
its output base while tmps can be staged, so a clean run tests one name
whatever the directory holds, and the run after a kill removes exactly
the derived names one ``os.path.exists`` a name removed. The
``sweep.plan`` span says what it tested and removed."""

import glob
import json
import os
from types import SimpleNamespace

import pytest

from pypulsar_tpu.cli import sweep as cli_sweep
from pypulsar_tpu.resilience import faultinject

from tests.test_accel_pipeline import HANDOFF_ARGS, SWEEP_ARGS, _pulsar_fil

ARGS = SimpleNamespace(accel_zmax=20.0)
DMS = [70.0, 70.25, 71.5]


def _tree(root):
    """Every entry under ``root``, relative, dangling symlinks included."""
    out = set()
    for dp, dns, fns in os.walk(root):
        out.update(os.path.relpath(os.path.join(dp, n), root)
                   for n in dns + fns)
    return out


def _tmps(base):
    return [base + ".dat.tmp", base + ".inf.tmp",
            base + "_ACCEL_20.cand.tmp", base + "_ACCEL_20.txtcand.tmp"]


def _output_tmps(outbase, dms=DMS):
    return cli_sweep._remove_stale_output_tmps(outbase, dms, ARGS)


# (files made, symlinks made (name, target), the clean-up, names that
# must go, names that must stay)
CASES = {
    "each_kind": (
        _tmps("out/b_DM70.00") + _tmps("out/b_DM71.50")[:2]
        + ["out/b_DM70.25_ACCEL_20.txtcand.tmp", "out/b_DM70.00.dat",
           "out/b.cands"],
        [], lambda: _output_tmps("out/b"),
        _tmps("out/b_DM70.00") + _tmps("out/b_DM71.50")[:2]
        + ["out/b_DM70.25_ACCEL_20.txtcand.tmp"],
        ["out/b_DM70.00.dat", "out/b.cands"]),
    "look_alikes": (
        ["out/b_DM70.00.dat", "out/b_DM70.00.dat.tmp.keep",
         "out/b_DM70.001.dat.tmp", "out/c_DM70.00.dat.tmp",
         "out/b_DM70.00_ACCEL_50.cand.tmp", "out/b_DM70.25.inf.tmp"],
        [], lambda: _output_tmps("out/b"),
        ["out/b_DM70.25.inf.tmp"],
        ["out/b_DM70.00.dat", "out/b_DM70.00.dat.tmp.keep",
         "out/b_DM70.001.dat.tmp", "out/c_DM70.00.dat.tmp",
         "out/b_DM70.00_ACCEL_50.cand.tmp"]),
    "no_dir_part": (
        _tmps("b_DM70.25") + ["b_DM70.25.dat", "out/b_DM70.25.dat.tmp"],
        [], lambda: _output_tmps("b"),
        _tmps("b_DM70.25"), ["b_DM70.25.dat", "out/b_DM70.25.dat.tmp"]),
    "missing_dir": (
        ["out/b_DM70.00.dat.tmp"], [], lambda: _output_tmps("gone/b"),
        [], ["out/b_DM70.00.dat.tmp"]),
    "dangling_symlink": (
        ["out/b_DM70.25.inf.tmp"],
        [("out/b_DM70.00.dat.tmp", "nowhere"),
         ("out/b_DM71.50.dat.tmp", "b_DM70.25.inf.tmp")],
        lambda: _output_tmps("out/b"),
        # the second link dangles once its target, an earlier name, went
        ["out/b_DM70.25.inf.tmp"],
        ["out/b_DM70.00.dat.tmp", "out/b_DM71.50.dat.tmp"]),
    "checkpoints": (
        ["ck/run", "ck/run.tmp.npz", "ck/run.step0.npz",
         "ck/run.step3.done.npz.tmp.npz", "ck/run.step255.done.npz",
         "ck/run.step256.npz", "ck/run.step3.npz.bak", "ck/other.step0.npz"],
        [], lambda: cli_sweep._remove_stale_checkpoints("ck/run"),
        ["ck/run", "ck/run.tmp.npz", "ck/run.step0.npz",
         "ck/run.step3.done.npz.tmp.npz", "ck/run.step255.done.npz"],
        ["ck/run.step256.npz", "ck/run.step3.npz.bak",
         "ck/other.step0.npz"]),
}


def _make(root, files, links):
    for fn in files:
        path = os.path.join(root, fn)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(fn)
    for fn, target in links:
        path = os.path.join(root, fn)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        os.symlink(target, path)


@pytest.mark.parametrize("case", sorted(CASES))
def test_clean_up_removes_exactly_the_derived_names(case, tmp_path,
                                                     monkeypatch):
    files, links, clean, gone, kept = CASES[case]
    _make(str(tmp_path), files, links)
    before = _tree(tmp_path)
    monkeypatch.chdir(tmp_path)
    counts = clean()
    after = _tree(tmp_path)
    assert before - after == set(gone)
    assert set(kept) <= after
    if counts is not None:  # the output clean-up reports its counts
        assert counts[1] == len(gone)


@pytest.mark.parametrize("n_others", [0, 5000])
def test_clean_run_tests_one_name_whatever_the_directory_holds(
        n_others, tmp_path, monkeypatch):
    """At 1024 trials (4096 derived names) in a directory holding other
    observations' artifacts, a run with no marker tests the marker alone
    and lays it down; a run that finds it (its predecessor was killed)
    tests every derived name and removes the debris."""
    monkeypatch.chdir(tmp_path)
    dms = [0.25 * i for i in range(1024)]
    debris = ["out/b_DM0.25.dat.tmp", "out/b_DM100.00.inf.tmp",
              "out/b_DM255.75_ACCEL_20.txtcand.tmp"]
    others = [f"out/o{i}_DM0.25.dat" for i in range(n_others)]
    _make(str(tmp_path), debris + others + ["out/b.cands"], [])
    calls = []
    real_exists = os.path.exists

    def exists(path):
        calls.append(path)
        return real_exists(path)

    monkeypatch.setattr(os.path, "exists", exists)
    marker = "out/b" + cli_sweep.RUN_MARKER
    assert cli_sweep._clear_killed_run("out/b", dms, ARGS) == (0, 0)
    assert calls == [marker]
    assert real_exists(marker)
    calls.clear()
    assert cli_sweep._clear_killed_run("out/b", dms, ARGS) == (4096, 3)
    assert len(calls) == 1 + 4096
    assert glob.glob("out/*.tmp") == []


def test_rerun_after_kill_clears_debris_and_counts_it(tmp_path, monkeypatch):
    """A run killed between .dat appends leaves .dat.tmp staging files and
    its marker; a plain rerun removes them before it reads, and its
    ``sweep.plan`` span's ``removed`` is that debris count."""
    monkeypatch.chdir(tmp_path)
    fil = _pulsar_fil(tmp_path)
    argv = [fil, "-o", "t", *SWEEP_ARGS, *HANDOFF_ARGS, "--chunk", "4096",
            "--write-dats"]
    with pytest.raises(faultinject.InjectedKill):
        cli_sweep.main(argv + ["--fault-inject", "kill:dats.append:2"])
    faultinject.reset()
    debris = [f for f in os.listdir(".") if f.endswith(".tmp")]
    assert debris and all(f.startswith("t_DM") for f in debris)
    marker = "t" + cli_sweep.RUN_MARKER
    assert os.path.exists(marker)  # the kill left it behind
    assert cli_sweep.main(argv + ["--telemetry", "tlm.jsonl"]) == 0
    assert glob.glob("*.tmp") == []
    assert not os.path.exists(marker)  # a run that ends drops it
    assert len(glob.glob("t_DM*.dat")) == 8
    # the second sweep.plan with n_trials is sweep_flat's (nchan, chunk)
    plans = [r["attrs"] for r in map(json.loads, open("tlm.jsonl"))
             if r.get("type") == "span" and r["name"] == "sweep.plan"
             and "listed" in r.get("attrs", {})]
    assert plans == [{"n_trials": 8, "listed": 4 * 8,
                      "removed": len(debris)}]
