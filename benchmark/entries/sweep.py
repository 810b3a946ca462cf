"""Entry ``sweep``: ``pypulsar_tpu.cli.sweep.main`` over one batch file per
step — DM sweep and boxcar single-pulse detection, nothing downstream."""

from __future__ import annotations

import os

import numpy as np

import counts
from entries import common
from reference import compare, dedisp, sigproc


def cli_main(argv):
    from pypulsar_tpu.cli import sweep

    return sweep.main(argv)


def prepare(cell) -> None:
    common.make_input(cell)


def run(cell, outdir: str, telemetry: bool = False) -> int:
    outbase = os.path.join(outdir, "batch")
    argv = common.fill(cell.wl["argv"], infile=cell.infile, outbase=outbase,
                       **cell.cfg)
    if telemetry:
        argv += ["--telemetry", os.path.join(outdir, "tlm.jsonl")]
    rc = cli_main(argv)
    if not os.path.exists(outbase + ".cands"):
        return rc or 1
    return rc


def telemetry_files(step) -> list:
    return [os.path.join(step["outdir"], "tlm.jsonl")]


fallbacks = common.fallbacks


def _grid(cfg):
    return cfg["dm_lo"] + cfg["dm_step"] * np.arange(cfg["dm_trials"])


def check(cell, control=None) -> list:
    """Every step's ``.cands`` against the float64 detection of a sample of
    trials drawn from the seed (read and unpack, dedispersion, boxcar)."""
    cfg, chk = cell.cfg, cell.wl["check"]
    fil = sigproc.Filterbank(cell.infile)
    dms = _grid(cfg)
    plan = dedisp.Plan(dms, fil.freqs, fil.tsamp, nsub=cfg["nsub"],
                       widths=tuple(cfg["widths"]), chunk=cfg.get("chunk"))
    inj = int(round((cell.injected["dm"] - cfg["dm_lo"]) / cfg["dm_step"]))
    trials = compare.sample_trials(cell.seed, len(dms), inj,
                                   chk["sample_trials"])
    dtype = compare.lower_dtype(control)
    if dtype is not None:
        tables = [compare.rows_from_detection(
            dedisp.detect(fil, plan, trials, dtype=dtype), dms, trials,
            cfg["threshold"])]
    else:
        tables = [compare.parse_cands(
            os.path.join(s["outdir"], "batch.cands"))
            for s in cell.steps if not s["rc"]]
    numbers = compare.sweep_rows(dedisp.detect(fil, plan, trials), plan,
                                 trials, tables, cfg["threshold"],
                                 chk["limits"])
    return [(name, v, float(chk["limits"].get(name, 0.0)))
            for name, v in numbers.items()]


def work(cell) -> dict:
    """Least work of one step, by stage (``counts.py``)."""
    cfg = cell.cfg
    n = cell.injected["nsamp"]
    return {
        "dedispersion": counts.dedispersion(
            nchan=cfg["nchan"], nsamp=n, nbits=cfg["nbits"],
            trials=cfg["dm_trials"], keep_series=False),
        "boxcar": counts.boxcar(nsamp=n, trials=cfg["dm_trials"],
                                widths=len(cfg["widths"])),
    }
