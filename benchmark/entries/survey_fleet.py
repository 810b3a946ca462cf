"""Entry ``survey_fleet``: ``pypulsar_tpu.cli.survey.main`` over the
``beams`` files of one multibeam pointing per step, in ONE call with
``--devices <chips> --gang auto`` in the cell's argv — as many
observations in flight as the host has chips, one a chip.

Beam ``b`` is made from ``seed + b`` with the workload's injection and
interferer, so every beam has its own noise and the same pulsar to
recover. A step is complete when every beam's ``_snr.json`` is there. The
check is entry ``survey``'s, beam by beam: its ``Reference`` from that
beam's raw file (on the padded spectrum of entry ``survey_gang``), its
comparisons and limits, the trials drawn from ``seed + b``; the worst
number over beams and steps counts, plus the exact ``beams_missing``.
The beams' references are independent float64 NumPy, so they are computed
side by side in spawned worker processes that never import JAX.

Before any input is made, one question to the program (as entry
``survey_gang`` asks its own): is a one-chip program that the first chip
built found on the second, or compiled there again? Where every chip
compiles for itself, the one warm-up step the harness gives warms only
the chips its leases fell on, and the leases of the window fall elsewhere:
steps of the window compile, now and then (the parent of the PR that
brought this cell: 12 programs, 13.4 s of a 72 s window, its six runs 11%
apart). That is no measurement, and such a program is refused at once.
"""

from __future__ import annotations

import multiprocessing
import os
import sys
import types
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from entries import common, survey, survey_gang
from traffic import make_synthetic_fil

telemetry_files = survey.telemetry_files
fallbacks = common.fallbacks


def plane_builds_once_for_all_chips() -> bool:
    """True when a plane-wrapped one-chip function built under a lease on
    the first chip costs no second compile under a lease on the second
    (``compile.cache_miss`` 1 after both calls)."""
    import jax

    from pypulsar_tpu.compile import plane_jit
    from pypulsar_tpu.obs import telemetry

    probe = plane_jit(lambda x: x * 2.0 + 1.0, name="fleet_probe")
    x = np.zeros(8, np.float32)
    with telemetry.session() as tlm:
        for dev in jax.devices()[:2]:
            with jax.default_device(dev):  # what a lease's thread sets
                probe(x)
        return tlm.counter_totals().get("compile.cache_miss", 0) == 1


def prepare(cell) -> None:
    if not plane_builds_once_for_all_chips():
        print(f"refused: the program's compile plane builds a one-chip "
              f"program again on every chip it is asked for "
              f"(compile.cache_miss), so one warm-up step cannot warm the "
              f"chips the window's leases fall on and steps of "
              f"{cell.name!r} compile at random: this program cannot run "
              f"the fleet deployment as a measurement",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    cfg, traffic = cell.cfg, cell.wl["traffic"]
    inj = traffic["injection"]
    cell.infiles, cell.injected_by_beam = [], []
    for b in range(int(cfg["beams"])):
        path = os.path.join(cell.workdir, f"beam{b}_s{cell.seed + b}.fil")
        cell.injected_by_beam.append(make_synthetic_fil.generate(
            path, nchan=cfg["nchan"], tsamp=cfg["tsamp"],
            nsamp=cfg["nsamp"], fch1=cfg["fch1"], bw=cfg["bw"],
            nbits=cfg["nbits"], seed=cell.seed + b, dm=inj["dm"],
            period=inj["period_samples"], width=inj["width_samples"],
            rfi=traffic.get("rfi")))
        cell.infiles.append(path)
    cell.infile = cell.infiles[0]  # the one the harness prints
    cell.injected = cell.injected_by_beam[0]
    cell.sky_s_per_step = sum(i["nsamp"] for i in cell.injected_by_beam) \
        * cfg["tsamp"]


def _beam(cell, b: int):
    """Beam ``b`` of the fleet as entry ``survey`` sees a cell: one input,
    its own seed and injection, the window's steps."""
    return types.SimpleNamespace(
        name=cell.name, cfg=cell.cfg, wl=cell.wl, seed=cell.seed + b,
        infile=cell.infiles[b], injected=cell.injected_by_beam[b],
        steps=[{"rc": s["rc"], "outdir": s["outdir"]} for s in cell.steps])


def _snr_json(cell, b: int, outdir: str) -> str:
    stem = os.path.splitext(os.path.basename(cell.infiles[b]))[0]
    return os.path.join(outdir, stem + "_snr.json")


def run(cell, outdir: str, telemetry: bool = False) -> int:
    argv = []
    for a in cell.wl["argv"]:
        argv += (cell.infiles if a == "{infiles}"
                 else common.fill([a], outdir=outdir, **cell.cfg))
    if telemetry:
        argv += ["--telemetry-dir", os.path.join(outdir, "tlm")]
    rc = survey.cli_main(argv)
    if not all(os.path.exists(_snr_json(cell, b, outdir))
               for b in range(len(cell.infiles))):
        return rc or 1
    return rc


def _check_beam(beam, control):
    return survey_gang.check(beam, control)


def check(cell, control=None) -> list:
    """Entry ``survey``'s comparisons for every beam; the worst counts."""
    beams = [_beam(cell, b) for b in range(len(cell.infiles))]
    missing = sum(1 for s in cell.steps for b in range(len(beams))
                  if not os.path.exists(_snr_json(cell, b, s["outdir"])))
    workers = min(len(beams), os.cpu_count() or 1)
    with ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as ex:
        per_beam = list(ex.map(_check_beam, beams, [control] * len(beams)))
    worst, limits = {}, {}
    for compared in per_beam:
        for name, v, lim in compared:
            prev = worst.get(name, 0.0)
            worst[name] = v if (v != v or v > prev) else prev  # NaN sticks
            limits[name] = lim
    lim = cell.wl["check"]["limits"]
    return [(name, v, limits[name]) for name, v in worst.items()] + [
        ("beams_missing", float(missing), float(lim["beams_missing"]))]


def work(cell) -> dict:
    """Entry ``survey``'s least work of every beam, added up."""
    total = {}
    for b in range(len(cell.infiles)):
        for stage, w in survey.work(_beam(cell, b)).items():
            ent = total.setdefault(stage, {"flops": 0.0, "bytes": 0.0})
            ent["flops"] += w["flops"]
            ent["bytes"] += w["bytes"]
    return total
