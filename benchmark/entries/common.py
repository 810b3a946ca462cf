"""What the entry drivers share: argv templates, the input, counters."""

from __future__ import annotations

import os

from traffic import make_synthetic_fil

# counters and events that would let a run pass without the device path
FALLBACK_COUNTERS = ("fold.numpy_fallbacks", "accel.serial_fallbacks",
                     "compile.aot_fallback", "resilience.oom_backoffs")
FALLBACK_EVENTS = ("resilience.oom_backoff", "survey.stage_retry",
                   "survey.device_evicted", "mesh.device_quarantined")


def fill(template, **values):
    """An argv template with ``{name}`` fields filled from the cell's
    configuration and the step's paths."""
    if "widths" in values:
        values["widths_csv"] = ",".join(str(w) for w in values["widths"])
    return [str(a).format(**values) for a in template]


def make_input(cell) -> None:
    """The cell's one input file, from the seed."""
    cfg, inj = cell.cfg, cell.wl["traffic"]["injection"]
    cell.infile = os.path.join(cell.workdir, f"input_s{cell.seed}.fil")
    cell.injected = make_synthetic_fil.generate(
        cell.infile, nchan=cfg["nchan"], tsamp=cfg["tsamp"],
        nsamp=cfg["nsamp"], fch1=cfg["fch1"], bw=cfg["bw"],
        nbits=cfg["nbits"], seed=cell.seed, dm=inj["dm"],
        period=inj["period_samples"], width=inj["width_samples"],
        rfi=cell.wl["traffic"].get("rfi"))
    cell.sky_s_per_step = cell.injected["nsamp"] * cfg["tsamp"]


def fallbacks(cell) -> dict:
    tlm = cell.telemetry
    out = {k: tlm["counters"].get(k, 0) for k in FALLBACK_COUNTERS}
    out.update({k: tlm["events"].get(k, 0) for k in FALLBACK_EVENTS})
    return out
