"""Chips that held a sweep lease in one step: the distinct ``chips`` of the
step's ``survey.lease`` spans whose ``stage`` is ``sweep``, the smallest
count over the window's completed steps. With as many observations in
flight as the host has chips, 4 says every beam's sweep had a chip of its
own and 1 that the lanes collapsed onto one. It is where the scheduler
placed the leases, read from the spans' attributes in the steps' own
telemetry files; what each chip then did is ``device_idle_pct``'s."""
import json
import os

from metrics.common import done

UNIT = "count"


def _sweep_chips(path):
    chips, seen = set(), False
    with open(path) as f:
        for line in f:
            if '"survey.lease"' not in line:
                continue
            try:
                attrs = json.loads(line)["attrs"]
                if attrs["stage"] != "sweep":
                    continue
                chips.update(int(c) for c in attrs["chips"])
                seen = True
            except (ValueError, KeyError, TypeError):
                continue
    return len(chips) if seen else None


def read(cell):
    if cell.telemetry is None:
        return None
    used = [n for step in done(cell)
            for path in cell.entry.telemetry_files(step)
            if os.path.exists(path)
            for n in [_sweep_chips(path)] if n is not None]
    return float(min(used)) if used else None
