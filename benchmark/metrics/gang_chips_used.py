"""Chips the fleet scheduler gang-leased at once: ``k`` of the widest
``survey.gang_decision`` of each completed step, the smallest over the
window's steps, so one observation swept on fewer chips shows. It is the
scheduler's grant, read from the events' attributes in the steps' own
telemetry files; what each chip then did is ``device_idle_pct``'s."""
import json
import os

from metrics.common import done

UNIT = "count"


def _widest_gang(path):
    k = None
    with open(path) as f:
        for line in f:
            if '"survey.gang_decision"' not in line:
                continue
            try:
                granted = json.loads(line)["attrs"]["k"]
            except (ValueError, KeyError, TypeError):
                continue
            k = max(k or 0, int(granted))
    return k


def read(cell):
    if cell.telemetry is None:
        return None
    widest = [k for step in done(cell)
              for path in cell.entry.telemetry_files(step)
              if os.path.exists(path)
              for k in [_widest_gang(path)] if k is not None]
    return float(min(widest)) if widest else None
