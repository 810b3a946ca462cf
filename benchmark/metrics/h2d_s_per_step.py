"""Seconds inside ``h2d.ship`` spans per completed step (all threads)."""
from metrics.common import done

UNIT = "s"
SPAN = "h2d.ship"


def read(cell, span=SPAN):
    if cell.telemetry is None or not done(cell):
        return None
    ent = cell.telemetry["spans"].get(span)
    if not ent or not ent[1]:
        return None
    return ent[0] / len(done(cell))
