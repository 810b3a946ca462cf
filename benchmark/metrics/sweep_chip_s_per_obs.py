"""Chip-seconds leased to the sweep stage (k x the lease's wall) per
completed observation."""
from metrics.common import done

UNIT = "s"


def read(cell):
    if cell.telemetry is None or not done(cell):
        return None
    leased = cell.telemetry["counters"].get("survey.lease_chip_s.sweep")
    if leased is None:
        return None
    return leased / len(done(cell))
