"""Seconds of sky searched to the cell's depth per wall second: the sky time
of every step completed in the window over the whole window (first start to
last finish)."""
from metrics.common import done

UNIT = "sky_s/s"


def read(cell):
    n = len(done(cell))
    return n * cell.sky_s_per_step / cell.window_s if n else None
