"""95th percentile over all batches of the window of one batch's wall, from
the call into the entry to its table on disk."""
import numpy as np

UNIT = "s"


def read(cell):
    walls = [s["wall"] for s in cell.steps]
    return float(np.percentile(walls, 95)) if walls else None
