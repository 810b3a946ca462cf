"""Seconds inside ``d2h.pull`` spans per completed step; a pull's wall
includes the wait for the programs that produce what it fetches."""
from metrics import h2d_s_per_step

UNIT = "s"


def read(cell):
    return h2d_s_per_step.read(cell, span="d2h.pull")
