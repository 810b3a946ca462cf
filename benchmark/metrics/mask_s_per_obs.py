"""Mean wall of the ``survey.stage.mask`` span per observation."""
from metrics.common import stage_seconds_per_obs

UNIT = "s"


def read(cell):
    return stage_seconds_per_obs(cell, "mask")
