"""Mean wall of the ``survey.stage.fold`` span per observation."""
from metrics.common import stage_seconds_per_obs

UNIT = "s"


def read(cell):
    return stage_seconds_per_obs(cell, "fold")
