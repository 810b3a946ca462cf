"""Shared by the readers."""

from __future__ import annotations


def done(cell):
    return [s for s in cell.steps if not s["rc"]]


def stage_seconds_per_obs(cell, stage: str):
    """Mean wall of one ``survey.stage.<stage>`` span per completed
    observation, from the run's telemetry (host clock, traced runs)."""
    if cell.telemetry is None:
        return None
    ent = cell.telemetry["spans"].get(f"survey.stage.{stage}")
    if not ent or not ent[1]:
        return None
    return ent[0] / ent[1]
