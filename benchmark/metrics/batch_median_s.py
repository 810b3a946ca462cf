"""Median batch wall; stands beside the tail."""
import statistics

UNIT = "s"


def read(cell):
    walls = [s["wall"] for s in cell.steps]
    return statistics.median(walls) if walls else None
