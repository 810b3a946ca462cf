"""100 x the least-leased chip's leased seconds
(``survey.lease_chip_s.chip<N>``) / the scheduler's wall
(``survey.pool_chip_s`` over the cell's chips), summed over the window's
steps: the share of the run for which the chip the scheduler used least
held a lease. A chip that no lease ever fell on reads 0."""
UNIT = "%"

PREFIX = "survey.lease_chip_s.chip"


def read(cell):
    if cell.telemetry is None:
        return None
    c = cell.telemetry["counters"]
    chips = int(cell.wl["chips"])
    pool = c.get("survey.pool_chip_s")
    if not pool or not any(k.startswith(PREFIX) for k in c):
        return None
    least = min(c.get(f"{PREFIX}{n}", 0.0) for n in range(chips))
    return 100.0 * least / (pool / chips)
