"""100 x (1 - leased chip-seconds / chip-seconds the pool offered): the
share of the host's chips that no stage held a lease on."""
UNIT = "%"


def read(cell):
    if cell.telemetry is None:
        return None
    c = cell.telemetry["counters"]
    leased, pool = c.get("survey.lease_chip_s"), c.get("survey.pool_chip_s")
    if leased is None or not pool:
        return None
    return 100.0 * (1.0 - leased / pool)
