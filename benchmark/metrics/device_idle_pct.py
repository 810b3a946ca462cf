"""100 x (1 - union of device-busy intervals / traced window)."""
UNIT = "%"


def read(cell):
    ts = cell.trace_summary
    if not ts or not ts["window_s"]:
        return None
    return 100.0 * (1.0 - ts["busy_s"] / ts["window_s"])
