"""``compile.cache_miss`` summed over the window's steps (should read 0)."""
UNIT = "count"


def read(cell):
    if cell.telemetry is None or not cell.telemetry["n_files"]:
        return None
    return float(cell.telemetry["counters"].get("compile.cache_miss", 0))
