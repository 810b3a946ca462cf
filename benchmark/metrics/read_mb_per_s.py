"""Megabytes as on disk (``io.bytes_read``) per second spent in ``io.read``
spans: what the reader delivers while something waits on it."""
UNIT = "MB/s"


def read(cell):
    if cell.telemetry is None:
        return None
    ent = cell.telemetry["spans"].get("io.read")
    nbytes = cell.telemetry["counters"].get("io.bytes_read")
    if not ent or not ent[0] or not nbytes:
        return None
    return nbytes / 1e6 / ent[0]
