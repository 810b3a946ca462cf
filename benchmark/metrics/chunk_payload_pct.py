"""100 x ``sweep.payload_samples`` / ``sweep.chunk_samples`` over the
window: the share of every sample the detection pass's chunk programs
transformed that was new sky, the rest being the dedispersion overlap
carried again and the padding up to the transform's power-of-two length.
A program without the second counter reports nothing."""
UNIT = "%"


def read(cell):
    if cell.telemetry is None:
        return None
    c = cell.telemetry["counters"]
    payload, chunk = c.get("sweep.payload_samples"), c.get(
        "sweep.chunk_samples")
    if payload is None or not chunk:
        return None
    return 100.0 * payload / chunk
