"""100 x least device seconds the window's work needs / device-busy seconds
of the trace. Least seconds per step come from ``counts.py`` through the
entry's ``work``; a line of output says which bound governs each stage."""
import counts
from metrics.common import done

UNIT = "%"


def read(cell):
    ts = cell.trace_summary
    if not ts or not ts["busy_s"] or not cell.peaks:
        return None
    per_step, stages = counts.least_seconds(cell.entry.work(cell),
                                            cell.peaks)
    for stage, (sec, bound) in stages.items():
        print(f"least work {stage}: {sec * 1e3:.4f} ms per step, "
              f"{bound}-bound", flush=True)
    return 100.0 * per_step * len(done(cell)) / ts["busy_s"]
