"""100 x least device seconds of dedispersion and boxcar detection for the
window's completed steps / device seconds of the dedispersion chunk
programs, by the names the trace prints: ``jit__sweep_chunk_jit`` (the
detection pass) and ``jit__dedisperse_series_jit`` (the series pass of the
accel handoff). Least seconds come from ``counts.dedispersion`` (the
input once at its packed width, the kept series once) and ``counts.boxcar``
at the device's peaks; a line of output says which bound governs each."""
import counts
from metrics.common import done

UNIT = "%"
PROGRAMS = ("jit__sweep_chunk_jit", "jit__dedisperse_series_jit")


def read(cell):
    ts = cell.trace_summary
    if not ts or not cell.peaks:
        return None
    device_s = sum(ts["program_seconds"].get(p, 0.0) for p in PROGRAMS)
    if not device_s:
        return None
    cfg, n = cell.cfg, cell.injected["nsamp"]
    per_step, stages = counts.least_seconds({
        "dedispersion": counts.dedispersion(
            nchan=cfg["nchan"], nsamp=n, nbits=cfg["nbits"],
            trials=cfg["dm_trials"], keep_series=True),
        "boxcar": counts.boxcar(nsamp=n, trials=cfg["dm_trials"],
                                widths=len(cfg["widths"])),
    }, cell.peaks)
    for stage, (sec, bound) in stages.items():
        print(f"dedisp roofline, least work {stage}: {sec * 1e3:.4f} ms "
              f"per step, {bound}-bound", flush=True)
    print(f"dedisp roofline: chunk programs {device_s:.6f} s of device "
          f"over {len(done(cell))} steps", flush=True)
    return 100.0 * per_step * len(done(cell)) / device_s
