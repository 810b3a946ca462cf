"""Roofline analysis of the batched (r, z) acceleration-search stage
(VERDICT r5 item 1a: "publish a roofline for the accel stage the way the
sweep has one — FLOPs+bytes per cell for the batched fft->multiply->ifft
+ stretch-gather, vs the measured 555-577M cells/s — so the gap is
known, not guessed").

The model walks the EXACT geometry the ladder runner executes
(fourier/accelsearch._ladder_scan): ONE segment grid for all harmonic
stages, whose origin is the lowest searched bin rounded down to a
multiple of the top stage, and per segment ONE pass over each distinct
subharmonic ratio — stage H adds the ratios b/H in lowest terms to the
running sum (1; 1/2; 1/4, 3/4; the odd eighths: 8 bank passes a segment
at numharm 8, where the per-stage programs before PR 32 made sum(H) =
15) — and one detection per stage (4 a segment). For every ratio it
derives the bank height (rows = 2*Z*Wn interleaved half-bin templates),
the template half-width (zresponse.zw_halfwidth of the ratio-scaled
drift), and the power-of-two FFT length L_b = fourier_chunk_len(segw*b/H
+ 4*hw_b) — then counts, per searched (r, z) cell (a stage's cells are
the columns it is VALID on, H*rlo up to Nyquist, not the whole grid):

- FFT flops (the 5 L log2 L convention): one forward FFT of the slice
  per (spectrum, segment, bank) plus ``rows`` inverse FFTs — the inverse
  transforms dominate everything else by an order of magnitude;
- non-FFT flops: the broadcast complex multiply (6/elem), |.|^2
  (3/elem), the stretch-gather + accumulate (2/cell/bank), and per
  stage the column mask, the 4-neighbour local-max test and the
  threshold (10/cell/stage; top_k's selection is not counted);
- HBM bytes under a no-fusion worst case and a fused best case, with the
  bank reads amortized over the batch (they are batch-invariant — the
  whole point of accel_search_batch).

``benchmark/counts.py``'s ``ACCEL_FLOPS_PER_CELL`` 346.6 is the OLD
model's figure (every stage built from scratch, 15 passes, at this
tool's reference geometry): it is the benchmark's yardstick for
``kernel_roofline_pct`` and the next ``benchmark`` issue's to revisit
(PERF.md section 7, "cannot see" (2)); this tool now prints what runs.

Practical ceilings come from MEASURED on-chip rates, not datasheet peaks:
XLA's TPU FFT throughput on this v5e measured 121 GFLOP/s (batched
irfft) to 204 GFLOP/s (rfft) in the component probe (BENCHNOTES), and
the HBM roofline is 819 GB/s. The default ``--measured`` 577M cells/s is
the dispatch-level rate of the per-stage programs (BENCHNOTES round 6,
which placed it at 90-105% of THEIR irfft-rate ceiling: that stage was
FFT-throughput-bound); against the ladder's fewer flops per cell the
same figure reads far under the ceiling, so pass the ladder's own
measured rate (PERF.md section 5) to place it. What is left to take out
of the FFT work is the padding (every L sits one bin past a power of
two) and half-size real transforms.

Usage: python tools/accel_roofline.py [--n 2097152] [--zmax 200]
           [--numharm 8] [--measured 577e6] [--json]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pypulsar_tpu.fourier.zresponse import zw_halfwidth  # noqa: E402
from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1 << 21,
                    help="spectrum bins (default 2^21, the bench/configs4 "
                         "geometry)")
    ap.add_argument("--zmax", type=float, default=200.0)
    ap.add_argument("--dz", type=float, default=2.0)
    ap.add_argument("--numharm", type=int, default=8, choices=(1, 2, 4, 8))
    ap.add_argument("--segw", type=int, default=1 << 14,
                    help="fundamental bins per segment (default 2^14)")
    ap.add_argument("--min-halfwidth", type=int, default=24)
    ap.add_argument("--batch", type=int, default=32,
                    help="spectra per dispatch (amortizes bank reads)")
    ap.add_argument("--flo-bins", type=int, default=269,
                    help="lowest searched fundamental bin (rlo; default "
                         "269 = 1 Hz at the 2^21-bin configs4 spectrum)")
    ap.add_argument("--fft-gflops", type=float, default=204.0,
                    help="measured XLA FFT rate for the practical "
                         "ceiling (default 204 = the TOP of the "
                         "121-204 GFLOP/s band the component probe "
                         "measured for batched TPU FFTs; --fft-gflops-lo "
                         "sets the bottom)")
    ap.add_argument("--fft-gflops-lo", type=float, default=121.0,
                    help="bottom of the measured FFT-rate band "
                         "(121 = batched irfft probe)")
    ap.add_argument("--hbm-gbs", type=float, default=819.0,
                    help="HBM roofline GB/s (v5e: 819)")
    ap.add_argument("--measured", type=float, default=577e6,
                    help="measured cells/s to place on the roofline "
                         "(default 577M, BENCH r4/r5 dispatch-level; CLI "
                         "level with I/O measured 400M)")
    ap.add_argument("--fused", action="store_true",
                    help="model the SPECTRAL-FUSION stage (round 10, "
                         "parallel/specfuse.py): the per-trial forward "
                         "FFT of the prep (and the sweep-side inverse "
                         "that fed it) drop from the per-spectrum "
                         "budget, so the stage ceiling is restated "
                         "without the prep transforms")
    ap.add_argument("--json", action="store_true",
                    help="emit the analysis as one JSON line")
    return ap.parse_args(argv)


def analyze(n, zmax, dz, numharm, segw, min_halfwidth, batch, rlo,
            Wn: int = 1):
    """Per-rung and total (flops, bytes) per searched cell. Returns a
    dict of the full accounting. A stage's row holds what ITS rung adds
    to the ladder (the banks it brings and its own detection); the
    cells are those the stage is valid on."""
    Z = int(math.floor(2 * zmax / dz)) + 1
    rows = 2 * Z * Wn  # interleaved integer/half-bin template rows
    stages = [h for h in (1, 2, 4, 8) if h <= numharm]
    hmax = max(stages)
    grid_lo, grid_hi = hmax * (rlo // hmax), n - 1
    n_seg = -(-(grid_hi - grid_lo) // segw)
    cells_seg = Z * Wn * 2 * segw  # plane cells per segment
    per_stage = []
    tot_cells = tot_fft = tot_other = 0.0
    tot_bytes_lo = tot_bytes_hi = 0.0
    n_banks = 0
    for H in stages:
        fft_seg = b_lo = b_hi = 0.0
        # the stage's own detection: mask, local max, threshold
        other_seg = 10.0 * cells_seg
        b_lo += 4 * cells_seg
        b_hi += 12 * cells_seg
        rung = [b for b in range(1, H + 1) if math.gcd(b, H) == 1]
        n_banks += len(rung)
        for b in rung:
            hw = zw_halfwidth(zmax * b / H, 0.0, min_halfwidth)
            L = fourier_chunk_len((segw * b) // H + 4 * hw)
            lg = math.log2(L)
            fft_seg += 5 * L * lg * (1 + rows)     # fwd slice + rows inv
            other_seg += (6 + 3) * rows * L        # multiply + |.|^2
            other_seg += 2 * cells_seg             # gather + accumulate
            # bytes, fused best case: slice read + bank read (amortized
            # over the batch) + plane accumulate; worst case adds the
            # cf/corr/power intermediates materialized
            b_lo += 8 * L + 8 * rows * L / batch + 8 * cells_seg
            b_hi += (8 * L + 16 * L + 8 * rows * L / batch
                     + 16 * rows * L + 4 * rows * L
                     + 4 * cells_seg + 8 * cells_seg)
        cells = Z * Wn * 2 * max(n - 1 - H * rlo, 0)
        per_stage.append(dict(
            H=H, n_seg=n_seg, cells=cells, banks=len(rung),
            fft_flops_per_cell=round(n_seg * fft_seg / cells, 1),
            other_flops_per_cell=round(n_seg * other_seg / cells, 1),
            bytes_per_cell_fused=round(n_seg * b_lo / cells, 1),
            bytes_per_cell_worst=round(n_seg * b_hi / cells, 1),
        ))
        tot_cells += cells
        tot_fft += n_seg * fft_seg
        tot_other += n_seg * other_seg
        tot_bytes_lo += n_seg * b_lo
        tot_bytes_hi += n_seg * b_hi
    return dict(
        Z=Z, rows=rows, stages=stages, per_stage=per_stage,
        n_seg=n_seg, bank_passes_per_seg=n_banks,
        detections_per_seg=len(stages),
        total_cells=int(tot_cells),
        fft_flops_per_cell=round(tot_fft / tot_cells, 1),
        other_flops_per_cell=round(tot_other / tot_cells, 1),
        flops_per_cell=round((tot_fft + tot_other) / tot_cells, 1),
        bytes_per_cell_fused=round(tot_bytes_lo / tot_cells, 1),
        bytes_per_cell_worst=round(tot_bytes_hi / tot_cells, 1),
    )


def prep_flops_per_spectrum(n: int, fused: bool) -> float:
    """Per-spectrum transform cost of GETTING the normalized spectrum —
    the round-10 fusion target. The streamed handoff pays one forward
    rfft of the 2n-sample series in prep PLUS the sweep-side inverse
    that produced that series (the irfft->rfft pair specfuse elides);
    each real transform of length L is ~2.5*L*log2(L) flops under this
    file's 5*L*log2(L) complex-FFT convention. The fused path pays
    ZERO per-trial transforms (decimate regime; the stitched regime
    keeps the pair but off the host link — this model states the
    transform-count claim, which the specfuse telemetry counters
    verify at run time)."""
    if fused:
        return 0.0
    L = 2 * n
    return 2 * 2.5 * L * math.log2(L)


def main(argv=None):
    a = parse_args(argv)
    r = analyze(a.n, a.zmax, a.dz, a.numharm, a.segw, a.min_halfwidth,
                a.batch, a.flo_bins)
    prep = prep_flops_per_spectrum(a.n, a.fused)
    prep_per_cell = prep / r["total_cells"]
    fft_ceiling = a.fft_gflops * 1e9 / r["fft_flops_per_cell"]
    fft_floor = a.fft_gflops_lo * 1e9 / r["fft_flops_per_cell"]
    ceiling_with_prep = a.fft_gflops * 1e9 / (r["fft_flops_per_cell"]
                                              + prep_per_cell)
    hbm_ceiling_fused = a.hbm_gbs * 1e9 / r["bytes_per_cell_fused"]
    hbm_ceiling_worst = a.hbm_gbs * 1e9 / r["bytes_per_cell_worst"]
    implied_gflops = a.measured * r["fft_flops_per_cell"] / 1e9
    frac = a.measured / fft_ceiling
    rec = {
        **{k: v for k, v in r.items() if k != "per_stage"},
        "per_stage": r["per_stage"],
        "fft_rate_band_gflops": [a.fft_gflops_lo, a.fft_gflops],
        "hbm_gbs": a.hbm_gbs,
        "batch": a.batch,
        "fused": bool(a.fused),
        "prep_fft_flops_per_spectrum": round(prep, 1),
        "prep_fft_flops_per_cell": round(prep_per_cell, 4),
        "ceiling_fft_incl_prep_cells_per_sec": round(ceiling_with_prep, 1),
        "ceiling_fft_cells_per_sec": round(fft_ceiling, 1),
        "ceiling_fft_lo_cells_per_sec": round(fft_floor, 1),
        "ceiling_hbm_fused_cells_per_sec": round(hbm_ceiling_fused, 1),
        "ceiling_hbm_worst_cells_per_sec": round(hbm_ceiling_worst, 1),
        "measured_cells_per_sec": a.measured,
        "implied_fft_gflops": round(implied_gflops, 1),
        "measured_over_fft_ceiling": round(frac, 3),
        "bound": ("fft" if fft_ceiling < min(hbm_ceiling_worst, 1e18)
                  else "hbm"),
    }
    if a.json:
        print(json.dumps(rec))
        return 0
    print(f"# accel (r,z) roofline @ N={a.n}, zmax={a.zmax:.0f}, "
          f"dz={a.dz:g}, H<={a.numharm}, segw={a.segw}, batch={a.batch}")
    print(f"# Z={r['Z']} drift rows x2 interleave = {r['rows']} bank rows")
    print(f"# one ladder a segment: {r['n_seg']} segments x "
          f"{r['bank_passes_per_seg']} bank passes + "
          f"{r['detections_per_seg']} detections")
    print("# rung   +banks   cells/spec    FFT fl/cell  other fl/cell  "
          "B/cell fused..worst")
    for s in r["per_stage"]:
        print(f"#  H={s['H']:<2d} {s['banks']:7d} {s['cells']:12d} "
              f"{s['fft_flops_per_cell']:12.1f} "
              f"{s['other_flops_per_cell']:14.1f}  "
              f"{s['bytes_per_cell_fused']:8.1f}.."
              f"{s['bytes_per_cell_worst']:.1f}")
    print(f"# TOTAL {r['total_cells']} cells/spectrum; "
          f"{r['fft_flops_per_cell']} FFT + {r['other_flops_per_cell']} "
          f"other flops/cell; {r['bytes_per_cell_fused']}.."
          f"{r['bytes_per_cell_worst']} bytes/cell")
    print(f"# ceilings: FFT-rate band ({a.fft_gflops_lo:.0f}-"
          f"{a.fft_gflops:.0f} GFLOP/s measured) -> "
          f"{fft_floor / 1e6:.0f}-{fft_ceiling / 1e6:.0f}M cells/s | "
          f"HBM ({a.hbm_gbs:.0f} GB/s) -> "
          f"{hbm_ceiling_fused / 1e9:.1f}G (fused) / "
          f"{hbm_ceiling_worst / 1e6:.0f}M (unfused)")
    print(f"# measured {a.measured / 1e6:.0f}M cells/s = "
          f"{100 * frac:.0f}% of the band-top FFT ceiling (implied FFT "
          f"rate {implied_gflops:.0f} GFLOP/s, inside the measured "
          f"band) -> the stage is {rec['bound'].upper()}-bound")
    if a.fused:
        print("# FUSED stage (round 10): per-trial prep transforms "
              "elided — 0 prep FFT flops/spectrum; the stage ceiling "
              "is the correlation-only number above")
    else:
        print(f"# prep (per-trial irfft+rfft pair the fused path "
              f"elides): {prep / 1e6:.1f}M flops/spectrum = "
              f"{prep_per_cell:.2f} flops/cell -> ceiling incl. prep "
              f"{ceiling_with_prep / 1e6:.0f}M cells/s "
              f"({100 * (1 - ceiling_with_prep / fft_ceiling):.1f}% "
              f"below correlation-only; compare --fused)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
