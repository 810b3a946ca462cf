"""Least work of each stage, from shapes alone.

Every function returns ``{"flops": .., "bytes": ..}`` for ONE step of a
cell: the operations of the cheapest known order for the stage and the
bytes that any implementation has to move over the device's memory — the
input read once at the width it is shipped in, each product the cell keeps
written once. They are lower bounds that no implementation can undercut,
so the roofline share they give means the same whatever implements the
stage. ``least_seconds`` turns them into the least device time at a chip's
peaks and says which bound governs each stage.
"""

from __future__ import annotations

import math

# harmonic-summed matched-filter work per searched (r, z) cell: the model of
# tools/accel_roofline.py (FFT correlation, multiply, power, harmonic
# gather) at its reference geometry. Kept as one constant: the least-work
# count of this stage is the open one (PERF.md, Open questions).
ACCEL_FLOPS_PER_CELL = 346.6


def dedispersion(*, nchan, nsamp, nbits, trials, keep_series):
    """Tree order: samples x trials x log2(channels) adds. Bytes: the
    input once at its packed width, and the float32 series where the cell
    keeps them (the .dat tee, the spectra's source)."""
    flops = float(nsamp) * trials * math.log2(nchan)
    nbytes = float(nsamp) * nchan * nbits / 8
    if keep_series:
        nbytes += 4.0 * nsamp * trials
    return {"flops": flops, "bytes": nbytes}


def boxcar(*, nsamp, trials, widths):
    """Doubling window sums: one add and one compare per trial-sample and
    width. Fused behind dedispersion: it keeps nothing but the maxima."""
    return {"flops": 2.0 * nsamp * trials * widths,
            "bytes": 8.0 * trials * widths}


def mask_stats(*, nchan, nsamp, nbits, ptsperint):
    """Per (interval, channel) block: mean, centred variance, and one real
    FFT of the block padded to a power of two (2.5 L log2 L), plus its
    power spectrum (3 per bin). The input is read once."""
    nint = max(nsamp // ptsperint, 1)
    L = 1 << max(ptsperint - 1, 1).bit_length()
    per_block = 3.0 * ptsperint + 2.5 * L * math.log2(L) + 3.0 * (L // 2)
    return {"flops": per_block * nint * nchan,
            "bytes": float(nsamp) * nchan * nbits / 8 + 12.0 * nint * nchan}


def spectrum_prep(*, nsamp, trials):
    """One real FFT per trial and a block-median normalisation (about ten
    operations per bin); the complex64 spectra handed to the search are
    written once."""
    nbins = nsamp // 2
    return {"flops": trials * (2.5 * nsamp * math.log2(nsamp) + 10.0 * nbins),
            "bytes": trials * (4.0 * nsamp + 8.0 * nbins)}


def accel_cells(*, nsamp, trials, zmax, dz, numharm):
    """(r, z) cells searched: per harmonic stage, half-bin steps over the
    spectrum times the z grid."""
    stages = int(math.log2(numharm)) + 1
    nz = int(math.floor(2 * zmax / dz)) + 1
    return float(trials) * stages * 2 * (nsamp // 2) * nz


def accel(*, nsamp, trials, zmax, dz, numharm):
    cells = accel_cells(nsamp=nsamp, trials=trials, zmax=zmax, dz=dz,
                        numharm=numharm)
    return {"flops": ACCEL_FLOPS_PER_CELL * cells,
            "bytes": trials * 8.0 * (nsamp // 2)}


def fold(*, nsamp, candidates, nbins, npart):
    """One add per candidate-sample; each candidate's series read once,
    its [npart, nbins] profile written once."""
    return {"flops": float(nsamp) * candidates,
            "bytes": candidates * (4.0 * nsamp + 4.0 * nbins * npart)}


def least_seconds(work: dict, peaks: dict):
    """(seconds, {stage: (seconds, governing bound)}) for one step."""
    per_stage = {}
    for stage, w in work.items():
        t_f = w["flops"] / peaks["flops_per_s"]
        t_b = w["bytes"] / peaks["hbm_bytes_per_s"]
        per_stage[stage] = (max(t_f, t_b), "flops" if t_f >= t_b else "bytes")
    return sum(t for t, _ in per_stage.values()), per_stage
