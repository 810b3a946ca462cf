"""Seeded SIGPROC filterbank with one injected, dispersed pulsar.

The benchmark's own copy of ``tools/make_synthetic_fil.py`` (same noise
map, same tiled injection, same header), cut to what the cells use and
free of the program's modules so that no later PR can move the input.

Noise is Uniform{0..noise_hi-1} from ``numpy.random.SFC64(seed)``; the pulse
is ``amp`` counts over ``width`` samples every ``period`` samples, delayed
per channel by the cold-plasma law rounded to whole samples. ``rfi`` names
channels that carry a persistent narrow-band interferer instead: a square
wave between 0 and full scale, the same for every seed, so that the RFI mask
has the same channels to zap (and the mask fill the same blocks to touch)
whatever the noise does.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from reference import sigproc  # noqa: E402
from reference.dedisp import bin_delays  # noqa: E402

# amplitude and noise range that keep the 8-bit defaults' per-sample SNR
DEFAULT_AMP = {8: 30, 4: 2, 2: 1}
DEFAULT_NOISE_HI = {8: 200, 4: 14, 2: 3}


def generate(path: str, *, nchan: int, tsamp: float, nsamp: int, fch1: float,
             bw: float, nbits: int, seed: int, dm: float, period: int,
             width: int, rfi: dict | None = None,
             periods_per_write: int = 4) -> dict:
    """Write the file; returns what was injected. ``nsamp`` is rounded down
    to whole periods (the injection is one tiled [period, nchan] pattern)."""
    amp, noise_hi = DEFAULT_AMP[nbits], DEFAULT_NOISE_HI[nbits]
    nsamp = max((nsamp // period) * period, period)
    foff = -bw / nchan
    freqs = fch1 + foff * np.arange(nchan)
    delays = bin_delays(dm, freqs, tsamp)
    pattern = np.zeros((period, nchan), np.uint8)
    rows = (np.arange(width)[:, None] + delays[None, :]) % period
    pattern[rows, np.arange(nchan)[None, :]] = amp
    rfi_chans = list(rfi["channels"]) if rfi else []
    half = int(rfi["half_period_samples"]) if rfi else 1
    if period % (2 * half):
        raise ValueError("the interferer's period must divide the pulse's")
    wave = (((np.arange(period) // half) % 2) * ((1 << nbits) - 1)).astype(
        np.uint8)
    hdr = {
        "source_name": f"SYNTH_DM{dm:g}_P{period}",
        "fch1": float(fch1), "foff": float(foff), "nchans": nchan,
        "tsamp": float(tsamp), "nsamples": nsamp, "nbits": nbits, "nifs": 1,
        "tstart": 60000.0, "data_type": 1, "telescope_id": 0,
        "machine_id": 0, "barycentric": 0, "src_raj": 0.0, "src_dej": 0.0,
        "az_start": 0.0, "za_start": 0.0,
    }
    rng = np.random.Generator(np.random.SFC64(int(seed)))
    block_len = period * periods_per_write
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(sigproc.pack_header(hdr))
        written = 0
        while written < nsamp:
            n = min(block_len, nsamp - written)
            raw = np.frombuffer(rng.bytes(n * nchan), np.uint8)
            block = ((raw.astype(np.uint16) * np.uint16(noise_hi))
                     >> np.uint16(8)).astype(np.uint8).reshape(n, nchan)
            tiled = block.reshape(n // period, period, nchan)
            tiled[:] += pattern[None]
            for ch in rfi_chans:
                tiled[:, :, ch] = wave[None, :]
            if nbits < 8:
                block = sigproc.pack_subbyte(block, nbits)
            block.tofile(f)
            written += n
    os.replace(tmp, path)
    return {"nsamp": nsamp, "dm": dm, "period": period, "width": width,
            "amp": amp, "noise_hi": noise_hi, "rfi_channels": rfi_chans}
