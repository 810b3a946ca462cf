"""counts.py against hand-worked shapes of both cells."""

import json
import os

import pytest

import counts

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_apertif_batch():
    c = cfg("apertif-rt")
    d = counts.dedispersion(nchan=c["nchan"], nsamp=c["nsamp"],
                            nbits=c["nbits"], trials=c["dm_trials"],
                            keep_series=False)
    # 2^18 samples x 1024 trials x log2(1024) adds; the 8-bit input once
    assert d["flops"] == 262144 * 1024 * 10 == 2684354560
    assert d["bytes"] == 262144 * 1024 == 268435456
    b = counts.boxcar(nsamp=c["nsamp"], trials=c["dm_trials"], widths=6)
    assert b["flops"] == 2 * 262144 * 1024 * 6
    sec, stages = counts.least_seconds({"dedispersion": d, "boxcar": b}, V5E)
    assert stages["dedispersion"][1] == "bytes"
    assert stages["dedispersion"][0] == pytest.approx(268435456 / 819e9)
    assert stages["boxcar"][1] == "flops"
    assert sec == pytest.approx(3.2776e-4 + 1.635e-5, rel=1e-3)


def test_htru_observation():
    c = cfg("htru-hilat")
    n = c["nsamp"]
    assert n == 1 << 19
    cells = counts.accel_cells(nsamp=n, trials=32, zmax=50, dz=2.0,
                               numharm=8)
    # 4 harmonic stages x half-bin steps over 2^18 bins x 51 drifts
    assert cells == 32 * 4 * 2 * 262144 * 51 == 3422552064
    a = counts.accel(nsamp=n, trials=32, zmax=50, dz=2.0, numharm=8)
    assert a["flops"] == pytest.approx(346.6 * 3422552064)
    d = counts.dedispersion(nchan=1024, nsamp=n, nbits=2, trials=32,
                            keep_series=True)
    assert d["bytes"] == n * 1024 // 4 + 4 * n * 32
    m = counts.mask_stats(nchan=1024, nsamp=n, nbits=2, ptsperint=15625)
    # 33 whole intervals; blocks padded to 2^14 for the FFT
    per_block = 3 * 15625 + 2.5 * 16384 * 14 + 3 * 8192
    assert m["flops"] == pytest.approx(per_block * 33 * 1024)
    f = counts.fold(nsamp=n, candidates=100, nbins=64, npart=32)
    assert f["flops"] == n * 100
    sec, stages = counts.least_seconds({"accel": a, "mask": m, "fold": f},
                                       V5E)
    assert stages["accel"] == (pytest.approx(346.6 * 3422552064 / 197e12),
                               "flops")
    assert stages["fold"][1] == "bytes"
    assert 5e-3 < sec < 8e-3
