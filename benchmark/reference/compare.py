"""The comparisons that decide ``correct``.

Each function returns a list of ``(name, value, limit)``: one number
compared, beside the limit it is held to. The limits are data of the cell
(``workloads/<cell>.json``, key ``check.limits``); how each was set is in
``PERF.md``. ``control`` names a lower precision: the reference computed in
it stands in for the program's output, and has to come out over a limit.
"""

from __future__ import annotations

import numpy as np



def lower_dtype(name):
    """The NumPy dtype of a control precision (None: no control)."""
    if name in (None, "", "float64"):
        return None
    if name == "float32":
        return np.float32
    if name == "bfloat16":
        import ml_dtypes

        return ml_dtypes.bfloat16
    raise ValueError(f"no control precision {name!r}")


def sample_trials(seed: int, n_trials: int, inj_trial: int, k: int):
    """``k`` trials drawn from the seed: the injected one, three of its
    neighbours, the rest anywhere on the grid."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    near = inj_trial + rng.choice(np.arange(-12, 13), size=3, replace=False)
    picks = {int(np.clip(t, 0, n_trials - 1)) for t in near}
    picks.add(int(np.clip(inj_trial, 0, n_trials - 1)))
    order = rng.permutation(n_trials)
    for t in order:
        if len(picks) >= min(k, n_trials):
            break
        picks.add(int(t))
    return sorted(picks)


def parse_cands(path: str):
    """Rows of a ``.cands`` table: (dm, snr, sample, width)."""
    rows = []
    with open(path) as f:
        for line in f:
            if line.startswith("#") or not line.strip():
                continue
            p = line.split()
            rows.append((float(p[0]), float(p[1]), int(p[3]), int(p[4])))
    return rows


def rows_from_detection(det, dms, trials, threshold):
    """What a ``.cands`` table holds for a detection result (the control's
    stand-in for the program's file): SNR to three decimals."""
    return [(float(dms[t]), round(float(det.snr[i, wi]), 3),
             int(det.best[i, wi]), int(w))
            for i, t in enumerate(trials)
            for wi, w in enumerate(det.widths)
            if det.snr[i, wi] >= threshold]


def sweep_rows(det, plan, trials, tables, threshold, limits) -> dict:
    """Detection rows of the sampled trials in every table against the
    float64 detection ``det`` (``dedisp.Detection``) of the same trials.

    ``snr_abs``: the widest gap between a row's SNR and the reference's SNR
    of the window that the row names, or of its own best window.
    ``rows_off``: rows the reference puts clearly over the threshold that a
    table lacks, plus rows it puts clearly under that a table holds."""
    snr, at = det.snr, det.at
    lo, step = float(plan.dms[0]), float(plan.dms[1] - plan.dms[0])
    index = {t: i for i, t in enumerate(trials)}
    widx = {w: wi for wi, w in enumerate(plan.widths)}
    margin = limits["snr_abs"]
    gap, off, n_rows = 0.0, 0, 0
    for rows in tables:
        seen = set()
        for dm, row_snr, sample, width in rows:
            t = int(round((dm - lo) / step))
            if t not in index or width not in widx:
                continue
            i, wi = index[t], widx[width]
            seen.add((i, wi))
            n_rows += 1
            if not 0 <= sample < det.T:
                off += 1
                continue
            gap = max(gap, abs(row_snr - snr[i, wi]),
                      abs(row_snr - at(i, wi, sample)))
            if snr[i, wi] < threshold - margin:
                off += 1
        for i in range(len(trials)):
            for wi in range(len(plan.widths)):
                if snr[i, wi] >= threshold + margin and (i, wi) not in seen:
                    off += 1
    if n_rows == 0:
        off += 1  # a sample that holds the injected trial always has rows
    return {"snr_abs": gap, "rows_off": float(off)}


# -- the whole chain (entry ``survey``) --------------------------------------


def parse_accelcands(path: str):
    """Sifted candidates in file order (decreasing sigma): dicts with dm,
    sigma, numharm, period (s), r, z."""
    out = []
    with open(path) as f:
        for line in f:
            if not line.strip() or line[0] in "# ":
                continue
            p = line.split()
            out.append({"dm": float(p[1]), "sigma": float(p[3]),
                        "numharm": int(p[4]), "period": float(p[7]) / 1e3,
                        "r": float(p[8]), "z": float(p[9])})
    return out


def harmonic_of(period: float, t_obs: float, inj_period: float,
                max_harm: int = 64):
    """(a, b) when the candidate's Fourier bin sits within half a bin of
    a/b times the injected fundamental's (b <= 4), else None. The injected
    pulse is narrow: hundreds of harmonics carry equal power, and which the
    search ranks first is the noise's choice — but a harmonic it must be."""
    import math

    r, r0 = t_obs / period, t_obs / inj_period
    for b in (1, 2, 3, 4):
        a = int(round(r * b / r0))
        if 1 <= a <= max_harm * b and math.gcd(a, b) == 1 \
                and abs(r * b - a * r0) < 0.5:
            return a, b
    return None


def rel_gap(got, want, floor=1.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), floor)))
