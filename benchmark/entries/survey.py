"""Entry ``survey``: ``pypulsar_tpu.cli.survey.main`` over one observation
per step — mask -> sweep + accel -> sift -> fold -> snr on one chip."""

from __future__ import annotations

import glob
import json
import os

import numpy as np

import counts
from entries import common
from reference import accel, compare, dedisp, fold, rfimask, sigproc


def cli_main(argv):
    from pypulsar_tpu.cli import survey

    return survey.main(argv)


def prepare(cell) -> None:
    common.make_input(cell)


def _stem(cell) -> str:
    return os.path.splitext(os.path.basename(cell.infile))[0]


def run(cell, outdir: str, telemetry: bool = False) -> int:
    argv = common.fill(cell.wl["argv"], infile=cell.infile, outdir=outdir,
                       **cell.cfg)
    if telemetry:
        argv += ["--telemetry-dir", os.path.join(outdir, "tlm")]
    rc = cli_main(argv)
    if not os.path.exists(os.path.join(outdir, _stem(cell) + "_snr.json")):
        return rc or 1
    return rc


def telemetry_files(step) -> list:
    return [os.path.join(step["outdir"], "tlm", "fleet.jsonl")]


fallbacks = common.fallbacks


def _grid(cfg):
    return cfg["dm_lo"] + cfg["dm_step"] * np.arange(cfg["dm_trials"])


class Reference:
    """What the chain should have written for the sampled trials, computed
    once per run from the raw file (``dtype`` lower than float64: the
    control, which stands in for the program's output)."""

    def __init__(self, cell, dtype=None):
        cfg = cell.cfg
        self.cfg = cfg
        self.fil = fil = sigproc.Filterbank(cell.infile)
        self.dms = _grid(cfg)
        self.dtype = np.float64 if dtype is None else dtype
        inj = int(round((cell.injected["dm"] - cfg["dm_lo"])
                        / cfg["dm_step"]))
        self.inj_trial = inj
        self.trials = compare.sample_trials(
            cell.seed, len(self.dms), inj, cell.wl["check"]["sample_trials"])
        # mask stage
        (mean, std, maxpow), self.pts = rfimask.file_stats(
            fil, cfg["mask_time"])
        if dtype is not None:
            mean, std, maxpow = (np.asarray(x.astype(dtype), np.float64)
                                 for x in (mean, std, maxpow))
        self.stats = {"mean": mean, "std": std, "maxpow": maxpow}
        flags, self.margin = rfimask.clip(mean, std, maxpow, self.pts)
        self.table = rfimask.zap_table(flags)
        hi_first = self.table[:, ::-1]
        # detection pass and series pass stream with their own overlaps
        plan = dedisp.Plan(self.dms, fil.freqs, fil.tsamp, nsub=cfg["nsub"],
                           widths=tuple(cfg["widths"]),
                           chunk=cfg.get("chunk"))
        self.plan = plan
        self.detection = dedisp.detect(
            fil, plan, self.trials, hi_first, self.pts, dtype=self.dtype)
        splan = dedisp.Plan(self.dms, fil.freqs, fil.tsamp, nsub=cfg["nsub"],
                            widths=(1,), chunk=cfg.get("chunk"))
        self.series = dedisp.series(fil, splan, self.trials, hi_first,
                                    self.pts, dtype=self.dtype)
        self._spectra = {}

    def spectrum(self, i):
        if i not in self._spectra:
            self._spectra[i] = accel.spectrum(self.series[i])
        return self._spectra[i]

    def power(self, i, r, z, H):
        cfg = self.cfg
        return accel.summed_power(self.spectrum(i), r, z, H, cfg["zmax"],
                                  cfg["dz"])

    def profile(self, i, period):
        cfg = self.cfg
        return fold.fold_parts(self.series[i], self.fil.tsamp, period,
                               cfg["fold_nbins"], cfg["fold_npart"])


def _program_outputs(cell, step, ref: Reference) -> dict:
    """What one timed step wrote, for the sampled trials."""
    cfg = cell.cfg
    base = os.path.join(step["outdir"], _stem(cell))
    out = {}
    with np.load(base + "_rfifind.stats.npz") as z:
        out["stats"] = {k: np.asarray(z[k], np.float64)
                        for k in ("mean", "std", "maxpow")}
    out["table"], _ = rfimask.read_mask(base + "_rfifind.mask")
    out["rows"] = compare.parse_cands(base + ".cands")
    out["series"] = [np.fromfile(f"{base}_DM{ref.dms[t]:.2f}.dat", "<f4")
                     for t in ref.trials]
    out["cands"] = [accel.read_cands(
        f"{base}_DM{ref.dms[t]:.2f}_ACCEL_{int(round(cfg['zmax']))}.cand")
        for t in ref.trials]
    out["sifted"] = compare.parse_accelcands(base + ".accelcands")
    out["pfds"] = sorted(glob.glob(base + "_cand*.pfd"))
    with open(base + "_snr.json") as f:
        out["snr_rows"] = json.load(f)
    return out


def _control_outputs(cell, ctl: Reference, prog) -> dict:
    """The control in the program's place: the same tables computed in
    the lower precision, at the candidates the program reported."""
    out = dict(prog)
    out["stats"], out["table"] = ctl.stats, ctl.table
    out["rows"] = compare.rows_from_detection(
        ctl.detection, ctl.dms, ctl.trials, cell.cfg["threshold"])
    out["series"] = list(ctl.series)
    out["control"] = ctl
    return out


def _numbers(cell, ref: Reference, got: dict) -> dict:
    """Every number compared for one set of outputs, by name."""
    cfg, lim = cell.cfg, cell.wl["check"]["limits"]
    ctl = got.get("control")
    n = {}
    n["mask_stats"] = max(compare.rel_gap(got["stats"][k], ref.stats[k])
                          for k in ("mean", "std", "maxpow"))
    # a cell within a thousandth of a threshold may fall either way
    firm = ref.margin > 1e-3
    n["mask_cells"] = float(np.sum((got["table"] != ref.table) & firm)) \
        if got["table"].shape == ref.table.shape else float("nan")
    gap = 0.0
    for i in range(len(ref.trials)):
        s = np.asarray(got["series"][i], np.float64)
        if s.shape != ref.series[i].shape:
            gap = float("nan")
            break
        gap = max(gap, float(np.max(np.abs(s - ref.series[i]))
                             / ref.series[i].std()))
    n["dat_series"] = gap
    n.update(compare.sweep_rows(ref.detection, ref.plan, ref.trials,
                                [got["rows"]], cfg["threshold"], lim))
    # accel: the strongest candidates of every sampled trial
    gap, seen = 0.0, 0
    for i, recs in enumerate(got["cands"]):
        for rec in recs[:cell.wl["check"]["cands_per_trial"]]:
            H = int(round(float(rec["locpow"])))
            want = ref.power(i, float(rec["r"]), float(rec["z"]), H)
            have = float(rec["pow"]) if ctl is None else ctl.power(
                i, float(rec["r"]), float(rec["z"]), H)
            gap = max(gap, abs(have - want) / want)
            seen += 1
    n["accel_power"] = gap if seen else float("nan")
    # fold: every archive at a sampled trial's DM (the best one among them)
    gap, seen = 0.0, 0
    index = {round(float(ref.dms[t]), 2): i
             for i, t in enumerate(ref.trials)}
    for path in got["pfds"]:
        pfd = fold.read_pfd(path)
        i = index.get(round(pfd["dm"], 2))
        if i is None or seen >= cell.wl["check"]["pfds_checked"]:
            continue
        want = ref.profile(i, pfd["period"])
        have = pfd["profs"][:, 0, :] if ctl is None else ctl.profile(
            i, pfd["period"])
        gap = max(gap, float(np.max(np.abs(have - want))
                             / np.max(np.abs(want))))
        seen += 1
    n["fold_profile"] = gap if seen else float("nan")
    # the injected pulsar comes out of sift, fold and snr
    n["not_recovered"], n["snr_shortfall"] = _recovery(cell, got)
    return n


def _recovery(cell, got):
    cfg, inj = cell.cfg, cell.injected
    sifted = got["sifted"]
    if not sifted:
        return 1.0, float("nan")
    best = sifted[0]
    t_obs = inj["nsamp"] * cfg["tsamp"]
    ok = abs(best["dm"] - inj["dm"]) <= cfg["dm_step"] \
        and compare.harmonic_of(best["period"], t_obs,
                                inj["period"] * cfg["tsamp"]) is not None
    # a row per archive; a weak candidate's row may hold no SNR (no bin of
    # its profile stands out), the recovered pulsar's has to
    rows = {os.path.basename(r["pfd"]): r.get("snr")
            for r in got["snr_rows"]}
    missing = [p for p in got["pfds"] if os.path.basename(p) not in rows]
    first = [p for p in got["pfds"] if "_cand0000_" in os.path.basename(p)]
    snr = rows.get(os.path.basename(first[0])) if first else None
    floor = cell.wl["check"]["fold_snr_floor"]
    print(f"recovered: best of {len(sifted)} sifted candidates DM "
          f"{best['dm']:g}, P {best['period'] * 1e3:.4f} ms, sigma "
          f"{best['sigma']:g}; folded SNR {snr} (floor {floor:g}); "
          f"{len(got['pfds'])} archives", flush=True)
    short = float("nan") if snr is None else max(0.0, floor - float(snr))
    return (0.0 if ok and not missing else 1.0), short


def check(cell, control=None) -> list:
    """What every completed step wrote against the float64 chain on a
    sample of trials drawn from the seed; the worst step counts."""
    lim = cell.wl["check"]["limits"]
    ref = Reference(cell)
    steps = [s for s in cell.steps if not s["rc"]]
    if not steps:
        return [("steps_completed", 1.0, 0.0)]
    dtype = compare.lower_dtype(control)
    if dtype is not None:  # the control stands in for one step's outputs
        ctl, steps = Reference(cell, dtype), steps[:1]
    worst = {}
    for step in steps:
        got = _program_outputs(cell, step, ref)
        if dtype is not None:
            got = _control_outputs(cell, ctl, got)
        for name, v in _numbers(cell, ref, got).items():
            prev = worst.get(name, 0.0)
            worst[name] = v if (v != v or v > prev) else prev  # NaN sticks
    return [(name, v, float(lim.get(name, 0.0)))
            for name, v in worst.items()]


def work(cell) -> dict:
    cfg = cell.cfg
    n = cell.injected["nsamp"]
    n_pfd = len(glob.glob(os.path.join(
        cell.steps[-1]["outdir"], _stem(cell) + "_cand*.pfd")))
    return {
        "mask": counts.mask_stats(
            nchan=cfg["nchan"], nsamp=n, nbits=cfg["nbits"],
            ptsperint=max(int(round(cfg["mask_time"] / cfg["tsamp"])), 2)),
        "dedispersion": counts.dedispersion(
            nchan=cfg["nchan"], nsamp=n, nbits=0, trials=cfg["dm_trials"],
            keep_series=True),
        "boxcar": counts.boxcar(nsamp=n, trials=cfg["dm_trials"],
                                widths=len(cfg["widths"])),
        "spectrum_prep": counts.spectrum_prep(nsamp=n,
                                              trials=cfg["dm_trials"]),
        "accel": counts.accel(nsamp=n, trials=cfg["dm_trials"],
                              zmax=cfg["zmax"], dz=cfg["dz"],
                              numharm=cfg["numharm"]),
        "fold": counts.fold(nsamp=n, candidates=n_pfd,
                            nbins=cfg["fold_nbins"],
                            npart=cfg["fold_npart"]),
    }
