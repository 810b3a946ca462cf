"""``python -m pypulsar_tpu.cli tune`` — inspect, build and clear the
auto-tuning cache (round 17).

Modes (one required):

- ``--show``: render every cache entry (key, tuned config, provenance);
- ``--search``: run the bounded coordinate-descent search for the
  given ``--stage`` list at an explicit geometry (``--nchan/--nsamp/
  --zmax`` or derived from ``--file obs.fil``), persisting winners to
  the cache the pipeline entry points consult automatically;
- ``--clear``: drop all entries (or one ``--stage``'s).

The same machinery runs on-line when ``PYPULSAR_TPU_TUNE=search`` is
set (a stage's first run at a new geometry pays the bounded trial
budget, every later run is a pure cache hit) — this CLI is for warming
the cache deliberately, e.g. once per fleet geometry before a survey.
"""

from __future__ import annotations

import argparse
import json

from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.resilience import faultinject


def build_parser():
    p = argparse.ArgumentParser(
        prog="tune.py",
        description="Auto-tuning cache: show/search/clear (tune/ "
                    "subsystem; see README 'Auto-tuning').")
    mode = p.add_mutually_exclusive_group(required=True)
    mode.add_argument("--show", action="store_true",
                      help="render the cache entries and exit")
    mode.add_argument("--search", action="store_true",
                      help="run the bounded search for --stage at the "
                           "given geometry and persist the winners")
    mode.add_argument("--clear", action="store_true",
                      help="drop cache entries (all, or one --stage's)")
    p.add_argument("--stage", default=None,
                   help="comma list of stages (--search default: "
                        "sweep,accel — the stages with searchable knob "
                        "domains; --clear default: every stage)")
    p.add_argument("--cache", default=None, metavar="PATH",
                   help="cache file (default: PYPULSAR_TPU_TUNE_CACHE "
                        "or ~/.cache/pypulsar_tpu/tune.json)")
    g = p.add_argument_group("search geometry")
    g.add_argument("--file", default=None, metavar="OBS",
                   help="derive --nchan/--nsamp from this filterbank/"
                        "PSRFITS header instead of passing them")
    g.add_argument("--nchan", type=int, default=64)
    g.add_argument("--nsamp", type=int, default=1 << 16,
                   help="series length in samples (bucketed to the "
                        "next power of two in the cache key)")
    g.add_argument("--nbits", type=int, default=32,
                   help="input sample width the sweep key carries "
                        "(derived from --file when given; must match "
                        "the observations the cache will serve)")
    g.add_argument("--zmax", type=int, default=200,
                   help="accel-stage zmax the cache entry keys on")
    g.add_argument("--numharm", type=int, default=2, choices=(1, 2, 4, 8))
    g.add_argument("--dm-count", type=int, default=32,
                   help="DM trials the sweep measure dedisperses")
    g.add_argument("--nspec", type=int, default=16,
                   help="spectra the accel measure preps+searches")
    g.add_argument("--engine", default=None,
                   help="sweep engine the entry keys on (default: the "
                        "resolved auto engine for this backend)")
    g.add_argument("--trials", type=int, default=None,
                   help="trial budget per stage (default: the "
                        "PYPULSAR_TPU_TUNE_TRIALS knob, 20)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    telemetry.add_telemetry_flag(
        p, what="tune.trials counters, tune.winner events")
    return p


def _geometry(args, ap):
    """(nchan, nsamp, dtype) the cache keys carry — EXACTLY the fields
    cli/sweep's consult derives from its open reader, so a warmed entry
    is the entry the pipeline run will hit."""
    if not args.file:
        return args.nchan, args.nsamp, "nbits%d" % args.nbits
    from pypulsar_tpu.io.opener import open_reader

    try:
        reader = open_reader(args.file)
        import numpy as np

        return (len(np.asarray(reader.frequencies)),
                int(getattr(reader, "nsamples", 0) or args.nsamp),
                "nbits%d" % int(getattr(reader, "nbits", 32) or 32))
    except Exception as e:  # noqa: BLE001 - argparse-style exit
        ap.error("--file %s: %s: %s" % (args.file, type(e).__name__, e))


def _show(cache, as_json: bool) -> int:
    entries = cache.entries()
    if as_json:
        print(json.dumps({"path": cache.path, "entries": entries},
                         indent=1, sort_keys=True))
        return 0
    print("# tuning cache: %s (%d entries)" % (cache.path, len(entries)))
    for key in sorted(entries):
        ent = entries[key]
        meta = ent.get("meta", {})
        cfg = " ".join("%s=%s" % (k.replace("PYPULSAR_TPU_", ""), v)
                       for k, v in sorted(ent.get("config", {}).items()))
        extra = ""
        if meta.get("baseline_s") and meta.get("best_s"):
            extra = "  %.4fs -> %.4fs (%.2fx, %d trials)" % (
                meta["baseline_s"], meta["best_s"],
                meta.get("speedup", 0.0), meta.get("n_trials", 0))
        print("#   %s\n#     %s%s" % (key, cfg or "(defaults won)",
                                      extra))
    return 0


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    faultinject.configure_from_env()
    from pypulsar_tpu.tune import TuneCache, autotune

    cache = TuneCache(args.cache)
    if args.show:
        return _show(cache, args.json)
    stages = [s.strip() for s in (args.stage or "sweep,accel").split(",")
              if s.strip()]
    if args.clear:
        for stage in (stages if args.stage else [None]):
            n = cache.clear(stage)
            print("# cleared %d entr%s%s from %s"
                  % (n, "y" if n == 1 else "ies",
                     " (stage %s)" % stage if stage else "", cache.path))
        return 0
    # --search
    nchan, nsamp, dtype = _geometry(args, ap)
    engine = args.engine
    if engine is None:
        from pypulsar_tpu.parallel.sweep import resolve_engine

        engine = resolve_engine("auto")
    results = {}
    with telemetry.session_from_flag(args.telemetry, tool="tune"):
        for stage in stages:
            from pypulsar_tpu.tune.stages import measure_for_stage

            try:
                measure = measure_for_stage(
                    stage, nchan=nchan, nsamp=nsamp, zmax=args.zmax,
                    engine=engine, ndm=args.dm_count, nspec=args.nspec,
                    numharm=args.numharm)
            except ValueError as e:
                ap.error(str(e))
            applied = autotune(
                stage, nchan=(nchan if stage == "sweep" else None),
                nsamp=nsamp, zmax=(args.zmax if stage == "accel"
                                   else None),
                dtype=(dtype if stage == "sweep" else None),
                engine=(engine if stage == "sweep" else None),
                measure=measure, cache=cache, budget=args.trials,
                force_search=True, verbose=not args.json)
            results[stage] = applied
            if not args.json:
                cfg = " ".join(
                    "%s=%s" % (k.replace("PYPULSAR_TPU_", ""), v)
                    for k, v in sorted(applied.items()))
                print("# tune[%s]: winner %s" % (stage,
                                                 cfg or "(defaults)"))
    if args.json:
        print(json.dumps({"cache": cache.path, "tuned": results},
                         indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
