"""Fourier-domain acceleration search over a .dat / .fft file.

Fills the reference pipeline's missing stage (the reference shells out to
PRESTO's ``accelsearch`` and only consumes its ``*_ACCEL_*.cand`` output —
``bin/plot_accelcands.py:50-71``, ``formats/accelcands.py``).  Pipeline:

  .dat (or pre-computed .fft) -> rfft -> deredden (red-noise normalize)
  -> optional zaplist masking -> (r, z) matched-template search with
  harmonic summing (fourier/accelsearch.py) -> ``<base>_ACCEL_<zmax>.cand``
  (PRESTO fourierprops records readable by cli/plot_accelcands) +
  ``<base>_ACCEL_<zmax>.txtcand`` human-readable summary.

Flag names follow PRESTO's accelsearch where they exist (-zmax, -numharm,
-sigma, -flo, -fhi).
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

from pypulsar_tpu.fourier.accelsearch import AccelSearchConfig, accel_search
from pypulsar_tpu.fourier.kernels import deredden, deredden_schedule
from pypulsar_tpu.io.infodata import InfoData
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.tune import knobs

# sentinel: "this input must take the host prep path" — distinct from None
# ("skipped") so the batch dispatch below cannot confuse the two (the old
# string-compare dispatch was fragile, ADVICE r5)
_HOST = object()


def load_spectrum(fn: str):
    """(complex spectrum, T seconds, base filename) from a .dat or .fft."""
    base, ext = os.path.splitext(fn)
    inf = InfoData(base + ".inf")
    if ext == ".dat":
        from pypulsar_tpu.io.datfile import Datfile

        dat = Datfile(fn)
        series = dat.read_all()
        fft = np.fft.rfft(series)
        n = len(series)
    elif ext == ".fft":
        from pypulsar_tpu.fourier.prestofft import PrestoFFT

        pf = PrestoFFT(fn, inffn=base + ".inf")
        fft = pf.fft
        n = int(inf.N)
    else:
        raise ValueError(f"expected a .dat or .fft file, got {fn!r}")
    T = n * float(inf.dt)
    return np.asarray(fft), T, base


def zap_spectrum(fft: np.ndarray, T: float, zapfile: str) -> np.ndarray:
    """Replace zaplist intervals (centre/width Hz rows, reference
    bin/autozap.py:262-287 format) with unit-power noise-free zeros."""
    fft = fft.copy()
    for line in open(zapfile):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        parts = line.split()
        fc, w = float(parts[0]), float(parts[1])
        lo = max(int(np.floor((fc - w / 2) * T)), 0)
        hi = min(int(np.ceil((fc + w / 2) * T)) + 1, len(fft))
        if hi > lo:
            fft[lo:hi] = 0.0
    return fft


def build_parser():
    p = argparse.ArgumentParser(
        prog="accelsearch.py",
        description="Search an FFT or time series for accelerated periodic "
                    "signals (TPU backend).")
    p.add_argument("infiles", nargs="+", metavar="infile",
                   help=".dat or .fft file(s) with matching .inf; a "
                        "multi-file run amortizes template banks and "
                        "compiled search programs over the whole DM set")
    p.add_argument("--skip-existing", action="store_true",
                   help="skip inputs whose candidate file already exists "
                        "(restartable batch runs)")
    p.add_argument("-b", "--batch", type=_batch_arg, default=1,
                   help="search this many same-length spectra per device "
                        "dispatch against the shared template banks "
                        "(fourier.accelsearch.accel_search_batch; measured "
                        "6x the serial rate at batch 32 on a v5e — the "
                        "per-DM spectra of one observation all qualify). "
                        "Inputs whose (bins, T) differ flush the pending "
                        "group and start a new one. 'auto' takes the "
                        "tuned default from the PYPULSAR_TPU_ACCEL_BATCH "
                        "knob (auto-tuning cache > registry default 32; "
                        "an explicit number here always wins). "
                        "Default 1 = serial")
    p.add_argument("-z", "--zmax", type=float, default=200.0,
                   help="max drift in Fourier bins over the observation "
                        "(default 200)")
    p.add_argument("--dz", type=float, default=2.0,
                   help="drift step in bins (default 2)")
    p.add_argument("--coarse-dz", type=float, default=0.0,
                   help="coarse-to-fine z search: first scan every stage "
                        "at this z step with the power threshold scaled "
                        "by --coarse-frac, then re-search only the "
                        "segments with coarse hits at the fine --dz "
                        "(2*dz keeps >=~84%% of matched power at the "
                        "nearest coarse template, so the preselection "
                        "loses nothing above threshold). 0 = single pass")
    p.add_argument("--coarse-frac", type=float, default=0.7,
                   help="coarse-pass power-threshold fraction "
                        "(default 0.7; lower = safer recall, more "
                        "refine work)")
    p.add_argument("--device-prep", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="with --batch: rfft + deredden each group on "
                        "DEVICE in one fused dispatch (kernels."
                        "prep_spectra_batch) and hand the spectra to the "
                        "search without leaving HBM, instead of "
                        "np.fft.rfft per file on the host plus a "
                        "deredden round trip. 2-3x the end-to-end rate "
                        "on a 1-core host; DEFAULT ON for --batch >= 2 "
                        "under the matched-candidate contract (every "
                        "candidate above the floor matches host prep "
                        "within (dr, dz, dsig) bounds — enforced by "
                        "tests/test_accelsearch.py::test_device_prep_"
                        "candidate_contract; see README). "
                        "--no-device-prep restores the byte-parity host "
                        "path. Ignored for .fft inputs, --zapfile, or "
                        "--no-deredden (host prep used)")
    p.add_argument("--prefetch", type=int, default=4, metavar="N",
                   help="with --batch: read + prep up to N inputs AHEAD "
                        "of the device search on a background thread "
                        "(parallel.prefetch), overlapping the .dat read/"
                        "host prep of batch N+1 with the device search "
                        "of batch N — the round-5 A/B measured 6.4 of "
                        "8.7 s/spectrum of serial host time without "
                        "this. Queue fill lands on the accel.prep."
                        "pending_depth telemetry gauge. 0 = inline "
                        "(single-threaded debugging). Default 4")
    p.add_argument("-w", "--wmax", type=float, default=0.0,
                   help="max jerk in bins over T^3 (0 = no w search; "
                        "cost scales with the w grid size)")
    p.add_argument("--dw", type=float, default=20.0,
                   help="jerk step in bins (default 20)")
    p.add_argument("-n", "--numharm", type=int, default=8,
                   choices=(1, 2, 4, 8),
                   help="max harmonics summed (default 8)")
    p.add_argument("-s", "--sigma", type=float, default=2.0,
                   help="candidate significance threshold (default 2)")
    p.add_argument("--flo", type=float, default=1.0,
                   help="lowest searched frequency, Hz (default 1)")
    p.add_argument("--fhi", type=float, default=None,
                   help="highest searched frequency, Hz (default Nyquist)")
    p.add_argument("--zapfile", default=None,
                   help="zaplist of RFI intervals to blank before searching")
    p.add_argument("--no-deredden", action="store_true",
                   help="input spectrum is already normalized")
    p.add_argument("--max-cands", type=int, default=200,
                   help="cap on written candidates (default 200)")
    p.add_argument("-o", "--outbase", default=None,
                   help="output base name (default: input base)")
    telemetry.add_telemetry_flag(
        p, what="prep/search/write spans, batch counters, fallbacks")
    from pypulsar_tpu.resilience import faultinject

    faultinject.add_fault_flag(p)
    return p


def _out_names(infile, args):
    """(candfn, txtfn) for one input under the current flags (the naming
    itself lives in parallel.accelpipe, shared with the streamed
    sweep->accel handoff so the two paths' artifacts cannot diverge)."""
    from pypulsar_tpu.parallel.accelpipe import accel_out_names

    outbase = args.outbase or os.path.splitext(infile)[0]
    return accel_out_names(outbase, args.zmax, args.wmax)


def prepare_one(infile, args):
    """(normalized complex spectrum, T) for one input, or None when the
    output already exists under --skip-existing (decided without IO:
    restarting a large batch must not re-read and re-FFT every
    already-searched file)."""
    if _skip_existing(infile, args):
        return None
    fft, T, _ = load_spectrum(infile)
    N = len(fft)
    print(f"# {infile}: {N} bins, T = {T:.1f} s", file=sys.stderr)
    if args.no_deredden:
        norm = fft.astype(np.complex64)
    else:
        norm = np.asarray(deredden(fft.astype(np.complex64),
                                   schedule=deredden_schedule(N)))
    if args.zapfile:
        norm = zap_spectrum(norm, T, args.zapfile)
    return norm, T


def write_results(infile, cands, T, args):
    """Write the per-input .txtcand + .cand pair; returns the .cand path.
    The format lives in parallel.accelpipe.write_candfiles, shared with
    the streamed sweep->accel handoff (one definition of the artifact)."""
    from pypulsar_tpu.parallel.accelpipe import write_candfiles

    candfn, txtfn = _out_names(infile, args)
    write_candfiles(candfn, txtfn, cands, T, args.max_cands)
    print(f"# wrote {len(cands[:args.max_cands])} candidates to {candfn} "
          f"and {txtfn}", file=sys.stderr)
    return candfn


def _skip_existing(infile, args) -> bool:
    """True when --skip-existing says this input's .cand is already done
    (shared by both prep paths so skip semantics can't diverge).

    Existence is not completion: the .cand must VALIDATE (whole
    fourierprops records, .txtcand twin with matching row count —
    resilience.candfile_complete) or the input is re-searched. A
    zero-byte .cand from a killed run used to be treated as done, which
    permanently wedged that trial out of every restarted batch."""
    if not args.skip_existing:
        return False
    from pypulsar_tpu.resilience.journal import candfile_complete

    candfn, txtfn = _out_names(infile, args)
    if candfile_complete(candfn, txtfn):
        print(f"# {infile}: {candfn} exists, skipping", file=sys.stderr)
        return True
    if os.path.exists(candfn):
        print(f"# {infile}: {candfn} exists but FAILS validation "
              f"(truncated or killed run?); re-searching", file=sys.stderr)
    return False


def prepare_one_series(infile, args):
    """(raw float32 time series, T) for one .dat input — the device-prep
    batch path defers rfft + deredden to the grouped device dispatch.
    Returns None when skipped, or the ``_HOST`` sentinel when this input
    cannot use device prep (.fft input, --zapfile, --no-deredden)."""
    if _skip_existing(infile, args):
        return None
    if (os.path.splitext(infile)[1] != ".dat" or args.zapfile
            or args.no_deredden):
        return _HOST
    from pypulsar_tpu.io.datfile import Datfile

    base = os.path.splitext(infile)[0]
    inf = InfoData(base + ".inf")
    series = np.asarray(Datfile(infile).read_all(), dtype=np.float32)
    T = len(series) * float(inf.dt)
    print(f"# {infile}: {len(series) // 2 + 1} bins, T = {T:.1f} s "
          f"(device prep)", file=sys.stderr)
    return series, T


def search_one(infile, cfg, args):
    """Search one input; returns the written .cand path (or None if
    skipped)."""
    with telemetry.span("accel_prep_host", infile=infile):
        prep = prepare_one(infile, args)
    if prep is None:
        return None
    norm, T = prep
    with telemetry.span("accel_search", aggregate=False, batch=1):
        cands = accel_search(norm, T, cfg)
    with telemetry.span("accel_write"):
        return write_results(infile, cands, T, args)


def _batch_arg(value: str):
    """--batch value: an int, or 'auto' for the tuned registry default
    (resolved AFTER the tuning-cache consult in main, so a cached
    winner for this geometry takes effect)."""
    if value == "auto":
        return "auto"
    try:
        return int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            "--batch expects an integer or 'auto', got %r" % (value,))


def _apply_tuning(args) -> None:
    """Round-17 auto-tuning consult: install the cached throughput
    config for this stage geometry (tune/cache.py key: nsamp bucket,
    zmax, backend, jax version), then resolve --batch 'auto' through
    the registry so a cached winner takes effect. Env vars and explicit
    flags still win; PYPULSAR_TPU_TUNE=off disables the consult."""
    from pypulsar_tpu import tune

    nsamp = None
    try:
        sz = os.path.getsize(args.infiles[0])
        # .dat: f32 samples; .fft: N/2+1 complex64 bins of an N-sample
        # series (prestofft layout) -> N = (bins - 1) * 2, so the key
        # buckets to the same power of two as the equivalent .dat
        nsamp = (sz // 4 if not args.infiles[0].endswith(".fft")
                 else max(1, sz // 8 - 1) * 2)
    except OSError:
        pass  # missing input fails later with the real reader error
    tune.apply_cached("accel", nsamp=nsamp, zmax=int(args.zmax))
    if args.batch == "auto":
        args.batch = max(1, knobs.env_int("PYPULSAR_TPU_ACCEL_BATCH"))


def main(argv=None):
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.outbase and len(args.infiles) > 1:
        parser.error("-o/--outbase only applies to a single input file")
    _apply_tuning(args)
    if args.device_prep and args.batch < 2:
        # silently ignoring the flag hid a 2-3x perf knob (ADVICE r5):
        # device prep only exists on the grouped batch dispatch
        parser.error("--device-prep only takes effect with --batch >= 2 "
                     "(device prep is the grouped-dispatch path)")
    if args.device_prep is None:
        # default-on for the grouped path (VERDICT r5 item 2): the
        # matched-candidate contract is test-enforced, so the faster
        # prep is the path of record; --no-device-prep opts out
        args.device_prep = args.batch >= 2
    cfg = AccelSearchConfig(
        zmax=args.zmax, dz=args.dz, numharm=args.numharm,
        sigma_min=args.sigma, flo=args.flo, fhi=args.fhi,
        wmax=args.wmax, dw=args.dw,
        coarse_dz=args.coarse_dz, coarse_power_frac=args.coarse_frac,
    )
    from pypulsar_tpu.resilience import faultinject

    faultinject.configure_from_env()
    if args.fault_inject:
        faultinject.configure(args.fault_inject)
    with telemetry.session_from_flag(args.telemetry, tool="accelsearch"):
        return _run(args, cfg)


def _run(args, cfg):
    # template banks (fourier.accelsearch._build_ratio_bank), deredden
    # schedules and compiled stage programs are process-cached: searching
    # many per-DM files in one invocation pays setup once
    done, failed = 0, 0

    def fail(infile, e):
        nonlocal failed
        if len(args.infiles) == 1:
            raise e
        failed += 1
        print(f"# {infile} FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)

    if args.batch > 1:
        from pypulsar_tpu.fourier.accelsearch import accel_search_batch

        # groups of same-geometry spectra search in one device dispatch
        # a chunk; a (bins, T), prep-kind, or full-group boundary flushes
        group: list = []  # (infile, payload, T, kind); kind in {norm,series}

        def flush():
            nonlocal done
            if not group:
                return
            names = [g[0] for g in group]
            T = group[0][2]
            try:
                if group[0][3] == "series":
                    from pypulsar_tpu.fourier.kernels import \
                        prep_spectra_batch

                    # bound prep residency by the same knob that chunks
                    # the search: series + plane + rfft workspace is
                    # ~24 bytes/sample per spectrum, and the whole
                    # prepped slice lives in HBM until its search ends
                    n1 = len(group[0][1])
                    budget = int(
                        knobs.env_float("PYPULSAR_TPU_ACCEL_HBM"))
                    cap = max(1, budget // (24 * n1))
                    all_cands = []
                    for c0 in range(0, len(group), cap):
                        with telemetry.span("accel_prep_device",
                                            batch=len(group[c0:c0 + cap])):
                            stacked = np.stack(
                                [g[1] for g in group[c0:c0 + cap]])
                            planes = prep_spectra_batch(stacked)
                        with telemetry.span("accel_search", aggregate=False,
                                            batch=len(group[c0:c0 + cap])):
                            all_cands.extend(accel_search_batch(
                                planes, T, cfg))
                else:
                    with telemetry.span("accel_search", aggregate=False,
                                        batch=len(group)):
                        all_cands = accel_search_batch(
                            np.stack([g[1] for g in group]), T, cfg)
            except Exception as e:  # noqa: BLE001 - fall back to serial:
                from pypulsar_tpu.resilience import health

                if health.no_degrade(e):
                    # watchdog interrupts, chip-indicting and injected
                    # faults escalate to the caller's retry machinery
                    # instead of degrading to the serial path
                    raise
                # one poison spectrum must fail alone, not take down (and,
                # under --skip-existing restarts, permanently wedge) its
                # whole group
                telemetry.counter("accel.serial_fallbacks")
                telemetry.event("accel.batch_serial_fallback",
                                n=len(group), kind=group[0][3],
                                error=type(e).__name__)
                print(f"# batch of {len(group)} failed "
                      f"({type(e).__name__}: {e}); retrying serially",
                      file=sys.stderr)
                for fn, payload, T1, kind in group:
                    try:
                        if kind == "series":
                            prep1 = prepare_one(fn, args)
                            if prep1 is None:  # e.g. --skip-existing saw
                                continue       # a .cand written meanwhile
                            norm1, T1 = prep1
                        else:
                            norm1 = payload
                        write_results(fn, accel_search(norm1, T1, cfg),
                                      T1, args)
                        done += 1
                    except Exception as e1:  # noqa: BLE001
                        fail(fn, e1)
                group.clear()
                return
            for fn, cands in zip(names, all_cands):
                try:
                    with telemetry.span("accel_write"):
                        write_results(fn, cands, T, args)
                    done += 1
                except Exception as e:  # noqa: BLE001
                    fail(fn, e)
            group.clear()

        def prepped_inputs():
            """Per-file host prep as a stream: each yield is either a
            ready (infile, payload, T, kind, None) record or the file's
            prep error (infile, None, None, None, exc) — errors travel
            as values so the per-file failure policy stays with the
            consumer even when prep runs on the prefetch thread. The
            prep (the actual .dat/.fft read) runs under the transient-IO
            retry policy: one NFS hiccup must not mark the file failed
            for the whole restartable batch."""
            from pypulsar_tpu.resilience.retry import retry_transient

            for infile in args.infiles:
                try:
                    with telemetry.span("accel_prep_host", infile=infile):
                        def attempt(infile=infile):
                            p = (prepare_one_series(infile, args)
                                 if args.device_prep else _HOST)
                            if p is _HOST:  # explicit host-path sentinel
                                return prepare_one(infile, args), "norm"
                            return p, "series"

                        prep, kind = retry_transient(attempt, retries=2,
                                                     what="accel.read")
                except Exception as e:  # noqa: BLE001 - consumer decides
                    yield infile, None, None, None, e
                    continue
                if prep is None:  # skipped (--skip-existing)
                    continue
                payload, T = prep
                yield infile, payload, T, kind, None

        # the pipeline (tentpole of VERDICT r5 item 1b): prep of input
        # N+k rides a background thread while the device searches the
        # current group — the .dat read + rfft/deredden host time that
        # measured 6.4 of 8.7 s/spectrum serial overlaps the search.
        # Queue fill -> accel.prep.pending_depth gauge (tlmsum shows it)
        if args.prefetch > 0:
            from pypulsar_tpu.parallel.prefetch import prefetch

            source = prefetch(prepped_inputs(), depth=args.prefetch,
                              name="accel.prep", retries=2)
        else:
            source = prepped_inputs()
        for infile, payload, T, kind, err in source:
            if err is not None:
                fail(infile, err)
                continue
            if group and (kind != group[0][3]
                          or len(payload) != len(group[0][1])
                          or abs(T - group[0][2]) > 1e-9):
                flush()
            group.append((infile, payload, T, kind))
            if len(group) >= args.batch:
                flush()
        flush()
    else:
        for infile in args.infiles:
            try:
                if search_one(infile, cfg, args) is not None:
                    done += 1
            except Exception as e:  # noqa: BLE001 - one bad file must not
                # abort a restartable batch; report and continue
                fail(infile, e)
    if len(args.infiles) > 1:
        print(f"# searched {done}/{len(args.infiles)} files"
              + (f" ({failed} failed)" if failed else ""), file=sys.stderr)
    return 0 if failed == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
