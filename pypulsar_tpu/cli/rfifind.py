"""Native RFI mask generator CLI (PRESTO ``rfifind`` equivalent).

The reference pipeline consumes ``.mask`` files (bin/waterfaller.py:28-48)
that only PRESTO's external C ``rfifind`` could produce — one of the L0
dependencies SURVEY.md marks for replacement. This tool generates them
natively: device block statistics + host sigma clipping
(ops/rfifind.py), written in the reference binary layout so both our
tools (waterfaller --mask, sweep --mask) and PRESTO's can read them.

Flag names follow PRESTO's rfifind (-time/-timesig/-freqsig/-chanfrac/
-intfrac/-zapchan/-zapints/-o) in argparse form.
"""

from __future__ import annotations

import argparse
import sys


def parse_int_list(text: str):
    """'2,5,7:10' -> [2, 5, 7, 8, 9, 10] (PRESTO-style ranges)."""
    out = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            lo, hi = part.split(":")
            out.extend(range(int(lo), int(hi) + 1))
        else:
            out.append(int(part))
    return out


def build_parser():
    parser = argparse.ArgumentParser(
        prog="rfifind.py",
        description="Generate an rfifind-compatible RFI mask from a "
                    "filterbank or PSRFITS file (TPU backend).")
    parser.add_argument("infile", help="input .fil or .fits file")
    parser.add_argument("-o", "--outbase", required=True,
                        help="output basename (writes "
                             "<outbase>_rfifind.mask + .stats.npz)")
    parser.add_argument("-t", "--time", type=float, default=1.0,
                        help="seconds per statistics interval "
                             "(default: %(default)s)")
    parser.add_argument("--timesig", type=float, default=10.0,
                        help="time-domain clip threshold in sigma "
                             "(default: %(default)s)")
    parser.add_argument("--freqsig", type=float, default=4.0,
                        help="Fourier-power clip threshold in equivalent "
                             "Gaussian sigma (default: %(default)s)")
    parser.add_argument("--chanfrac", type=float, default=0.7,
                        help="zap a whole channel when more than this "
                             "fraction of its intervals are bad "
                             "(default: %(default)s)")
    parser.add_argument("--intfrac", type=float, default=0.3,
                        help="zap a whole interval when more than this "
                             "fraction of its channels are bad "
                             "(default: %(default)s)")
    parser.add_argument("--zapchan", type=parse_int_list, default=[],
                        help="extra channels to zap, e.g. '2,5,7:10', in "
                             "MASK channel order (channel 0 = lowest "
                             "frequency, the PRESTO convention — the "
                             "reverse of on-disk order for foff<0 files)")
    parser.add_argument("--zapints", type=parse_int_list, default=[],
                        help="extra intervals to zap")
    from pypulsar_tpu.obs import telemetry

    telemetry.add_telemetry_flag(
        parser, what="block-stats spans, D2H counters, device stats")
    return parser


def main(argv=None):
    args = build_parser().parse_args(argv)
    from pypulsar_tpu.io.opener import open_reader
    from pypulsar_tpu.obs import telemetry
    from pypulsar_tpu.ops.rfifind import rfifind

    with telemetry.session_from_flag(args.telemetry, tool="rfifind"), \
            telemetry.span("cli.rfifind", aggregate=False):
        reader = open_reader(args.infile)
        try:
            stats, flags, maskfn = rfifind(
                reader, time=args.time, time_sigma=args.timesig,
                freq_sigma=args.freqsig, chanfrac=args.chanfrac,
                intfrac=args.intfrac, zap_chans=args.zapchan,
                zap_ints=args.zapints, outbase=args.outbase,
            )
        finally:
            reader.close()
    print(f"wrote {maskfn}: {stats.nint} intervals x {stats.nchan} "
          f"channels, {float(flags.mean()) * 100:.2f}% of blocks flagged, "
          f"mask covers {stats.mask_coverage * 100:.2f}% of the data")
    return 0


if __name__ == "__main__":
    sys.exit(main())
