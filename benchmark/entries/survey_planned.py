"""Entry ``survey_planned``: entry ``survey``, letter for letter, for a
deployment whose block and chunk lengths the program has to plan from the
channel count and the device's memory (``pypulsar_tpu/plan/lengths.py``).

A program without that planner cannot run such a deployment and does not
say so soon: at 4096 channels its mask stage asks the compiler for 16.00 G
of a v5e's 15.75 G, the observation is quarantined after 162 s, and the
harness prints a result (exit code 0, no step completed) 7.5 minutes after
it started (the parent of PR 34 on ``gbncc-350.search``). This entry asks
first and refuses such a program at once, before any input is made, with
exit code 2; everything else is ``entries/survey.py``'s.
"""

from __future__ import annotations

import sys

from entries import survey
from entries.survey import (  # noqa: F401 - the entry's interface
    check,
    cli_main,
    fallbacks,
    run,
    telemetry_files,
    work,
)


def prepare(cell) -> None:
    try:
        from pypulsar_tpu.plan import lengths  # noqa: F401
    except ImportError as e:
        print(f"refused: this program plans no block or chunk length from "
              f"the observation ({e}); it cannot hold "
              f"{cell.cfg['nchan']} channels", file=sys.stderr, flush=True)
        raise SystemExit(2)
    survey.prepare(cell)
