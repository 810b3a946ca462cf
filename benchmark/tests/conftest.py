"""The benchmark's own tests: run by hand (and in the chip rehearsal) with

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They sit outside ``tests/``: the repository's tier-1 count does not change.
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def run_cell_main(capfd):
    """Run the harness in-process; returns (exit code, result line)."""
    import run_cell

    def run(*argv):
        rc = run_cell.main(list(argv))
        out = capfd.readouterr().out.strip().splitlines()
        try:
            result = json.loads(out[-1]) if out else None
        except json.JSONDecodeError:
            result = None
        return rc, result

    return run
