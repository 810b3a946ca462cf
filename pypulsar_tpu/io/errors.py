"""The data-format error hierarchy every reader raises.

Real telescope recordings arrive truncated, bit-flipped and padded with
garbage (dropped packets are the NORM for live transient surveys,
PAPERS.md 1601.01165) — and before round 13 the readers answered that
with raw ``struct.error`` / ``IndexError`` / silent nonsense, because
``struct.unpack`` at EOF sees ``b''`` and headers were trusted verbatim.
This module is the one vocabulary for "the bytes are wrong":

- :class:`DataFormatError` — a ``ValueError`` subclass (existing
  ``except ValueError`` handlers keep working) carrying the *path*, the
  byte *offset* where parsing failed, and a human-readable detail. The
  reader-fuzz contract (tests/test_dataguard.py) is that every reader,
  fed arbitrary mutated bytes, either parses (possibly salvaging a
  prefix) or raises exactly this — never a hang, never a raw codec
  exception, never a crash.
- :func:`read_exact` — the bounds-checked replacement for the bare
  ``f.read(n)`` + ``struct.unpack`` pairs: a short read at EOF raises a
  located :class:`DataFormatError` instead of ``struct.error: unpack
  requires a buffer``.

The salvage half of the contract (read the whole valid prefix, report
the missing span) lives on the readers themselves (``reader.salvage``,
a plain dict) and is rolled up by :mod:`pypulsar_tpu.resilience.
dataguard`.
"""

from __future__ import annotations

from typing import BinaryIO, Optional

__all__ = ["DataFormatError", "read_exact"]


class DataFormatError(ValueError):
    """The input file's bytes violate its format contract.

    Subclasses ``ValueError`` so existing callers that classify reader
    failures broadly (``is_PSRFITS``'s sniff, CLI error paths) keep
    working; new code should catch this type and treat it as "the INPUT
    is bad" — retrying cannot help, but the survey can quarantine the
    observation with reason ``"data"`` and move on.
    """

    def __init__(self, path: str, detail: str,
                 offset: Optional[int] = None):
        self.path = path
        self.offset = offset
        self.detail = detail
        loc = f" at byte {offset}" if offset is not None else ""
        super().__init__(f"{path}{loc}: {detail}")


def read_exact(f: BinaryIO, n: int, path: str, what: str) -> bytes:
    """``f.read(n)`` that raises a located :class:`DataFormatError` on a
    short read — the EOF-mid-field case that used to surface as a bare
    ``struct.error`` with no filename or offset."""
    pos = f.tell()
    data = f.read(n)
    if len(data) != n:
        raise DataFormatError(
            path, f"truncated while reading {what}: wanted {n} bytes, "
                  f"got {len(data)}", offset=pos)
    return data
