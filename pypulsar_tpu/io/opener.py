"""Open a raw-data file with the reader its first bytes ask for.

One answer to "what format is this file?" for every tool that takes raw
data (``sweep``, ``rfifind``, ``tune``, the survey's warm pool): the
SIGPROC ``HEADER_START`` magic or the FITS ``SIMPLE`` card sits in the
first 16 bytes, so nothing past them is read to tell the two apart.
"""

from __future__ import annotations

from typing import Optional

from pypulsar_tpu.obs import telemetry

SNIFF_LEN = 16  # int32 length + "HEADER_START"; "SIMPLE  =" fits inside


def format_of(head: bytes) -> Optional[str]:
    """``"sigproc"``, ``"fits"`` or None from a file's first
    :data:`SNIFF_LEN` bytes: which format the file *claims* (a parse
    that fails after a positive answer is a data error, not an
    unrecognized file)."""
    if head[4:16] == b"HEADER_START":
        return "sigproc"
    if head.startswith(b"SIMPLE"):
        return "fits"
    return None


def open_reader(fn: str):
    """``FilterbankFile`` or ``PsrfitsFile`` for ``fn``, under one
    ``io.open`` span (format sniff + header parse).

    A SIGPROC file never reaches a FITS codec. A FITS file is PSRFITS by
    ``FITSTYPE`` or a ``SUBINT`` extension (headers only). Anything else
    (missing, empty, unrecognized, FITS but not PSRFITS) goes to
    ``FilterbankFile``, whose parse fails with the located error.
    ``io.sniff_bytes`` counts what was read to decide."""
    from pypulsar_tpu.io import filterbank, psrfits

    with telemetry.span("io.open") as sp:
        try:
            with open(fn, "rb") as f:
                head = f.read(SNIFF_LEN)
        except OSError:
            head = b""
        fmt = format_of(head)
        nread = len(head)
        if fmt == "fits":
            is_psrfits, nhdr = psrfits.sniff_PSRFITS(fn)
            nread += nhdr
            if not is_psrfits:
                fmt = None
        telemetry.counter("io.sniff_bytes", nread)
        if sp is not None:
            sp.set(format=fmt or "unknown", sniff_bytes=nread)
        if fmt == "fits":
            return psrfits.PsrfitsFile(fn)
        return filterbank.FilterbankFile(fn)
