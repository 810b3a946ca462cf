"""Data-integrity layer: validity through the device chain, finite
gates, corruption tooling.

The resilience layer so far hardens the *runtime* (OOM/IO/kill recovery,
watchdogs, device quarantine) but trusted its *input bytes*. Real
telescope recordings are dirty — dropped packets, truncated tails,
saturated or zeroed blocks are the norm for live transient surveys
(PAPERS.md 1601.01165), which is why the reference pipeline carries the
whole rfifind/mask machinery. This module is the data-plane counterpart
of :mod:`.health`:

- **Stream scrub** (:func:`guard_source` / :class:`GuardedSource`) —
  decorates the staged block sources so every float chunk passes a
  cheap fused ``isfinite`` reduction ON DEVICE: non-finite cells are
  zero-filled (rfifind-mask semantics: flagged data contributes
  nothing) and accounted in the ``data.*`` telemetry counters, so a NaN
  born in one chunk can never silently propagate into SNRs. Integer
  sources (uint filterbanks) cannot hold non-finite values and pass
  through unwrapped — the guard costs the hot 8-bit path nothing.
- **Finite-output gates** (:func:`finite_rows` / :func:`finite_cands`)
  — the candidate and SNR writers filter non-finite rows (counted as
  ``data.nonfinite_cands_dropped``), so a non-finite value provably
  cannot reach a ``.cands``/``.cand``/``.txtcand`` file or a SNR row.
- **Ingest validation** (:func:`validate_input`) — the survey DAG's
  admission check: recognized formats get a cheap header + size
  cross-check and return a data-quality report (salvaged span, masked
  fraction denominators); a recognized-but-broken file raises
  :class:`~pypulsar_tpu.io.errors.DataFormatError` and the scheduler
  quarantines the observation with reason ``"data"`` (distinct from
  runtime quarantine) instead of burning retries on it.
- **Corruption tooling** (:func:`corrupt_file`, :func:`fuzz_mutate`,
  :func:`run_reader_fuzz`) — seeded deterministic file corruption (the
  one code path ``tools/make_synthetic_fil.py --corrupt`` and
  ``bench.py --corruption`` share) and the structure-aware reader fuzz
  harness whose contract is: every reader, fed mutated bytes, parses
  (possibly salvaging a prefix) or raises ``DataFormatError`` — never a
  hang, never a crash.

Knobs: ``PYPULSAR_TPU_DATAGUARD=0`` disables the stream scrub (the
gates and validation stay on — they are correctness, not policy);
``PYPULSAR_TPU_MAX_BAD_FRAC`` sets the survey's degrade-vs-quarantine
threshold (default 0.5: an observation reporting more than half its
samples missing/invalid at ingest is data-quarantined).
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from pypulsar_tpu.io.errors import DataFormatError
from pypulsar_tpu.io.opener import SNIFF_LEN, format_of
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.resilience import faultinject
from pypulsar_tpu.tune import knobs

__all__ = [
    "CORRUPT_KINDS",
    "DataFormatError",
    "GuardedSource",
    "StreamQuality",
    "corrupt_file",
    "finite_cands",
    "finite_rows",
    "fuzz_mutate",
    "guard_enabled",
    "guard_source",
    "max_bad_frac_default",
    "reader_quality",
    "run_reader_fuzz",
    "validate_input",
]

ENV_GUARD = "PYPULSAR_TPU_DATAGUARD"
ENV_MAX_BAD_FRAC = "PYPULSAR_TPU_MAX_BAD_FRAC"
DEFAULT_MAX_BAD_FRAC = 0.5


def guard_enabled() -> bool:
    return knobs.env_str(ENV_GUARD) != "0"


def max_bad_frac_default() -> float:
    # registry read is typo-tolerant (bad value -> declared default)
    return float(knobs.env_float(ENV_MAX_BAD_FRAC))


# ---------------------------------------------------------------------------
# stream scrub
# ---------------------------------------------------------------------------

_scrub_jit = None


def _device_scrub(block):
    """(clean block, n_nonfinite, n_zero) on device — one fused
    elementwise pass + two scalar reductions, compiled once per shape."""
    global _scrub_jit
    if _scrub_jit is None:
        import jax.numpy as jnp

        from pypulsar_tpu.compile import plane_jit

        @plane_jit(stage="data")
        def f(b):
            finite = jnp.isfinite(b)
            clean = jnp.where(finite, b, jnp.zeros((), b.dtype))
            return (clean,
                    jnp.sum(~finite, dtype=jnp.int32),
                    jnp.sum(clean == 0, dtype=jnp.int32))

        _scrub_jit = f
    return _scrub_jit(block)


@dataclasses.dataclass
class StreamQuality:
    """Running per-stream account of what the scrub saw/did. Shared
    across reroots of the same source (resume must not double-zero the
    telemetry story, but totals may legitimately re-count replayed
    chunks — the counters are diagnostics, not science)."""

    cells: int = 0
    nonfinite_cells: int = 0
    zero_cells: int = 0
    chunks: int = 0

    def fraction_bad(self) -> float:
        return self.nonfinite_cells / self.cells if self.cells else 0.0

    def to_dict(self) -> Dict:
        return {"cells": self.cells,
                "nonfinite_cells": self.nonfinite_cells,
                "zero_cells": self.zero_cells,
                "chunks": self.chunks,
                "fraction_bad": round(self.fraction_bad(), 6)}


class GuardedSource:
    """Decorates a staged block source (``frequencies``/``tsamp``/
    ``nsamples``/``chan_major_blocks``) with the data-integrity scrub.

    Sits INSIDE any rfifind mask wrapper: the mask fill computes channel
    medians, and a NaN reaching that reduction would poison the whole
    channel — scrub first, mask second. Device blocks scrub on device
    (counts accumulate as lazy device scalars; ONE host sync when the
    stream ends), host blocks scrub in numpy. Every completed iteration
    flushes its deltas to the ``data.*`` telemetry counters.
    """

    FAULT_POINT = "data.block"

    def __init__(self, src, stats: Optional[StreamQuality] = None):
        self._src = src
        self.frequencies = src.frequencies
        self.tsamp = src.tsamp
        self.nsamples = src.nsamples
        self.stats = stats if stats is not None else StreamQuality()

    def chan_major_blocks(self, payload: int, overlap: int):
        try:
            import jax
        except Exception:  # noqa: BLE001 - backend-less: host scrub only
            jax = None
        dev_bad = dev_zero = None
        host_bad = host_zero = 0
        cells = chunks = 0
        try:
            for pos, block in self._src.chan_major_blocks(payload,
                                                          overlap):
                block = faultinject.trip_data(self.FAULT_POINT, block)
                chunks += 1
                cells += int(np.prod(np.shape(block)))
                if jax is not None and isinstance(block, jax.Array):
                    block, n_bad, n_zero = _device_scrub(block)
                    dev_bad = n_bad if dev_bad is None else dev_bad + n_bad
                    dev_zero = (n_zero if dev_zero is None
                                else dev_zero + n_zero)
                else:
                    a = np.asarray(block)
                    if np.issubdtype(a.dtype, np.floating):
                        finite = np.isfinite(a)
                        n_bad = int(a.size - np.count_nonzero(finite))
                        if n_bad:
                            a = np.where(finite, a,
                                         np.zeros((), a.dtype))
                            host_bad += n_bad
                            block = a
                        host_zero += int(np.count_nonzero(a == 0))
                yield pos, block
        finally:
            n_bad = host_bad + (int(dev_bad) if dev_bad is not None else 0)
            n_zero = host_zero + (int(dev_zero)
                                  if dev_zero is not None else 0)
            self.stats.cells += cells
            self.stats.nonfinite_cells += n_bad
            self.stats.zero_cells += n_zero
            self.stats.chunks += chunks
            if chunks:
                telemetry.counter("data.chunks", chunks)
                telemetry.counter("data.cells", cells)
            if n_zero:
                telemetry.counter("data.zero_cells", n_zero)
            if n_bad:
                telemetry.counter("data.nonfinite_cells", n_bad)
                telemetry.event(
                    "data.nonfinite_scrubbed", cells=n_bad,
                    frac=round(n_bad / max(cells, 1), 6))


def _source_is_float(src) -> bool:
    """True when the source's delivered blocks are float-typed (can
    carry non-finite values): in-memory Spectra, PSRFITS (scale/offset/
    weight make f32), and 32-bit filterbanks. uint filterbanks cannot
    hold a NaN and skip the guard (which also preserves their exact-
    integer host-downsample fast path)."""
    r = getattr(src, "reader", None)
    if r is None:
        return True  # _SpectraSource: float payload
    nbits = getattr(r, "nbits", None)
    if nbits is None:
        return True  # psrfits & friends deliver float32
    return int(nbits) >= 32


def guard_source(src):
    """Wrap a staged block source with :class:`GuardedSource` when it
    can carry non-finite values — or unconditionally when a DATA fault
    is armed (the injection needs somewhere to land). Identity when
    ``PYPULSAR_TPU_DATAGUARD=0`` or the source is integer-typed."""
    if isinstance(src, GuardedSource):
        return src
    if not guard_enabled():
        return src
    if not (faultinject.data_faults_armed() or _source_is_float(src)):
        return src
    return GuardedSource(src)


# ---------------------------------------------------------------------------
# finite-output gates
# ---------------------------------------------------------------------------

def _finite(v) -> bool:
    try:
        return bool(np.isfinite(v))
    except TypeError:
        return True  # non-numeric fields pass


def finite_rows(rows: Sequence[dict], keys: Sequence[str],
                what: str = "cands") -> List[dict]:
    """Filter dict rows whose ``keys`` are all finite; count drops in
    ``data.nonfinite_cands_dropped``. The gate every text-table writer
    calls so a non-finite value can never reach a published row."""
    good = [r for r in rows
            if all(_finite(r.get(k)) for k in keys)]
    dropped = len(rows) - len(good)
    if dropped:
        telemetry.counter("data.nonfinite_cands_dropped", dropped)
        telemetry.event("data.nonfinite_rows_dropped", what=what,
                        dropped=dropped)
        print(f"# dataguard: dropped {dropped} non-finite {what} "
              f"row(s) at the output gate")
    return good


def finite_cands(cands, T: float, what: str = "accel") -> list:
    """The accel-candidate form of the gate: sigma/power/r/z finite AND
    a usable frequency (r=0 debris would divide by zero in the period
    column)."""
    cands = list(cands)
    good = []
    for c in cands:
        vals = (c.sigma, c.power, c.r, c.z)
        if all(_finite(v) for v in vals):
            freq = c.freq(T) if T else 0.0
            if np.isfinite(freq) and freq > 0:
                good.append(c)
    dropped = len(cands) - len(good)
    if dropped:
        telemetry.counter("data.nonfinite_cands_dropped", dropped)
        telemetry.event("data.nonfinite_rows_dropped", what=what,
                        dropped=dropped)
        print(f"# dataguard: dropped {dropped} non-finite {what} "
              f"candidate(s) at the output gate")
    return good


# ---------------------------------------------------------------------------
# ingest validation + data-quality reports
# ---------------------------------------------------------------------------

def reader_quality(reader) -> Optional[Dict]:
    """The salvage half of a reader's data-quality story (None when the
    file read back whole)."""
    return getattr(reader, "salvage", None)


def validate_input(path: str) -> Optional[Dict]:
    """Cheap ingest-time validation of one observation input.

    Returns a data-quality report dict for recognized formats
    (``format``, geometry, ``salvage``, ``bad_frac`` — the fraction of
    expected samples missing), None for missing/unrecognized files (the
    stage itself will fail with a proper error — synthetic test DAGs
    use dummy paths), and raises :class:`DataFormatError` for a file
    that *claims* a recognized format but violates it — the signal the
    survey scheduler turns into a reason-``"data"`` quarantine."""
    if not os.path.isfile(path):
        return None
    try:
        with open(path, "rb") as f:
            magic = f.read(SNIFF_LEN)
    except OSError:
        return None
    fmt = format_of(magic)
    if fmt == "fits":
        return _validate_psrfits(path)
    if fmt == "sigproc":
        return _validate_filterbank(path)
    return None


def _validate_filterbank(path: str) -> Dict:
    from pypulsar_tpu.io.filterbank import FilterbankFile

    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # salvage warns; we REPORT it
        fb = FilterbankFile(path)
    try:
        salvage = fb.salvage
        nsamp = int(fb.number_of_samples)
        report = {
            "format": "filterbank",
            "nsamples": nsamp,
            "nchan": int(fb.nchans),
            "nbits": int(fb.nbits),
            "salvage": salvage,
        }
    finally:
        fb.close()
    bad = 0.0
    if nsamp == 0:
        bad = 1.0  # a header with no payload is all-bad
    elif salvage and salvage.get("expected_samples"):
        bad = salvage["missing_samples"] / salvage["expected_samples"]
    report["bad_frac"] = round(float(bad), 6)
    return report


def _validate_psrfits(path: str) -> Dict:
    from pypulsar_tpu.io.psrfits import PsrfitsFile

    pf = PsrfitsFile(path)
    try:
        report = {
            "format": "psrfits",
            "nsamples": int(pf.nspec),
            "nchan": int(pf.nchan),
            "nbits": int(pf.nbits),
            "salvage": None,
            "bad_frac": 1.0 if int(pf.nspec) == 0 else 0.0,
        }
    finally:
        pf.close()
    return report


# ---------------------------------------------------------------------------
# deterministic file corruption (ONE code path for tools + bench + tests)
# ---------------------------------------------------------------------------

CORRUPT_KINDS = ("truncate", "bitflip", "dropblock", "nanburst",
                 "dcjump", "header")


def _rng(seed: int, tag: str):
    h = hashlib.sha256(f"{tag}:{seed}".encode()).digest()
    return np.random.Generator(np.random.SFC64(list(h[:16])))


def _sigproc_header_size(path: str) -> int:
    from pypulsar_tpu.io import sigproc

    try:
        with open(path, "rb") as f:
            _, _, hsize = sigproc.read_header(f, path=path)
        return hsize
    except (DataFormatError, OSError):
        return 0


def corrupt_file(path: str, kind: str, seed: int = 0) -> Dict:
    """Deterministically corrupt ``path`` in place with one data-fault
    kind (see :data:`CORRUPT_KINDS`) — the shared recipe behind
    ``make_synthetic_fil --corrupt`` and ``bench.py --corruption``, so
    tests, bench and tooling can never drift apart on what "a truncated
    file" means. Returns a description of what was done.

    Payload-relative kinds locate the SIGPROC header first (header_size
    0 for non-SIGPROC files: the whole file is payload). ``nanburst``
    and ``dcjump`` interpret the payload as float32 — the depth the
    synthetic survey inputs use."""
    if kind not in CORRUPT_KINDS:
        raise ValueError(f"unknown corruption kind {kind!r}; expected "
                         f"one of {CORRUPT_KINDS}")
    size = os.path.getsize(path)
    rng = _rng(seed, f"{kind}:{os.path.basename(path)}")
    desc: Dict = {"kind": kind, "seed": seed, "path": path}
    if kind == "header":
        # scribble over the keyword stream right after HEADER_START:
        # parses must fail loudly (DataFormatError), never wander
        with open(path, "r+b") as f:
            f.seek(min(16, size))
            f.write(rng.integers(0, 256, size=32,
                                 dtype=np.uint8).tobytes())
        desc["span"] = (16, 48)
        return desc
    hsize = _sigproc_header_size(path)
    payload = size - hsize
    if payload <= 0:
        raise ValueError(f"{path}: no payload to corrupt")
    if kind == "truncate":
        # drop the tail 40%, deliberately landing mid-spectrum so the
        # reader's partial-tail salvage path is the one exercised
        keep = hsize + int(payload * 0.6) + 1
        os.truncate(path, min(keep, size))
        desc["truncated_to"] = keep
        return desc
    if kind == "bitflip":
        with open(path, "r+b") as f:
            offs = sorted(int(o) for o in
                          rng.integers(0, payload, size=64))
            for o in offs:
                f.seek(hsize + o)
                b = f.read(1)
                f.seek(hsize + o)
                f.write(bytes([b[0] ^ (1 << int(rng.integers(0, 8)))]))
        desc["flips"] = 64
        return desc
    # span/offset are 4-byte aligned RELATIVE TO THE PAYLOAD (not the
    # file): float32 cells start at hsize, so a file-aligned offset on
    # an odd-size header would write the NaN pattern straddling cell
    # boundaries — denormal soup instead of NaNs
    span = max(4, (payload // 20) & ~3)  # ~5% of the payload
    off = int(rng.integers(0, max(payload - span, 1))) & ~3
    start = hsize + off
    desc["span"] = (start, start + span)
    if kind == "dropblock":
        with open(path, "r+b") as f:
            f.seek(start)
            f.write(b"\x00" * span)
        return desc
    if kind == "nanburst":
        burst = np.full(span // 4, np.nan, dtype=np.float32)
        burst[0] = np.inf
        with open(path, "r+b") as f:
            f.seek(start)
            f.write(burst.tobytes())
        return desc
    # dcjump: add a large offset to the span's float32 values
    with open(path, "r+b") as f:
        f.seek(start)
        vals = np.frombuffer(f.read(span), dtype=np.float32).copy()
        vals += np.float32(1e4)
        f.seek(start)
        f.write(vals.tobytes())
    return desc


# ---------------------------------------------------------------------------
# structure-aware reader fuzz
# ---------------------------------------------------------------------------

def fuzz_mutate(data: bytes, rng) -> bytes:
    """One seeded structural mutation of a file image: truncation at a
    random offset, byte flips, a zeroed span, a garbage-overwritten
    span, or a duplicated span — the shapes real corruption takes
    (dropped packets, torn copies, bit rot)."""
    if not data:
        return data
    op = int(rng.integers(0, 5))
    n = len(data)
    if op == 0:  # truncate
        return data[: int(rng.integers(0, n))]
    buf = bytearray(data)
    if op == 1:  # flip 1-8 random bytes
        for _ in range(int(rng.integers(1, 9))):
            i = int(rng.integers(0, n))
            buf[i] ^= 1 << int(rng.integers(0, 8))
    elif op == 2:  # zero a span
        span = int(rng.integers(1, max(n // 4, 2)))
        i = int(rng.integers(0, max(n - span, 1)))
        buf[i:i + span] = b"\x00" * span
    elif op == 3:  # garbage a span
        span = int(rng.integers(1, max(n // 8, 2)))
        i = int(rng.integers(0, max(n - span, 1)))
        buf[i:i + span] = rng.integers(0, 256, size=span,
                                       dtype=np.uint8).tobytes()
    else:  # duplicate a span over another (framing slip)
        span = int(rng.integers(1, max(n // 8, 2)))
        i = int(rng.integers(0, max(n - span, 1)))
        j = int(rng.integers(0, max(n - span, 1)))
        buf[j:j + span] = buf[i:i + span]
    return bytes(buf)


def run_reader_fuzz(fmt: str, n: int, seed: int,
                    workdir: str) -> Tuple[Dict[str, int], List]:
    """Fuzz one reader with ``n`` seeded mutations of a small valid
    file. Returns ``(outcome counts, failures)`` where outcomes are
    ``ok`` (parsed whole), ``salvage`` (parsed a reported prefix) and
    ``error`` (clean :class:`DataFormatError`); ``failures`` lists any
    mutation that escaped the contract (raw exception) — the fuzz tests
    assert it empty. ``fmt``: ``filterbank`` | ``psrfits`` | ``dat``."""
    os.makedirs(workdir, exist_ok=True)
    base = _fuzz_base(fmt, workdir)
    rng = _rng(seed, f"fuzz:{fmt}")
    counts = {"ok": 0, "salvage": 0, "error": 0}
    failures: List = []
    for i in range(n):
        mutated = fuzz_mutate(base, rng)
        try:
            outcome = _fuzz_open(fmt, workdir, mutated)
        except DataFormatError:
            counts["error"] += 1
        except Exception as e:  # noqa: BLE001 - the contract violation
            failures.append((i, f"{type(e).__name__}: {e}"))
        else:
            counts[outcome] += 1
    return counts, failures


def _fuzz_base(fmt: str, workdir: str) -> bytes:
    """A small VALID file image of ``fmt`` (plus sidecars on disk where
    the format needs them)."""
    rng = np.random.default_rng(7)
    if fmt == "filterbank":
        from pypulsar_tpu.io.filterbank import write_filterbank

        fn = os.path.join(workdir, "base.fil")
        data = rng.standard_normal((64, 16)).astype(np.float32)
        write_filterbank(fn, dict(nchans=16, tsamp=1e-3, fch1=1500.0,
                                  foff=-1.0, nbits=32), data)
    elif fmt == "psrfits":
        from pypulsar_tpu.io.psrfits import write_psrfits

        fn = os.path.join(workdir, "base.fits")
        data = rng.integers(0, 40, size=(8, 64)).astype(np.float32)
        write_psrfits(fn, data, 1500.0 - np.arange(8.0), 1e-3,
                      nsamp_per_subint=16, nbits=8)
    elif fmt == "dat":
        from pypulsar_tpu.io.datfile import write_dat
        from pypulsar_tpu.io.infodata import InfoData

        base = os.path.join(workdir, "base")
        inf = InfoData()
        inf.epoch = 55000.0
        inf.dt = 1e-3
        inf.DM = 10.0
        write_dat(base, rng.standard_normal(256).astype(np.float32), inf)
        fn = base + ".dat"
        # the .inf sidecar stays valid on disk; the .dat bytes mutate
    else:
        raise ValueError(f"unknown fuzz format {fmt!r}")
    with open(fn, "rb") as f:
        return f.read()


def _fuzz_open(fmt: str, workdir: str, mutated: bytes) -> str:
    """Open + exercise one mutated image; returns ``ok``/``salvage`` or
    raises (DataFormatError = clean outcome, anything else = contract
    violation recorded by the caller)."""
    if fmt == "filterbank":
        from pypulsar_tpu.io.filterbank import FilterbankFile

        fn = os.path.join(workdir, "mut.fil")
        with open(fn, "wb") as f:
            f.write(mutated)
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            fb = FilterbankFile(fn)
        try:
            n = min(int(fb.number_of_samples), 8)
            if n > 0:
                fb.get_samples(0, n)
            return "salvage" if fb.salvage else "ok"
        finally:
            fb.close()
    if fmt == "psrfits":
        from pypulsar_tpu.io.psrfits import PsrfitsFile, is_PSRFITS

        fn = os.path.join(workdir, "mut.fits")
        with open(fn, "wb") as f:
            f.write(mutated)
        if not is_PSRFITS(fn):
            raise DataFormatError(fn, "no longer sniffs as PSRFITS")
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            pf = PsrfitsFile(fn)
            try:
                n = min(int(pf.nspec), 4)
                if n > 0:
                    pf.get_spectra(0, n)
                return "ok"
            finally:
                pf.close()
    if fmt == "dat":
        from pypulsar_tpu.io.datfile import Datfile

        fn = os.path.join(workdir, "base.dat")  # .inf sidecar lives here
        with open(fn, "wb") as f:
            f.write(mutated)
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            d = Datfile(fn)
        try:
            d.read_all()
            return "salvage" if d.salvage else "ok"
        finally:
            d.close()
    raise ValueError(f"unknown fuzz format {fmt!r}")
