"""Block and chunk lengths planned from the observation and the device.

Two lengths decide how much of an observation is on the device at once:
the FFT chunk of the sweep's overlap-save stream (``parallel/sweep``,
``parallel/staged``) and the intervals a block of the mask stage holds
(``ops/rfifind``). Both were constants measured at 1024 channels (2^18
samples, 16 intervals). The bytes a chunk or a block needs grow with the
channel count, so a 4096-channel file at those constants asks for more
than a 16 GB chip has before its first transform.

:func:`plan_lengths` is the one place both are decided: a pure function
of the observation's geometry and the device's memory that counts the
arrays the chunk program and the block-statistics program keep alive and
takes the largest power of two, not above the measured default, whose
count fits a stated share of the device's memory. At 1024 channels on a
16 GB chip it returns the old constants. The operator's explicit chunk
(``PYPULSAR_TPU_SWEEP_CHUNK``, ``--chunk``) is not the planner's to
change; only its growth to hold the dedispersion overlap is bounded.

The answer depends on the device through its memory size alone
(``memory_stats()``'s ``bytes_limit``); a backend that reports none (the
CPU) plans without a bound.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

__all__ = ["DEFAULT_CHUNK", "DEFAULT_MASK_INTERVALS", "MEMORY_SHARE",
           "MIN_CHUNK", "LengthPlanError", "Lengths", "chunk_bytes",
           "device_memory", "mask_block_bytes", "plan_lengths"]

# Round-5 chunk-length A/B on v5e (BENCHNOTES): at 1024 channels and 1024
# trials the fourier chunk measures 0.67 G trial-samples/s at n=2^17, 0.95
# G at 2^18 (+41%), 0.87 G at 2^19: the FFT amortizes and the overlap
# fraction shrinks up to 2^18, then working-set growth wins. The planner
# never goes above it for memory's sake, only for the overlap's.
DEFAULT_CHUNK = 1 << 18
MIN_CHUNK = 1 << 12  # a typo cannot degenerate the stream (chunk_fft_len)
DEFAULT_MASK_INTERVALS = 16
# the share of the device's memory a stage's stream plans for; the rest
# is left to what the counts below do not see: the compiler's scratch
# beyond its reported temporaries, fragmentation, other stages' residue
MEMORY_SHARE = 0.75
# a trial group's planes are counted at most this wide
# (parallel.sweep.choose_group_size's max_group)
MAX_GROUP = 128


class LengthPlanError(ValueError):
    """No chunk both holds the dedispersion overlap in half its length
    and fits the device's memory."""


class Lengths(NamedTuple):
    chunk: int            # FFT chunk length of the sweep, samples
    chunk_bound: str      # default | memory | overlap | operator
    chunk_need: int       # bytes counted for that chunk
    mask_intervals: int   # intervals a block of the mask stage
    mask_bound: str       # default | memory
    mask_need: int        # bytes counted for that block (0: no interval)
    budget: Optional[int]  # bytes planned for; None: device reports none

    @property
    def cut(self) -> bool:
        """Memory made the chunk shorter than the default."""
        return self.chunk_bound == "memory"


def chunk_bytes(nchan: int, nsub: int, trials: int, n: int) -> int:
    """Bytes alive while one ``n``-sample chunk goes through the sweep's
    stream and its chunk program (``sweep_stream`` /
    ``iter_dedispersed_chunks`` over ``ops/fourier_dedisperse``), a
    sample of every row counted once per array that holds it:

    - the packed blocks the ship-ahead thread keeps in flight (4 of them,
      ``_ship_ahead``'s depth + 2, at most a byte a sample): 4 C
    - the float32 forms of a block: the one the program transforms (its
      argument), the next one being ingested, filled and
      baseline-subtracted behind it, and one transient between those
      programs: 3 x 4 C
    - the program's temporaries: the transform X[C, n/2+1] complex64, its
      padded input, the factored view and the stage-1 products: 16 C
      (what the TPU compiler reports for the chunk program compiled for
      a described v5e: 4.33 GB at [4096, 2^16], tests/test_chip_compile)
    - the subband planes of one trial group and the products summed into
      them: 2 x 4 S
    - a group's trial planes, spectrum and series: 2 x 4 g
    - every trial's series as the series program returns it: 4 D
    """
    group = min(trials, MAX_GROUP)
    per_sample = 32 * nchan + 8 * nsub + 8 * group + 4 * trials
    return int(per_sample) * int(n)


def mask_block_bytes(nchan: int, interval_samples: int,
                     intervals: int) -> int:
    """Bytes alive while ``rfifind_block_stats`` runs over one block of
    ``intervals`` intervals: per (interval, channel) the packed samples
    (a byte each) and their float32 form (the program's argument) over
    ``interval_samples``, and over the transform's power-of-two length
    the program's temporaries, 16 bytes a sample: the centred and padded
    input, the complex64 spectrum and the power table (the TPU compiler
    reports 8.59 GB for [4096, 8 x 12207] padded to 16384)."""
    n_fft = 1 << max(int(interval_samples) - 1, 0).bit_length()
    per_cell = 5 * int(interval_samples) + 16 * n_fft
    return int(intervals) * int(nchan) * per_cell


def plan_lengths(nchan: int, nsub: int, max_delay: int, trials: int,
                 bytes_limit: Optional[int], resident: int = 0, *,
                 interval_samples: int = 0,
                 chunk: Optional[int] = None) -> Lengths:
    """The sweep's FFT chunk length and the mask stage's intervals a
    block for ``nchan`` channels in ``nsub`` subbands, ``trials`` DM
    trials in a dispatch and a largest delay (the plan's ``min_overlap``)
    of ``max_delay`` samples, on a device of ``bytes_limit`` bytes of
    which ``resident`` stay in use beside the stream. ``chunk`` is the
    operator's explicit
    FFT length, taken as given. ``interval_samples`` of 0 plans no mask
    block. Raises :class:`LengthPlanError` where the overlap cannot be
    held within the memory."""
    budget = None
    if bytes_limit:
        budget = max(int(MEMORY_SHARE * bytes_limit) - int(resident), 0)

    def fits(need: int) -> bool:
        return budget is None or need <= budget

    if chunk is not None:
        n, bound = int(chunk), "operator"
    else:
        n, bound = DEFAULT_CHUNK, "default"
        while n > MIN_CHUNK and not fits(chunk_bytes(nchan, nsub, trials, n)):
            n >>= 1
    grown = False
    while max_delay >= n // 2:
        n, grown = n << 1, True
    need = chunk_bytes(nchan, nsub, trials, n)
    if grown and not fits(need):
        raise LengthPlanError(
            f"no sweep chunk fits: a delay of {max_delay} samples needs a "
            f"chunk of {n}, which for {nchan} channels, {nsub} subbands "
            f"and {trials} trials holds {need} bytes on the device where "
            f"{budget} may be planned for ({MEMORY_SHARE:g} of "
            f"{bytes_limit} less {resident} resident); sweep fewer "
            f"trials a pass, a lower top DM, or downsample")
    if chunk is None and n < DEFAULT_CHUNK:
        bound = "memory"
    elif grown:
        bound = "overlap"

    ints, mbound, mneed = DEFAULT_MASK_INTERVALS, "default", 0
    if interval_samples > 0:
        while ints > 1 and not fits(
                mask_block_bytes(nchan, interval_samples, ints)):
            ints, mbound = ints >> 1, "memory"
        mneed = mask_block_bytes(nchan, interval_samples, ints)
    return Lengths(n, bound, need, ints, mbound, mneed, budget)


def device_memory() -> Optional[int]:
    """``bytes_limit`` of the device this thread's work runs on (the
    lease's first chip), or None where the backend reports no memory
    statistics (the CPU). What is in use at that moment is not asked:
    when a pass is planned it is the last pass's blocks on their way
    out, and subtracting them would let the two passes of one
    observation plan different chunks. A caller that keeps something on
    the device for the stream's whole life passes it as ``resident``."""
    from pypulsar_tpu.parallel.mesh import lease_devices

    limit = (lease_devices()[0].memory_stats() or {}).get("bytes_limit")
    return int(limit) if limit else None
