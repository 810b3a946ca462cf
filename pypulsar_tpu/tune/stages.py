"""Per-stage measure builders: the *real* dispatches the searcher times.

Each builder returns a zero-arg callable that runs one representative
slice of the stage's production dispatch — the same jitted kernels, at
the **actual run geometry** (nchan/nsamp/zmax the caller passed), with
every tunable resolved through the knob registry so the searcher's
trial overlay takes effect. Work is held constant across candidate
configs (a fixed total of output samples / spectra), so "faster" means
faster *throughput*, not less work:

- ``sweep``: dedisperses a fixed span of seeded synthetic [C, T] data
  through :func:`parallel.sweep.dedisperse_series_chunk` in chunks of
  the tuned ``PYPULSAR_TPU_SWEEP_CHUNK`` payload;
- ``accel``: preps + searches a fixed count of seeded synthetic series
  through ``fourier.kernels.prep_spectra_batch`` +
  ``fourier.accelsearch.accel_search_batch`` in groups of the tuned
  ``PYPULSAR_TPU_ACCEL_BATCH``, under the tuned
  ``PYPULSAR_TPU_ACCEL_HBM`` plan budget.

Synthetic inputs are seeded (``PYPULSAR_TPU_TUNE_SEED``) and cached per
shape, so a search is deterministic and repeat timings drop the
generation + XLA compile cost (the searcher takes the min over
repeats). Imports are lazy: this module is reachable from CLI bootstrap
via tune/__init__ and must not drag jax in until a search actually
runs.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

from pypulsar_tpu.tune import knobs

__all__ = ["measure_for_stage", "sweep_measure", "accel_measure"]


def _rng(seed_bump: int = 0):
    import numpy as np

    seed = (knobs.env_int("PYPULSAR_TPU_TUNE_SEED") or 0) + seed_bump
    return np.random.RandomState(1234 + seed)


def sweep_measure(nchan: int, nsamp: int, *, ndm: int = 32,
                  dt: float = 6.4e-5, engine: str = "gather",
                  nsub: Optional[int] = None,
                  seed_bump: int = 0) -> Callable[[], None]:
    """Time dedispersing an ``nsamp``-sample span of [nchan, T] noise
    at ``ndm`` trials — the streamed sweep's chunk loop with the tuned
    chunk payload, clamped to the geometry exactly as the pipeline
    clamps it."""
    import numpy as np

    from pypulsar_tpu.parallel import sweep as psweep

    nsub_eff = nsub or min(64, nchan)
    freqs = 1500.0 - (400.0 / nchan) * np.arange(nchan)
    dms = np.linspace(0.0, 30.0 * ndm / 32.0, ndm)
    gsize = psweep.choose_group_size(dms, freqs, dt, nsub_eff)
    plan = psweep.make_sweep_plan(dms, freqs, dt, nsub=nsub_eff,
                                  group_size=gsize)
    data_cache: Dict[int, object] = {}

    def run() -> None:
        import jax

        # clamp EXACTLY like the streamed pipeline (staged.py): a chunk
        # candidate larger than the observation runs one nsamp-sized
        # dispatch, not a payload-sized one — without the clamp every
        # over-length candidate is charged phantom work it would never
        # do in production, biasing the search against large chunks
        payload = min(psweep.default_chunk_payload(plan), int(nsamp))
        if payload <= plan.min_overlap:
            payload = min(int(nsamp), 2 * plan.min_overlap + 1)
        # hold total work constant across candidates: every config
        # dedisperses the same nsamp-sample span (the trailing partial
        # chunk costs a full dispatch, exactly as the real chain's does)
        total = max(1, int(nsamp))
        L = payload + plan.min_overlap
        block = data_cache.get(L)
        if block is None:
            block = _rng(seed_bump).randn(nchan, L).astype(np.float32)
            data_cache.clear()  # one resident block, not one per config
            data_cache[L] = block
        done = 0
        out = None
        while done < total:
            out = psweep.dedisperse_series_chunk(
                block, plan.stage1_bins, plan.stage2_bins, plan.nsub,
                payload, plan.max_shift2, engine)
            done += payload
        jax.block_until_ready(out)

    return run


def accel_measure(nsamp: int, *, zmax: int = 20, numharm: int = 2,
                  nspec: int = 16, dt: float = 6.4e-5,
                  seed_bump: int = 0) -> Callable[[], None]:
    """Time prepping + accel-searching ``nspec`` synthetic series of
    ``nsamp`` samples, dispatched in groups of the tuned batch size
    under the tuned HBM plan budget — the batched accel stage."""
    import numpy as np

    from pypulsar_tpu.fourier.accelsearch import AccelSearchConfig

    n = 1 << max(10, (int(nsamp) - 1).bit_length())  # pow2 FFT length
    cfg = AccelSearchConfig(zmax=zmax, numharm=numharm)
    series = _rng(100 + seed_bump).randn(nspec, n).astype(np.float32)
    T = n * dt

    def run() -> None:
        from pypulsar_tpu.fourier.accelsearch import accel_search_batch
        from pypulsar_tpu.fourier.kernels import prep_spectra_batch

        batch = max(1, knobs.env_int("PYPULSAR_TPU_ACCEL_BATCH"))
        for b0 in range(0, nspec, batch):
            group = series[b0:b0 + batch]
            planes = prep_spectra_batch(group)
            accel_search_batch(planes, T, cfg)
        # accel_search_batch returns host candidate lists — the device
        # work is already synchronized, nothing left to block on

    return run


def measure_for_stage(stage: str, *, nchan: Optional[int] = None,
                      nsamp: Optional[int] = None,
                      zmax: Optional[int] = None,
                      engine: Optional[str] = None,
                      ndm: int = 32, nspec: int = 16,
                      numharm: int = 2) -> Callable[[], None]:
    """The measure callable for ``stage`` at the given geometry — what
    ``cli tune --search``, ``bench --tune`` and the on-line
    ``PYPULSAR_TPU_TUNE=search`` path all share."""
    if stage == "sweep":
        return sweep_measure(int(nchan or 64), int(nsamp or 1 << 16),
                             ndm=ndm, engine=engine or "gather")
    if stage == "accel":
        return accel_measure(int(nsamp or 1 << 14), zmax=int(zmax or 20),
                             numharm=numharm, nspec=nspec)
    raise ValueError("no measure builder for stage %r (searchable "
                     "stages: sweep, accel)" % (stage,))
