"""Entry ``survey_gang``: entry ``survey`` with ``--devices 4 --gang
auto`` in the cell's argv — one pointing whose sweep stage the fleet
scheduler gang-leases over the chips of a host. Everything is entry
``survey``'s, handed on; what this file adds first is one question
asked before the input is made: can the program's compile plane key a
batch sharded over the cell's chips? A program that cannot runs every
sharded dispatch of the gang outside the AOT registry
(``compile.aot_fallback``, which the traced check holds to 0), so it
cannot run this deployment as a measurement and is refused at once.

The check is entry ``survey``'s too, on a reference spectrum carried on
past Nyquist by zeros, as the search pads its own. The cell samples DM
trials far from the injection, whose strongest candidates are noise
anywhere up to Nyquist; ``reference.accel.summed_power`` slices its
template window out of the spectrum as it stands and raises on a
candidate whose top harmonic lies within a template's half-width (49
bins here) of the end."""

from __future__ import annotations

import sys

import numpy as np

from entries import survey
from reference import accel

run = survey.run
telemetry_files = survey.telemetry_files
fallbacks = survey.fallbacks
work = survey.work


def plane_keys_sharded_batch(chips: int) -> bool:
    """True when a plane-wrapped function called with an array sharded
    over ``chips`` devices lands in the plane's AOT registry."""
    import jax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from pypulsar_tpu.compile import plane_jit

    mesh = Mesh(np.array(jax.devices()[:chips]), ("dm",))
    batch = jax.device_put(np.zeros((chips, 8), np.float32),
                           NamedSharding(mesh, P("dm")))
    probe = plane_jit(lambda x: x + 1.0, name="gang_probe")
    probe(batch)
    return probe.cache_size() == 1


def prepare(cell) -> None:
    if not plane_keys_sharded_batch(int(cell.wl["chips"])):
        print(f"refused: the program's compile plane falls back on a batch "
              f"sharded over {cell.wl['chips']} devices "
              f"(compile.aot_fallback), which the traced check of "
              f"{cell.name!r} holds to 0: this program cannot run the "
              f"gang-leased deployment as a measurement",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    survey.prepare(cell)


class Reference(survey.Reference):
    """Entry ``survey``'s reference; every number it gives away from the
    spectrum's end is the same to the last bit."""

    def spectrum(self, i):
        if i not in self._spectra:
            reach = accel.halfwidth(self.cfg["zmax"]) + 1
            self._spectra[i] = np.concatenate(
                [super().spectrum(i), np.zeros(reach, np.complex128)])
        return self._spectra[i]


def check(cell, control=None) -> list:
    """Entry ``survey``'s check, run on this module's reference."""
    held, survey.Reference = survey.Reference, Reference
    try:
        return survey.check(cell, control)
    finally:
        survey.Reference = held
