"""Spectra — the central freq x time data container, as an immutable pytree.

TPU-native redesign of the reference's mutable NumPy ``Spectra``
(reference formats/spectra.py:8-351): ``data[nchan, nspec]`` lives on device,
ops are functional (return a new Spectra) and dispatch to the jitted kernels
in ``pypulsar_tpu.ops.kernels``. Integer bin delays for concrete-DM ops are
computed host-side in float64 (exactly the reference's NumPy delay math) so
results are bit-compatible with the golden twins regardless of device
precision; the traced-DM path used by the vmapped sweep engine lives in
``ops.kernels``/``parallel.sweep``.

Fixes honored (SURVEY.md §2.6): the constructor stores the ``dm`` argument
(the reference's :37 silently discards it), and ``trim`` implements its
documented semantics for negative bins.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pypulsar_tpu.core import psrmath
from pypulsar_tpu.ops import kernels


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Spectra:
    """2-D spectra: axis 0 channels (``data[0, :]`` is one channel), axis 1
    time samples. ``freqs`` are per-channel observing freqs in MHz, ``dt`` the
    sample time in seconds, ``starttime`` seconds from obs start, ``dm`` the
    dispersion measure the data are currently dedispersed at."""

    freqs: Any
    dt: float
    data: Any
    starttime: float = 0.0
    dm: float = 0.0

    def __post_init__(self):
        d = jnp.asarray(self.data)
        f = jnp.asarray(self.freqs)
        if d.ndim != 2 or f.shape[0] != d.shape[0]:
            raise ValueError(
                "data must be 2-D [nchan, nspec] with len(freqs) == nchan; "
                f"got data {d.shape}, freqs {f.shape}"
            )
        object.__setattr__(self, "data", d)
        object.__setattr__(self, "freqs", f)

    # --- pytree protocol: arrays are leaves, scalars static metadata ---
    def tree_flatten(self):
        return (self.data, self.freqs), (self.dt, self.starttime, self.dm)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, freqs = children
        dt, starttime, dm = aux
        obj = object.__new__(cls)
        object.__setattr__(obj, "data", data)
        object.__setattr__(obj, "freqs", freqs)
        object.__setattr__(obj, "dt", dt)
        object.__setattr__(obj, "starttime", starttime)
        object.__setattr__(obj, "dm", dm)
        return obj

    # --- basic accessors (reference spectra.py:39-52) ---
    @property
    def numchans(self) -> int:
        return self.data.shape[0]

    @property
    def numspectra(self) -> int:
        return self.data.shape[1]

    def get_chan(self, channum):
        return self.data[channum, :]

    def get_spectrum(self, specnum):
        return self.data[:, specnum]

    def __getitem__(self, key):
        return self.data[key]

    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.data)

    def _replace(self, **kw) -> "Spectra":
        return dataclasses.replace(self, **kw)

    # --- host-side exact bin-delay math (float64, reference-parity) ---
    def _rel_bindelays(self, dm: float, ref_freq=None) -> np.ndarray:
        freqs = np.asarray(self.freqs, dtype=np.float64)
        if ref_freq is None:
            ref_freq = np.max(freqs)
        rel = psrmath.delay_from_DM(dm - self.dm, freqs) - psrmath.delay_from_DM(
            dm - self.dm, ref_freq
        )
        return np.round(rel / self.dt).astype(np.int32)

    # --- ops (each returns a NEW Spectra) ---
    def _shift_nfft(self, bins):
        """Tight static FFT length for the TPU fourier shift backend:
        host-known bins bound the wrap region exactly (kernels.
        shift_channels n_fft contract), halving the default 2T pad.
        Returns None (default padding) unless ``bins`` is already a host
        array — concretizing a traced value would fail, and pulling a
        device array is a device->host sync per call."""
        if not isinstance(bins, (np.ndarray, list, tuple)):
            return None
        from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len

        return fourier_chunk_len(
            self.data.shape[-1] + int(np.max(np.abs(np.asarray(bins)))))

    def shift_channels(self, bins, padval=0) -> "Spectra":
        n_fft = self._shift_nfft(bins)
        bins = jnp.asarray(bins, dtype=jnp.int32)
        return self._replace(data=kernels.shift_channels(
            self.data, bins, padval, n_fft=n_fft))

    def dedisperse(self, dm=0.0, padval=0, trim=False) -> "Spectra":
        bins = self._rel_bindelays(dm)
        data = kernels.shift_channels(self.data, jnp.asarray(bins), padval,
                                      n_fft=self._shift_nfft(bins))
        ntrim = int(bins.max()) if trim else 0
        if ntrim > 0:
            data = data[:, :-ntrim]
        return self._replace(data=data, dm=float(dm))

    def subband(self, nsub, subdm=None, padval=0) -> "Spectra":
        if self.numchans % nsub:
            raise ValueError(f"nsub={nsub} must divide numchans={self.numchans}")
        per = self.numchans // nsub
        freqs = np.asarray(self.freqs, dtype=np.float64)
        hif = freqs[np.arange(nsub) * per]
        lof = freqs[(1 + np.arange(nsub)) * per - 1]
        ctr = 0.5 * (hif + lof)
        data = self.data
        if subdm is not None:
            ref = psrmath.delay_from_DM(subdm - self.dm, hif)
            delays = psrmath.delay_from_DM(subdm - self.dm, freqs)
            rel = delays - np.repeat(ref, per)
            bins = np.round(rel / self.dt).astype(np.int32)
            data = kernels.shift_channels(data, jnp.asarray(bins), padval,
                                          n_fft=self._shift_nfft(bins))
        data = data.reshape(nsub, per, self.numspectra).sum(axis=1)
        return self._replace(data=data, freqs=jnp.asarray(ctr))

    def scaled(self, indep=False) -> "Spectra":
        return self._replace(data=kernels.scaled(self.data, indep))

    def scaled2(self, indep=False) -> "Spectra":
        return self._replace(data=kernels.scaled2(self.data, indep))

    def masked(self, mask, maskval="median-mid80") -> "Spectra":
        mask = jnp.asarray(mask)
        if mask.shape != self.data.shape:
            raise ValueError("mask shape must match data shape")
        return self._replace(data=kernels.masked(self.data, mask, maskval))

    def smooth(self, width=1, padval=0) -> "Spectra":
        return self._replace(data=kernels.smooth(self.data, int(width), padval))

    def trim(self, bins=0) -> "Spectra":
        if abs(bins) >= self.numspectra:
            raise ValueError("cannot trim more spectra than exist")
        if bins == 0:
            return self
        data = kernels.trim(self.data, int(bins))
        start = self.starttime if bins > 0 else self.starttime - bins * self.dt
        return self._replace(data=data, starttime=start)

    def downsample(self, factor=1, trim=True) -> "Spectra":
        factor = int(factor)
        if factor <= 1:
            return self
        if not trim and self.numspectra % factor:
            raise ValueError("factor must divide numspectra when trim=False")
        return self._replace(
            data=kernels.downsample(self.data, factor), dt=self.dt * factor
        )

    def dedispersed_timeseries(self, dm: float) -> jnp.ndarray:
        """Channel-summed time series at ``dm`` (circular shifts)."""
        bins = self._rel_bindelays(dm)
        return kernels.dedispersed_timeseries(self.data, jnp.asarray(bins))
