"""Fourier-domain acceleration search: the (r, z) = (frequency, drift) plane.

Fills the reference pipeline's gap between ``.fft`` files and
``*_ACCEL*.cand`` candidate files (the reference defers this stage to
PRESTO's ``accelsearch`` and only consumes its output —
``bin/plot_accelcands.py:50-104``, ``formats/accelcands.py``; BASELINE.md
configs[4] names the workload: 4096 DM x ~200 z-trials).

TPU-native design
-----------------
The search correlates the normalized FFT with a bank of constant-
:math:`\\dot f` templates (fourier/zresponse.py) for every drift ``z`` in
``[-zmax, zmax]`` and sums harmonics — all as *batched power-of-two FFT
convolutions*:

- The template bank for one harmonic stage is a single ``[2*Z, L]``
  complex64 array (interleaved integer/half-bin phase rows, PRESTO's
  ``numbetween=2`` resolution); its FFT is precomputed once per search.
- The spectrum streams through in fundamental-bin segments (overlap-save,
  exactly the sweep engine's chunking pattern); each segment x harmonic is
  one batched ``fft -> multiply -> ifft`` over the z axis, a shape XLA
  tiles well on TPU (power-of-two lengths only: XLA lowers other sizes
  through a dense DFT matmul that allocates O(L^2)).
- Harmonic summing searches the grid of the *highest* summed harmonic and
  adds subharmonics by stretch-gather (see accel_search's docstring for
  the geometry). The stages H in (1, 2, 4, 8) share ONE segment grid and
  one harmonic ladder a segment: stage 2H's plane is stage H's plus its
  odd subharmonics, so the running sum takes the ratios 1; 1/2; 1/4,
  3/4; 1/8, 3/8, 5/8, 7/8 and each stage detects on it after its rung —
  8 correlation+stretch passes per span (the distinct ratios), not
  sum(H) = 15; a stage differs only in the columns it is valid on and
  its threshold (see _ladder_scan).
- Detection is on-device: 4-neighbour local-max + threshold + ``lax.top_k``
  per (segment, stage); only O(K) winners (with their 3x3 neighbourhoods for
  sub-bin refinement) ever reach the host. Host-side refinement fits a
  parabola in r and z and converts powers to equivalent-Gaussian
  significance in float64.

Calibration: with the FFT normalized to unit mean noise power (deredden)
and unit-energy templates, every plane power is mean-1 exponential under
noise, and an H-harmonic sum is Gamma(H, 1) — significance follows from
``gammaincc(H, P)`` with a trials correction, no empirical scaling.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import gammaincc, gammainccinv, gammaln, log_ndtr, ndtri

from pypulsar_tpu.compile import plane_jit

from pypulsar_tpu.fourier.zresponse import template_bank_zw
from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.ops.fourier_dedisperse import fourier_chunk_len
from pypulsar_tpu.ops.transfer import join_planes, pull_host, split_complex
from pypulsar_tpu.tune import knobs

__all__ = [
    "AccelSearchConfig",
    "AccelCandidate",
    "accel_search",
    "accel_search_batch",
    "equivalent_gaussian_sigma",
    "power_threshold",
]

HARM_STAGES = (1, 2, 4, 8)


# ---------------------------------------------------------------------------
# significance (host, float64)
# ---------------------------------------------------------------------------


def _log_gamma_sf(power: float, numsum: int) -> float:
    """log of P(X > power) for X ~ Gamma(numsum, 1) (sum of ``numsum``
    unit-mean exponential powers), stable for large powers where
    ``gammaincc`` underflows."""
    p = gammaincc(numsum, power)
    if p > 1e-280:
        return float(np.log(p))
    # asymptotic tail: p ~ power^(numsum-1) e^-power / Gamma(numsum)
    return float((numsum - 1) * np.log(power) - power - gammaln(numsum))


def equivalent_gaussian_sigma(logp: float) -> float:
    """Gaussian sigma whose upper-tail probability is ``exp(logp)``.

    Uses ``ndtri`` directly where the probability is representable; in the
    far tail solves ``log_ndtr(-x) = logp`` by Newton iteration (converges
    quadratically; 4-5 iterations from the asymptotic seed)."""
    if logp > -700.0:
        p = math.exp(logp)
        if p >= 1.0:
            return 0.0
        return float(-ndtri(p))
    # seed from log Q(x) ~ -x^2/2 - log(x sqrt(2 pi))
    x = math.sqrt(-2.0 * logp)
    for _ in range(6):
        f = log_ndtr(-x) - logp
        # d/dx log Q(x) = -phi(x)/Q(x); use asymptotic phi/Q ~ x
        df = -math.exp(-0.5 * x * x - 0.5 * math.log(2 * math.pi) - log_ndtr(-x))
        step = f / df
        x -= step
        if abs(step) < 1e-10:
            break
    return float(x)


def candidate_sigma(power: float, numsum: int, numindep: float) -> float:
    """Equivalent Gaussian significance of a summed power ``power`` over
    ``numsum`` harmonics given ``numindep`` independent trials."""
    logp1 = _log_gamma_sf(power, numsum)
    # p_total = 1 - (1-p1)^numindep, computed in log space
    if logp1 > math.log(1e-8):
        p1 = math.exp(logp1)
        ptot = -math.expm1(numindep * math.log1p(-p1))
        logp = math.log(max(ptot, 1e-320))
    else:
        logp = logp1 + math.log(numindep)
    return equivalent_gaussian_sigma(min(logp, 0.0))


def power_threshold(sigma: float, numsum: int, numindep: float) -> float:
    """Summed-power threshold whose significance is ``sigma`` after the
    ``numindep`` trials correction (inverse of candidate_sigma)."""
    # invert the trials correction p_total = 1 - (1 - p1)^numindep:
    # p1 = -expm1(log1p(-p_total)/numindep), ~ p_total/numindep when tiny
    logp = log_ndtr(-sigma)
    if logp > math.log(1e-8):
        p1 = -math.expm1(math.log1p(-math.exp(logp)) / numindep)
    else:
        p1 = math.exp(logp - math.log(numindep))
    p1 = min(max(p1, 1e-320), 1.0)
    return float(gammainccinv(numsum, p1))


# ---------------------------------------------------------------------------
# configuration / results
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AccelSearchConfig:
    zmax: float = 200.0
    dz: float = 2.0
    numharm: int = 8  # highest harmonic stage (1, 2, 4 or 8)
    sigma_min: float = 2.0
    flo: float = 1.0  # Hz, lowest searched fundamental frequency
    fhi: Optional[float] = None  # Hz, default Nyquist
    seg_width: int = 1 << 14  # fundamental bins per device segment
    topk: int = 64  # max raw hits per (segment, stage)
    min_halfwidth: int = 24
    # jerk search (PRESTO -wmax equivalent): wmax > 0 extends the template
    # bank to a (z, w) product grid — cost scales by len(ws)
    wmax: float = 0.0
    dw: float = 20.0
    # coarse-to-fine z search (VERDICT r4 item 1 stretch): > dz runs every
    # stage first on a coarse z grid at this spacing with the power
    # threshold scaled by coarse_power_frac, then re-searches ONLY the
    # segments with coarse hits at the fine dz. Candidates are identical
    # to the full search as long as a fine-grid detection keeps at least
    # coarse_power_frac of its power at the nearest coarse template —
    # measured worst-case retention at coarse_dz = 2*dz is ~0.84
    # (z-mismatch dz loses 5.4% of matched power, 2*dz loses 20%,
    # z-independent; tests/test_accelsearch.py::test_coarse_grid_power_
    # retention), so the 0.7 default leaves margin. 0 = single-pass.
    coarse_dz: float = 0.0
    coarse_power_frac: float = 0.7

    def __post_init__(self):
        import warnings

        if not 0.0 < self.coarse_power_frac <= 1.0:
            raise ValueError(f"coarse_power_frac must be in (0, 1]; got "
                             f"{self.coarse_power_frac}")
        if self.coarse_dz != 0.0 and self.coarse_dz <= self.dz:
            warnings.warn(
                f"coarse_dz={self.coarse_dz} <= dz={self.dz} has no "
                f"effect: the coarse-to-fine prepass only runs when "
                f"coarse_dz > dz", stacklevel=2)
        elif self.coarse_dz > 2.0 * self.dz:
            warnings.warn(
                f"coarse_dz={self.coarse_dz} > 2*dz: worst-case matched-"
                f"power retention at the coarse grid falls below the "
                f"calibrated ~0.80 (it is ~0.60 at a 3-bin z mismatch), "
                f"so coarse_power_frac={self.coarse_power_frac} may drop "
                f"near-threshold candidates the fine-only search would "
                f"keep", stacklevel=2)

    @property
    def zs(self) -> np.ndarray:
        """Drift grid at *exactly* ``dz`` spacing starting from -zmax (the
        top end is trimmed when dz does not divide 2*zmax — spacing, which
        the sub-cell refinement relies on, wins over symmetry)."""
        n = int(np.floor(2 * self.zmax / self.dz)) + 1
        return -self.zmax + self.dz * np.arange(n)

    @property
    def ws(self) -> np.ndarray:
        """Jerk grid (bins of second-order drift over T^3); [0] when the
        w dimension is off."""
        if self.wmax <= 0.0:
            return np.zeros(1)
        n = int(np.floor(2 * self.wmax / self.dw)) + 1
        return -self.wmax + self.dw * np.arange(n)

    @property
    def stages(self) -> Tuple[int, ...]:
        return tuple(h for h in HARM_STAGES if h <= self.numharm)


@dataclasses.dataclass
class AccelCandidate:
    """One accepted (r, z) candidate. ``r``/``z`` are fundamental Fourier
    bin and drift (bins) at the *mid-observation* epoch; ``power`` is the
    H-harmonic summed matched power; ``sigma`` its trials-corrected
    equivalent-Gaussian significance."""

    r: float
    z: float
    power: float
    sigma: float
    numharm: int
    rerr: float = 0.0
    zerr: float = 0.0
    w: float = 0.0
    werr: float = 0.0

    def freq(self, T: float) -> float:
        return self.r / T

    def fdot(self, T: float) -> float:
        return self.z / (T * T)

    def fddot(self, T: float) -> float:
        return self.w / (T * T * T)

    def as_fourierprops(self) -> Dict[str, float]:
        """Field mapping for io.prestocand.write_rzwcands."""
        return dict(
            r=self.r, rerr=self.rerr, z=self.z, zerr=self.zerr,
            w=self.w, werr=self.werr,
            pow=self.power, powerr=math.sqrt(self.numharm),
            sig=self.sigma, rawpow=self.power, phs=0.0, phserr=0.0,
            cen=0.0, cenerr=0.0, pur=0.0, purerr=0.0,
            locpow=float(self.numharm),
        )


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------


@plane_jit(static_argnames=("front", "pad"), stage="accel")
def _build_spec_pad(re, im, front, pad):
    """Padded search spectrum as [2, Np] float planes: conjugate
    reflection in front (bin -k of a real input's FFT is conj(bin k)) so
    templates overhanging the lowest bins correlate against physically
    correct values; zeros past Nyquist. Float planes in and out — complex64
    lives only inside jit (the ops/transfer.py convention)."""
    f = join_planes(re, im)
    sp = jnp.concatenate([jnp.conj(jnp.flip(f[1:front + 1])), f,
                          jnp.zeros(pad, jnp.complex64)])
    return jnp.stack([sp.real, sp.imag])


@plane_jit(static_argnames=("front", "pad"), stage="accel")
def _build_spec_pad_batch(re, im, front, pad):
    """Batched :func:`_build_spec_pad`: [B, N] planes -> [B, 2, Np]."""
    f = join_planes(re, im)  # [B, N]
    sp = jnp.concatenate(
        [jnp.conj(jnp.flip(f[:, 1:front + 1], axis=1)), f,
         jnp.zeros((f.shape[0], pad), jnp.complex64)], axis=1)
    return jnp.stack([sp.real, sp.imag], axis=1)


def _ladder_scan(spec_pad, tfs, idxs, grid_lo, lo, hi, thresh, seg_ids, *,
                 segw: int, Z: int, Wn: int, topk: int, rungs,
                 batched: bool):
    """The search of ``seg_ids`` segments of one [Np] complex spectrum
    (or, ``batched``, of a [B, Np] batch: the same body under vmap): ONE
    ``lax.scan`` over the one segment grid, whose body walks the harmonic
    ladder once and detects after each rung.

    At one top-harmonic position ``r0 + col/2`` stage 2H's sum over
    ``b/2H`` holds, for every even ``b``, exactly stage H's term: the
    same ratio bank, the same slice start ``rho * r0``, the same stretch
    index ``round(rho * col)``. So on a common grid stage 2H's plane is
    stage H's plus the odd subharmonics: the running sum takes ratio 1
    (stage 1 detects), 1/2 (stage 2), 1/4 and 3/4 (stage 4), the odd
    eighths (stage 8), and every ratio bank is correlated once a
    segment. What differs between stages is where each is valid and its
    threshold: ``lo[s] <= r_top < hi[s]`` is a column mask on what stage
    ``s`` DETECTS, never on the running sum.

    ``rungs[s]`` holds the ``(off0, step, hw, L)`` of the banks stage
    ``s`` adds; ``tfs`` / ``idxs`` are flat in the same order. Slice
    starts are affine in the segment index (``off0 + si * step``, exact
    because the grid's origin and ``segw`` are divisible by every
    stage's H). Returns (vals, zi, ri, neigh), each
    ``[n_seg, n_stages, Wn, topk, ...]``, batched with B after the
    stage axis.
    """
    batch = (functools.partial(jax.vmap, in_axes=(0, None), out_axes=1)
             if batched else (lambda f: f))
    col = jnp.arange(2 * segw, dtype=jnp.int32)

    def search_segment(spec, si):
        """One spectrum's [Np] ladder over segment ``si``."""
        r0 = grid_lo + si * segw
        plane = jnp.zeros((Z * Wn, 2 * segw), jnp.float32)
        bank = zip(tfs, idxs)
        outs = []
        for s, rung in enumerate(rungs):
            for off0, step, _hw, L in rung:
                tf2, idx = next(bank)
                with jax.named_scope("accel.correlate"):
                    tf = join_planes(tf2[0], tf2[1])  # [rows, L]
                    sl = jax.lax.dynamic_slice(spec, (off0 + si * step,),
                                               (L,))
                    cf = jnp.fft.fft(sl)
                    corr = jnp.fft.ifft(cf[None, :] * tf, axis=1)
                    p = (jnp.abs(corr) ** 2).astype(jnp.float32)
                    p = p.reshape(p.shape[0] // 2, 2 * L)
                with jax.named_scope("accel.harmonic_sum"):
                    plane = plane + jnp.take(p, idx, axis=1)
            valid = (col >= 2 * (lo[s] - r0)) & (col < 2 * (hi[s] - r0))
            stage_plane = jnp.where(valid[None, :], plane,
                                    jnp.float32(-jnp.inf))
            per_w = []
            for wi in range(Wn):
                with jax.named_scope("accel.detect"):
                    per_w.append(_detect_impl(stage_plane[wi::Wn],
                                              thresh[s], topk))
            # [Wn, k, ...] for each of (vals, zi, ri, neigh)
            outs.append([jnp.stack(f) for f in zip(*per_w)])
        return tuple(jnp.stack(f) for f in zip(*outs))  # [S, Wn, k, ...]

    search = batch(search_segment)

    def body(carry, si):
        return carry, search(spec_pad, si)

    _, res = jax.lax.scan(body, 0, seg_ids)
    return res


@functools.lru_cache(maxsize=64)
def _make_ladder_runner(segw: int, Z: int, Wn: int, topk: int, rungs,
                        batched: bool = True, mesh_devs: Tuple = ()):
    """One compiled program for the ENTIRE search of a spectrum (or of a
    batch chunk): every segment and every harmonic stage in one dispatch
    (:func:`_ladder_scan`). The naive driver dispatches (segments x
    stages x subharmonics) small device calls, each paying dispatch
    latency that can dwarf the math.

    The returned callable takes (spec_pad2, tfs, idxs, grid_lo, lo, hi,
    thresh, seg_ids): the padded spectrum and the template banks as
    float planes (complex never crosses the jit boundary, the
    ops/transfer.py convention), the per-stage validity bounds and
    thresholds as ``[n_stages]`` arrays, and the int32 segment indices
    to scan — ``arange(n_seg)`` for a full pass, or the coarse pass's
    hit segments for a coarse-to-fine refine (results land in seg_ids
    order; only its LENGTH keys compilation).

    ``batched`` False is the serial program ``accel_stage``: one [2, Np]
    spectrum, results without the batch axis. Batched (VERDICT r3 item
    2), B spectra correlate against the SHARED banks: the bank FFTs and
    stretch indices are DM-independent — across a 4096-trial batch only
    the spectrum changes — so the segment slice is a [B, L] batched
    FFT, the correlation a [B, rows, L] broadcast multiply against the
    one [rows, L] bank, and detection a vmap of the detector. Larger FFT
    batches are exactly what the TPU FFT lowering needs.

    A non-empty ``mesh_devs`` (a tuple of jax devices — resolved by the
    caller through the gang lease, never ``jax.devices()[:k]``, so two
    gang-leased observations cannot collide on chips 0..k-1)
    additionally shard_maps the batch axis over the 'dm' axis of a mesh
    built on exactly those devices (each device holds B/k spectra and
    the full banks — zero cross-device communication; candidates gather
    on host), the same layout the sweep uses.
    """
    scan = functools.partial(_ladder_scan, segw=segw, Z=Z, Wn=Wn, topk=topk,
                             rungs=rungs, batched=batched)

    # explicit signatures: the plane binds a call's arguments by name
    if not batched:
        def run_one(spec_pad2, tfs, idxs, grid_lo, lo, hi, thresh, seg_ids):
            return scan(join_planes(spec_pad2[0], spec_pad2[1]),
                        tfs, idxs, grid_lo, lo, hi, thresh, seg_ids)

        return plane_jit(run_one, stage="accel", name="accel_stage")

    def run(spec_pad2, tfs, idxs, grid_lo, lo, hi, thresh, seg_ids):
        return scan(join_planes(spec_pad2[:, 0], spec_pad2[:, 1]),
                    tfs, idxs, grid_lo, lo, hi, thresh, seg_ids)

    if not mesh_devs:
        return plane_jit(run, stage="accel", name="accel_stage_batch")

    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(list(mesh_devs)), ("dm",))

    def run_sharded(spec_pad2, tfs, idxs, grid_lo, lo, hi, thresh, seg_ids):
        shd = jax.shard_map(
            run, mesh=mesh,
            in_specs=(P("dm"), P(), P(), P(), P(), P(), P(), P()),
            out_specs=P(None, None, "dm"),
            check_vma=False,
        )
        return shd(spec_pad2, tfs, idxs, jnp.int32(grid_lo), lo, hi, thresh,
                   seg_ids)

    # one wrapper per mesh (this factory is memoised on mesh_devs): its
    # AOT executables belong to exactly those chips, and the sharded
    # batch keys by its own mesh and partition spec besides
    return plane_jit(run_sharded, stage="accel", name="accel_stage_sharded")


def _detect_impl(accum, thresh, k: int):
    """Traceable body of :func:`_detect` (shared)."""
    Z, R2 = accum.shape
    neg = jnp.float32(-jnp.inf)
    pad = jnp.pad(accum, 1, constant_values=neg)
    c = pad[1:-1, 1:-1]
    ismax = (
        (c >= pad[:-2, 1:-1]) & (c >= pad[2:, 1:-1])
        & (c >= pad[1:-1, :-2]) & (c > pad[1:-1, 2:])
        & (c > thresh)
    )
    flat = jnp.where(ismax, accum, neg).ravel()
    vals, idx = jax.lax.top_k(flat, k)
    zi = idx // R2
    ri = idx % R2
    zo = zi[:, None, None] + jnp.arange(3)[None, :, None]
    ro = ri[:, None, None] + jnp.arange(3)[None, None, :]
    neigh = pad[zo, ro]
    return vals, zi, ri, neigh


# ---------------------------------------------------------------------------
# the search driver
# ---------------------------------------------------------------------------


_BANK_CACHE: Dict[tuple, tuple] = {}
_BANK_CACHE_BYTES = [0]


def _bank_cache_limit() -> float:
    """Host-RAM bound on cached template banks (jerk banks reach GB
    scale) — the old inline ``_BANK_CACHE_LIMIT = 4e9`` constant,
    registered as ``PYPULSAR_TPU_ACCEL_BANK_CACHE`` (round 24) so a
    RAM-tight host can shrink it without editing source."""
    return float(knobs.env_float("PYPULSAR_TPU_ACCEL_BANK_CACHE"))


def _build_ratio_bank(rho_num: int, rho_den: int, zs: tuple, ws: tuple,
                      segw: int, min_halfwidth: int):
    """(tf[2, rows, L] float32 re/im planes, hw, L, stretch idx[2*segw]
    int32) for one subharmonic ratio: harmonic b/H of a signal with
    (z, w) drifts at the top harmonic has drifts scaled by the same
    ratio. Cached — bank construction (host FFT synthesis) dominates
    setup when many spectra are searched with one configuration."""
    rf = rho_num / rho_den
    zs = np.asarray(zs)
    ws = np.asarray(ws)
    tb, hw = template_bank_zw(zs * rf, ws * rf, numbetween=2,
                              min_halfwidth=min_halfwidth)
    wrho = (segw * rho_num) // rho_den
    m = tb.shape[1]
    L = fourier_chunk_len(wrho + 2 * hw + m)
    padded = np.zeros((tb.shape[0], L), dtype=np.complex128)
    padded[:, :m] = tb
    rev = np.zeros_like(padded)
    rev[:, 0] = padded[:, 0]
    rev[:, 1:] = padded[:, :0:-1]
    tf_c = np.fft.fft(rev, axis=1).astype(np.complex64)
    # stored as [2, rows, L] float32 planes: that is the form shipped to
    # the device every search (complex cannot cross the jit boundary,
    # ops/transfer.py), so caching planes avoids a bank-sized stack +
    # copy per accel_search call
    tf = np.stack([tf_c.real, tf_c.imag])
    # static stretch: plane column `col` (top position r0 + col/2) maps to
    # subharm half-bin index round(rho*col) relative to rho*r0; corr[j]
    # evaluates spectrum position s0 + j (the template's -hw offset cancels
    # the slice's -hw start), so the column index is rel//2 with no hw term
    rel = np.floor(rf * np.arange(2 * segw) + 0.5).astype(np.int64)
    idx = ((rel % 2) * L + (rel // 2)).astype(np.int32)
    return tf, hw, L, idx


def _cached_ratio_bank(rho_num, rho_den, zs, ws, segw, min_halfwidth):
    """Byte-bounded memo of :func:`_build_ratio_bank` — repeated searches
    with one configuration (the 4096-trial batch) reuse banks, while a
    parameter sweep cannot pin unbounded host RAM. Eviction is
    oldest-first (dict insertion order), not clear-all: a coarse-to-fine
    search holds TWO grids' banks per configuration, and a clear-all
    policy would thrash the whole cache once the combined set crossed
    the limit — rebuilding every bank (the setup-dominating host FFT
    synthesis) per spectrum of a survey loop."""
    key = (rho_num, rho_den, zs, ws, segw, min_halfwidth)
    hit = _BANK_CACHE.pop(key, None)
    if hit is not None:
        _BANK_CACHE[key] = hit  # move-to-end: eviction is LRU, not FIFO
        return hit
    bank = _build_ratio_bank(rho_num, rho_den, zs, ws, segw, min_halfwidth)
    size = bank[0].nbytes + bank[3].nbytes
    limit = _bank_cache_limit()
    if size > limit:
        return bank  # uncacheable; evicting everything for it helps nobody
    while _BANK_CACHE and _BANK_CACHE_BYTES[0] + size > limit:
        old_key = next(iter(_BANK_CACHE))
        old = _BANK_CACHE.pop(old_key)
        _BANK_CACHE_BYTES[0] -= old[0].nbytes + old[3].nbytes
    _BANK_CACHE[key] = bank
    _BANK_CACHE_BYTES[0] += size
    return bank


def _ladder_grid(stages, rlo: int, rhi: int, N: int, segw: int):
    """(grid_lo, n_seg, lo, hi) of the ONE segment grid every harmonic
    stage is searched on (shared by the serial and batched drivers and
    their coarse passes — segment indices must map one-to-one between
    passes). The origin is divisible by every stage's H, so each ratio's
    slice start ``rho * r0`` is exact; stage ``stages[s]`` is valid on
    top-harmonic positions ``lo[s] <= r_top < hi[s]``, which the scan
    applies as a column mask."""
    hmax = max(stages)
    grid_lo = hmax * (rlo // hmax)
    grid_hi = min(hmax * rhi, N - 1)
    n_seg = -(-(grid_hi - grid_lo) // segw) if grid_hi > grid_lo else 0
    lo = np.array([H * rlo for H in stages], dtype=np.int32)
    hi = np.array([min(H * rhi, N - 1) for H in stages], dtype=np.int32)
    return grid_lo, n_seg, lo, hi


def _select_segments(N, T, cfg: AccelSearchConfig, front, Np, n_seg,
                     thresh_vals, hit_fn):
    """(ids, scan_ids) shared by both drivers: the segments whose hits
    are unpacked and the segment list the fine pass scans. Single-pass,
    both are every segment. With ``cfg.coarse_dz`` the coarse pass
    decides: rerun :func:`_search_setup` on the coarse z grid (identical
    padding geometry — asserted — so segment indices map one-to-one),
    then ask ``hit_fn(banks_coarse, n_z_rows, thresh_vals, seg_ids)`` —
    the driver's own ladder executor — which segments hold a hit of ANY
    stage at the reduced thresholds (there is one grid, so the fine pass
    scans the union over the stages), padded to a compiled length."""
    ids = np.arange(n_seg)
    if cfg.coarse_dz <= cfg.dz:
        return ids, ids
    ccfg = dataclasses.replace(cfg, dz=cfg.coarse_dz, coarse_dz=0.0)
    (zs_c, _wc, _sc, _gc, _rl, _rh, banks_c, front_c, Np_c,
     _nc, _tc) = _search_setup(N, T, ccfg)
    if (front_c, Np_c) != (front, Np):
        raise AssertionError("coarse/fine padding geometry diverged")
    hits = hit_fn(banks_c, len(zs_c), cfg.coarse_power_frac * thresh_vals,
                  ids)
    ids = np.nonzero(hits)[0]
    return ids, _pad_pow2(ids, n_seg)


def _pad_pow2(ids: np.ndarray, n_seg: int) -> np.ndarray:
    """Pad a segment-id list to the next power-of-two length (capped at
    the grid's ``n_seg``) by repeating the last id. Refine-pass hit
    counts vary per spectrum, and every distinct ``seg_ids`` LENGTH is
    one XLA compile — pow2 padding
    bounds the compile count at log2(n_seg) shapes per search geometry.
    The cap keeps a near-full selection from scanning MORE segments than
    the single-pass search would (and its length is the shape a full
    pass compiles anyway). Duplicate positions produce duplicate raw
    hits, which the final sift already collapses; callers additionally
    unpack only the first len(ids) positions."""
    n = int(len(ids))
    m = min(1 << max(n - 1, 0).bit_length(), n_seg)
    if m <= n:
        return ids
    return np.concatenate([ids, np.full(m - n, ids[-1], dtype=ids.dtype)])


def _parabola_peak(ym, y0, yp):
    """Sub-cell offset and peak value of the parabola through three
    equally spaced samples (offset clipped to the cell)."""
    denom = ym - 2.0 * y0 + yp
    if denom >= 0.0 or not np.isfinite(denom):
        return 0.0, y0
    d = 0.5 * (ym - yp) / denom
    d = float(np.clip(d, -0.5, 0.5))
    return d, float(y0 - 0.25 * (ym - yp) * d)


def _search_setup(N: int, T: float, cfg: AccelSearchConfig):
    """Shared host-side setup of the serial and batched drivers: the
    (z, w) grids, harmonic stages, subharmonic ratio banks, spectrum
    padding geometry, and per-stage trials corrections — all of it
    DM-independent, which is exactly why a batch of spectra can share
    one set of device-resident banks."""
    from fractions import Fraction

    zs = cfg.zs
    ws = cfg.ws
    stages = cfg.stages
    segw = cfg.seg_width
    if segw % max(stages):
        raise ValueError(f"seg_width {segw} must be divisible by "
                         f"numharm {max(stages)}")
    rlo = max(int(np.ceil(cfg.flo * T)), 1)
    rhi = int(np.floor((cfg.fhi * T) if cfg.fhi else (N - 1)))
    rhi = min(rhi, N - 1)
    if rhi <= rlo:
        raise ValueError(f"empty search range: rlo={rlo} rhi={rhi}")
    ratios = sorted({Fraction(b, H) for H in stages for b in range(1, H + 1)})
    banks = {
        rho: _cached_ratio_bank(rho.numerator, rho.denominator,
                                tuple(zs), tuple(ws), segw,
                                cfg.min_halfwidth)
        for rho in ratios
    }
    maxhw = max(hw for _, hw, _, _ in banks.values())
    front = maxhw + 1
    maxL = max(L for _, _, L, _ in banks.values())
    Np = N + maxL + front + 8
    Z, Wn = len(zs), len(ws)
    numindep, thresh = {}, {}
    for H in stages:
        ntop = max(min(H * rhi, N - 1) - H * rlo, 1)
        numindep[H] = max(ntop * Z * Wn / H, 1.0)
        thresh[H] = power_threshold(cfg.sigma_min, H, numindep[H])
    return zs, ws, stages, segw, rlo, rhi, banks, front, Np, numindep, thresh


def _ladder_banks(banks, stages, grid_lo: int, segw: int, front: int):
    """(rungs, tfs, idxs): device copies of the search's ratio banks,
    each once, in ladder order — stage H adds the ratios b/H in lowest
    terms (1; 1/2; 1/4, 3/4; the odd eighths). ``rungs[s]`` holds the
    ``(off0, step, hw, L)`` of stage ``s``'s additions, ``tfs`` / ``idxs``
    are flat in the same order (residency: accel_search's run_ladder)."""
    from fractions import Fraction

    rungs, tfs, idxs = [], [], []
    for H in stages:
        rung = []
        for b in range(1, H + 1):
            rho = Fraction(b, H)
            if rho.denominator != H:
                continue  # a lower rung's bank, already in the sum
            tf, hw, L, idx = banks[rho]
            rung.append((front + (b * grid_lo) // H - hw,
                         (b * segw) // H, hw, L))
            tfs.append(jnp.asarray(tf))  # [2, rows, L] float planes
            idxs.append(jnp.asarray(idx))
        rungs.append(tuple(rung))
    return tuple(rungs), tuple(tfs), tuple(idxs)


def _refine_hits(raw_hits, zs, ws, cfg: AccelSearchConfig,
                 numindep, thresh) -> List[AccelCandidate]:
    """Host-side (float64) refine + significance + sift of raw device
    hits: parabola sub-cell peaks in r and z, trials-corrected Gaussian
    sigma, then greedy duplicate removal by fundamental proximity."""
    cands: List[AccelCandidate] = []
    for H, wi, r0, vals, zi, ri, neigh in raw_hits:
        # vectorized pre-filter: most top-k slots are -inf (below the
        # detection threshold) and the Python loop below runs per
        # (spectrum, stage, segment, k) — 10^7-scale at survey batch
        # sizes if every slot is visited. float64 so the threshold
        # compare matches the old per-element float(p) <= thresh exactly
        vals = np.asarray(vals, dtype=np.float64)
        keep = np.isfinite(vals) & (vals > thresh[H])
        for j in np.nonzero(keep)[0]:
            p = float(vals[j])
            nb = neigh[j].astype(np.float64)
            dr, _ = _parabola_peak(nb[1, 0], nb[1, 1], nb[1, 2])
            dzo, _ = _parabola_peak(nb[0, 1], nb[1, 1], nb[2, 1])
            r_top = r0 + 0.5 * (float(ri[j]) + dr)
            z_top = zs[int(zi[j])] + dzo * cfg.dz
            w_top = float(ws[wi])
            sig = candidate_sigma(p, H, numindep[H])
            if sig < cfg.sigma_min:
                continue
            # matched-filter location uncertainties (linear-chirp Fisher
            # information approximations, cf. Ransom et al. 2002 app. A),
            # scaled to the fundamental
            rerr = 3.0 / (np.pi * math.sqrt(6.0 * p)) / H
            zerr = 3.0 * math.sqrt(105.0 / p) / np.pi / H
            werr = (cfg.dw / math.sqrt(max(p, 1.0))) / H if len(ws) > 1 else 0.0
            cands.append(AccelCandidate(
                r=r_top / H, z=z_top / H, power=p, sigma=sig,
                numharm=H, rerr=rerr, zerr=zerr,
                w=w_top / H, werr=werr))

    # sift: sort by sigma, greedily keep candidates whose fundamental is
    # not within 1 bin (and 2 z grid cells) of an already-accepted one
    cands.sort(key=lambda c: -c.sigma)
    kept: List[AccelCandidate] = []
    for c in cands:
        dup = False
        for kc in kept:
            if abs(c.r - kc.r) < 1.0 and abs(c.z - kc.z) <= 2 * cfg.dz:
                dup = True
                break
        if not dup:
            kept.append(c)
    return kept


def accel_search(
    fft,
    T: float,
    config: AccelSearchConfig = AccelSearchConfig(),
) -> List[AccelCandidate]:
    """Search a *normalized* FFT (unit mean noise power, e.g. the output of
    fourier.kernels.deredden) for accelerated periodic signals.

    ``fft`` is the one-sided complex spectrum (bin k = frequency k/T);
    ``T`` is the observation length in seconds. Returns sifted candidates
    (fundamental ``r``/``z``) sorted by decreasing sigma.

    Harmonic geometry (the PRESTO structure): stage ``H`` searches the grid
    of the *highest* summed harmonic ``r_top = H*r_fund`` at half-bin
    resolution and adds subharmonics at ``r_top * b/H`` — downward
    "stretching", so position quantization is at most 1/4 bin for every
    subharmonic. (Summing upward from a fundamental grid undersamples
    harmonic ``h`` by ``h/4`` bins — measurably losing the high harmonics;
    caught by tests/test_accelsearch.py::test_harmonic_summing_beats_
    fundamental during development.) ``zmax`` bounds the drift of the top
    harmonic (PRESTO convention); a stage-``H`` candidate's fundamental
    drift resolution is ``dz/H``.
    """
    cfg = config
    f_re, f_im = split_complex(fft)
    N = int(f_re.shape[0])
    (zs, ws, stages, segw, rlo, rhi, banks, front, Np,
     numindep, thresh) = _search_setup(N, T, cfg)
    Z, Wn = len(zs), len(ws)

    grid_lo, n_seg, lo, hi = _ladder_grid(stages, rlo, rhi, N, segw)
    if not n_seg:
        return []
    # pad the spectrum: conjugate reflection in front (bin -k of a real
    # input's FFT is conj(bin k)) so templates overhanging the lowest bins
    # correlate against physically correct values; zeros past Nyquist
    spec_pad2 = _build_spec_pad(jnp.asarray(f_re), jnp.asarray(f_im),
                                front, int(max(Np - N, 8)))
    thresh_vals = np.array([thresh[H] for H in stages])

    def run_ladder(banks_src, Zrows, tvals, seg_ids):
        """Every harmonic stage over ``seg_ids``, each [len(seg_ids),
        n_stages, Wn, ...]. Device residency is the search's distinct
        ratio banks, each once (what the deepest stage alone needs; a
        jerk bank set is GB-scale at survey parameters); the whole pass
        is one compiled lax.scan (one dispatch; see _make_ladder_runner)
        and the tfs/idxs device buffers free on return."""
        rungs, tfs, idxs = _ladder_banks(banks_src, stages, grid_lo, segw,
                                         front)
        runner = _make_ladder_runner(segw, Zrows, Wn, cfg.topk, rungs,
                                     batched=False)
        telemetry.counter("accel.bank_passes", len(seg_ids) * len(tfs))
        with telemetry.span("accel_stage", stages=len(stages),
                            banks=len(tfs), n_seg=int(len(seg_ids))):
            return pull_host(*runner(
                spec_pad2, tfs, idxs, grid_lo, lo, hi,
                tvals.astype(np.float32),
                jnp.asarray(seg_ids, dtype=jnp.int32)))

    def coarse_hits(banks_c, Zc, thresh_c, seg_ids):
        vals, _zi, _ri, _ne = run_ladder(banks_c, Zc, thresh_c, seg_ids)
        return np.isfinite(vals).any(axis=(1, 2, 3))

    # optional coarse pass (cfg.coarse_dz): the same ladder on a coarse z
    # grid at reduced power thresholds selects which segments the fine
    # pass scans
    ids, scan_ids = _select_segments(N, T, cfg, front, Np, n_seg,
                                     thresh_vals, coarse_hits)
    if not len(ids):
        return []

    vals, zi, ri, neigh = run_ladder(banks, Z, thresh_vals, scan_ids)
    raw_hits = []  # (stage, w idx, seg r0, vals, zidx, colidx, neigh)
    for s, H in enumerate(stages):
        for pos, si in enumerate(ids):
            r0 = grid_lo + int(si) * segw
            for wi in range(Wn):
                raw_hits.append((H, wi, r0, vals[pos, s, wi], zi[pos, s, wi],
                                 ri[pos, s, wi], neigh[pos, s, wi]))

    return _refine_hits(raw_hits, zs, ws, cfg, numindep, thresh)


def _stage_chunk_bytes(tfs, Z: int, Wn: int, segw: int) -> int:
    """Estimated device bytes PER BATCHED SPECTRUM for the ladder's
    scan body: every ratio bank (``tfs`` entry, [2, rows, L])
    materializes a [rows, L] complex64 correlation plus its FFT-input
    product (16 B/cell live at once), the |.|^2 power (4 B/cell), and
    the [Z*Wn, 2*segw] gathered plane (two f32 copies around the
    accumulate). Used to pick the batch chunk that fits HBM up front: an
    oversized dispatch (B=32, N=2^21, zmax=200 was one) costs at best a
    RESOURCE_EXHAUSTED and a halved retry (resilience/retry.py), at worst
    the process. The estimate carries a 1.25x safety factor because XLA
    fusion can hold an extra temporary; if a batched search still
    exhausts the device, lowering
    ``PYPULSAR_TPU_ACCEL_HBM`` is the first knob."""
    tot = sum(int(t.shape[1]) * int(t.shape[2]) * 25 for t in tfs)
    return tot + Z * Wn * 2 * segw * 10


def accel_search_batch(
    ffts,
    T: float,
    config: AccelSearchConfig = AccelSearchConfig(),
    mesh_devices: int = 0,
    hbm_budget_bytes: Optional[int] = None,
    devices: Optional[Tuple] = None,
) -> List[List[AccelCandidate]]:
    """Search a BATCH of normalized FFTs sharing one configuration
    (VERDICT r3 item 2: the 4096-DM-trial workload searches thousands of
    spectra with identical template banks — only the spectrum changes).

    ``ffts`` is [B, N] complex (anything np.asarray makes so), or a
    ``(re, im)`` tuple of real [B, N] plane arrays — the complex-boundary
    convention (ops/transfer) that lets device-resident spectra from
    ``kernels.prep_spectra_batch`` feed the search without a host round
    trip. The
    whole harmonic ladder correlates all B spectra against the one set
    of device-resident banks in a single dispatch (_make_ladder_runner),
    so the bank FFT cost, the dispatch latency, and the TPU's preference
    for large FFT batches all amortize over the batch. Returns one
    sifted candidate list per input spectrum, in order — identical to
    ``[accel_search(f, T, config) for f in ffts]`` (parity-tested).

    The batch axis is internally processed in chunks sized so the
    scan's working set fits ``hbm_budget_bytes`` (default: the
    ``PYPULSAR_TPU_ACCEL_HBM`` env var or 5e9). The full batch of padded
    spectra stays device-resident across chunks (B*Np complex ~ 17 MB
    per 2^21-bin spectrum); only the scan working set is chunked.

    ``mesh_devices`` > 0 shards the batch over that many devices
    (shard_map over a 'dm' mesh axis; B must be a multiple of it, and
    chunks round down to a multiple of it). The device set comes from
    ``devices`` when given, else from the gang-lease resolver
    (parallel.mesh.lease_devices) — NEVER bare ``jax.devices()[:k]``,
    so a gang-leased search addresses exactly its leased chips.
    """
    cfg = config
    if devices is not None:
        devices = tuple(devices)
        mesh_devices = len(devices)
    elif mesh_devices:
        from pypulsar_tpu.parallel.mesh import lease_devices

        devices = tuple(lease_devices(mesh_devices))
    else:
        devices = ()
    if isinstance(ffts, tuple):
        # (re, im) REAL-dtyped plane arrays — possibly already device-
        # resident (kernels.prep_spectra_batch): no host conversion, no
        # re-ship. A tuple of complex spectra is a contract error, not a
        # batch: stack complex arrays instead.
        re_a, im_a = ffts
        if re_a.ndim != 2 or re_a.shape != im_a.shape:
            raise ValueError(f"plane tuple must be two [B, N] arrays; got "
                             f"{re_a.shape} / {im_a.shape}")
        if np.iscomplexobj(re_a) or np.iscomplexobj(im_a):
            raise ValueError("plane tuple must hold REAL re/im arrays; "
                             "pass complex spectra as one stacked [B, N] "
                             "array instead")
    else:
        arr = np.asarray(ffts)
        if arr.ndim != 2:
            raise ValueError(f"ffts must be [B, N]; got {arr.shape}")
        re_a = np.ascontiguousarray(arr.real, dtype=np.float32)
        im_a = np.ascontiguousarray(arr.imag, dtype=np.float32)
    B, N = re_a.shape
    if mesh_devices and B % mesh_devices:
        raise ValueError(f"batch {B} must be divisible by "
                         f"mesh_devices {mesh_devices}")
    (zs, ws, stages, segw, rlo, rhi, banks, front, Np,
     numindep, thresh) = _search_setup(N, T, cfg)
    Z, Wn = len(zs), len(ws)

    if hbm_budget_bytes is None:
        hbm_budget_bytes = int(
            knobs.env_float("PYPULSAR_TPU_ACCEL_HBM"))

    # the padded spectra themselves stay device-resident across chunks
    # (~8*Np bytes each); a batch large enough to blow half the budget on
    # residency alone is processed in top-level slices (each slice still
    # amortizes the banks over its spectra)
    max_resident = max(1, (hbm_budget_bytes // 2) // (Np * 8))
    if mesh_devices:
        max_resident = max(mesh_devices,
                           (max_resident // mesh_devices) * mesh_devices)
    if B > max_resident:
        out: List[List[AccelCandidate]] = []
        for c0 in range(0, B, max_resident):
            out.extend(accel_search_batch(
                (re_a[c0:c0 + max_resident], im_a[c0:c0 + max_resident]),
                T, config, mesh_devices=mesh_devices,
                hbm_budget_bytes=hbm_budget_bytes,
                devices=devices or None))
        return out

    spec_pad2 = _build_spec_pad_batch(jnp.asarray(re_a), jnp.asarray(im_a),
                                      front, int(max(Np - N, 8)))

    grid_lo, n_seg, lo, hi = _ladder_grid(stages, rlo, rhi, N, segw)
    if not n_seg:
        return [[] for _ in range(B)]
    thresh_vals = np.array([thresh[H] for H in stages])

    def run_ladder_chunks(banks_src, Zrows, tvals, seg_ids):
        """Yield (c0, nb, vals, zi, ri, neigh) per batch chunk for the
        whole ladder scanned over ``seg_ids``; the chunk size respects
        the per-device HBM budget and the bank buffers free when the
        generator is exhausted."""
        rungs, tfs, idxs = _ladder_banks(banks_src, stages, grid_lo, segw,
                                         front)
        # the budget is per device: a sharded chunk splits across the
        # mesh, so the whole chunk may hold mesh_devices x the budget
        per_dev = max(1, hbm_budget_bytes
                      // _stage_chunk_bytes(tfs, Zrows, Wn, segw))
        chunk = max(1, min(B, per_dev * max(1, mesh_devices)))
        if mesh_devices:
            chunk = max(mesh_devices, (chunk // mesh_devices) * mesh_devices)
        runner = _make_ladder_runner(segw, Zrows, Wn, cfg.topk, rungs,
                                     mesh_devs=devices)
        ids_dev = jnp.asarray(seg_ids, dtype=jnp.int32)
        thresh_dev = tvals.astype(np.float32)
        span_attrs = {}
        if devices:
            span_attrs["dev"] = [int(getattr(d, "id", -1))
                                 for d in devices]
        from pypulsar_tpu.resilience import faultinject
        from pypulsar_tpu.resilience.retry import halving_dispatch

        for c0 in range(0, B, chunk):
            # slice (not pad): a short tail chunk costs one extra compile
            # for its shape but never ships dead spectra through the scan
            nc = min(chunk, B - c0)

            def dispatch(lo_b, hi_b, c0=c0):
                faultinject.trip("accel.stage_dispatch")
                sl = spec_pad2[c0 + lo_b:c0 + hi_b]
                telemetry.counter("accel.bank_passes",
                                  len(seg_ids) * len(tfs))
                with telemetry.span("accel_stage_batch",
                                    stages=len(stages), banks=len(tfs),
                                    batch=int(hi_b - lo_b),
                                    n_seg=int(len(seg_ids)),
                                    **span_attrs):
                    # [len(seg_ids), n_stages, nb, Wn, k] each; one
                    # batched pull
                    return pull_host(*runner(
                        sl, tfs, idxs, grid_lo, lo, hi, thresh_dev,
                        ids_dev))

            # the HBM budget is an estimate: a chunk it admitted that
            # still RESOURCE_EXHAUSTs auto-halves with bounded backoff
            # (per-spectrum results are independent — the halves are the
            # chunk, bit-identically). Sharded chunks stay divisible by
            # the mesh via min_size
            for lo_b, hi_b, outs in halving_dispatch(
                    dispatch, nc, min_size=max(1, mesh_devices),
                    what="accel.stage"):
                vals, zi, ri, neigh = outs
                yield c0 + lo_b, hi_b - lo_b, vals, zi, ri, neigh

    def coarse_hits(banks_c, Zc, thresh_c, seg_ids):
        hit = np.zeros(len(seg_ids), bool)
        for _c0, _nb, vals, _zi, _ri, _ne in run_ladder_chunks(
                banks_c, Zc, thresh_c, seg_ids):
            hit |= np.isfinite(vals).any(axis=(1, 2, 3, 4))
        return hit

    # optional coarse pass (cfg.coarse_dz): segments are selected by the
    # UNION of coarse hits over the stages and the whole batch — the
    # per-DM spectra of one observation concentrate their signal in the
    # same segments, which is also why the bank sharing works
    ids, scan_ids = _select_segments(N, T, cfg, front, Np, n_seg,
                                     thresh_vals, coarse_hits)
    if not len(ids):
        return [[] for _ in range(B)]

    # a spectrum lies in one chunk, so its hits arrive stage by stage
    # (the order the sift sees ties in)
    raw_per_b: List[list] = [[] for _ in range(B)]
    for c0, nb, vals, zi, ri, neigh in run_ladder_chunks(
            banks, Z, thresh_vals, scan_ids):
        for s, H in enumerate(stages):
            for pos, si in enumerate(ids):
                r0 = grid_lo + int(si) * segw
                for bl in range(nb):
                    for wi in range(Wn):
                        raw_per_b[c0 + bl].append(
                            (H, wi, r0, vals[pos, s, bl, wi],
                             zi[pos, s, bl, wi], ri[pos, s, bl, wi],
                             neigh[pos, s, bl, wi]))

    return [_refine_hits(raw, zs, ws, cfg, numindep, thresh)
            for raw in raw_per_b]
