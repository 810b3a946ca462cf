"""Tree dedispersion: O(log2 nchan) shared-work sweep over ALL DM trials.

Why: every other sweep engine computes each DM trial independently —
per output sample the two-stage engines pay ``G*C`` stage-1 adds plus
``D*S`` stage-2 adds (parallel/sweep.py), and PR 2's roofline proved the
accel stage already runs at 85% of its FFT ceiling, so the remaining
order of magnitude at production DM counts (thousands of trials, not the
toy 16) must come from *sharing work between trials*. The Fast DM
Transform / tree recurrences (PAPERS.md 1201.5380 "Accelerating
incoherent dedispersion"; 2311.05341 "Accelerating Dedispersion using
Many-Core Architectures") compute all trials together through log2(nchan)
pairwise subband-merge levels: a partial sum over a 2w-channel block is
one add of two w-channel partial sums, and trials whose per-channel
shifts agree on a block SHARE that block's row instead of re-summing it.

The classic FDMT buys its complexity bound with a linear-delay
approximation inside each block. This engine does NOT approximate: the
per-level shift tables are derived from the EXACT integer shifts the
direct engines apply (``stage1_bins + stage2_bins``, i.e. the
numpy_ref.bin_delays rounding, split exactly as the two-stage plan splits
it), and the merge recurrence is exact by construction —

    row(block, v)[t] = sum_{c in block} data[c, t + P_v(c)]

where each variant profile ``P_v`` is a *normalized* (min-zero) restriction
of some trial's exact shift vector to the block. Merging blocks L|R:
``P_v`` restricted to L is itself a variant ``vL`` of L shifted by
``offA = min_L P_v`` and likewise for R, so

    row(LR, v)[t] = row(L, vL)[t + offA] + row(R, vR)[t + offB]

— one batched gather+add per level over the previous level's rows, with
static-shape tables and dynamic gather indices, expressed as a
``lax.scan`` over the levels. The final **exact-shift snap stage** maps
trial d to its top-level variant row read at offset ``min_c shift[d, c]``:
every channel's total shift in trial d's series is then BYTE-FOR-BIT the
same ``s1 + s2`` the gather/scan/fourier engines apply. What differs is
only the f32 *summation tree* (balanced pairwise vs reshape-reduce),
which lands inside the sweep's existing ≤2e-6 relative-SNR parity
contract (tests/test_sweep.py::test_tree_engine_snr_tolerance).

Work accounting (the structural counters tools/dedisp_roofline.py and
``bench.py --dedisp-tree`` report): per output sample the tree performs
``sum_l R_l`` adds, where ``R_l`` is level l's merged-row count — bounded
by ``nblocks_l * min(D_distinct, span_l + 1)`` with ``span_l`` the
dispersion-delay spread across a level-l block. At the FDMT-regime
diagonal (trial spacing ~ the delay step, delay span ~ nchan) that is
~``max(nchan, span) * log2(nchan)`` for ALL trials, versus
``D * (C/g + S)`` for the two-stage direct engine and ``D * C`` naive —
and with the delay span held fixed it scales ~log2(nchan) while direct
scales ~nchan. Because the tables are deduplicated against the ACTUAL
trial list, toy grids collapse to near-direct row counts instead of
paying the full FDMT delay enumeration.

Host/device split: the merge tables are built host-side (NumPy, cached —
``PYPULSAR_TPU_TREE_PLAN_CACHE`` entries) because deduplication is
data-dependent; the kernels are pure static-shape scans, so everything
jits with dynamic table CONTENT and static table SHAPE. The engine
therefore dispatches from the Python wrappers in parallel/sweep.py
(``sweep_chunk`` / ``dedisperse_series_chunk``), never from inside a
traced ``_sweep_chunk_impl``.

Sharding: the per-trial value of a tree row depends only on that trial's
own shift vector (the merge structure over channels is fixed), so a
'dm'-mesh shard that builds its OWN tables for its local trial groups
produces rows bit-identical to the unsharded engine's — the same
device-count-independence contract the other engines' sharded paths
carry (tests assert array_equal, not allclose).

Reference treatment: nonexistent (the reference rolls channels one trial
at a time, formats/spectra.py:54-94; PRESTO's prepsubband shares work
only through the two-stage subband split this engine's exact tables
inherit).
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from functools import lru_cache, partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.ops.pallas_kernels import boxcar_stats
from pypulsar_tpu.tune import knobs

__all__ = [
    "TreePlan",
    "plan_from_bins",
    "sweep_chunk_tree",
    "dedisperse_series_tree",
    "make_sharded_tree_sweep_chunk",
    "make_sharded_tree_series_chunk",
]


class TreePlan:
    """Host-built merge-tree tables for one (stage1_bins, stage2_bins)
    shift set.

    tabs[4, NL, R] int32   per-level (srcA, srcB, offA, offB); rows past a
                           level's real count (and passthrough srcB) point
                           at the constant zero row ``R``
    trial_row[D] int32     top-level row of each trial (group-major order)
    trial_off[D] int32     the snap offset: min_c of the trial's exact
                           per-channel shift (its profile is stored
                           min-normalized)
    pad                    static shift bound for the per-level slices
                           (max exact total shift)
    adds_per_sample        sum of real (two-child) merges over all levels
                           — the structural work counter
    """

    def __init__(self, tabs, trial_row, trial_off, pad, group_size,
                 rows, n_levels, adds_per_sample, rows_per_level,
                 n_channels):
        self.tabs = tabs
        self.trial_row = trial_row
        self.trial_off = trial_off
        self.pad = int(pad)
        self.group_size = int(group_size)
        self.rows = int(rows)
        self.n_levels = int(n_levels)
        self.adds_per_sample = int(adds_per_sample)
        self.rows_per_level = tuple(int(r) for r in rows_per_level)
        self.n_channels = int(n_channels)
        self.n_trials = int(len(trial_row))
        self._dev = None  # lazily cached device copies of the tables

    def device_tables(self):
        """(tabs, trial_row, trial_off) as device arrays, converted once
        so the per-chunk dispatches of a streamed sweep reuse the same
        buffers instead of re-shipping the tables every chunk."""
        if self._dev is None:
            self._dev = (jnp.asarray(self.tabs),
                         jnp.asarray(self.trial_row),
                         jnp.asarray(self.trial_off))
        return self._dev

    def state_bytes(self, chunk_len: int) -> int:
        """f32 bytes of the [R+1, chunk_len] merge-state buffer one
        dispatch keeps resident (the ``tree.bytes_on_device`` counter)."""
        return 4 * (self.rows + 1) * int(chunk_len)


def _build_plan(s1: np.ndarray, s2: np.ndarray) -> TreePlan:
    """Build the merge tables from the exact two-stage shift tables.

    ``s1[G, C]`` / ``s2[G, g, S]`` are the plan's integer shifts; the
    exact per-trial per-channel total is ``s1[g(d), c] + s2[g(d), t(d),
    c // per]`` — the same sum every other engine applies."""
    s1 = np.asarray(s1, dtype=np.int64)
    s2 = np.asarray(s2, dtype=np.int64)
    G, C = s1.shape
    _, g, S = s2.shape
    per = C // S
    D = G * g
    tot = (s1[:, None, :] + np.repeat(s2, per, axis=2)).reshape(D, C)

    # level 0: one row per channel; a trial's "variant" of channel c is
    # the row itself, its base the exact shift (profiles are min-zero
    # normalized, and a single channel's profile is trivially {0})
    assign = np.broadcast_to(np.arange(C, dtype=np.int64), (D, C)).copy()
    base = tot.copy()
    ZERO = -1  # sentinel for "the constant zero row"; patched to R below
    levels = []
    rows_per_level = []
    adds = 0
    rows_max = C
    nb = C
    while nb > 1:
        nb_new = (nb + 1) // 2
        new_assign = np.empty((D, nb_new), dtype=np.int64)
        new_base = np.empty((D, nb_new), dtype=np.int64)
        srcA: list = []
        srcB: list = []
        offA: list = []
        offB: list = []
        for p in range(nb_new):
            lc, rc = 2 * p, 2 * p + 1
            k0 = len(srcA)
            if rc >= nb:
                # odd block count: the last block passes through (add of
                # the zero row — structurally zero adds)
                uniq, inv = np.unique(assign[:, lc], return_inverse=True)
                srcA.extend(int(u) for u in uniq)
                srcB.extend(ZERO for _ in uniq)
                offA.extend(0 for _ in uniq)
                offB.extend(0 for _ in uniq)
                new_assign[:, p] = k0 + inv
                new_base[:, p] = base[:, lc]
                continue
            bl, br = base[:, lc], base[:, rc]
            nbase = np.minimum(bl, br)
            # parent variant identity: (left variant, right variant,
            # child offsets after re-normalization) — trials sharing the
            # key share the parent row, which is where the work sharing
            # happens; offsets are >= 0 by the min-normalization even
            # where per-term rounding makes the raw shifts non-monotonic
            key = np.stack([assign[:, lc], assign[:, rc],
                            bl - nbase, br - nbase], axis=1)
            uniq, inv = np.unique(key, axis=0, return_inverse=True)
            srcA.extend(int(u) for u in uniq[:, 0])
            srcB.extend(int(u) for u in uniq[:, 1])
            offA.extend(int(u) for u in uniq[:, 2])
            offB.extend(int(u) for u in uniq[:, 3])
            adds += len(uniq)
            new_assign[:, p] = k0 + inv
            new_base[:, p] = nbase
        levels.append((np.asarray(srcA, dtype=np.int64),
                       np.asarray(srcB, dtype=np.int64),
                       np.asarray(offA, dtype=np.int64),
                       np.asarray(offB, dtype=np.int64)))
        rows_per_level.append(len(srcA))
        rows_max = max(rows_max, len(srcA))
        assign, base, nb = new_assign, new_base, nb_new

    NL = len(levels)
    R = rows_max
    tabs = np.empty((4, max(NL, 1), R), dtype=np.int32)
    # unused table cells read the zero row at shift 0 (0 + 0 rows): the
    # scan keeps static [R] width while real row counts vary per level
    tabs[0], tabs[1] = R, R
    tabs[2], tabs[3] = 0, 0
    for li, (a, b, oa, ob) in enumerate(levels):
        n = len(a)
        tabs[0, li, :n] = np.where(a < 0, R, a)
        tabs[1, li, :n] = np.where(b < 0, R, b)
        tabs[2, li, :n] = oa
        tabs[3, li, :n] = ob
    if NL == 0:  # single channel: no merges, trials snap straight to it
        tabs = tabs[:, :0]
    return TreePlan(
        tabs=tabs,
        trial_row=assign[:, 0].astype(np.int32),
        trial_off=base[:, 0].astype(np.int32),
        pad=max(int(tot.max(initial=0)), 0),
        group_size=g,
        rows=R,
        n_levels=NL,
        adds_per_sample=adds,
        rows_per_level=rows_per_level,
        n_channels=C,
    )


# Plan cache: keyed by a digest of the exact shift tables so the
# streamed sweep's per-chunk dispatches (and OOM-halved group slices,
# which arrive as table SLICES) reuse their host-built tables. Bounded
# because each entry holds ~NL*R*16 bytes of tables: the knob trades
# rebuild time against host RAM when many distinct slicings are live.
_PLAN_CACHE: "OrderedDict[bytes, TreePlan]" = OrderedDict()


def _plan_cache_size() -> int:
    # registry read is typo-tolerant (bad value -> declared default 8)
    return max(1, int(knobs.env_int("PYPULSAR_TPU_TREE_PLAN_CACHE")))


def _digest(s1: np.ndarray, s2: np.ndarray) -> bytes:
    h = hashlib.sha256()
    for a in (s1, s2):
        h.update(np.int64(a.shape).tobytes())
        h.update(np.ascontiguousarray(a, dtype=np.int32).tobytes())
    return h.digest()


def plan_from_bins(stage1_bins, stage2_bins) -> TreePlan:
    """Cached :class:`TreePlan` for these exact shift tables (device
    arrays accepted — the tables are KBs)."""
    s1 = np.asarray(stage1_bins)
    s2 = np.asarray(stage2_bins)
    key = _digest(s1, s2)
    plan = _PLAN_CACHE.pop(key, None)
    if plan is None:
        plan = _build_plan(s1, s2)
    _PLAN_CACHE[key] = plan  # (re)insert as most-recent
    while len(_PLAN_CACHE) > _plan_cache_size():
        _PLAN_CACHE.popitem(last=False)
    return plan


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------


def _shift_rows(rows, offs, pad: int, L: int):
    """rows[N, L] shifted left per-row by offs (0 <= off <= pad), zero
    fill on the right — the level-merge move. The zero-extended reads
    can only reach the tail region the final snap never consumes (the
    chunk carries >= ``pad`` overlap samples past every payload)."""
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    return jax.vmap(
        lambda r, s: jax.lax.dynamic_slice(r, (s,), (L,))
    )(rows, offs.astype(jnp.int32))


def _tree_rows_impl(data, tabs, pad: int):
    """Run the merge scan: data[C, L] -> state[R+1, L] of top-level rows
    (row R is the constant zero row every passthrough/padding entry
    reads)."""
    C, L = data.shape
    R = tabs.shape[2]
    state = jnp.zeros((R + 1, L), jnp.float32).at[:C].set(
        data.astype(jnp.float32))
    zero_row = jnp.zeros((1, L), jnp.float32)

    def level(st, t):
        a, b, oa, ob = t[0], t[1], t[2], t[3]
        new = _shift_rows(st[a], oa, pad, L) + _shift_rows(st[b], ob,
                                                           pad, L)
        return jnp.concatenate([new, zero_row], axis=0), None

    if tabs.shape[1]:
        # under shard_map the tables vary over the mesh axis while the
        # zero-initialised state does not: the scan carry must enter with
        # the varying axes it leaves with
        vma = tuple(jax.typeof(tabs).vma)
        if vma:
            state = jax.lax.pcast(state, vma, to="varying")
        state, _ = jax.lax.scan(level, state, tabs.transpose(1, 0, 2))
    return state


def _snap(state, trial_row, trial_off, out_len: int):
    """The exact-shift snap: trial d's series is its top row read at its
    min-shift offset, so channel c contributes data[c, t + (off + P(c)))]
    = data[c, t + s1 + s2] exactly."""
    return jax.vmap(
        lambda r, o: jax.lax.dynamic_slice(state[r], (o,), (out_len,))
    )(trial_row, trial_off.astype(jnp.int32))


@partial(jax.jit, static_argnames=("out_len", "pad"))
def _tree_series(data, tabs, trial_row, trial_off, out_len, pad):
    state = _tree_rows_impl(data, tabs, pad)
    return _snap(state, trial_row, trial_off, out_len)


def _tree_stats_impl(data, tabs, trial_row, trial_off, out_len, widths,
                     stat_len, group, pad):
    state = _tree_rows_impl(data, tabs, pad)
    D = trial_row.shape[0]
    G = D // group
    tr = trial_row.reshape(G, group)
    to = trial_off.reshape(G, group)

    def per_group(carry, xs):
        r, o = xs
        ts = _snap(state, r, o, out_len)  # [g, out_len]
        s, ss, mb_g, ab_g = boxcar_stats(ts, widths, stat_len)
        return carry, (s, ss, mb_g, ab_g)

    _, (s, ss, mb, ab) = jax.lax.scan(per_group, 0, (tr, to))
    return (
        s.reshape(D),
        ss.reshape(D),
        mb.reshape(D, len(widths)),
        ab.reshape(D, len(widths)),
    )


_tree_stats = jax.jit(
    _tree_stats_impl,
    static_argnames=("out_len", "widths", "stat_len", "group", "pad"),
)


def _note_dispatch(plan: TreePlan, chunk_len: int, n_samples: int,
                   dev_ids=None) -> None:
    """Host-side structural counters per dispatch (kernels cannot emit
    from inside jit): merge depth, shared-work adds actually performed
    for this chunk's samples, and the resident merge-state bytes —
    stamped per device under a mesh per the PR 6 lease contract."""
    if not telemetry.is_active():
        return
    telemetry.gauge("tree.merge_levels", plan.n_levels)
    adds = plan.adds_per_sample * int(n_samples)
    state_b = plan.state_bytes(chunk_len)
    telemetry.counter("tree.adds_total", adds)
    telemetry.counter("tree.bytes_on_device", state_b)
    for d in dev_ids or ():
        telemetry.counter(f"device{d}.tree.adds_total", adds)
        telemetry.counter(f"device{d}.tree.bytes_on_device", state_b)


def sweep_chunk_tree(data, stage1_bins, stage2_bins, out_len: int,
                     widths: Tuple[int, ...], stat_len: int):
    """Tree-engine twin of ``parallel.sweep.sweep_chunk``: per-trial
    (sum, sumsq, maxbox, argbox) for one chunk, all trials through the
    shared merge tree + exact snap."""
    plan = plan_from_bins(stage1_bins, stage2_bins)
    _note_dispatch(plan, data.shape[-1], stat_len)
    tabs, tr, to = plan.device_tables()
    return _tree_stats(data, tabs, tr, to, out_len, tuple(widths),
                       stat_len, plan.group_size, plan.pad)


def dedisperse_series_tree(data, stage1_bins, stage2_bins, out_len: int):
    """Tree-engine twin of ``parallel.sweep.dedisperse_series_chunk``:
    the raw [D, out_len] dedispersed series for one chunk — the kernel
    the streamed .dat writer, the accel handoff and the specfuse stitch
    consume when ``engine='tree'``."""
    plan = plan_from_bins(stage1_bins, stage2_bins)
    _note_dispatch(plan, data.shape[-1], out_len)
    tabs, tr, to = plan.device_tables()
    return _tree_series(data, tabs, tr, to, out_len, plan.pad)


# ---------------------------------------------------------------------------
# 'dm'-mesh sharding: per-device tables, stacked + padded to one shape
# ---------------------------------------------------------------------------


def _stack_shard_plans(s1: np.ndarray, s2: np.ndarray, k: int):
    """Build one TreePlan per device shard of the trial groups and stack
    the tables to a common [k, NL, 4, R] shape (per-device zero-row
    indices remapped to the common R). Returns (plans, tabs, trial_row,
    trial_off, pad) as host arrays, trial arrays flat [D] in group
    order so a P('dm') sharding gives each device its own trials."""
    G = s1.shape[0]
    if G % k:
        raise ValueError(f"group count {G} must divide the mesh 'dm' "
                         f"axis {k}; use make_sweep_plan(pad_groups_to=...)")
    per = G // k
    plans = [plan_from_bins(s1[i * per:(i + 1) * per],
                            s2[i * per:(i + 1) * per]) for i in range(k)]
    NL = max(p.tabs.shape[1] for p in plans)
    R = max(p.rows for p in plans)
    pad = max(p.pad for p in plans)
    tabs = np.empty((k, NL, 4, R), dtype=np.int32)
    tabs[:, :, 0:2] = R
    tabs[:, :, 2:4] = 0
    for i, p in enumerate(plans):
        t = p.tabs  # [4, NLp, Rp]
        nl, r = t.shape[1], t.shape[2]
        src = np.where(t[0:2] == p.rows, R, t[0:2])
        tabs[i, :nl, 0:2, :r] = src.transpose(1, 0, 2)
        tabs[i, :nl, 2:4, :r] = t[2:4].transpose(1, 0, 2)
    trial_row = np.concatenate([p.trial_row for p in plans])
    trial_off = np.concatenate([p.trial_off for p in plans])
    return plans, tabs, trial_row, trial_off, pad


@lru_cache(maxsize=32)
def _sharded_tree_fn(mesh, out_len, widths, stat_len, group, pad,
                     series: bool):
    """Compiled shard_map'd tree kernel for one (mesh, geometry) — each
    device runs the scan over ITS stacked table slice and its local
    trials; rows concatenate in group order (P('dm')), bit-identical to
    the unsharded engine per trial."""
    from jax.sharding import PartitionSpec as P

    def impl(data, tabs, trial_row, trial_off):
        t = tabs[0].transpose(1, 0, 2)  # local [NL, 4, R] -> [4, NL, R]
        if series:
            state = _tree_rows_impl(data, t, pad)
            return _snap(state, trial_row, trial_off, out_len)
        return _tree_stats_impl(data, t, trial_row, trial_off, out_len,
                                widths, stat_len, group, pad)

    out = P("dm") if series else (P("dm"),) * 4
    fn = jax.shard_map(impl, mesh=mesh,
                       in_specs=(P(), P("dm"), P("dm"), P("dm")),
                       out_specs=out)
    return jax.jit(fn)


def _make_sharded_tree(mesh, out_len, widths, stat_len, series: bool):
    from jax.sharding import NamedSharding, PartitionSpec as P

    k = int(mesh.shape["dm"])
    dev_ids = [int(getattr(d, "id", -1)) for d in mesh.devices.flat]
    cache: "OrderedDict[bytes, tuple]" = OrderedDict()

    def fn(data, stage1_bins, stage2_bins):
        s1 = np.asarray(stage1_bins)
        s2 = np.asarray(stage2_bins)
        key = _digest(s1, s2)
        entry = cache.pop(key, None)
        if entry is None:
            plans, tabs, tr, to, pad = _stack_shard_plans(s1, s2, k)
            spec = NamedSharding(mesh, P("dm"))
            entry = (
                [p for p in plans],
                jax.device_put(jnp.asarray(tabs), spec),
                jax.device_put(jnp.asarray(tr), spec),
                jax.device_put(jnp.asarray(to), spec),
                pad,
            )
        cache[key] = entry
        while len(cache) > _plan_cache_size():
            cache.popitem(last=False)
        plans, tabs_d, tr_d, to_d, pad = entry
        for p, d in zip(plans, dev_ids):
            _note_dispatch(p, data.shape[-1],
                           out_len if series else stat_len, dev_ids=[d])
        run = _sharded_tree_fn(mesh, out_len, widths, stat_len,
                               plans[0].group_size, pad, series)
        return run(data, tabs_d, tr_d, to_d)

    return fn


def make_sharded_tree_sweep_chunk(mesh, out_len: int,
                                  widths: Tuple[int, ...], stat_len: int):
    """Tree-engine twin of ``parallel.sweep.make_sharded_sweep_chunk``
    — returns ``fn(data, stage1_bins, stage2_bins)``; the tables may be
    group slices (the OOM-halving contract)."""
    return _make_sharded_tree(mesh, out_len, tuple(widths), stat_len,
                              series=False)


def make_sharded_tree_series_chunk(mesh, out_len: int):
    """Tree-engine twin of ``parallel.sweep.make_sharded_series_chunk``."""
    return _make_sharded_tree(mesh, out_len, (1,), 0, series=True)
