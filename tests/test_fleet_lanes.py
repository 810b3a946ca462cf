"""Several observations in flight on several chips (PR 31): four toy
beams searched at once on four virtual devices write what each writes
alone; a lane finds its one-chip programs on whichever chip it is
leased (one persistent-cache entry a program, which every chip loads
under its own device assignment); an observation keeps its chip from stage to stage and from
run to run; no batch lane while a chip stands free; and the spans and
counters that say how long an observation took among its neighbours,
which chips it held and what each chip was leased."""

import json
import threading

import numpy as np
import pytest

from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.obs.summarize import load_records, summarize
from pypulsar_tpu.parallel import broker as broker_mod
from pypulsar_tpu.survey import scheduler as sched_mod
from pypulsar_tpu.survey.dag import SurveyConfig
from pypulsar_tpu.survey.scheduler import FleetScheduler
from pypulsar_tpu.survey.state import Observation

from tests.test_accel_pipeline import _pulsar_fil
from tests.test_survey import (
    CFG_KW,
    OBS,
    _artifact_bytes,
    _fleet_obs,
    _stub,
)

BEAMS = 4


def _need_devices(n=BEAMS):
    import jax

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices (tests/conftest.py forces 8)")


def _run(fils, outdir, cfg_kw, trace, devices, gang="auto"):
    obs = _fleet_obs(fils, outdir)
    with telemetry.session(trace):
        result = FleetScheduler(obs, SurveyConfig(**cfg_kw), gang=gang,
                                max_host_workers=2, devices=devices).run()
    assert result.ok
    return [json.loads(line) for line in open(trace)]


@pytest.fixture(scope="module")
def quartet(tmp_path_factory):
    """Four same-geometry toy beams: each searched alone on one lease,
    then all four at once on four leases (twice: the second run is the
    one that must find every program where the first left it)."""
    _need_devices()
    root = tmp_path_factory.mktemp("lanes")
    fils = [_pulsar_fil(root, name=f"beam{i}.fil", seed=11 + i, **OBS)
            for i in range(BEAMS)]
    alone = {}
    for i, fil in enumerate(fils):
        outdir = str(root / f"alone{i}")
        _run([fil], outdir, CFG_KW, str(root / f"alone{i}.jsonl"), 1)
        alone[f"beam{i}"] = _artifact_bytes(outdir, f"beam{i}")
    runs = [_run(fils, str(root / f"fleet{n}"), CFG_KW,
                 str(root / f"fleet{n}.jsonl"), BEAMS) for n in range(2)]
    return {"root": root, "fils": fils, "alone": alone, "runs": runs}


@pytest.mark.parametrize("beam", range(BEAMS))
def test_fleet_beam_is_byte_identical_to_the_beam_alone(quartet, beam):
    """A lane's dispatch shapes are a lone run's: one chip, one
    observation's batches; the neighbours share the process, not the
    programs' inputs."""
    stem = f"beam{beam}"
    want = quartet["alone"][stem]
    assert want, "the lone run wrote no artifact"
    got = _artifact_bytes(str(quartet["root"] / "fleet0"), stem)
    assert got.keys() == want.keys()
    for name, data in want.items():
        assert got[name] == data, f"{name} differs from the lone run's"


def _final(recs):
    (final,) = [r for r in recs if r.get("type") == "counters"
                and not r.get("partial")]
    return final


def _spans(recs, name):
    return [r for r in recs if r.get("type") == "span"
            and r.get("name") == name]


@pytest.mark.parametrize("run", [0, 1])
def test_every_beam_keeps_the_chip_of_its_place_in_the_fleet(quartet, run):
    """Beam i is leased chip i for every device stage, in both runs:
    placement repeats, so what the first run compiled and left on a
    chip the second finds there."""
    recs = quartet["runs"][run]
    leases = _spans(recs, "survey.lease")
    assert len(leases) == 3 * BEAMS  # mask, sweep, fold of every beam
    obs = _spans(recs, "survey.obs")
    assert sorted(o["attrs"]["obs"] for o in obs) == [
        f"beam{i}" for i in range(BEAMS)]
    for o in obs:
        i = int(o["attrs"]["obs"][4:])
        assert o["attrs"]["chips"] == [i] and o["attrs"]["moves"] == 0
        assert o["attrs"]["state"] == "done" and o.get("noagg") is True
    for stage in ("mask", "sweep", "fold"):
        chips = sorted(c for r in leases if r["attrs"]["stage"] == stage
                       for c in r["attrs"]["chips"])
        assert chips == list(range(BEAMS)), (stage, chips)
    assert not [r for r in recs if r.get("name") == "survey.lane_decision"]


def test_lease_counters_by_chip_add_up_to_the_pool_counter(quartet):
    recs = quartet["runs"][0]
    final = _final(recs)
    c = final["counters"]
    by_chip = [c[f"survey.lease_chip_s.chip{i}"] for i in range(BEAMS)]
    assert all(v > 0 for v in by_chip)
    assert sum(by_chip) == pytest.approx(c["survey.lease_chip_s"], rel=1e-9)
    assert sum(by_chip) == pytest.approx(
        sum(r["dur"] for r in _spans(recs, "survey.lease")), rel=0.02)
    assert c["survey.lease_moves"] == 0  # present, and nothing moved
    assert final["gauges"]["survey.lanes_in_flight"]["max"] == BEAMS
    # an observation's wall covers its leases and is covered by the run
    for o in _spans(recs, "survey.obs"):
        i = int(o["attrs"]["obs"][4:])
        assert by_chip[i] <= o["dur"] <= c["survey.pool_chip_s"] / BEAMS
    # tlmsum's roll-up of the same
    import io

    from pypulsar_tpu.obs.summarize import render

    buf = io.StringIO()
    render(summarize(load_records(str(quartet["root"] / "fleet0.jsonl"))),
           buf)
    (line,) = [ln for ln in buf.getvalue().splitlines()
               if ln.startswith("# lanes:")]
    assert "chip3" in line and "moves 0" in line and "survey.obs mean" in line
    assert "in flight max 4" in line


def test_second_fleet_run_compiles_nothing_on_any_chip(quartet):
    """After the first run no program is built again, at the plane's door
    or at JAX's, and none is even loaded: every lane is where it was."""
    final = _final(quartet["runs"][1])
    c = final["counters"]
    assert c.get("compile.cache_miss", 0) == 0
    assert c.get("compile.chip_load", 0) == 0
    assert c.get("jit.compiles", 0) == 0
    assert not final["events"].get("jit.compile")
    assert c["compile.cache_hit"] > 0


_NEW_CHIP = """
import json, sys
from tests.test_accel_pipeline import _pulsar_fil
from tests.test_fleet_lanes import _run
from tests.test_survey import CFG_KW, OBS, _artifact_bytes
import pathlib
root = pathlib.Path(sys.argv[1])
kw = dict(CFG_KW, numdms=5, fold_nbins=24)
a, b = (_pulsar_fil(root, name=f"{n}.fil", seed=s, **OBS)
        for n, s in (("a", 31), ("b", 32)))
# beam A alone on four leases (one chip a stage: no gang over idle chips)
first = _run([a], str(root / "one"), kw, str(root / "one.jsonl"), 4, gang=1)
# then the fleet [B, A]: A is second in line
second = _run([b, a], str(root / "two"), kw, str(root / "two.jsonl"), 4, gang=1)
same = _artifact_bytes(str(root / "two"), "a") == _artifact_bytes(
    str(root / "one"), "a")
print(json.dumps({"first": first, "second": second, "same": same}))
"""


def test_stage_leased_on_a_chip_it_never_ran_on_compiles_nothing(tmp_path):
    """Beam A alone on four leases lands on chip 0 and compiles there.
    Then the fleet [B, A]: A is second in line and is leased chip 1,
    which has run nothing. The plane builds nothing there: chip 1 loads
    what chip 0 compiled. In a process of its own, with the warm pool off:
    the frames above a lowering are in its cache key (the plane keys the
    cache on metadata) and JAX keeps a program's first trace on a chip,
    frames and all, so a program that the pool's thread (or another test's)
    traced first on chip 0 is another entry than the one a stage on chip 1
    asks for, and that chip compiles its own."""
    _need_devices()
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               PYPULSAR_TPU_COMPILE_WARMPOOL="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _NEW_CHIP, str(tmp_path)],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    first, second = out["first"], out["second"]
    c1 = _final(first)["counters"]
    assert c1["compile.cache_miss"] > 0
    assert {ch for r in _spans(first, "survey.lease")
            for ch in r["attrs"]["chips"]} == {0}
    built = [r for r in first
             if str(r.get("name", "")).startswith("compile.first.")]
    assert built and all(r["attrs"]["chip"] == 0 for r in built)
    (obs_a,) = [o for o in _spans(second, "survey.obs")
                if o["attrs"]["obs"] == "a"]
    assert obs_a["attrs"]["chips"] == [1]
    c2 = _final(second)["counters"]
    assert c2["compile.chip_load"] >= 8  # the sweep's, accel's, fold's
    assert not c2.get("compile.aot_fallback")
    # chip 1 built nothing. (Chip 0 builds b's own shapes: another beam,
    # another number of candidates to fold.)
    assert not [r for r in second
                if str(r.get("name", "")).startswith("compile.first.")
                and r["attrs"]["chip"] == 1]
    # and what chip 1 wrote is what chip 0 wrote for the same beam
    assert out["same"] is True


# -- the plane alone ---------------------------------------------------------


def test_plane_compiles_once_and_loads_onto_the_other_chips(tmp_path):
    """Four threads pinned to four chips miss together: one compiles, the
    others wait for it and load its cache entry; the result sits on each
    thread's chip and is the same bits. An unpinned thread keys ``auto``
    as before and is a miss of its own."""
    _need_devices()
    import jax
    import jax.numpy as jnp

    from pypulsar_tpu.compile import plane_jit

    @plane_jit(static_argnames=("k",), stage="lanes_probe")
    def f(x, y, k=2):
        return jnp.fft.rfft(x * k + y).real.sum(axis=-1), x @ y.T

    x = np.random.default_rng(0).standard_normal((8, 64)).astype(np.float32)
    y = x[::-1].copy()
    devs = jax.devices()[:BEAMS]
    outs, errs = {}, []

    def lane(d):
        try:
            with jax.default_device(d):
                outs[d.id] = (f(x, y, k=3), f(jax.device_put(x, d), y, k=3))
        except BaseException as e:  # noqa: BLE001 - reported below
            errs.append(e)

    with telemetry.session(str(tmp_path / "plane.jsonl")) as s:
        threads = [threading.Thread(target=lane, args=(d,)) for d in devs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        assert not errs, errs
        assert s.counters["compile.cache_miss"] == 1
        assert s.counters["compile.chip_load"] == BEAMS - 1
        assert s.counters["compile.cache_hit"] == BEAMS
        assert not s.counters.get("compile.aot_fallback")
        assert f.cache_size() == BEAMS
        f(x, y, k=3)  # unpinned: the key of a one-lease run, its own compile
        assert s.counters["compile.cache_miss"] == 2
        assert f.cache_size() == BEAMS + 1
    want = [np.asarray(a) for a in outs[devs[0].id][0]]
    for d in devs:
        for res in outs[d.id]:
            assert all(a.devices() == {d} for a in res)
            assert all(np.array_equal(np.asarray(a), w)
                       for a, w in zip(res, want))


def test_plane_shares_nothing_for_an_array_on_another_chip():
    """A key that names a placement (an argument committed to another
    chip, a gang's mesh) is no one-chip program: built under its own
    key, as before."""
    _need_devices(2)
    import jax

    from pypulsar_tpu.compile.plane import PlaneJit

    d0, d1 = jax.devices()[:2]
    wrapper = PlaneJit(lambda x: x + 1.0, name="lanes_probe_placed")
    x = np.zeros(4, np.float32)
    with jax.default_device(d0):
        here, _, _ = wrapper._split((x,), {})
        there, _, _ = wrapper._split((jax.device_put(x, d1),), {})
        assert wrapper._program_key(here) is not None
        assert wrapper._program_key(there) is None
    unpinned, _, _ = wrapper._split((x,), {})
    assert unpinned[1] == "auto" and wrapper._program_key(unpinned) is None


# -- the scheduler's placement and lanes, on stub stages ----------------------


def _stub_fleet(tmp_path, devices, n_obs=2, stage="sweep"):
    obs = [Observation(f"o{i}", str(tmp_path / f"o{i}.raw"),
                       str(tmp_path / f"o{i}")) for i in range(n_obs)]
    return FleetScheduler(obs, SurveyConfig(),
                          stages=[_stub(stage, True, ())], devices=devices)


def test_one_chip_claim_takes_the_preferred_chip_when_it_is_free(tmp_path):
    _need_devices()
    s = _stub_fleet(tmp_path, BEAMS)
    assert s._acquire_devices(1, prefer=2) == [2]
    assert s._acquire_devices(1, prefer=2) == [0]  # taken: the lowest free
    assert s._acquire_devices(1) == [1]
    s._release_devices([2])
    assert s._acquire_devices(2, prefer=2) == [2, 3]  # a gang: lowest free
    s._release_devices([0, 1, 2, 3])
    assert s._free_ids == {0, 1, 2, 3}


@pytest.mark.parametrize("devices,free,mates", [
    (4, {1, 2, 3}, 0),   # chips stand free: the queued task takes one
    (4, set(), 1),       # every chip leased: the lane as before
    (1, set(), 1),       # one lease, held by the leader: as before
])
def test_lane_mates_only_when_no_chip_is_free(tmp_path, monkeypatch,
                                              devices, free, mates):
    _need_devices()
    monkeypatch.setenv("PYPULSAR_TPU_BROKER", "1")
    broker_mod.reset()
    s = _stub_fleet(tmp_path, devices)
    leader, other = (s._tasks[(i, "sweep")] for i in range(2))
    leader.state = sched_mod._RUNNING
    other.state, other.seq = sched_mod._QUEUED, 7
    s._free_ids = set(free)
    claimed = s._claim_lane_mates(leader, 1)
    assert len(claimed) == mates
    if mates:
        assert claimed == [other] and other.state == sched_mod._RUNNING
        assert other.lane_seq == 7
    else:
        assert other.state == sched_mod._QUEUED and other.lane_seq is None
    broker_mod.reset()


def test_a_lease_on_another_chip_is_counted_as_a_move(tmp_path):
    """Two device stages of one observation; chip 0 is held by someone
    else when the second is asked for: the lease moves to chip 1, and
    the span and the counter say so."""
    _need_devices(2)
    obs = [Observation("o0", str(tmp_path / "o0.raw"), str(tmp_path / "o0"))]
    s = FleetScheduler(obs, SurveyConfig(), devices=2, stages=[
        _stub("dev1", True, ()), _stub("dev2", True, ("dev1",))])
    taken = []
    held = s._acquire_devices

    def acquire(k, prefer=None):
        ids = held(k, prefer)
        if not taken:  # after the first grant, someone else takes chip 0
            taken.append(ids)
            release = s._release_devices

            def release_then_take(back):
                release(back)
                with s._cv:
                    s._free_ids.discard(0)
                s._release_devices = release

            s._release_devices = release_then_take
        return ids

    s._acquire_devices = acquire
    trace = str(tmp_path / "move.jsonl")
    with telemetry.session(trace):
        assert s.run().ok
    recs = [json.loads(line) for line in open(trace)]
    assert [r["attrs"]["chips"] for r in _spans(recs, "survey.lease")] == [
        [0], [1]]
    (o,) = _spans(recs, "survey.obs")
    assert o["attrs"]["chips"] == [0, 1] and o["attrs"]["moves"] == 1
    assert _final(recs)["counters"]["survey.lease_moves"] == 1
