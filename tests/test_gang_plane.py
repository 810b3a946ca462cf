"""The gang-leased deployment (one pointing over the four chips of a host,
``survey --devices 4 --gang auto``) on the virtual CPU mesh: sharded
batches through the compile plane's AOT registry, and the fleet
scheduler's lease accounting."""

import io
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from pypulsar_tpu.compile import plane_jit
from pypulsar_tpu.compile.plane import _leaf_key
from pypulsar_tpu.obs import telemetry


def _mesh(ids):
    devs = jax.devices()
    if len(devs) <= max(ids):
        pytest.skip(f"needs {max(ids) + 1} virtual devices")
    return Mesh(np.array([devs[i] for i in ids]), ("dm",))


def _sharded(mesh, spec=P("dm"), shape=(8, 16)):
    x = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    return x, jax.device_put(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# keying


# taken on the parent commit (PR 25's tree), jax 0.9.0, 8 virtual CPU
# devices: what _leaf_key returned for every single-device leaf form. The
# persistent cache's markers and the warm pool hang on these, letter for
# letter.
PARENT_KEYS = {
    "numpy_f32": ("a", (4, 8), "float32", "host"),
    "numpy_i32": ("a", (3,), "int32", "host"),
    "jnp_default": ("a", (4, 8), "float32", "host"),
    "jnp_bf16": ("a", (2, 2), "bfloat16", "host"),
    "on_dev2": ("a", (5,), "float32", "TFRT_CPU_2"),
    "sds": ("a", (4, 8), "float32", "host"),
    "py_int": ("s", "int"),
    "py_float": ("s", "float"),
    "py_bool": ("s", "bool"),
    "none": ("s", "NoneType"),
    "py_complex": ("s", "complex"),
}


def _leaf(name):
    devs = jax.devices()
    return {
        "numpy_f32": lambda: np.zeros((4, 8), np.float32),
        "numpy_i32": lambda: np.zeros((3,), np.int32),
        "jnp_default": lambda: jnp.zeros((4, 8), jnp.float32),
        "jnp_bf16": lambda: jnp.zeros((2, 2), jnp.bfloat16),
        "on_dev2": lambda: jax.device_put(np.zeros((5,), np.float32),
                                          devs[2]),
        "sds": lambda: jax.ShapeDtypeStruct((4, 8), jnp.float32),
        "py_int": lambda: 3,
        "py_float": lambda: 2.5,
        "py_bool": lambda: True,
        "none": lambda: None,
        "py_complex": lambda: 1j,
    }[name]()


@pytest.mark.parametrize("name", sorted(PARENT_KEYS))
def test_single_device_leaf_key_is_the_parents(name):
    _mesh([2])  # skips without the virtual devices
    assert _leaf_key(_leaf(name)) == PARENT_KEYS[name]


@pytest.mark.parametrize("pinned,where,want", [
    (3, 3, ("a", (5,), "float32", "host")),
    (3, 0, ("a", (5,), "float32", "TFRT_CPU_0")),
])
def test_single_device_leaf_key_under_a_lease_is_the_parents(pinned, where,
                                                             want):
    _mesh([3])
    devs = jax.devices()
    with jax.default_device(devs[pinned]):
        x = jax.device_put(np.zeros((5,), np.float32), devs[where])
        assert _leaf_key(x) == want


def test_whole_registry_key_of_a_single_device_call_is_the_parents():
    _mesh([1])

    def f(x, tables, n, scale=2.0):
        return x * scale + tables["a"].sum() + n

    w = plane_jit(f, static_argnames=("n",), stage="sweep", name="t_keys")

    def args():  # made where they are used: on the thread's own device
        return (np.zeros((4, 8), np.float32),
                {"a": jnp.ones((3,), jnp.int32)})

    shape_key = (
        (("n", "7"),),
        (("x", "PyTreeDef(*)", (("a", (4, 8), "float32", "host"),)),
         ("tables", "PyTreeDef({'a': *})", (("a", (3,), "int32", "host"),)),
         ("scale", "PyTreeDef(*)", (("s", "float"),))))
    key, digest, _ = w._split(args(), {"n": 7})
    assert key[:2] == (shape_key, "auto")
    with jax.default_device(jax.devices()[1]):
        key1, digest1, _ = w._split(args(), {"n": 7})
    assert key1[:2] == (shape_key, "TFRT_CPU_1")
    assert digest1 == digest  # the marker's digest carries no placement
    if jax.__version__ == "0.9.0":
        # the digest holds the stage's knob configuration: it moves when
        # a knob of stage "sweep" comes or goes, and only then
        assert digest == "f7fe32982286ef1107cc460a173f9dbdbf0ce92a"


def test_sharded_leaf_keys_by_mesh_devices_in_order_and_spec():
    mesh = _mesh([0, 1, 2, 3])
    _, a = _sharded(mesh)
    assert _leaf_key(a) == ("a", (8, 16), "float32",
                            ("mesh", (0, 1, 2, 3), ("dm",), (4,),
                             str(P("dm"))))
    _, rep = _sharded(mesh, P())
    assert _leaf_key(rep)[-1][-1] == str(P())
    assert _leaf_key(rep) != _leaf_key(a)


@pytest.mark.parametrize("other", [[2, 3], [1, 0], [4, 5, 6, 7]])
def test_meshes_on_other_devices_key_differently(other):
    _, a = _sharded(_mesh([0, 1]))
    _, b = _sharded(_mesh(other))
    assert _leaf_key(a) != _leaf_key(b)


# ---------------------------------------------------------------------------
# dispatch


def test_sharded_batch_compiles_once_hits_after_and_never_falls_back():
    mesh = _mesh([0, 1, 2, 3])
    x, xs = _sharded(mesh)

    def f(v, w):
        return jnp.cumsum(v * w, axis=1) + 1.0

    w = np.linspace(0.5, 1.5, 16).astype(np.float32)
    g = plane_jit(f, name="t_gang_batch")
    with telemetry.session() as tlm:
        first = np.asarray(g(xs, w))
        t1 = tlm.counter_totals()
        second = np.asarray(g(xs, w))
        t2 = tlm.counter_totals()
    assert t1.get("compile.cache_miss", 0) == 1
    assert t1.get("compile.cache_hit", 0) == 0
    assert t2.get("compile.cache_miss", 0) == 1
    assert t2.get("compile.cache_hit", 0) == 1
    assert t2.get("compile.aot_fallback", 0) == 0
    assert g.cache_size() == 1
    # the values the plain jit gives (never byte identity across shapes or
    # layouts: ROADMAP D0)
    want = np.asarray(jax.jit(f)(x, w))
    np.testing.assert_allclose(first, want, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(second, want, rtol=2e-6, atol=2e-6)


def test_two_gangs_on_other_chips_hold_their_own_executables():
    g = plane_jit(lambda v: v * 2.0, name="t_two_gangs")
    _, a = _sharded(_mesh([0, 1]))
    _, b = _sharded(_mesh([2, 3]))
    with telemetry.session() as tlm:
        ya, yb = g(a), g(b)
        g(a), g(b)
        t = tlm.counter_totals()
    assert g.cache_size() == 2
    assert t.get("compile.cache_miss", 0) == 2
    assert t.get("compile.cache_hit", 0) == 2
    assert t.get("compile.aot_fallback", 0) == 0
    # each result stays on the chips of its own gang
    assert {d.id for d in ya.devices()} == {0, 1}
    assert {d.id for d in yb.devices()} == {2, 3}


def test_sharded_output_of_one_program_keys_into_the_next():
    """A gang step chains programs: the [D, T] series comes out of the
    sharded chunk program sharded, and the next plane-wrapped program
    takes it as it is."""
    mesh = _mesh([0, 1, 2, 3])
    _, xs = _sharded(mesh)
    first = plane_jit(lambda v: v + 1.0, name="t_chain_a")
    second = plane_jit(lambda v: v.sum(axis=1), name="t_chain_b")
    with telemetry.session() as tlm:
        y = second(first(xs))
        t = tlm.counter_totals()
    assert t.get("compile.aot_fallback", 0) == 0
    assert t.get("compile.cache_miss", 0) == 2
    np.testing.assert_allclose(
        np.asarray(y), (np.arange(128, dtype=np.float32).reshape(8, 16)
                        + 1.0).sum(axis=1), rtol=2e-6)


def _series_case():
    from pypulsar_tpu.parallel.sweep import make_sweep_plan

    C, nsub = 16, 8
    freqs = 1500.0 - 2.0 * np.arange(C)
    dms = 10.0 * np.arange(8)
    plan = make_sweep_plan(dms, freqs, 1e-3, nsub=nsub, group_size=2,
                           widths=(1,))
    out_len = 1024
    data = np.random.default_rng(5).standard_normal(
        (C, out_len + plan.min_overlap + 64)).astype(np.float32)
    return plan, data, out_len


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_sharded_series_chunk_goes_through_the_registry(engine):
    """The mesh-closing factory is memoised per (mesh, geometry): a
    second stream on the same gang gets the same wrapper and hits; the
    rows are the single-device program's. `fourier` is what the gang
    cell runs (jit_series_sharded_chunk)."""
    from pypulsar_tpu.parallel.sweep import (
        dedisperse_series_chunk,
        make_sharded_series_chunk,
    )

    mesh = _mesh([0, 1, 2, 3])
    plan, data, out_len = _series_case()
    args = (jnp.asarray(data), jnp.asarray(plan.stage1_bins),
            jnp.asarray(plan.stage2_bins))
    fn = make_sharded_series_chunk(mesh, plan.nsub, out_len,
                                   plan.max_shift2, engine)
    assert make_sharded_series_chunk(mesh, plan.nsub, out_len,
                                     plan.max_shift2, engine) is fn
    assert make_sharded_series_chunk(_mesh([4, 5, 6, 7]), plan.nsub,
                                     out_len, plan.max_shift2,
                                     engine) is not fn
    with telemetry.session() as tlm:
        got = np.asarray(fn(*args))
        fn(*args)
        t = tlm.counter_totals()
    assert t.get("compile.aot_fallback", 0) == 0
    assert t.get("compile.cache_miss", 0) == 1
    assert t.get("compile.cache_hit", 0) == 1
    want = np.asarray(dedisperse_series_chunk(
        *args, plan.nsub, out_len, plan.max_shift2, engine))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("engine", ["gather", "fourier"])
def test_sharded_sweep_chunk_goes_through_the_registry(engine):
    from pypulsar_tpu.parallel.sweep import (
        make_sharded_sweep_chunk,
        sweep_chunk,
    )

    mesh = _mesh([0, 1, 2, 3])
    plan, data, out_len = _series_case()
    spec = NamedSharding(mesh, P("dm"))
    s1 = jax.device_put(jnp.asarray(plan.stage1_bins), spec)
    s2 = jax.device_put(jnp.asarray(plan.stage2_bins), spec)
    widths, stat_len = (1, 2, 4), out_len - 8
    fn = make_sharded_sweep_chunk(mesh, plan.nsub, out_len,
                                  plan.max_shift2, list(widths), stat_len,
                                  engine=engine)
    assert make_sharded_sweep_chunk(mesh, plan.nsub, out_len,
                                    plan.max_shift2, widths, stat_len,
                                    engine=engine) is fn
    with telemetry.session() as tlm:
        got = [np.asarray(a) for a in fn(jnp.asarray(data), s1, s2)]
        fn(jnp.asarray(data), s1, s2)
        t = tlm.counter_totals()
    assert t.get("compile.aot_fallback", 0) == 0
    assert t.get("compile.cache_miss", 0) == 1
    assert t.get("compile.cache_hit", 0) == 1
    want = sweep_chunk(jnp.asarray(data), jnp.asarray(plan.stage1_bins),
                       jnp.asarray(plan.stage2_bins), plan.nsub, out_len,
                       plan.max_shift2, widths, stat_len, engine=engine)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, np.asarray(w), rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------------------
# the fleet scheduler's leases


@pytest.fixture(scope="module")
def gang_run(tmp_path_factory):
    """One toy pointing through ``survey --devices 4 --gang auto``."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    import importlib.util

    from pypulsar_tpu.cli import survey as cli_survey
    from pypulsar_tpu.obs.summarize import load_records, summarize

    root = tmp_path_factory.mktemp("gang4")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "make_synthetic_fil.py")
    spec = importlib.util.spec_from_file_location("make_synthetic_fil", path)
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    fil = str(root / "toy.fil")
    gen.main(["--out", fil, "--nchan", "64", "--duration", "4.194304",
              "--period-samples", "1024", "--nbits", "4"])
    outdir, tlmdir = str(root / "out"), str(root / "tlm")
    rc = cli_survey.main([fil, "-o", outdir, "--devices", "4", "--gang",
                          "auto", "--telemetry-dir", tlmdir, "--lodm", "60",
                          "--dmstep", "2", "--numdms", "16", "--accel-zmax",
                          "20"])
    records = list(load_records(os.path.join(tlmdir, "fleet.jsonl")))
    # the same pointing again, as a benchmark window's step follows the
    # warm-up step: same process, fresh output directory
    again = str(root / "tlm2")
    rc2 = cli_survey.main([fil, "-o", str(root / "out2"), "--devices", "4",
                           "--gang", "auto", "--telemetry-dir", again,
                           "--lodm", "60", "--dmstep", "2", "--numdms", "16",
                           "--accel-zmax", "20"])
    warm = summarize(load_records(os.path.join(again, "fleet.jsonl")))
    return {"rc": rc or rc2, "records": records,
            "summary": summarize(records), "warm": warm}


def _leases(run):
    return [r for r in run["records"]
            if r.get("type") == "span" and r.get("name") == "survey.lease"]


def test_gang_auto_gives_the_sweep_stage_all_four_chips(gang_run):
    assert gang_run["rc"] == 0
    decisions = {r["attrs"]["stage"]: r["attrs"]
                 for r in gang_run["records"]
                 if r.get("type") == "event"
                 and r.get("name") == "survey.gang_decision"}
    assert decisions["sweep"]["k"] == 4
    assert decisions["sweep"]["chips"] == [0, 1, 2, 3]
    assert decisions["mask"]["k"] == decisions["fold"]["k"] == 1


def test_gang_step_counts_no_fallback_and_registry_misses(gang_run):
    c = gang_run["summary"].counters
    assert c.get("compile.aot_fallback", 0) == 0
    assert c.get("compile.cache_miss", 0) >= 3  # the sharded programs too
    stages = gang_run["summary"].stages
    assert "compile.first.sweep" in stages and "compile.first.accel" in stages


def test_second_gang_step_compiles_nothing(gang_run):
    """Every program of the step, the three sharded ones among them, is
    found in the registry; the warm pool (pinned where a one-chip lease
    would run, and leaving the ganged stage to its mesh) finds the fold
    programs there too."""
    c = gang_run["warm"].counters
    assert c.get("compile.cache_miss", 0) == 0
    assert c.get("compile.aot_fallback", 0) == 0
    assert c.get("survey.precompiled", 0) == 0
    assert c.get("compile.cache_hit", 0) >= 3
    assert c.get("device3.accel.stream_batches", 0) >= 1


def test_lease_spans_carry_stage_k_chips_and_wait(gang_run):
    by_stage = {r["attrs"]["stage"]: r for r in _leases(gang_run)}
    assert set(by_stage) == {"mask", "sweep", "fold"}
    assert by_stage["sweep"]["attrs"]["k"] == 4
    assert by_stage["sweep"]["attrs"]["chips"] == [0, 1, 2, 3]
    assert all(r.get("noagg") and r["attrs"]["wait_s"] >= 0
               for r in by_stage.values())
    # sink-only: the flat per-stage table holds the stage span alone
    assert "survey.lease" not in gang_run["summary"].stages


def test_lease_chip_seconds_are_k_times_the_lease_wall(gang_run):
    c = gang_run["summary"].counters
    for r in _leases(gang_run):
        stage, k = r["attrs"]["stage"], r["attrs"]["k"]
        # the counter closes a few lines after the span does
        assert c[f"survey.lease_chip_s.{stage}"] == pytest.approx(
            k * r["dur"], rel=0.02, abs=0.02)
    # the same seconds twice over: by stage, and (PR 31) by chip
    by_chip = {n: v for n, v in c.items()
               if n.startswith("survey.lease_chip_s.chip")}
    assert sorted(by_chip) == [f"survey.lease_chip_s.chip{i}"
                               for i in range(4)]
    assert c["survey.lease_chip_s"] == pytest.approx(sum(by_chip.values()))
    assert c["survey.lease_chip_s"] == pytest.approx(
        sum(v for n, v in c.items()
            if n.startswith("survey.lease_chip_s.") and n not in by_chip))


def test_leased_chip_seconds_stay_inside_what_the_pool_offered(gang_run):
    c = gang_run["summary"].counters
    assert 0 < c["survey.lease_chip_s"] <= c["survey.pool_chip_s"]
    assert c["survey.lease_wait_s"] >= 0
    # three chips wait while the one-chip stages run
    assert c["survey.lease_chip_s.sweep"] > c["survey.lease_chip_s.mask"]


def test_stage_spans_show_four_lanes_in_the_per_device_rollup(gang_run):
    s = gang_run["summary"]
    assert sorted(s.device_busy) == [0, 1, 2, 3]
    sweep = [r for r in gang_run["records"] if r.get("type") == "span"
             and r.get("name") == "survey.stage.sweep"]
    assert sweep[0]["attrs"]["dev"] == [0, 1, 2, 3]
    assert sweep[0]["attrs"]["gang"] == 4


def test_tlmsum_prints_the_lease_rollup(gang_run):
    from pypulsar_tpu.obs.summarize import render

    buf = io.StringIO()
    render(gang_run["summary"], buf)
    text = buf.getvalue()
    assert "# leases:" in text and "chip-s leased of" in text
    assert "of the pool unleased" in text and "lease wait" in text
    for stage in ("mask", "sweep", "fold"):
        assert any(line.split()[1:2] == [stage] and "chip-s" in line
                   for line in text.splitlines()), stage
    assert "# per-device:" in text and "device 3" in text


def test_one_chip_run_counts_leases_too(tmp_path):
    """``--devices 1`` (the accepted cells' shape): every device stage
    takes a one-chip lease, and the pool is that one chip."""
    from pypulsar_tpu.survey.dag import StageSpec, SurveyConfig
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    def stage(name, device, deps):
        return StageSpec(name, "none", device, deps, lambda o, c: [],
                         lambda o, c: [], run=lambda o, c: 0)

    obs = Observation("psr0", str(tmp_path / "psr0.fil"),
                      str(tmp_path / "out"))
    open(obs.infile, "wb").close()
    with telemetry.session() as tlm:
        result = FleetScheduler(
            [obs], SurveyConfig(),
            stages=[stage("a", True, ()), stage("b", False, ("a",)),
                    stage("c", True, ("b",))], devices=1).run()
        c = tlm.counter_totals()
    assert result.ok
    assert set(n for n in c if n.startswith("survey.lease_chip_s.")) == {
        "survey.lease_chip_s.a", "survey.lease_chip_s.c",
        "survey.lease_chip_s.chip0"}
    assert c["survey.lease_chip_s.chip0"] == pytest.approx(
        c["survey.lease_chip_s"])
    assert c["survey.lease_chip_s"] <= c["survey.pool_chip_s"]
    assert c["survey.pool_chip_s"] == pytest.approx(result.wall)
