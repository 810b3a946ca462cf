"""Survey-orchestrator tests (round 9): the fleet scheduler must add
CONCURRENCY, never a second implementation — a 2-observation toy fleet's
artifacts are byte-identical to the serial per-tool chain; kill+resume
at every stage boundary re-runs exactly the unjournaled stages; a
persistently failing observation quarantines while the other completes;
the device lease serializes device-bound stages while host stages
overlap."""

import glob
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.resilience import faultinject
from pypulsar_tpu.survey.dag import StageSpec, SurveyConfig, build_dag
from pypulsar_tpu.survey.scheduler import FleetScheduler
from pypulsar_tpu.survey.state import (
    Observation,
    format_status,
    status_rows,
)

from tests.test_accel_pipeline import _pulsar_fil


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.reset()
    yield
    faultinject.reset()


# toy fleet geometry: small enough that a full 5-stage chain runs in a
# few seconds warm, strong enough that the accel search recovers the
# injected pulsar through sift into real .pfd archives
OBS = dict(C=16, T=8192)
CFG_KW = dict(mask=True, mask_time=1.0, lodm=0.0, dmstep=10.0, numdms=6,
              nsub=8, group_size=2, threshold=8.0,
              accel_zmax=20.0, accel_numharm=2, accel_sigma=3.0,
              accel_batch=4, sift_sigma=5.0, sift_min_hits=2,
              fold_nbins=32, fold_npart=8)
SURVEY_FLAGS = ["--lodm", "0", "--dmstep", "10", "--numdms", "6",
                "-s", "8", "--group-size", "2", "--threshold", "8",
                "--mask-time", "1.0",
                "--accel-zmax", "20", "--accel-numharm", "2",
                "--accel-sigma", "3", "--accel-batch", "4",
                "--sift-sigma", "5", "--sift-min-hits", "2",
                "--fold-nbins", "32", "--fold-npart", "8"]
ARTIFACT_PATTERNS = (".cands", "_DM*_ACCEL_*.cand", "_DM*_ACCEL_*.txtcand",
                     ".accelcands", "_cand*.pfd")


def _fleet_obs(fils, outdir):
    os.makedirs(outdir, exist_ok=True)
    return [Observation(os.path.splitext(os.path.basename(f))[0], f,
                        os.path.join(outdir,
                                     os.path.splitext(
                                         os.path.basename(f))[0]))
            for f in fils]


def _serial_chain(fil, outbase):
    """The exact per-tool chain the orchestrator composes, run serially
    by hand — the parity reference. Note: NO --journal on the sweep (the
    orchestrated stage passes one); artifact bytes must not depend on
    it."""
    from pypulsar_tpu.cli import foldbatch as cli_foldbatch
    from pypulsar_tpu.cli import pfd_snr as cli_pfd_snr
    from pypulsar_tpu.cli import rfifind as cli_rfifind
    from pypulsar_tpu.cli import sift as cli_sift
    from pypulsar_tpu.cli import sweep as cli_sweep

    assert cli_rfifind.main([fil, "-o", outbase, "-t", "1.0"]) == 0
    assert cli_sweep.main(
        [fil, "-o", outbase, "--lodm", "0", "--dmstep", "10",
         "--numdms", "6", "-s", "8", "--group-size", "2",
         "--threshold", "8", "--write-dats", "--accel-search",
         "--accel-zmax", "20", "--accel-dz", "2.0",
         "--accel-numharm", "2", "--accel-sigma", "3",
         "--accel-batch", "4",
         "--mask", outbase + "_rfifind.mask"]) == 0
    cands = sorted(glob.glob(outbase + "_DM*_ACCEL_*.cand"))
    assert cli_sift.main(cands + ["-s", "5", "--min-hits", "2",
                                  "-o", outbase + ".accelcands"]) == 0
    assert cli_foldbatch.main(
        ["--cands", outbase + ".accelcands", "--datbase", outbase,
         "-o", outbase, "-n", "32", "--npart", "8", "--batch", "32"]) == 0
    pfds = sorted(glob.glob(outbase + "_cand*.pfd"))
    assert pfds, "sift kept no candidates; the toy fleet is too weak"
    assert cli_pfd_snr.main(pfds + ["--json", outbase + "_snr.json"]) == 0


def _artifact_bytes(outdir, stem):
    out = {}
    for pat in ARTIFACT_PATTERNS:
        for f in sorted(glob.glob(os.path.join(outdir, stem + pat))):
            out[os.path.basename(f)] = open(f, "rb").read()
    return out


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """Two distinguishable toy observations + the serial-chain reference
    artifacts, computed once per module (the parity target for the
    orchestrated and kill/resume runs, and the jit warmup)."""
    root = tmp_path_factory.mktemp("survey")
    fils = [_pulsar_fil(root, name=f"psr{i}.fil", seed=5 + i, **OBS)
            for i in range(2)]
    refdir = str(root / "serial")
    os.makedirs(refdir)
    ref = {}
    for i, fil in enumerate(fils):
        stem = f"psr{i}"
        _serial_chain(fil, os.path.join(refdir, stem))
        ref[stem] = _artifact_bytes(refdir, stem)
        assert ref[stem], stem
    return {"root": root, "fils": fils, "refdir": refdir, "ref": ref}


def _assert_matches_reference(fleet_dict, outdir, stems=("psr0", "psr1")):
    for stem in stems:
        got = _artifact_bytes(outdir, stem)
        assert got.keys() == fleet_dict["ref"][stem].keys(), stem
        for name, data in fleet_dict["ref"][stem].items():
            assert got[name] == data, f"{stem}: {name} diverged"


# ---------------------------------------------------------------------------
# end-to-end parity
# ---------------------------------------------------------------------------


def test_fleet_end_to_end_byte_identical_to_serial_chain(fleet):
    """The acceptance contract: the orchestrated fleet's candidate
    tables and archives are byte-identical to running the serial chain
    per observation, and the SNR summaries carry the same science."""
    from pypulsar_tpu.cli import survey as cli_survey
    from pypulsar_tpu.cli import sweep as cli_sweep

    outdir = str(fleet["root"] / "orch")
    tlmdir = str(fleet["root"] / "tlm")
    rc = cli_survey.main(fleet["fils"] + ["-o", outdir,
                                          "--telemetry-dir", tlmdir,
                                          *SURVEY_FLAGS])
    assert rc == 0
    _assert_matches_reference(fleet, outdir)
    for stem in ("psr0", "psr1"):
        a = json.load(open(os.path.join(fleet["refdir"],
                                        stem + "_snr.json")))
        b = json.load(open(os.path.join(outdir, stem + "_snr.json")))
        assert [(r["name"], r["best_dm"], r["snr"]) for r in a] \
            == [(r["name"], r["best_dm"], r["snr"]) for r in b]
    # one trace per observation + one fleet trace, all tlmsum-readable
    traces = sorted(os.path.basename(f)
                    for f in glob.glob(os.path.join(tlmdir, "*.jsonl")))
    assert traces == ["fleet.jsonl", "psr0.jsonl", "psr1.jsonl"]
    from pypulsar_tpu.obs.summarize import load_records, summarize

    obs_sum = summarize(load_records(os.path.join(tlmdir, "psr0.jsonl")))
    assert "survey.stage.sweep" in obs_sum.stages
    # no sweep was killed, so neither tests a tmp name, however many
    # artifacts the shared output directory holds, and none leaves its
    # in-progress marker behind
    cleanups = [
        r["attrs"]
        for r in load_records(os.path.join(tlmdir, "fleet.jsonl"))
        if r.get("name") == "sweep.plan" and "listed" in r.get("attrs", {})]
    assert [(c["listed"], c["removed"]) for c in cleanups] == [(0, 0)] * 2
    assert not glob.glob(os.path.join(outdir, "*" + cli_sweep.RUN_MARKER))
    fleet_sum = summarize(load_records(os.path.join(tlmdir,
                                                    "fleet.jsonl")))
    assert fleet_sum.counters.get("survey.stages_run") == 10
    # --status renders both observations complete
    rc = cli_survey.main(["--status", "-o", outdir])
    assert rc == 0


# ---------------------------------------------------------------------------
# kill + resume at every stage boundary
# ---------------------------------------------------------------------------


def test_kill_resume_every_stage_boundary_bit_identical(fleet):
    """Kill the fleet at EVERY stage's completion boundary (artifacts
    written, manifest record pending — the torn window) plus one
    start boundary; ``--resume`` must re-run exactly the stages the
    manifests do not validate, and every final artifact is
    byte-identical to the serial chain."""
    cfg = SurveyConfig(**CFG_KW)
    all_stages = {s.name for s in build_dag(cfg)}
    points = [f"survey.stage_done.{s}"
              for s in ("mask", "sweep", "sift", "fold", "snr")]
    points.append("survey.stage_start.sweep")
    for ki, point in enumerate(points):
        outdir = str(fleet["root"] / f"kill{ki}")
        obs = _fleet_obs(fleet["fils"], outdir)
        faultinject.configure(f"kill:{point}:1")
        with pytest.raises(faultinject.InjectedKill):
            FleetScheduler(obs, cfg, max_host_workers=2).run()
        faultinject.reset()
        # what the manifests recorded done at the kill is what resume
        # must skip; everything else must re-run
        recorded = {(r["obs"], s)
                    for r in status_rows([o.manifest for o in obs])
                    for s in r["done"]}
        result = FleetScheduler(obs, cfg, max_host_workers=2,
                                resume=True).run()
        assert result.ok, point
        assert set(result.skipped) == recorded, point
        assert set(result.ran) == (
            {(o.name, s) for o in obs for s in all_stages} - recorded), \
            point
        _assert_matches_reference(fleet, outdir)


def test_kill9_subprocess_exit_then_resume(fleet):
    """The literal kill -9 semantics (os._exit(137): no finally blocks,
    no flushing) in a real subprocess, mid-fleet; a --resume completes
    the fleet without re-running validated stages."""
    outdir = str(fleet["root"] / "kill9")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (repo_root + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "pypulsar_tpu.cli", "survey",
         *fleet["fils"], "-o", outdir, *SURVEY_FLAGS,
         "--fault-inject", "exit:survey.stage_done.sweep:1"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 137, proc.stderr[-2000:]
    obs = _fleet_obs(fleet["fils"], outdir)
    recorded = {(r["obs"], s)
                for r in status_rows([o.manifest for o in obs])
                for s in r["done"]}
    # the killed subprocess completed (and journaled) at least one stage
    assert recorded, "kill fired before any stage completed"
    from pypulsar_tpu.cli import survey as cli_survey

    rc = cli_survey.main(fleet["fils"] + ["-o", outdir, "--resume",
                                          *SURVEY_FLAGS])
    assert rc == 0
    _assert_matches_reference(fleet, outdir)


def test_resume_skips_whole_validated_fleet_and_redoes_corruption(fleet):
    """Resuming a COMPLETE fleet runs nothing; corrupting one artifact
    re-runs exactly that stage chainward (size/sha256 validation)."""
    cfg = SurveyConfig(**CFG_KW)
    outdir = str(fleet["root"] / "revalidate")
    obs = _fleet_obs(fleet["fils"], outdir)
    assert FleetScheduler(obs, cfg).run().ok
    result = FleetScheduler(obs, cfg, resume=True).run()
    assert result.ran == [] and len(result.skipped) == 10
    # truncate one observation's sifted list: its sift stage (only) is
    # redone; the other observation still skips everything
    victim = os.path.join(outdir, "psr0.accelcands")
    ref = open(victim, "rb").read()
    with open(victim, "wb") as f:
        f.write(ref[: len(ref) // 2])
    result = FleetScheduler(obs, cfg, resume=True).run()
    assert result.ok
    assert ("psr0", "sift") in result.ran
    assert all(o == "psr0" for o, _ in result.ran)
    assert open(victim, "rb").read() == ref
    _assert_matches_reference(fleet, outdir)


def test_changed_config_restarts_manifest(tmp_path):
    """A resume under different stage parameters must restart the
    manifest (fingerprint mismatch) instead of trusting stale
    artifacts — the sweep-journal contract at fleet scope."""
    stages = _stub_stages()
    obs = [Observation("a", str(tmp_path / "a.raw"),
                       str(tmp_path / "a"))]
    cfg = SurveyConfig(numdms=8)
    assert FleetScheduler(obs, cfg, stages=stages).run().ok
    r = FleetScheduler(obs, cfg, stages=stages, resume=True).run()
    assert r.ran == [] and len(r.skipped) == 2
    r = FleetScheduler(obs, SurveyConfig(numdms=16), stages=stages,
                       resume=True).run()
    assert r.skipped == [] and len(r.ran) == 2


def test_replaced_input_file_restarts_manifest(tmp_path):
    """A regenerated raw file — even at the SAME size — restarts the
    manifest (the fingerprint includes mtime): resuming against
    artifacts derived from the old input would report stale science."""
    stages = _stub_stages()
    raw = str(tmp_path / "a.raw")
    with open(raw, "wb") as f:
        f.write(b"A" * 64)
    obs = [Observation("a", raw, str(tmp_path / "a"))]
    cfg = SurveyConfig()
    assert FleetScheduler(obs, cfg, stages=stages).run().ok
    assert FleetScheduler(obs, cfg, stages=stages,
                          resume=True).run().ran == []
    time.sleep(0.01)  # distinct mtime even on coarse filesystems
    with open(raw, "wb") as f:
        f.write(b"B" * 64)  # same size, new content
    r = FleetScheduler(obs, cfg, stages=stages, resume=True).run()
    assert r.skipped == [] and len(r.ran) == 2


def test_multi_device_leases_bind_distinct_jax_devices(tmp_path):
    """--devices N pins each device worker to its own JAX device
    (thread-local default_device), so N leases are N chips — not N-fold
    oversubscription of device 0. conftest forces an 8-device CPU mesh,
    so the binding is observable."""
    import jax
    import jax.numpy as jnp

    used = []

    def dev_run(obs, cfg):
        d, = jnp.ones(4).sum().devices()
        with _conc_lock:
            used.append(d.id)
        with open(f"{obs.outbase}.dev1.out", "w") as f:
            f.write("x")
        return 0

    stages = [StageSpec("dev1", "stub", True, (), lambda o, c: [],
                        _stub_outputs("dev1"), run=dev_run)]
    obs = [Observation(f"o{i}", str(tmp_path / f"o{i}.raw"),
                       str(tmp_path / f"o{i}")) for i in range(6)]
    assert FleetScheduler(obs, SurveyConfig(), stages=stages,
                          devices=2).run().ok
    assert len(used) == 6
    assert set(used) <= {d.id for d in jax.local_devices()[:2]}
    # with one lease (the default) nothing is pinned: process default
    used.clear()
    assert FleetScheduler(obs, SurveyConfig(), stages=stages,
                          devices=1).run().ok
    assert set(used) == {jax.local_devices()[0].id}


def test_obs_trace_appends_on_resume(tmp_path):
    """A resumed fleet appends to the per-observation trace instead of
    truncating the killed run's recorded spans."""
    from pypulsar_tpu.obs.summarize import load_records, summarize
    from pypulsar_tpu.survey.state import ObsTrace

    path = str(tmp_path / "o.jsonl")
    t = ObsTrace(path, "o")
    t.span("survey.stage.mask", 0.0, 1.0)
    t.close()
    t = ObsTrace(path, "o", append=True)  # the --resume run
    t.span("survey.stage.sweep", 0.0, 2.0)
    t.close()
    s = summarize(load_records(path))
    assert set(s.stages) == {"survey.stage.mask", "survey.stage.sweep"}
    # a fresh (non-resume) run still truncates
    t = ObsTrace(path, "o")
    t.close()
    assert summarize(load_records(path)).stages == {}


# ---------------------------------------------------------------------------
# quarantine + retry
# ---------------------------------------------------------------------------


def test_reconfigured_rerun_scrubs_stale_artifacts(fleet):
    """Rerunning a SMALLER configuration into the same outdir must not
    let the previous grid's files leak into the glob-driven stage
    inputs (sift would cluster old-grid .cand trails): a fresh manifest
    scrubs every stage's enumerable artifacts first, so the rerun
    matches a clean-dir run byte for byte."""
    cfg6 = SurveyConfig(**CFG_KW)
    cfg4 = SurveyConfig(**{**CFG_KW, "numdms": 4})
    fil = fleet["fils"][0]
    shared = str(fleet["root"] / "reconf")
    assert FleetScheduler(_fleet_obs([fil], shared), cfg6).run().ok
    assert glob.glob(os.path.join(shared, "psr0_DM50.00_ACCEL_*.cand"))
    assert FleetScheduler(_fleet_obs([fil], shared), cfg4).run().ok
    # old-grid trails (DM 40/50) are gone, not globbed into the sift
    assert not glob.glob(os.path.join(shared, "psr0_DM[45]0*"))
    clean = str(fleet["root"] / "reconf_clean")
    assert FleetScheduler(_fleet_obs([fil], clean), cfg4).run().ok
    got = _artifact_bytes(shared, "psr0")
    want = _artifact_bytes(clean, "psr0")
    assert got.keys() == want.keys()
    for name, data in want.items():
        assert got[name] == data, name


def test_retry_timer_does_not_resurrect_quarantined_stage(tmp_path):
    """The backoff timer's requeue must drop a task whose observation
    was quarantined (or whose fleet stopped) while it waited."""
    sched = FleetScheduler(
        [Observation("a", str(tmp_path / "a.raw"), str(tmp_path / "a"))],
        SurveyConfig(), stages=_stub_stages())
    task = sched._tasks[(0, "host1")]
    task.state = 4  # _QUARANTINED
    sched._requeue_retry(task)
    assert sched._host_q.empty()
    task.state = 2  # _RUNNING (normal backing-off state)
    sched._requeue_retry(task)
    assert not sched._host_q.empty()
    # a stopped fleet also drops the requeue
    task2 = sched._tasks[(0, "dev1")]
    sched._stop = True
    sched._requeue_retry(task2)
    assert sched._device_q.empty()


def test_quarantine_keeps_other_observation_complete(fleet):
    """A persistently failing observation (unreadable input) is
    quarantined after bounded retries; the OTHER observation's chain
    completes with byte-identical artifacts and the verdict lands in
    the manifest + --status."""
    from pypulsar_tpu.cli import survey as cli_survey

    bad = str(fleet["root"] / "bad.fil")
    with open(bad, "wb") as f:
        f.write(b"this is not a filterbank")
    outdir = str(fleet["root"] / "quarantine")
    rc = cli_survey.main([fleet["fils"][0], bad, "-o", outdir,
                          "--retries", "1", *SURVEY_FLAGS])
    assert rc == 1
    _assert_matches_reference(fleet, outdir, stems=("psr0",))
    assert os.path.exists(os.path.join(outdir, "psr0_snr.json"))
    rows = {r["obs"]: r for r in status_rows(
        sorted(glob.glob(os.path.join(outdir, "*.survey.jsonl"))))}
    assert rows["bad"]["quarantine"] is not None
    assert rows["bad"]["quarantine"]["stage"] == "mask"
    assert rows["psr0"]["quarantine"] is None
    assert len(rows["psr0"]["done"]) == 5
    table = format_status(rows.values())
    assert "QUARANTINED" in table and "complete" in table
    # --status over the same manifests
    assert cli_survey.main(["--status", "-o", outdir]) == 0


def test_stage_retry_recovers_from_transient_fault(tmp_path):
    """An injected transient IO fault at a stage boundary is retried
    (bounded backoff) and the fleet completes — visible as a
    survey.stage_retry telemetry event."""
    stages = _stub_stages()
    obs = [Observation("a", str(tmp_path / "a.raw"), str(tmp_path / "a"))]
    faultinject.configure("io:survey.stage_start.host1:1")
    with telemetry.session() as tlm:
        result = FleetScheduler(obs, SurveyConfig(), stages=stages,
                                retries=2).run()
        assert tlm.event_counts.get("survey.stage_retry") == 1
        assert tlm.event_counts.get("survey.stage_failed") == 1
    assert result.ok and result.retried == 1
    assert ("a", "host1") in result.ran


def test_retries_exhausted_quarantines_not_aborts(tmp_path):
    """A stage that fails every attempt quarantines its observation;
    the scheduler returns (no exception) and the other observation
    completes."""

    # only observation 'a' fails; 'b' runs the normal stub body
    def selective_fail(o, c):
        if o.name == "a":
            raise OSError("persistent read failure")
        return _stub_body("host1")(o, c)

    stages = [_stub("dev1", True, ()),
              StageSpec("host1", "stub", False, ("dev1",),
                        lambda o, c: [], _stub_outputs("host1"),
                        run=selective_fail)]
    obs = [Observation(n, str(tmp_path / f"{n}.raw"), str(tmp_path / n))
           for n in ("a", "b")]
    with telemetry.session() as tlm:
        result = FleetScheduler(obs, SurveyConfig(), stages=stages,
                                retries=1).run()
        assert tlm.event_counts.get("survey.quarantine") == 1
    assert not result.ok
    assert set(result.quarantined) == {"a"}
    assert result.quarantined["a"]["stage"] == "host1"
    assert ("b", "host1") in result.ran
    assert os.path.exists(str(tmp_path / "b") + ".host1.out")


# ---------------------------------------------------------------------------
# scheduler semantics (synthetic stages; no pipeline cost)
# ---------------------------------------------------------------------------

_conc_lock = threading.Lock()


def _stub_body(name, sleep=0.0, conc=None, key=None, order=None):
    def run(obs, cfg):
        if conc is not None:
            with _conc_lock:
                conc[key] += 1
                conc[key + "_max"] = max(conc[key + "_max"], conc[key])
        if order is not None:
            with _conc_lock:
                order.append((obs.name, name))
        if sleep:
            time.sleep(sleep)
        if conc is not None:
            with _conc_lock:
                conc[key] -= 1
        with open(f"{obs.outbase}.{name}.out", "w") as f:
            f.write(f"{name} {obs.name}\n")
        return 0
    return run


def _stub_outputs(name):
    def outputs(obs, cfg):
        return [f"{obs.outbase}.{name}.out"]
    return outputs


def _stub(name, device, deps, **kw):
    return StageSpec(name, "stub", device, deps, lambda o, c: [],
                     _stub_outputs(name), run=_stub_body(name, **kw))


def _stub_stages():
    return [_stub("dev1", True, ()), _stub("host1", False, ("dev1",))]


def test_device_lease_exclusive_host_pool_overlaps(tmp_path):
    """Device-bound stages never overlap (one lease); host-bound stages
    from different observations DO overlap on the worker pool — the
    wall-clock mechanism the bench A/B measures."""
    conc = {"dev": 0, "dev_max": 0, "host": 0, "host_max": 0}
    stages = [
        _stub("dev1", True, (), sleep=0.02, conc=conc, key="dev"),
        _stub("host1", False, ("dev1",), sleep=0.15, conc=conc,
              key="host"),
    ]
    obs = [Observation(f"o{i}", str(tmp_path / f"o{i}.raw"),
                       str(tmp_path / f"o{i}")) for i in range(4)]
    result = FleetScheduler(obs, SurveyConfig(), stages=stages,
                            max_host_workers=2, devices=1).run()
    assert result.ok and len(result.ran) == 8
    assert conc["dev_max"] == 1          # exclusive lease
    assert conc["host_max"] >= 2         # B's post overlaps A's device time


def test_device_queue_prefers_deeper_stages(tmp_path):
    """Priority + FIFO on the device lease: when a later-chain stage
    becomes ready it runs before an earlier-chain stage of another
    observation (drain observations toward completion)."""
    order = []
    stages = [
        _stub("dev1", True, (), order=order),
        _stub("dev2", True, ("dev1",), order=order),
    ]
    obs = [Observation(f"o{i}", str(tmp_path / f"o{i}.raw"),
                       str(tmp_path / f"o{i}")) for i in range(2)]
    result = FleetScheduler(obs, SurveyConfig(), stages=stages,
                            devices=1).run()
    assert result.ok
    # o0.dev1 runs first; its dev2 (deeper) then outranks o1.dev1
    assert order[0] == ("o0", "dev1")
    assert order[1] == ("o0", "dev2")


def test_scheduler_rejects_bad_dags_and_duplicate_names(tmp_path):
    with pytest.raises(ValueError, match="unknown stage"):
        FleetScheduler([], SurveyConfig(),
                       stages=[_stub("a", True, ("missing",))])
    obs = [Observation("x", "x.raw", str(tmp_path / "x")),
           Observation("x", "y.raw", str(tmp_path / "y"))]
    with pytest.raises(ValueError, match="duplicate"):
        FleetScheduler(obs, SurveyConfig(), stages=_stub_stages())


# ---------------------------------------------------------------------------
# gang leases (multi-chip single-observation scale-out)
# ---------------------------------------------------------------------------

# cached capability gate shared with the sharded-handoff tests (same
# pattern as test_distributed's CPU-collectives probe)
from tests.test_accel_pipeline import require_virtual_mesh as \
    _require_virtual_mesh


def _gang_stub(name, deps=(), devices_max=4, body=None):
    def run(obs, cfg):
        if body is not None:
            body(obs, cfg)
        with open(f"{obs.outbase}.{name}.out", "w") as f:
            f.write(f"{name} {obs.name}\n")
        return 0

    return StageSpec(name, "stub", True, deps, lambda o, c: [],
                     _stub_outputs(name), run=run,
                     devices_max=devices_max)


def test_gang_lease_pins_k_distinct_devices(tmp_path):
    """A gang-leased stage sees its k chips through the thread-local
    lease (parallel.mesh.device_lease / lease_devices) — the resolver
    every mesh-building call site goes through, so `sweep --mesh k`
    inside the stage can only address the leased chips."""
    _require_virtual_mesh(2)
    import jax

    from pypulsar_tpu.parallel import mesh as mesh_mod

    seen = []

    def body(obs, cfg):
        lease = mesh_mod.current_lease()
        devs = mesh_mod.lease_devices(2)
        with _conc_lock:
            seen.append((tuple(d.id for d in lease),
                         tuple(d.id for d in devs)))

    stages = [_gang_stub("gangdev", devices_max=2, body=body)]
    obs = [Observation(f"o{i}", str(tmp_path / f"o{i}.raw"),
                       str(tmp_path / f"o{i}")) for i in range(3)]
    assert FleetScheduler(obs, SurveyConfig(), stages=stages,
                          devices=2, gang=2).run().ok
    assert len(seen) == 3
    local = [d.id for d in jax.local_devices()]
    for lease_ids, resolved_ids in seen:
        assert len(set(lease_ids)) == 2          # two DISTINCT chips
        assert resolved_ids == lease_ids         # resolver == the lease
        assert set(lease_ids) <= set(local)


def test_gang_auto_places_both_shapes(tmp_path):
    """The placement policy demonstrably picks BOTH shapes: a deep
    fleet stays fleet-parallel (k obs x 1 chip), a lone observation
    widens onto the idle chips (1 obs x k chips) — and each decision is
    recorded with its reason (survey.gang_decision)."""
    _require_virtual_mesh(2)

    def decisions(n_obs, subdir):
        path = str(tmp_path / f"{subdir}.jsonl")
        stages = [_gang_stub("gangable", devices_max=2)]
        obs = [Observation(f"o{i}", str(tmp_path / f"{subdir}{i}.raw"),
                           str(tmp_path / f"{subdir}_o{i}"))
               for i in range(n_obs)]
        with telemetry.session(path):
            assert FleetScheduler(obs, SurveyConfig(), stages=stages,
                                  devices=2, gang="auto").run().ok
        recs = [json.loads(l) for l in open(path)]
        return [r["attrs"] for r in recs
                if r.get("type") == "event"
                and r.get("name") == "survey.gang_decision"]

    deep = decisions(4, "deep")
    assert len(deep) == 4
    # with 4 ready observations on 2 chips at least the contended
    # decisions stay fleet-parallel, with the reason recorded
    assert any(d["k"] == 1 and "fleet-parallel" in d["reason"]
               for d in deep)
    lone = decisions(1, "lone")
    assert len(lone) == 1
    assert lone[0]["k"] == 2 and len(lone[0]["chips"]) == 2
    assert "idle" in lone[0]["reason"]


def test_gang_auto_cost_gate():
    """The measured-cost gate: a gang-able stage that owns a sliver of
    the measured device chain runs 1-chip even with idle chips; the
    dominant stage gangs. (Unit-level: the policy reads the same
    per-stage costs the obs traces record.)"""
    stages = [_gang_stub("cheap", devices_max=4),
              _gang_stub("dominant", devices_max=4)]
    sched = FleetScheduler(
        [Observation("a", "a.raw", "/tmp/unused_a")],
        SurveyConfig(), stages=stages, devices=4, gang="auto")
    sched._stage_cost = {"cheap": [0.1, 1], "dominant": [9.9, 1]}
    k, reason = sched._gang_size(sched._tasks[(0, "cheap")])
    assert k == 1 and "not worth" in reason
    k, reason = sched._gang_size(sched._tasks[(0, "dominant")])
    assert k == 4 and "99%" in reason


def test_lease_pool_larger_than_real_devices_is_refused(tmp_path):
    """A device lease is one real chip: a pool wider than the real
    device count would wrap onto chip 0 and report k-chip work that ran
    on one, so the scheduler refuses it, the CLI exits 2, and lease i
    binds local device i."""
    _require_virtual_mesh(2)
    import jax

    from pypulsar_tpu.cli import survey as cli

    n = len(jax.local_devices())
    obs = [Observation("a", "a.raw", str(tmp_path / "a"))]
    with pytest.raises(ValueError, match="exceeds the .* local JAX"):
        FleetScheduler(obs, SurveyConfig(), stages=_stub_stages(),
                       devices=n + 1)
    assert cli.main(["a.fil", "-o", str(tmp_path / "out"),
                     "--devices", str(n + 1)]) == 2
    sched = FleetScheduler(obs, SurveyConfig(), stages=_stub_stages(),
                           devices=n, gang="auto")
    assert sched._jax_gang([0, n - 1]) == [jax.local_devices()[0],
                                           jax.local_devices()[n - 1]]


def test_gang_acquisition_fifo_no_starvation(tmp_path):
    """Device-pool acquisition is FIFO with reservation: a waiting wide
    gang reserves freed chips, so 1-chip traffic cannot starve it."""
    sched = FleetScheduler(
        [Observation("a", "a.raw", str(tmp_path / "a"))],
        SurveyConfig(), stages=_stub_stages(), devices=2)
    one = sched._acquire_devices(1)
    assert one == [0]
    got = []
    t = threading.Thread(target=lambda: got.append(
        sched._acquire_devices(2)))
    t.start()
    time.sleep(0.05)
    assert not got                       # gang waits: only 1 chip free
    # a younger 1-chip claim must NOT overtake the waiting gang's
    # reservation once the first chip frees
    sched._release_devices(one)
    t.join(timeout=5.0)
    assert got and sorted(got[0]) == [0, 1]
    sched._release_devices(got[0])
    assert sched._acquire_devices(1) is not None


def test_gang_lease_kill_resume_byte_identical(fleet):
    """Kill a gang-leased fleet at the sweep completion boundary; a
    --resume under the same gang shape completes with artifacts
    byte-identical to the serial 1-chip chain — placement is not
    science, so the manifest resumes across ANY gang shape."""
    _require_virtual_mesh(4)
    cfg = SurveyConfig(**CFG_KW)
    outdir = str(fleet["root"] / "gangkill")
    obs = _fleet_obs(fleet["fils"][:1], outdir)
    faultinject.configure("kill:survey.stage_done.sweep:1")
    with pytest.raises(faultinject.InjectedKill):
        FleetScheduler(obs, cfg, devices=4, gang="auto").run()
    faultinject.reset()
    result = FleetScheduler(obs, cfg, devices=4, gang="auto",
                            resume=True).run()
    assert result.ok
    assert ("psr0", "sweep") in result.ran   # the torn stage redone
    _assert_matches_reference(fleet, outdir, stems=("psr0",))


def test_gang_fleet_byte_identical_and_per_device_rollup(fleet):
    """One observation spanning 4 chips end to end produces artifacts
    byte-identical to the serial chain, and the traces carry per-chip
    attribution tlmsum's per-device roll-up renders."""
    _require_virtual_mesh(4)
    from pypulsar_tpu.cli import survey as cli_survey
    from pypulsar_tpu.obs.summarize import load_records, summarize

    outdir = str(fleet["root"] / "gangfleet")
    tlmdir = str(fleet["root"] / "gangtlm")
    rc = cli_survey.main([fleet["fils"][0], "-o", outdir,
                          "--devices", "4", "--gang", "4",
                          "--telemetry-dir", tlmdir, *SURVEY_FLAGS])
    assert rc == 0
    _assert_matches_reference(fleet, outdir, stems=("psr0",))
    s = summarize(load_records(os.path.join(tlmdir, "fleet.jsonl")))
    assert s.events.get("survey.gang_decision")
    # the sharded sweep/accel spans stamped all 4 leased chips
    assert len(s.device_busy) == 4
    for _d, (busy, nsp) in sorted(s.device_busy.items()):
        assert busy > 0 and nsp > 0
    assert s.counters.get("device0.dedisperse.chunks", 0) >= 1
    import io

    from pypulsar_tpu.obs.summarize import render

    buf = io.StringIO()
    render(s, buf)
    assert "# per-device:" in buf.getvalue()
    assert "device 3" in buf.getvalue()


def test_gang_rejects_more_than_devices():
    with pytest.raises(ValueError, match="exceeds"):
        FleetScheduler([], SurveyConfig(), stages=_stub_stages(),
                       devices=2, gang=4)


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------


def _load_make_synthetic_fil():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "make_synthetic_fil.py")
    spec = importlib.util.spec_from_file_location("make_synthetic_fil",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_make_synthetic_fil_src_name_and_start_mjd(tmp_path):
    """--src-name/--start-mjd land in the header (round-trip through the
    reader); defaults unchanged."""
    from pypulsar_tpu.io.filterbank import FilterbankFile

    mod = _load_make_synthetic_fil()
    common = ["--nchan", "8", "--duration", "0.5", "--tsamp", "1e-3",
              "--period-samples", "128", "--width", "2"]
    fn = str(tmp_path / "beam7.fil")
    mod.main(["--out", fn, *common,
              "--src-name", "FLEET_BEAM7", "--start-mjd", "58765.5"])
    with FilterbankFile(fn) as fb:
        assert fb.header["source_name"] == "FLEET_BEAM7"
        assert fb.header["tstart"] == 58765.5
    fn2 = str(tmp_path / "default.fil")
    mod.main(["--out", fn2, *common])
    with FilterbankFile(fn2) as fb:
        assert fb.header["source_name"].startswith("SYNTH_DM")
        assert fb.header["tstart"] == 60000.0


def test_status_rows_and_render_from_raw_manifests(tmp_path):
    """--status reads manifests fingerprint-agnostically, tolerating a
    torn trailing line, and renders progress/quarantine states."""
    p1 = str(tmp_path / "a.survey.jsonl")
    with open(p1, "w") as f:
        f.write(json.dumps({"type": "journal", "tool": "survey",
                            "fingerprint": "zzz"}) + "\n")
        f.write(json.dumps({"type": "note", "event": "plan", "obs": "a",
                            "stages": ["s1", "s2", "s3"]}) + "\n")
        f.write(json.dumps({"type": "done", "unit": "stage:s1",
                            "outputs": []}) + "\n")
        f.write('{"type": "done", "unit": "stage:s2", "outp')  # torn
    p2 = str(tmp_path / "b.survey.jsonl")
    with open(p2, "w") as f:
        f.write(json.dumps({"type": "journal", "tool": "survey",
                            "fingerprint": "zzz"}) + "\n")
        f.write(json.dumps({"type": "note", "event": "plan", "obs": "b",
                            "stages": ["s1", "s2"]}) + "\n")
        f.write(json.dumps({"type": "note", "event": "quarantine",
                            "stage": "s1", "error": "boom"}) + "\n")
    rows = status_rows([p1, p2])
    assert rows[0]["done"] == ["s1"] and rows[0]["quarantine"] is None
    assert rows[1]["quarantine"]["stage"] == "s1"
    table = format_status(rows)
    assert "1/3" in table and "next: s2" in table
    assert "QUARANTINED at s1 (boom)" in table
    # a LATER done record for the quarantined stage (a resume got past
    # it) supersedes the verdict — --status must not say QUARANTINED
    # about a completed observation
    with open(p2, "a") as f:
        f.write(json.dumps({"type": "done", "unit": "stage:s1",
                            "outputs": []}) + "\n")
        f.write(json.dumps({"type": "done", "unit": "stage:s2",
                            "outputs": []}) + "\n")
    rows = status_rows([p1, p2])
    assert rows[1]["quarantine"] is None
    assert "complete" in format_status([rows[1]])


# ---------------------------------------------------------------------------
# fleet health (round 12): watchdog, device strikes, admission, chaos
# ---------------------------------------------------------------------------


def test_stalled_stage_interrupted_and_retried(tmp_path, monkeypatch):
    """Acceptance: a stage that stops heartbeating is detected within
    its bound, its worker is interrupted, the lease is reclaimed and
    the observation RETRIES — the fleet completes, the verdict is a
    survey.stage_stalled event, and the other observation is never
    stalled behind the wedged one."""
    monkeypatch.setenv(faultinject.ENV_HANG_S, "30")  # hang >> stall
    # the stub pipeline trips a fault point per loop like the real hot
    # paths do; the armed hang wedges attempt 1 of ONE observation
    faultinject.configure("hang:stub.step:1")

    def body(obs, cfg):
        for _ in range(3):
            faultinject.trip("stub.step")
            telemetry.counter("stub.steps")  # heartbeat
        with open(f"{obs.outbase}.dev1.out", "w") as f:
            f.write(f"dev1 {obs.name}\n")
        return 0

    stages = [StageSpec("dev1", "stub", True, (), lambda o, c: [],
                        _stub_outputs("dev1"), run=body)]
    obs = [Observation(n, str(tmp_path / f"{n}.raw"), str(tmp_path / n))
           for n in ("a", "b")]
    t0 = time.monotonic()
    with telemetry.session() as tlm:
        result = FleetScheduler(obs, SurveyConfig(), stages=stages,
                                retries=1, stall_s=0.5).run()
        assert tlm.event_counts.get("survey.stage_stalled") == 1
        assert tlm.event_counts.get("survey.stage_retry") == 1
        assert tlm.counters.get("survey.watchdog_interrupts") == 1
    took = time.monotonic() - t0
    assert result.ok and result.timeouts == 1 and result.retried == 1
    assert took < 20.0  # interrupted within the bound, not HANG_S
    for n in ("a", "b"):
        assert os.path.exists(str(tmp_path / n) + ".dev1.out")
    # the retry verdict (attempt + stall excerpt) landed in the
    # manifest for --status
    from pypulsar_tpu.survey.state import status_rows

    rows = status_rows(sorted(glob.glob(str(tmp_path / "*.survey.jsonl"))))
    stalled = [r for r in rows if r["retries"]]
    assert len(stalled) == 1
    assert stalled[0]["retries"]["dev1"]["attempts"] == 1
    assert "StageStalled" in stalled[0]["retries"]["dev1"]["error"]


def test_deadline_exceeded_quarantines_without_stalling_fleet(tmp_path):
    """A stage that heartbeats but outruns its declared deadline is
    interrupted every attempt and the observation quarantines; the
    other observation completes and the fleet returns promptly."""

    def slow_body(obs, cfg):
        if obs.name == "a":
            for _ in range(100):  # ~5 s, beating the whole way
                time.sleep(0.05)
                telemetry.counter("stub.steps")
        with open(f"{obs.outbase}.dev1.out", "w") as f:
            f.write(f"dev1 {obs.name}\n")
        return 0

    stages = [StageSpec("dev1", "stub", True, (), lambda o, c: [],
                        _stub_outputs("dev1"), run=slow_body,
                        deadline_s=0.4)]
    obs = [Observation(n, str(tmp_path / f"{n}.raw"), str(tmp_path / n))
           for n in ("a", "b")]
    with telemetry.session() as tlm:
        result = FleetScheduler(obs, SurveyConfig(), stages=stages,
                                retries=1, stall_s=30.0).run()
        assert tlm.event_counts.get("survey.deadline_exceeded") == 2
        assert not tlm.event_counts.get("survey.stage_stalled")
    assert not result.ok
    assert set(result.quarantined) == {"a"}
    assert "StageDeadlineExceeded" in result.quarantined["a"]["error"]
    assert result.timeouts == 2  # first attempt + the retry
    assert ("b", "dev1") in result.ran
    assert os.path.exists(str(tmp_path / "b") + ".dev1.out")


def test_stage_deadline_per_mb_and_uniform_override(tmp_path):
    """deadline_for composes the flat and size-derived terms; the
    scheduler-level --stage-deadline overrides both."""
    raw = tmp_path / "o.raw"
    raw.write_bytes(b"\0" * 2_000_000)  # 2 MB
    obs = Observation("o", str(raw), str(tmp_path / "o"))
    s = StageSpec("x", "stub", True, (), lambda o, c: [],
                  _stub_outputs("x"), deadline_s=10.0,
                  deadline_per_mb=2.0)
    assert s.deadline_for(obs) == pytest.approx(14.0)
    s2 = StageSpec("x", "stub", True, (), lambda o, c: [],
                   _stub_outputs("x"), deadline_per_mb=3.0)
    assert s2.deadline_for(obs) == pytest.approx(6.0)
    # unstatable input contributes nothing (the stage reports it)
    gone = Observation("g", str(tmp_path / "gone.raw"),
                       str(tmp_path / "g"))
    assert s.deadline_for(gone) == pytest.approx(10.0)
    assert s2.deadline_for(gone) is None
    s3 = StageSpec("x", "stub", True, (), lambda o, c: [],
                   _stub_outputs("x"))
    assert s3.deadline_for(obs) is None
    sched = FleetScheduler([obs], SurveyConfig(), stages=[s],
                           stage_deadline=99.0)
    assert sched._deadline_for(s, obs) == 99.0


def test_device_fault_strikes_evict_lease_mid_fleet(tmp_path):
    """A lease past K strikes is quarantined OUT of the pool mid-fleet:
    the fleet completes on the survivors, the verdict is mirrored to
    _fleet_health.json, and survey --status renders it."""
    from pypulsar_tpu.survey.state import (
        format_status,
        read_fleet_health,
        status_rows,
    )

    flaky = {"n": 0}

    def body(obs, cfg):
        if obs.name == "a" and flaky["n"] < 1:
            flaky["n"] += 1
            raise faultinject.InjectedDeviceFault("stub.dispatch")
        with open(f"{obs.outbase}.dev1.out", "w") as f:
            f.write(f"dev1 {obs.name}\n")
        return 0

    stages = [StageSpec("dev1", "stub", True, (), lambda o, c: [],
                        _stub_outputs("dev1"), run=body)]
    obs = [Observation(n, str(tmp_path / f"{n}.raw"), str(tmp_path / n))
           for n in ("a", "b", "c")]
    with telemetry.session() as tlm:
        result = FleetScheduler(obs, SurveyConfig(), stages=stages,
                                devices=2, retries=2,
                                strike_limit=1).run()
        assert tlm.event_counts.get("survey.device_evicted") == 1
        assert tlm.event_counts.get("mesh.device_quarantined") == 1
    assert result.ok and len(result.evicted_devices) == 1
    evicted = result.evicted_devices[0]
    health = read_fleet_health(str(tmp_path))
    assert health is not None and health["strike_limit"] == 1
    dev = health["devices"][str(evicted)]
    assert dev["quarantined"] and dev["strikes"] >= 1
    assert "DEVICE_FAULT" in dev["last_error"]
    rendered = format_status(
        status_rows(sorted(glob.glob(str(tmp_path / "*.survey.jsonl")))),
        health=health)
    assert "QUARANTINED" in rendered and f"device {evicted}" in rendered
    for n in ("a", "b", "c"):
        assert os.path.exists(str(tmp_path / n) + ".dev1.out")


def test_last_healthy_lease_never_evicted(tmp_path):
    """Strikes on the only healthy lease are counted but the verdict is
    deferred: an empty pool is a hung fleet, strictly worse than a
    flaky one."""
    flaky = {"n": 0}

    def body(obs, cfg):
        if flaky["n"] < 2:
            flaky["n"] += 1
            raise faultinject.InjectedDeviceFault("stub.dispatch")
        with open(f"{obs.outbase}.dev1.out", "w") as f:
            f.write(f"dev1 {obs.name}\n")
        return 0

    stages = [StageSpec("dev1", "stub", True, (), lambda o, c: [],
                        _stub_outputs("dev1"), run=body)]
    obs = [Observation("a", str(tmp_path / "a.raw"), str(tmp_path / "a"))]
    result = FleetScheduler(obs, SurveyConfig(), stages=stages,
                            devices=1, retries=3, strike_limit=1).run()
    assert result.ok and result.evicted_devices == []
    assert result.retried == 2


def test_admission_gate_pauses_scheduling_not_inflight(tmp_path):
    """Backpressure (a pending_depth gauge above --max-pending) pauses
    LAUNCHING new stages; when the gauge drains the fleet resumes and
    completes. One paused + one resumed event per episode."""
    stages = _stub_stages()
    obs = [Observation(f"o{i}", str(tmp_path / f"o{i}.raw"),
                       str(tmp_path / f"o{i}")) for i in range(2)]
    with telemetry.session() as tlm:
        telemetry.gauge("stub.pending_depth", 10)
        sched = FleetScheduler(obs, SurveyConfig(), stages=stages,
                               max_pending=5)
        t = threading.Thread(target=sched.run)
        t.start()
        for _ in range(100):
            if tlm.event_counts.get("survey.admission_paused"):
                break
            time.sleep(0.05)
        assert tlm.event_counts.get("survey.admission_paused") == 1
        assert not sched.result.ran  # nothing launched while paused
        telemetry.gauge("stub.pending_depth", 0)  # the consumer drained
        t.join(timeout=30.0)
        assert not t.is_alive()
        assert tlm.event_counts.get("survey.admission_resumed") == 1
    assert sched.result.ok and len(sched.result.ran) == 4


def test_tlmsum_renders_fleet_health_rollup(tmp_path):
    """The fleet-health verdicts are visible in tlmsum: watchdog
    interrupts, deadline/stall events, device strikes/quarantines and
    injected-fault counts roll up into one `fleet health:` line."""
    import io

    from pypulsar_tpu.obs.summarize import load_records, render, summarize

    path = str(tmp_path / "t.jsonl")
    with telemetry.session(path):
        telemetry.counter("survey.watchdog_interrupts", 2)
        telemetry.event("survey.deadline_exceeded", obs="a", stage="sweep")
        telemetry.event("survey.stage_stalled", obs="b", stage="fold")
        telemetry.event("mesh.device_strike", dev=1, kind="oom", strikes=1)
        telemetry.event("mesh.device_quarantined", dev=1, strikes=3)
        telemetry.event("survey.device_evicted", devs=[1], stage="sweep")
        telemetry.counter("resilience.faults_injected", 4)
    buf = io.StringIO()
    render(summarize(load_records(path)), buf)
    out = buf.getvalue()
    assert "fleet health:" in out
    for bit in ("watchdog interrupts=2", "deadlines exceeded=1",
                "stalls=1", "device strikes=1", "devices quarantined=1",
                "lease evictions=1", "injected faults=4"):
        assert bit in out, bit


def test_gang_shrinks_after_eviction_byte_identical(fleet):
    """Acceptance: a chip-indicting fault mid-gang evicts the struck
    lease and the retried gang SHRINKS to the survivors — with the
    final artifacts byte-identical to the serial 1-chip chain, because
    placement is excluded from every fingerprint."""
    _require_virtual_mesh(2)
    cfg = SurveyConfig(**CFG_KW)
    outdir = str(fleet["root"] / "shrink")
    obs = _fleet_obs(fleet["fils"][:1], outdir)
    # the device fault escapes the accel batch dispatch mid-sweep (the
    # no_degrade contract forbids the serial fallback from absorbing
    # it), indicts the whole gang, and the strike evicts one lease
    faultinject.configure("device:accel.batch_dispatch:1")
    trace = str(fleet["root"] / "shrink_trace.jsonl")
    with telemetry.session(trace) as tlm:
        result = FleetScheduler(obs, cfg, devices=2, gang=2,
                                retries=2, strike_limit=1).run()
        assert tlm.event_counts.get("survey.device_evicted") == 1
    assert result.ok and result.retried >= 1
    assert len(result.evicted_devices) == 1
    # the sweep gang ran wide first, then retried shrunk (the decision
    # trail is in the trace, attrs and all)
    decisions = [r["attrs"] for r in map(json.loads, open(trace))
                 if r.get("type") == "event"
                 and r.get("name") == "survey.gang_decision"]
    sweep_ks = [d["k"] for d in decisions if d["stage"] == "sweep"]
    assert sweep_ks[0] == 2 and sweep_ks[-1] == 1
    # the shrunk retry ran on the SURVIVING chip, and said why
    last = [d for d in decisions if d["stage"] == "sweep"][-1]
    assert result.evicted_devices[0] not in last["chips"]
    assert "healthy" in last["reason"]
    _assert_matches_reference(fleet, outdir, stems=("psr0",))


@pytest.mark.slow
def test_seeded_chaos_fleet_recovers_byte_identical(fleet, monkeypatch):
    """The chaos harness's contract at pytest scale (bench.py --chaos is
    the committed record): a seeded probabilistic fault spray across
    every registered point, plus armed kill/hang faults in the nastiest
    windows, resumed until the fleet completes — with every artifact
    byte-identical to the serial chain. Marked slow: tier-1 runs with
    -m 'not slow'; `make test-chaos` runs the bench harness."""
    import random

    monkeypatch.setenv(faultinject.ENV_HANG_S, "12")
    cfg = SurveyConfig(**CFG_KW)
    outdir = str(fleet["root"] / "chaos")
    obs = _fleet_obs(fleet["fils"], outdir)
    faultinject.configure_chaos("3:0.004")
    faultinject.configure("kill:survey.stage_done.sweep:1,"
                          "hang:sweep.chunk_dispatch:2")
    result = None
    rounds = kills = 0
    while rounds < 15:
        rounds += 1
        sched = FleetScheduler(obs, cfg, max_host_workers=2,
                               retries=2, resume=(rounds > 1),
                               stall_s=8.0,
                               jitter_rng=random.Random(rounds))
        try:
            result = sched.run()
        except faultinject.InjectedKill:
            kills += 1
            continue
        if result.ok:
            break
    fired = faultinject.fired_counts()
    assert result is not None and result.ok, (rounds, fired)
    assert fired.get("kill", 0) >= 1 and fired.get("hang", 0) >= 1
    # the final no-chaos resume validates everything and runs NOTHING
    faultinject.reset()
    final = FleetScheduler(obs, cfg, max_host_workers=2,
                           resume=True).run()
    assert final.ok and len(final.ran) == 0
    _assert_matches_reference(fleet, outdir)
