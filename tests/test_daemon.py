"""Streaming survey daemon tests (round 23): multi-tenant admission,
quota-aware shedding, graceful degradation. The overload contract under
test: accepted work is sacred (journal-manifested, survives restart),
unaccepted work sheds lowest-priority/thinnest-quota first past the
queue bound with a trace-reconstructible reason, a starved low-quota
tenant cannot stall a high-priority one, and the guard's hysteresis
keeps a threshold-hovering gauge from flapping admission."""

import io
import json
import os
import socket
import threading
import time

import pytest

from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.resilience import faultinject
from pypulsar_tpu.survey.daemon import (
    SurveyDaemon,
    TenantSpec,
    journal_path,
    parse_tenant_spec,
    read_tenant_status,
)
from pypulsar_tpu.survey.dag import SurveyConfig
from pypulsar_tpu.survey.scheduler import FleetScheduler
from pypulsar_tpu.survey.state import Observation, format_status

from tests.test_survey import _stub, _stub_stages


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.reset()
    yield
    faultinject.reset()


def _raw(path, n=64):
    with open(path, "wb") as f:
        f.write(b"\x5a" * n)
    return str(path)


def _daemon(tmp_path, **kw):
    kw.setdefault("stages", _stub_stages())
    kw.setdefault("quiesce_s", 0.1)
    kw.setdefault("poll_s", 0.05)
    kw.setdefault("idle_exit_s", 0.8)
    kw.setdefault("min_free_mb", 0)
    return SurveyDaemon(str(tmp_path / "out"), SurveyConfig(), **kw)


def _run_to_drain(d, timeout=30):
    t = threading.Thread(target=d.run, daemon=True)
    t.start()
    t.join(timeout=timeout)
    if t.is_alive():  # salvage the wedge so pytest itself can exit
        d.request_drain()
        t.join(timeout=10)
    assert not t.is_alive(), "daemon did not drain"
    return d


# ---------------------------------------------------------------------------
# ResourceGuard hysteresis (satellite: no admission flapping)

def test_guard_hysteresis_counts_transitions(tmp_path, monkeypatch):
    """A pending gauge oscillating AT the threshold produces ONE
    pause/resume episode with the resume margin, not one per
    oscillation — the regression the hysteresis knob exists for."""
    from pypulsar_tpu.resilience import health

    def transitions(margin):
        g = health.ResourceGuard(str(tmp_path), min_free_bytes=0,
                                 max_pending=4, resume_margin=margin)
        flips, prev = 0, None
        with telemetry.session():
            for i in range(20):
                # hover: 5 (over the bound), 4 (at it), 5, 4, ...
                telemetry.gauge("accel.pending_depth",
                                5 if i % 2 == 0 else 4)
                paused = g.admit() is not None
                if prev is not None and paused != prev:
                    flips += 1
                prev = paused
        return flips

    # margin-free guard faithfully amplifies every oscillation
    assert transitions(0.0) >= 10
    # hysteretic guard latches: one pause, no resume until real slack
    # (resume bound 4/1.25 = 3.2; the gauge never gets there)
    assert transitions(0.25) <= 1


def test_guard_hysteresis_resumes_past_margin(tmp_path):
    from pypulsar_tpu.resilience import health

    g = health.ResourceGuard(str(tmp_path), min_free_bytes=0,
                             max_pending=4, resume_margin=0.25)
    with telemetry.session():
        telemetry.gauge("x.pending_depth", 5)
        reason = g.admit()
        assert reason is not None and "backpressure" in reason
        # back AT the bound is not enough while paused ...
        telemetry.gauge("x.pending_depth", 4)
        reason = g.admit()
        assert reason is not None and "resume margin" in reason
        # ... genuine slack past the margin is
        telemetry.gauge("x.pending_depth", 3)
        assert g.admit() is None
        # and the re-pause threshold is back to the base bound
        telemetry.gauge("x.pending_depth", 5)
        assert g.admit() is not None


# ---------------------------------------------------------------------------
# tenant grammar + token buckets

def test_parse_tenant_spec_grammar():
    t = parse_tenant_spec("vlbi:3:1.5:4")
    assert (t.name, t.priority, t.rate, t.burst) == ("vlbi", 3, 1.5, 4.0)
    t = parse_tenant_spec("archive")
    assert t.name == "archive" and t.priority == 0
    t = parse_tenant_spec("fast::2")  # skipped field keeps its default
    assert t.priority == 0 and t.rate == 2.0
    with pytest.raises(ValueError):
        parse_tenant_spec(":1")
    with pytest.raises(ValueError):
        parse_tenant_spec("a:b")
    with pytest.raises(ValueError):
        parse_tenant_spec("a:1:2:3:4")


def test_token_bucket_refills_at_rate():
    t = TenantSpec("x", rate=1000.0, burst=2.0)
    assert t.try_take() and t.try_take()
    assert not t.try_take()  # burst exhausted
    time.sleep(0.01)         # 1000/s refills ~10 tokens -> capped at 2
    assert t.try_take()
    unmetered = TenantSpec("y", rate=0.0, burst=1.0)
    assert all(unmetered.try_take() for _ in range(50))


# ---------------------------------------------------------------------------
# the daemon lifecycle: watch lane, socket lane, books, drain

def test_daemon_watch_and_socket_lanes(tmp_path):
    watch = tmp_path / "in"
    watch.mkdir()
    _raw(watch / "w0.raw")
    d = _daemon(tmp_path, watch=[(str(watch), "teamA")], port=0,
                tenants=[TenantSpec("teamA", priority=1)])
    t = threading.Thread(target=d.run, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 10
        while d.stats()["accepted"] < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        # socket lane: synchronous verdict
        p = _raw(tmp_path / "sock0.raw")
        with socket.create_connection(("127.0.0.1", d.port),
                                      timeout=5) as s:
            s.sendall(f"teamB {p}\n".encode())
            verdict = s.makefile().readline().split()
        assert verdict[0] in ("accepted", "pending"), verdict
        # malformed line gets an error verdict, not a dead handler
        with socket.create_connection(("127.0.0.1", d.port),
                                      timeout=5) as s:
            s.sendall(b"just-one-field\n")
            assert s.makefile().readline().startswith("error")
    finally:
        t.join(timeout=30)
    assert not t.is_alive()
    st = d.stats()
    assert st["submitted"] == 2 and st["accepted"] == 2
    assert st["completed"] == 2 and st["shed"] == 0
    assert d.result is not None and d.result.ok
    # artifacts from the stub chain exist for both lanes
    for stem in ("w0", "sock0"):
        assert os.path.exists(str(tmp_path / "out" / f"{stem}.host1.out"))
    # the tenants.json mirror reflects the drained books
    snap = read_tenant_status(str(tmp_path / "out"))
    assert snap["tenants"]["teamA"]["completed"] == 1
    assert snap["tenants"]["teamB"]["completed"] == 1
    assert snap["draining"] is True


def test_daemon_dedupes_resubmitted_paths(tmp_path):
    p = _raw(tmp_path / "a.raw")
    d = _daemon(tmp_path, initial=[("t", p), ("t", p)])
    _run_to_drain(d)
    st = d.stats()
    assert st["submitted"] == 1 and st["completed"] == 1


# ---------------------------------------------------------------------------
# overload shedding: priority- and quota-ordered, never accepted work

def test_shed_lowest_priority_thinnest_quota_first(tmp_path, monkeypatch):
    """Past the queue bound the daemon sheds the lowest-priority
    pending arrival (thinnest token bucket within a priority) and the
    decision trail reconstructs from the trace events alone."""
    trace = str(tmp_path / "trace.jsonl")
    d = _daemon(tmp_path, queue_bound=2,
                tenants=[TenantSpec("gold", priority=5, rate=0.0),
                         TenantSpec("lead", priority=0, rate=0.0)])
    # hold admission shut so arrivals pile up pending: the node-level
    # guard refusing is exactly the sustained-overload regime
    monkeypatch.setattr(d._guard, "admit", lambda: "backpressure: test")
    with telemetry.session(trace):
        for i in range(2):
            v, _ = d._arrive("gold", _raw(tmp_path / f"g{i}.raw"),
                             lane="test")
            assert v == "pending"
        # the bound is full of gold; lead arrivals shed THEMSELVES
        v, why = d._arrive("lead", _raw(tmp_path / "l0.raw"), lane="test")
        assert v == "shed" and "lowest priority 0" in why
        # another gold arrival sheds the remaining lead? none left —
        # gold itself is now the only tenant, newest sheds first
        v, _ = d._arrive("gold", _raw(tmp_path / "g2.raw"), lane="test")
        assert v == "shed"
    st = d.stats()
    assert st["submitted"] == 4 and st["shed"] == 2
    assert st["accepted"] == 0  # nothing admitted through a shut guard
    # shed trail from the trace alone: tenant/reason/queue_depth attrs
    evs = []
    with open(trace) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == "event" and rec["name"] == "daemon.shed":
                evs.append(rec["attrs"])
    assert len(evs) == 2
    assert {e["tenant"] for e in evs} == {"lead", "gold"}
    assert all(e["queue_depth"] == 3 and "queue full" in e["reason"]
               for e in evs)
    # and the journal carries the same verdicts for the restart replay
    recs = [json.loads(ln)
            for ln in open(journal_path(str(tmp_path / "out")))]
    assert sum(1 for r in recs if r["type"] == "shed") == 2


def test_starved_low_quota_tenant_does_not_stall_high_priority(tmp_path):
    """A pending over-quota arrival ahead of the queue must not block
    admission for tenants that still have tokens."""
    files = [("greedy", _raw(tmp_path / "g0.raw")),
             ("greedy", _raw(tmp_path / "g1.raw")),  # over quota: waits
             ("steady", _raw(tmp_path / "s0.raw")),
             ("steady", _raw(tmp_path / "s1.raw"))]
    d = _daemon(tmp_path, idle_exit_s=0.0, initial=files,
                tenants=[TenantSpec("greedy", priority=5, rate=1e-6,
                                    burst=1.0),
                         TenantSpec("steady", priority=0, rate=0.0)])
    t = threading.Thread(target=d.run, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 15
        while d.stats()["completed"] < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        # g1 waits on a near-never refill: the drain sheds it loudly
        d.request_drain()
        t.join(timeout=30)
    assert not t.is_alive()
    st = d.stats()
    # steady's work completed despite greedy's exhausted bucket parked
    # at the head of the (higher-priority) queue; greedy's second
    # arrival drains as unaccepted shed at shutdown, never silently
    assert st["completed"] >= 3, st
    assert st["shed"] == st["submitted"] - st["accepted"]
    b = d.tenant_snapshot()["tenants"]
    assert b["steady"]["completed"] == 2
    assert b["greedy"]["completed"] == 1
    assert b["greedy"]["shed"] == 1


# ---------------------------------------------------------------------------
# injected faults at the ingest edges (satellite: chaos arming points)

def test_arrival_fault_degrades_to_rescan(tmp_path):
    """An injected fault at daemon.arrival means the arrival was never
    seen: the watch lane re-sees the file next scan and the books count
    it exactly once."""
    watch = tmp_path / "in"
    watch.mkdir()
    _raw(watch / "w0.raw")
    faultinject.configure("io:daemon.arrival:1")
    d = _daemon(tmp_path, watch=[(str(watch), "t")])
    _run_to_drain(d)
    assert faultinject.fired_counts().get("io", 0) == 1
    st = d.stats()
    assert st["submitted"] == 1 and st["completed"] == 1


def test_admit_fault_repends_and_retries(tmp_path):
    """An injected fault at daemon.admit re-pends the arrival (counted
    once) and the next tick admits it."""
    faultinject.configure("io:daemon.admit:1")
    d = _daemon(tmp_path, initial=[("t", _raw(tmp_path / "a.raw"))])
    _run_to_drain(d)
    assert faultinject.fired_counts().get("io", 0) == 1
    st = d.stats()
    assert st["submitted"] == 1 and st["accepted"] == 1
    assert st["completed"] == 1


# ---------------------------------------------------------------------------
# accepted work is sacred: vanish handling + restart replay

def test_vanished_input_after_admit_data_quarantines(tmp_path):
    """An accepted observation whose source file disappears between
    admission and stage start is LOUDLY data-quarantined — not a crash,
    not a retry loop (satellite regression)."""
    gate = threading.Event()
    held = threading.Event()

    def slow_run(obs, cfg):
        held.set()
        assert gate.wait(10)
        with open(f"{obs.outbase}.dev1.out", "w") as f:
            f.write("ok\n")
        return 0

    from pypulsar_tpu.survey.dag import StageSpec

    stages = _stub_stages()
    stages[0] = StageSpec("dev1", "stub", True, (),
                          lambda o, c: [],
                          lambda o, c: [f"{o.outbase}.dev1.out"],
                          run=slow_run)
    outdir = str(tmp_path / "out")
    os.makedirs(outdir)
    sched = FleetScheduler([], SurveyConfig(), stages=stages,
                           service=True, devices=1, retries=2)
    t = threading.Thread(target=sched.run, daemon=True)
    t.start()
    try:
        assert sched.wait_ready(10)
        a = _raw(tmp_path / "a.raw")
        b = _raw(tmp_path / "b.raw")
        sched.submit(Observation("a", a, os.path.join(outdir, "a")))
        assert held.wait(10)  # a's device stage holds the one lease
        sched.submit(Observation("b", b, os.path.join(outdir, "b")))
        os.remove(b)          # vanishes between admit and stage start
        gate.set()
        sched.request_drain()
    finally:
        gate.set()
        t.join(timeout=30)
    assert not t.is_alive()
    # run() returned in the daemon thread; the manifests carry the
    # verdicts: b must be DATA-quarantined with a loud vanish reason
    import glob

    from pypulsar_tpu.survey.state import MANIFEST_SUFFIX, status_rows
    rows = {r["obs"]: r for r in status_rows(
        sorted(glob.glob(os.path.join(outdir, "*" + MANIFEST_SUFFIX))))}
    qb = rows["b"]["quarantine"]
    assert qb is not None and qb.get("reason") == "data"
    assert "vanished" in qb["error"]
    assert rows["b"].get("retries", {}) == {}  # no retry loop
    # the healthy observation completed normally
    assert rows["a"]["quarantine"] is None
    assert len(rows["a"]["done"]) == 2


def test_restart_replays_journal_without_rerunning_terminal(tmp_path):
    """A second daemon over the same outdir folds journaled terminal
    verdicts straight into the books and resubmits only open accepts."""
    p0 = _raw(tmp_path / "a.raw")
    p1 = _raw(tmp_path / "b.raw")
    d1 = _daemon(tmp_path, initial=[("t", p0), ("t", p1)])
    _run_to_drain(d1)
    assert d1.stats()["completed"] == 2
    # restart: nothing to resubmit, books carry the history
    d2 = _daemon(tmp_path, idle_exit_s=0.4)
    assert d2.recover() == 0
    assert d2.stats()["completed"] == 2
    assert d2.stats()["accepted"] == 2
    # a journal with an OPEN accept (no terminal record) resubmits with
    # resume=True: the already-journaled stages are skipped, not re-run
    p2 = _raw(tmp_path / "c.raw")
    with open(journal_path(str(tmp_path / "out")), "a") as f:
        f.write(json.dumps(
            {"type": "accept", "tenant": "t", "obs": "c", "infile": p2,
             "outbase": str(tmp_path / "out" / "c"),
             "t_unix": time.time()}) + "\n")
        # a torn tail must be tolerated, not crash the replay
        f.write('{"type": "accept", "tenant": "t", "obs"')
    d3 = _daemon(tmp_path, idle_exit_s=0.8)
    _run_to_drain(d3)
    st = d3.stats()
    assert st["completed"] == 3 and st["accepted_open"] == 0
    assert d3.result is not None and d3.result.ok
    # zero re-runs of a+b's validated stages: only c's two stages ran
    assert len(d3.result.ran) == 2, d3.result.ran


# ---------------------------------------------------------------------------
# status surfaces (satellite: tenants block + tlmsum roll-up)

def test_format_status_renders_tenants_block():
    snap = {"queue_depth": 1, "queue_bound": 8, "accepted_open": 2,
            "draining": False,
            "tenants": {"vlbi": {"priority": 3, "rate": 1.5, "burst": 4,
                                 "tokens": 2.5, "submitted": 7,
                                 "accepted": 5, "shed": 1,
                                 "quarantined": 1, "completed": 3},
                        "archive": {"priority": 0, "rate": 0,
                                    "burst": 8, "tokens": 8.0,
                                    "submitted": 2, "accepted": 2,
                                    "shed": 0, "quarantined": 0,
                                    "completed": 2}}}
    text = format_status([], tenants=snap)
    assert "# tenants (accept queue 1/8, 2 accepted in flight):" in text
    assert "vlbi" in text and "prio 3" in text
    assert "7 submitted / 5 accepted / 1 shed" in text
    assert "unmetered" in text          # archive has rate 0
    snap["draining"] = True
    assert "DRAINING" in format_status([], tenants=snap)
    # absent block (no daemon ever ran): no tenants section at all
    assert "tenants" not in format_status([], tenants=None)


def test_tlmsum_per_tenant_rollup_renders():
    from pypulsar_tpu.obs.summarize import (
        TraceSummary,
        combine_summaries,
        render,
    )

    s = TraceSummary()
    s.feed({"type": "event", "name": "daemon.arrival", "t": 0.0,
            "attrs": {"tenant": "vlbi", "path": "x.fil"}})
    s.feed({"type": "event", "name": "daemon.accept", "t": 0.1,
            "attrs": {"tenant": "vlbi", "obs": "x"}})
    s.feed({"type": "event", "name": "daemon.terminal", "t": 0.2,
            "attrs": {"tenant": "vlbi", "obs": "x", "state": "done"}})
    s.feed({"type": "event", "name": "daemon.shed", "t": 0.3,
            "attrs": {"tenant": "archive", "reason": "queue full",
                      "queue_depth": 9}})
    s.feed({"type": "event", "name": "daemon.terminal", "t": 0.4,
            "attrs": {"tenant": "archive", "obs": "y",
                      "state": "quarantined"}})
    s.finish()
    assert s.tenant_stats["vlbi"] == {"arrivals": 1, "accepted": 1,
                                      "completed": 1}
    assert s.tenant_stats["archive"] == {"shed": 1, "quarantined": 1}
    combined = combine_summaries([s, s])
    assert combined.tenant_stats["vlbi"]["accepted"] == 2
    buf = io.StringIO()
    render(combined, buf)
    out = buf.getvalue()
    assert "# per-tenant (daemon admission):" in out
    assert "vlbi" in out and "accepted     2" in out


def test_statusd_snapshot_carries_tenants(tmp_path):
    from pypulsar_tpu.obs.statusd import fleet_snapshot

    d = _daemon(tmp_path, initial=[("t", _raw(tmp_path / "a.raw"))])
    _run_to_drain(d)
    snap = fleet_snapshot(str(tmp_path / "out"))
    assert snap["tenants"] is not None
    assert snap["tenants"]["tenants"]["t"]["completed"] == 1
    # and the --status renderer consumes it end to end
    text = format_status(snap["rows"], tenants=snap["tenants"])
    assert "# tenants" in text


# the acceptance-scale soak twin (the committed record is SOAK_r01.json;
# marked slow per the chaos-harness convention so tier-1 stays bounded)

@pytest.mark.slow
def test_daemon_soak_harness():
    """bench.py --daemon-soak --quick in-process: the full overload
    storm (bulk flood + chaos spray + ingest quarantine), the SIGKILL'd
    and restarted --daemon subprocess, the SIGTERM drain, and byte
    parity vs the batch reference — every gate asserted by the harness
    itself."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    args = bench.parse_args(["--daemon-soak", "--quick"])
    record = bench.run_daemon_soak(args)
    assert record["value"] == 1.0
    assert record["soak_kill9_reruns"] == 0
    assert record["soak_sigterm_rc"] == 0
    assert record["soak_books"]["submitted"] == (
        record["soak_books"]["accepted"] + record["soak_books"]["shed"])
