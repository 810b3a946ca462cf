"""psrrace dynamic half: the lockdep wrappers (resilience/locks.py) and
the watchdog's defer-interrupt-while-locked contract.

Covers the round-19 acceptance surface: cycle detection across 3 locks,
reentrant-RLock no-false-positive, strict-vs-warn modes, hold-time gauge
emission into the telemetry session, the cross-thread held-set the
deferral rides on, the Condition-over-tracked-lock integration the
scheduler uses, the async-interrupt deferral regression (a stage parked
INSIDE a held lock is not shot; delivery lands after release), and the
slow-marked long-seed twin of ``bench.py --race``.
"""

import os
import threading
import time

import pytest

from pypulsar_tpu.obs import telemetry
from pypulsar_tpu.resilience import health, locks


@pytest.fixture(autouse=True)
def _clean_lockdep():
    locks.reset()
    yield
    locks.configure_race(None)
    locks.reset()


def test_cycle_detected_across_three_locks(monkeypatch):
    """A -> B -> C held orderings, then C -> A closes the 3-cycle: the
    violation names the full cycle, and under warn mode the acquire
    still succeeds (nothing strands)."""
    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "warn")
    a = locks.TrackedLock("t3.A")
    b = locks.TrackedLock("t3.B")
    c = locks.TrackedLock("t3.C")
    with a:
        with b:
            pass
    with b:
        with c:
            pass
    with c:
        with a:  # warn mode: recorded, not raised
            pass
    (v,) = locks.violations()
    assert v["acquiring"] == "t3.A" and v["held"] == "t3.C"
    assert v["cycle"] == ["t3.A", "t3.B", "t3.C", "t3.A"]
    # all three locks released cleanly despite the violation
    for lk in (a, b, c):
        assert lk.acquire(False)
        lk.release()


def test_strict_mode_raises_and_never_holds(monkeypatch):
    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "strict")
    a = locks.TrackedLock("ts.A")
    b = locks.TrackedLock("ts.B")
    with a:
        with b:
            pass
    with b:
        with pytest.raises(locks.LockOrderError) as ei:
            a.acquire()
    assert "ts.A" in str(ei.value) and "ts.B" in str(ei.value)
    # the offending lock was never left held
    assert a.acquire(False)
    a.release()
    assert len(locks.violations()) == 1


def test_rlock_reentrancy_no_false_positive(monkeypatch):
    """A reentrant re-acquire must not self-edge (no violation), and
    the held entry survives until the LAST release."""
    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "strict")
    r = locks.TrackedRLock("tr.R")
    tid = threading.get_ident()
    with r:
        with r:
            assert locks.thread_holds_lock(tid)
        assert locks.thread_holds_lock(tid)
    assert not locks.thread_holds_lock(tid)
    assert locks.violations() == []


def test_off_mode_disables_tracking(monkeypatch):
    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "off")
    locks.reset()  # re-resolve the cached mode under the new env
    a = locks.TrackedLock("toff.A")
    with a:
        assert not locks.thread_holds_lock(threading.get_ident())
    assert locks.snapshot() == {}


def test_hold_time_gauge_and_contention_counter(monkeypatch):
    """A non-quiet lock emits lock.<name>.hold_ms on release and a
    contended counter + wait gauge when a blocking acquire had to
    wait — the tlmsum 'lock health' roll-up's inputs."""
    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "warn")
    lk = locks.TrackedLock("tg.L")
    got_it = threading.Event()

    def worker():
        with lk:
            got_it.set()
            time.sleep(0.05)

    with telemetry.session() as tlm:
        with lk:
            time.sleep(0.02)
        t = threading.Thread(target=worker)
        t.start()
        assert got_it.wait(5)  # the worker definitely holds it now
        with lk:  # contended
            pass
        t.join(timeout=5)
        gauges = tlm.gauge_values()
        counters = tlm.counter_totals()
    assert gauges["lock.tg.L.hold_ms"]["max"] >= 20.0 * 0.5
    assert counters.get("lock.tg.L.contended", 0) >= 1
    assert gauges["lock.tg.L.wait_ms"]["max"] > 0
    snap = locks.snapshot()["tg.L"]
    assert snap["acquires"] >= 3 and snap["contentions"] >= 1


def test_quiet_lock_tracks_but_never_emits(monkeypatch):
    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "warn")
    lk = locks.TrackedLock("tq.L", quiet=True)
    with telemetry.session() as tlm:
        with lk:
            pass
        assert not any(k.startswith("lock.tq.L")
                       for k in tlm.gauge_values())
    assert locks.snapshot()["tq.L"]["acquires"] == 1


def test_held_set_is_cross_thread_queryable(monkeypatch):
    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "warn")
    lk = locks.TrackedLock("tc.L")
    holding = threading.Event()
    release = threading.Event()
    tids = []

    def hold():
        tids.append(threading.get_ident())
        with lk:
            holding.set()
            release.wait(5)

    t = threading.Thread(target=hold)
    t.start()
    assert holding.wait(5)
    assert locks.thread_holds_lock(tids[0])
    assert not locks.thread_holds_lock(threading.get_ident())
    release.set()
    t.join(timeout=5)
    assert not locks.thread_holds_lock(tids[0])


def test_condition_over_tracked_lock(monkeypatch):
    """The scheduler's shape: one TrackedLock behind both the bare lock
    and the Condition. wait() must drop the held entry while parked
    (the watchdog may interrupt a waiter) and re-add it on wake."""
    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "warn")
    mu = locks.TrackedLock("tcv.L")
    cv = locks.TrackedCondition("tcv.L", lock=mu)
    seen = {}

    def waiter():
        tid = threading.get_ident()
        with cv:
            seen["held_before"] = locks.thread_holds_lock(tid)
            cv.wait(1.0)
            seen["held_after"] = locks.thread_holds_lock(tid)
        seen["held_outside"] = locks.thread_holds_lock(tid)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.15)
    with cv:
        cv.notify_all()
    t.join(timeout=5)
    assert seen == {"held_before": True, "held_after": True,
                    "held_outside": False}
    assert locks.violations() == []


def test_interrupt_thread_defers_while_locked(monkeypatch):
    """The raw channel: interrupt_thread returns DEFERRED (truthy, not
    False) while the target holds a tracked lock, then delivers after
    release."""
    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "warn")
    lk = locks.TrackedLock("ti.L")
    state = {"interrupted": False}
    holding = threading.Event()
    release = threading.Event()
    tids = []

    def victim():
        tids.append(threading.get_ident())
        try:
            with lk:
                holding.set()
                deadline = time.monotonic() + 5
                while not release.is_set() \
                        and time.monotonic() < deadline:
                    time.sleep(0.01)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                time.sleep(0.01)
        except health.StageTimeout:
            state["interrupted"] = True

    t = threading.Thread(target=victim)
    t.start()
    assert holding.wait(5)
    res = health.interrupt_thread(tids[0], health.StageStalled)
    assert res is health.DEFERRED and res  # truthy by design
    release.set()
    deadline = time.monotonic() + 5
    delivered = False
    while time.monotonic() < deadline and not delivered:
        r = health.interrupt_thread(tids[0], health.StageStalled)
        if r is not health.DEFERRED:
            delivered = bool(r)
            break
        time.sleep(0.01)
    t.join(timeout=10)
    assert delivered and state["interrupted"]
    assert lk.acquire(False), "the deferred interrupt stranded the lock"
    lk.release()


def test_watchdog_defers_interrupt_inside_held_lock(monkeypatch):
    """End-to-end regression (the round-19 satellite): a stage parked
    INSIDE a held tracked lock outruns its deadline — the watchdog must
    emit survey.interrupt_deferred (not shoot), then deliver after the
    stage releases; the verdict lands as an ordinary quarantine and the
    lock is NOT stranded."""
    from pypulsar_tpu.survey.dag import StageSpec, SurveyConfig
    from pypulsar_tpu.survey.scheduler import FleetScheduler
    from pypulsar_tpu.survey.state import Observation

    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "warn")
    stage_lock = locks.TrackedLock("twd.stage")

    def run(o, c):
        with stage_lock:
            # well past the 0.2 s deadline, in interruptible slices —
            # every tick the watchdog fires it must choose deferral
            t_end = time.monotonic() + 0.8
            while time.monotonic() < t_end:
                time.sleep(0.01)
        # unlocked runway for the retried delivery to land on
        t_end = time.monotonic() + 10.0
        while time.monotonic() < t_end:
            time.sleep(0.01)
        return 0

    def _tmp_obs(tmp_path):
        raw = os.path.join(str(tmp_path), "o0.raw")
        with open(raw, "wb") as f:
            f.write(b"x" * 64)
        return [Observation("o0", raw, os.path.join(str(tmp_path), "o0"))]

    import tempfile

    with tempfile.TemporaryDirectory() as td:
        obs = _tmp_obs(td)
        spec = StageSpec("dev1", "stub", True, (), lambda o, c: [],
                         lambda o, c: [], run=run)
        with telemetry.session() as tlm:
            sched = FleetScheduler(obs, SurveyConfig(), stages=[spec],
                                   retries=0, stage_deadline=0.2)
            res = sched.run()
        assert "o0" in res.quarantined, res
        assert res.timeouts == 1
        deferred = tlm.event_counts.get("survey.interrupt_deferred", 0)
        assert deferred >= 1, (
            f"no deferral recorded: {tlm.event_counts}")
    assert stage_lock.acquire(False), "watchdog stranded the stage lock"
    stage_lock.release()


def test_race_pause_injection_is_seeded_and_counted(monkeypatch):
    monkeypatch.setenv("PYPULSAR_TPU_LOCKDEP", "warn")
    locks.configure_race(7, pause_us=10.0)
    lk = locks.TrackedLock("trp.L")
    for _ in range(5):
        with lk:
            pass
    n = locks.race_pauses()
    assert n >= 10  # acquire + release per pass
    locks.configure_race(None)
    with lk:
        pass
    assert locks.race_pauses() == n  # disarmed: no further pauses


@pytest.mark.slow
def test_race_harness_long_seed_twin():
    """The slow twin of `make test-race`'s quick bench leg: more seeds
    through the full bench.py --race harness (in-process)."""
    import bench

    args = bench.parse_args(["--race", "--quick", "--race-seeds", "3"])
    rec = bench.run_race(args)
    assert rec["value"] == 1.0
    assert all(p["order_violations"] == 0 for p in rec["race_per_seed"])
    assert sum(p["watchdog_interrupts"]
               for p in rec["race_per_seed"]) >= 3
