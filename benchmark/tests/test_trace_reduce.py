"""The reducer against hand-made events and a small recorded TPU trace."""

import json
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "fixtures")


def test_busy_union_merges_overlaps_and_clips():
    events = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["a", 32, 1],
              ["d", 100, 50]]
    busy, merged = trace_reduce.busy_union(events, lo=2, hi=120)
    assert merged == [[2, 15], [30, 35], [100, 120]]
    assert busy == 13 + 5 + 20
    assert trace_reduce.idle_gaps(merged, 2, 120) == [(15, 30), (35, 100)]
    totals = trace_reduce.op_totals(events, lo=2, hi=120)
    assert totals == {"a": 8 + 1, "b": 10, "c": 5, "d": 20}


def test_reduce_attributes_gaps_to_steps_and_stages():
    trace = {
        "devices": {"/device:TPU:0": [["k", 1e9, 1e9], ["k", 5e9, 2e9]]},
        "annotations": [["bench.step", 0.0, 4e9, 0],
                        ["bench.step", 4e9, 4e9, 1]],
    }
    # step 1's telemetry: mask for its first 0.9 s, sweep after that
    stages = [(1, "mask", 0.0, 0.9), (1, "sweep", 0.9, 3.0)]
    out = trace_reduce.reduce_trace(trace, chips=1, stage_spans=stages)
    assert out["window_s"] == pytest.approx(8.0)
    assert out["busy_s"] == pytest.approx(3.0)
    assert out["breakdown"]["device_ops"] == [["k", pytest.approx(3.0)]]
    gaps = dict(out["breakdown"]["idle_gaps"])
    # [0,1) and [2,4) lie in step 0. The gap [2,5) is split at the step
    # boundary: [4,4.9) is step 1's mask, [4.9,5) and [7,7.9) its sweep,
    # and [7.9,8) falls after the last stage span
    assert gaps["step 0"] == pytest.approx(3.0)
    assert gaps["step 1 mask"] == pytest.approx(0.9)
    assert gaps["step 1 sweep"] == pytest.approx(1.0)
    assert gaps["step 1"] == pytest.approx(0.1)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"devices": {}, "annotations": []})


def test_recorded_v5e_trace():
    with open(os.path.join(DATA, "trace_apertif_v5e.json")) as f:
        trace = json.load(f)
    events = trace["devices"]["/device:TPU:0"]
    assert len(events) == 600
    # operations nest (a while and its body both have events): the plain
    # sum of durations overcounts, the union does not
    assert sum(e[2] for e in events) == pytest.approx(401506238.0)
    busy, merged = trace_reduce.busy_union(events)
    assert busy == pytest.approx(359885415.0)
    assert len(merged) == 25
    out = trace_reduce.reduce_trace(trace)
    assert out["window_s"] == pytest.approx(7.040138933)
    assert out["busy_s"] == pytest.approx(0.359885415)
    idle_pct = 100 * (1 - out["busy_s"] / out["window_s"])
    assert idle_pct == pytest.approx(94.888, abs=1e-3)
    top = out["breakdown"]["device_ops"][0]
    assert top[0] == "%while.6" and top[1] == pytest.approx(0.285045308)
    assert out["breakdown"]["idle_gaps"][0][0] == "step 0"


def test_short_name():
    assert trace_reduce.short_name(
        "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") \
        == "%fusion.3"
    assert trace_reduce.short_name("jit__sweep_chunk_jit(276520556)") \
        == "jit__sweep_chunk_jit"


def test_read_telemetry(tmp_path):
    path = tmp_path / "fleet.jsonl"
    recs = [
        {"type": "span", "name": "survey.stage.mask", "t": 0.5, "dur": 2.0},
        {"type": "span", "name": "survey.stage.sweep", "t": 2.5, "dur": 3.0},
        {"type": "counters", "partial": True,
         "counters": {"compile.cache_miss": 9}},
        {"type": "counters", "counters": {"compile.cache_miss": 2,
                                          "h2d.bytes": 100},
         "events": {"survey.stage_retry": 1}},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\ngarbage\n")
    out = trace_reduce.read_telemetry([str(path), str(tmp_path / "none")])
    assert out["counters"] == {"compile.cache_miss": 2, "h2d.bytes": 100}
    assert out["events"] == {"survey.stage_retry": 1}
    assert out["spans"]["survey.stage.mask"] == [2.0, 1]
    assert out["stage_spans"] == [(0, "mask", 0.5, 2.0),
                                  (0, "sweep", 2.5, 3.0)]
