"""The trace reduction on a hand-made event list, and on a cut of a recorded
chip trace where one is kept beside this file (``trace_cut.json``)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import trace_reduce  # noqa: E402

P, M, O = "/device:TPU:0", trace_reduce.MODULE_LINE, trace_reduce.OP_LINE
MS = 1_000_000

HAND_MADE = [
    (P, M, "jit__search_kernel(111)", 0 * MS, 10 * MS),
    (P, O, "fusion.1", 0 * MS, 4 * MS),
    (P, O, "top_k.2", 3 * MS, 7 * MS),  # overlaps fusion.1 by 1 ms: union 0..10
    (P, M, "jit__lambda_(222)", 20 * MS, 5 * MS),
    (P, O, "fusion.9", 20 * MS, 5 * MS),
    (P, M, "jit__search_kernel(111)", 40 * MS, 10 * MS),
    (P, O, "fusion.1", 40 * MS, 4 * MS),
    (P, O, "top_k.2", 44 * MS, 6 * MS),
    ("/host:CPU", "python", "ignored", 0, 100 * MS),
]


def test_union_merges_overlaps_and_touching_intervals():
    assert trace_reduce.union_ns([(5, 7), (0, 3), (2, 4), (7, 9)]) == [(0, 4), (5, 9)]


def test_program_name_strips_the_fingerprint():
    assert trace_reduce.program_name("jit__search_kernel(123456)") == "jit__search_kernel"
    assert trace_reduce.program_name("jit__lambda_") == "jit__lambda_"


def test_hand_made_busy_programs_gaps_and_idle_share():
    r = trace_reduce.reduce(HAND_MADE, window_s=0.1)
    assert r["planes"] == 1
    assert r["busy_s"] == pytest.approx(0.025)  # 10 + 5 + 10 ms of a 100 ms window
    assert 1.0 - r["busy_s"] / r["window_s"] == pytest.approx(0.75)
    assert r["programs"]["jit__search_kernel"] == {"seconds": pytest.approx(0.020), "calls": 2}
    assert r["programs"]["jit__lambda_"] == {"seconds": pytest.approx(0.005), "calls": 1}
    assert trace_reduce.program_seconds(r, ["^jit__search_kernel$"]) == (pytest.approx(0.020), 2)
    assert trace_reduce.program_seconds(r, ["^jit__other"]) == (0.0, 0.0)
    assert r["device_ops"][0] == ["top_k.2", pytest.approx(0.013)]
    gaps = dict((name, s) for name, s in r["idle_gaps"])
    assert gaps["after jit__search_kernel, before jit__lambda_"] == pytest.approx(0.010)
    assert gaps["after jit__lambda_, before jit__search_kernel"] == pytest.approx(0.015)


def test_no_device_plane_reads_nothing():
    r = trace_reduce.reduce([("/host:CPU", "python", "x", 0, 5)], window_s=1.0)
    assert r["busy_s"] is None and r["programs"] == {}


def test_two_planes_average():
    other = [("/device:TPU:1", line, name, start, dur) for _, line, name, start, dur in HAND_MADE[:3]]
    r = trace_reduce.reduce(HAND_MADE + other, window_s=0.1)
    assert r["planes"] == 2
    assert r["busy_s"] == pytest.approx((0.025 + 0.010) / 2)


def test_recorded_chip_trace_cut():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace_cut.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace kept")
    events = [tuple(e) for e in json.load(open(path))]
    r = trace_reduce.reduce(events, window_s=0.25)
    assert r["planes"] == 1 and 0.0 < r["busy_s"] <= 0.25 * 1.05
    seconds, calls = trace_reduce.program_seconds(r, ["^jit__search_kernel$"])
    assert calls >= 1 and 0.0 < seconds <= r["busy_s"] * 1.01


def test_per_layer_readers_on_the_recorded_cut():
    """Every per-layer reader of the dense cell finds something to read in the
    recorded cut, and returns nothing (not 0) where there is no trace."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace_cut.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace kept")
    import run

    spec = run.resolve("serve-dense-2m", False)
    events = [tuple(e) for e in json.load(open(path))]
    records = [{"done": 0.01 * i, "status": 200, "query": "a b c d", "due": 0.0, "sent": 0.001, "phase": "window"}
               for i in range(12)]
    ctx = {"spec": spec, "stats": {"latency_ms": [1.0, 2.0], "late_ms": [0.1], "good": 2},
           "gen": {"records": records}, "setup_s": 1.0, "seconds": 40.0,
           "counters_before": {"svc_ticks": 1.0, "svc_rows": 2.0}, "counters_after": {"svc_ticks": 3.0, "svc_rows": 9.0},
           "peaks": run.load_json("peaks.json")["TPU v5 lite"], "percentile": run.percentile,
           "search_time": run.search_time, "trace": trace_reduce.reduce(events, 0.12),
           "trace_span": {"t0": 0.0, "t1": 0.12}, "work": run.load_module("work", spec["config"]["work"]),
           "n_rows": 2**21}
    got = run.read_metrics(spec["per_layer"], ctx)
    assert set(got) == {m["name"] for m in spec["per_layer"]}
    assert 0.0 < got["search_roofline"]["value"] <= 100.0 and 0.0 < got["retrieve_mfu"]["value"] < 100.0
    assert got["encsvc_rows_per_tick"]["value"] == pytest.approx(3.5)
    ctx["trace"] = None
    assert set(run.read_metrics(spec["per_layer"], ctx)) == {
        "retrieve_p95_ms", "gen_late_p95_ms", "encsvc_rows_per_tick"}
