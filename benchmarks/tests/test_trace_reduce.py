"""The trace reduction on a hand-made event list, and, with every per-layer
reader, on a cut of a recorded chip run where one is kept beside this file
(``trace_cut.json``, made by ``record_cut.py``)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import trace_reduce  # noqa: E402
from record_cut import events_of  # noqa: E402

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


CUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace_cut.json")


@pytest.fixture(scope="module")
def cut():
    """A quarter second of a traced chip run, as ``record_cut.py`` keeps it: the
    profiler's events, the program's spans, the generator's records and the
    counters, with what the readers returned over the whole traced span."""
    if not os.path.exists(CUT):
        pytest.skip("no recorded trace kept")
    with open(CUT) as f:
        return json.load(f)


def test_recorded_chip_trace_cut(cut):
    events = events_of(cut)
    r = trace_reduce.reduce(events, window_s=cut["seconds"])
    assert r["planes"] == 1 and 0.0 < r["busy_s"] <= cut["seconds"] * 1.05
    for program in ("^jit__search_kernel$", "^jit_encoder_forward$"):
        seconds, calls = trace_reduce.program_seconds(r, [program])
        assert calls >= 1 and 0.0 < seconds <= r["busy_s"] * 1.01
    annotations = {e[2] for e in events if e[0] == "/host:CPU"}
    assert {"pw.commit", "pw.search", "pw.search.device_wait", "pw.encode.device_wait"} <= annotations
    assert {s["kind"] for s in cut["spans"]} >= {"rest", "admit", "queue", "commit", "embed_wait", "search", "reply"}


def test_per_layer_readers_on_the_recorded_cut(cut):
    """Every per-layer reader of the dense cell finds something to read in the
    recorded cut, close to what it read over the whole traced span where a
    quarter second can say, and returns nothing (not 0) where there is no trace."""
    import run

    spec = run.resolve("serve-dense-2m", False)
    cfg = spec["config"]
    system_kind = run.load_module("systems", cfg.get("system", "vector_store"))
    ctx = {"spec": spec, "stats": {"latency_ms": [1.0, 2.0], "late_ms": [0.1], "good": 2},
           "gen": {"records": cut["records"], "start_at": 0.0}, "setup_s": 1.0, "seconds": 40.0,
           "counters_before": cut["counters_before"], "counters_after": cut["counters_after"],
           "peaks": run.load_json("peaks.json")["TPU v5 lite"], "percentile": run.percentile,
           "trace": trace_reduce.reduce(events_of(cut), cut["seconds"]),
           "trace_span": {"t0": 0.0, "t1": cut["seconds"]}, "spans": cut["spans"],
           "work": run.load_module("work", cfg["work"]), **system_kind.metric_context(cfg)}
    got = run.read_metrics(spec["per_layer"], ctx)
    assert set(got) == {m["name"] for m in spec["per_layer"]} and len(got) == 16
    assert 0.0 < got["search_roofline"]["value"] <= 100.0 and 0.0 < got["retrieve_mfu"]["value"] < 100.0
    whole = cut["read_over_the_whole_span"]
    for name in ("search_ms_per_call", "encoder_ms_per_call", "search_roofline", "encsvc_token_fill",
                 "encsvc_rows_per_tick", "rest_admit_p50_ms", "search_host_p50_ms"):
        assert got[name]["value"] == pytest.approx(whole[name]["value"], rel=0.25), name
    del ctx["trace_span"]
    ctx["trace"] = None
    assert set(run.read_metrics(spec["per_layer"], ctx)) == {
        "retrieve_p95_ms", "gen_late_p95_ms", "encsvc_rows_per_tick", "encsvc_token_fill"}
