"""Each reader of the program's host spans and counters on a hand-made span
list, and on a ``ctx`` without a trace (None): what the parent commit's program
gives, which records no such span and counts no such token."""

import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))
import run  # noqa: E402

START_AT = 1000.0  # ctx["gen"]["start_at"] on time.monotonic
MS = 1e-3


def span(kind, span_id, parent_id, start_ms, dur_ms, links=(), **attrs):
    return {"kind": kind, "trace_id": "t", "span_id": span_id, "parent_id": parent_id,
            "ts_mono": START_AT + 2.0 + start_ms * MS, "duration_s": dur_ms * MS, "attrs": attrs,
            "links": [{"trace_id": "t", "span_id": s} for s in links]}


def request(i, at, admit, queue, commit_id, reply_from, reply, total):
    """A rest span at ``at`` ms with its three children, laid end to end."""
    r = f"rest{i}"
    return [
        span("rest", r, None, at, total, route="/v1/retrieve", status=200),
        span("admit", f"admit{i}", r, at + 0.1, admit),
        span("queue", f"queue{i}", r, at + 0.1 + admit, queue, commit=commit_id),
        span("reply", f"reply{i}", r, at + reply_from, reply),
    ]


HAND_MADE = (
    # commit 7 starts at 6 ms and runs 30 ms; it took requests 0 and 1
    request(0, 0.0, 0.9, 5.0, 7, 38.0, 1.0, 40.0)
    + request(1, 3.0, 0.9, 2.0, 7, 36.0, 2.0, 35.5)
    + [span("commit", "c7", None, 6.0, 30.0, links=("rest0", "rest1"), commit=7, queries=2),
       span("embed_wait", "e7", "c7", 7.0, 4.0, rows=2),
       span("search", "s7", "c7", 12.0, 10.0, queries=2),
       span("search.prepare", "sp7", "s7", 12.0, 2.0),
       span("search.device_wait", "sw7", "s7", 14.0, 6.0),
       span("search.assemble", "sa7", "s7", 20.0, 2.0),
       # an idle commit, and one whose request replied outside the window
       span("commit", "c8", None, 37.0, 0.2, commit=8, queries=0),
       span("commit", "c9", None, 60.0, 12.0, links=("rest2",), commit=9, queries=1),
       span("embed_wait", "e9", "c9", 61.0, 2.0, rows=1),
       span("search", "s9", "c9", 64.0, 7.0, queries=1),
       span("search.device_wait", "sw9", "s9", 65.0, 5.0)]
    + request(2, 55.0, 0.5, 4.4, 9, 72.5, 0.5, 5000.0)  # ends after the traced span
)


def ctx_for(spans, traced=True):
    ctx = {"gen": {"start_at": START_AT}, "percentile": run.percentile, "trace": None,
           "counters_before": {"svc_rows": 10.0}, "counters_after": {"svc_rows": 30.0}}
    if traced:
        ctx.update(trace_span={"t0": 2.0, "t1": 6.0}, spans=spans,
                   trace={"programs": {"jit_encoder_forward": {"seconds": 0.012, "calls": 100.0},
                                       "jit__search_kernel": {"seconds": 0.5, "calls": 100.0}}},
                   counters_before={"svc_real_tokens": 100.0, "svc_padded_tokens": 1000.0},
                   counters_after={"svc_real_tokens": 340.0, "svc_padded_tokens": 2280.0})
    return ctx


EXPECTED = {
    "rest_admit_p50_ms": 0.9,  # 0.9, 0.9 and 0.5: the third request's admit ended in the window too
    "engine_queue_p50_ms": 4.4,  # 5.0, 2.0, 4.4
    "commit_p50_ms": 12.0,  # 30 and 12; the idle commit is left out: nearest rank takes the lower
    "encsvc_wait_p50_ms": 2.0,  # 4 and 2
    "search_host_p50_ms": 2.0,  # 10 - 6 and 7 - 5
    "reply_p50_ms": 2.5,  # rest ends 40.0 and 38.5, the commit 36.0: 4.0 and 2.5
    # request 0: 0.1 ms before its admit, 36..38 and 39..40 around its reply: 3.1 of 40;
    # request 1: 0.1 ms, and 36..38.5 (its reply span starts after its rest span's end): 2.6 of 35.5
    "request_unattributed": 100.0 * 2.6 / 35.5,
    "encoder_ms_per_call": 0.12,
    "encsvc_token_fill": 100.0 * 240.0 / 1280.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_made_spans(name):
    value = run.load_module("metrics", name).read(ctx_for(HAND_MADE))
    assert value == pytest.approx(EXPECTED[name], rel=1e-6), (name, value)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_without_a_trace_or_the_programs_spans(name):
    reader = run.load_module("metrics", name)
    assert reader.read(ctx_for(HAND_MADE, traced=False)) is None
    # the parent commit's program under a traced run: an empty ring, the
    # encoder's program under a lambda's name, no token counters
    bare = ctx_for([])
    bare["trace"] = {"programs": {"jit__lambda_": {"seconds": 0.012, "calls": 100.0}}}
    bare["counters_before"] = bare["counters_after"] = {"svc_rows": 1.0}
    assert reader.read(bare) is None


def test_every_new_metric_is_in_the_manifest_with_a_reader():
    import json

    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in EXPECTED:
        assert per_layer[name]["moves"] == "retrieve_p50_ms"
        # the vector store's own stages are kept to its cell; the REST connector's and the engine's hold for any
        own = name.startswith(("search_", "encsvc_", "encoder_"))
        assert per_layer[name].get("workloads") == (["serve-dense-2m"] if own else None), name
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))
    assert not math.isnan(sum(EXPECTED.values()))
