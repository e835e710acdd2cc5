"""Distributed-tracing plane tests (``engine/tracing.py``): header
round-trip, hash-of-trace-id sampling (one decision per trace, every rank),
slow-root promotion / fast-root drop of the pending buffer, epoch-bump
survival, ring flush + the cross-rank merger (clock-offset alignment,
flight-dump partials), the critical-path one-liner, and the ``trace.*``
counters on the strict OpenMetrics exposition.

Isolation note: these tests assert EXACT ring contents and counter values,
but the full suite leaks daemon ``pw.run`` threads that keep stepping
commits (see test_monitoring.py's noise-floor comment) — any of them would
write spans the moment the process-wide tracer turns on. So each test runs
against a PRIVATE ``Tracer`` instance while the global singleton is pinned
disabled: module-level sampling helpers still read the global's refreshed
rate, and the leaked engines stay silent.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from pathway_tpu.engine import telemetry, tracing
from pathway_tpu.engine.tracing import (
    TRACE_HEADER,
    TraceContext,
    Tracer,
    commit_trace_context,
    critical_path,
    critical_path_line,
    format_trace_header,
    format_trace_tree,
    get_tracer,
    load_flight_spans,
    load_trace_file,
    merge_trace_files,
    new_trace_context,
    parse_trace_header,
)

pytestmark = pytest.mark.trace


def _sync_env(inst: Tracer) -> None:
    """Re-read flipped env knobs on the private tracer AND the global one
    (``_head_sampled`` reads the global's rate) — the global stays DISABLED
    so leaked daemon engines from earlier suite files cannot write spans."""
    g = get_tracer()
    g.refresh()
    g.enabled = False
    inst.refresh()


@pytest.fixture(autouse=True)
def tracer(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "1.0")
    monkeypatch.delenv("PATHWAY_TRACE_DIR", raising=False)
    monkeypatch.delenv("PATHWAY_TRACE_SLOW_MS", raising=False)
    monkeypatch.delenv("PATHWAY_TRACE_RING", raising=False)
    telemetry.stage_reset("trace.")
    inst = Tracer()
    inst.configure(rank=0)
    _sync_env(inst)
    yield inst
    g = get_tracer()
    g.reset()
    g.enabled = False


# -- header propagation -------------------------------------------------------


def test_header_format_parse_round_trip():
    ctx = TraceContext("ab" * 8, "cd" * 8, True)
    assert format_trace_header(ctx) == "ab" * 8 + "-" + "cd" * 8 + "-01"
    back = parse_trace_header(format_trace_header(ctx))
    assert (back.trace_id, back.span_id, back.sampled) == (
        ctx.trace_id, ctx.span_id, True,
    )
    off = parse_trace_header("ab" * 8 + "-" + "cd" * 8 + "-00")
    assert off is not None and off.sampled is False


def test_header_parse_tolerates_malformed_input():
    # a bad client header must read as absent, never 500 the route
    for bad in (None, "", "zz", "abc-def", "g" * 16 + "-" + "cd" * 8,
                "ab" * 8, "ab" * 9 + "-" + "cd" * 8):
        assert parse_trace_header(bad) is None
    # a missing/unknown flag falls back to the hash decision (rate=1.0 here)
    assert parse_trace_header("ab" * 8 + "-" + "cd" * 8).sampled is True
    assert parse_trace_header("ab" * 8 + "-" + "cd" * 8 + "-xx").sampled is True


# -- sampling -----------------------------------------------------------------


def test_sampling_is_a_pure_function_of_the_trace_id(monkeypatch, tracer):
    # every rank and component derives the SAME verdict from the id alone —
    # no sampling bit ever needs to ride the wire
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "0.5")
    _sync_env(tracer)
    for i in range(64):
        ctx = new_trace_context()
        header = format_trace_header(
            TraceContext(ctx.trace_id, ctx.span_id, ctx.sampled)
        )
        again = parse_trace_header(header.rsplit("-", 1)[0])  # strip flag
        assert again.sampled == ctx.sampled
    sampled = sum(new_trace_context().sampled for _ in range(400))
    assert 80 < sampled < 320  # rate actually thins, and actually keeps


def test_commit_trace_context_agrees_across_ranks():
    a = commit_trace_context(3, 41, rank=0)
    b = commit_trace_context(3, 41, rank=1)
    assert a.trace_id == b.trace_id  # lockstep commit id IS the cross-rank key
    assert a.span_id != b.span_id  # each rank's commit span is its own sibling
    assert a.sampled == b.sampled
    assert commit_trace_context(3, 42).trace_id != a.trace_id
    assert commit_trace_context(4, 41).trace_id != a.trace_id


def test_trace_defaults_off_when_env_unset(monkeypatch):
    # the master gate is OPT-IN: a process that never set PATHWAY_TRACE must
    # pay zero span bookkeeping (README knob row: default off)
    monkeypatch.delenv("PATHWAY_TRACE", raising=False)
    inst = Tracer()
    assert inst.enabled is False
    with inst.trace_span("rest", "GET /never") as span:
        assert span is None


# -- span lifecycle / routing -------------------------------------------------


def test_trace_span_nests_and_lands_in_ring(tracer):
    with tracer.trace_span("rest", "GET /v1/retrieve") as root:
        assert tracing.current_context().span_id == root.span_id
        with tracer.trace_span("embed_wait", "embed_wait 2") as child:
            pass
    assert child.parent_id == root.span_id
    assert child.trace_id == root.trace_id
    recent = tracer.recent_spans()
    assert {s["span_id"] for s in recent} >= {root.span_id, child.span_id}
    assert telemetry.stage_snapshot("trace.")["trace.span"] == 2.0


def test_slow_root_promotes_buffered_children(monkeypatch, tracer):
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "0.0")
    monkeypatch.setenv("PATHWAY_TRACE_SLOW_MS", "0")
    _sync_env(tracer)
    with tracer.trace_span("rest", "GET /slow") as root:
        with tracer.trace_span("embed_wait", "admit"):
            pass
    assert root.sampled  # promoted at finish: slow roots always sample
    ids = {s["span_id"] for s in tracer.recent_spans()}
    assert root.span_id in ids and len(ids) == 2
    counters = telemetry.stage_snapshot("trace.")
    assert counters["trace.promoted"] == 1.0
    assert counters["trace.span"] == 2.0


def test_fast_root_drops_buffered_children(monkeypatch, tracer):
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "0.0")
    monkeypatch.setenv("PATHWAY_TRACE_SLOW_MS", "60000")
    _sync_env(tracer)
    with tracer.trace_span("rest", "GET /fast"):
        with tracer.trace_span("embed_wait", "admit"):
            pass
    assert tracer.recent_spans() == []
    assert telemetry.stage_snapshot("trace.")["trace.dropped"] == 1.0


def test_epoch_bump_never_orphans_pending_spans(monkeypatch, tracer):
    # the trace_ring_model invariant, exercised against the real tracer: a
    # membership epoch bump between a child's finish and its root's verdict
    # must not strand the buffered child
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "0.0")
    monkeypatch.setenv("PATHWAY_TRACE_SLOW_MS", "0")
    _sync_env(tracer)
    with tracer.trace_span("rest", "GET /bump") as root:
        with tracer.trace_span("embed_wait", "admit") as child:
            pass
        tracer.set_epoch(7)
    spans = {s["span_id"]: s for s in tracer.recent_spans()}
    assert child.span_id in spans and root.span_id in spans
    assert spans[child.span_id]["epoch"] == 0  # stamped at start, not at bump
    tracer.set_epoch(0)


def test_off_gate_disables_everything(monkeypatch, tracer, tmp_path):
    monkeypatch.setenv("PATHWAY_TRACE", "off")
    _sync_env(tracer)
    with tracer.trace_span("rest", "GET /off") as span:
        assert span is None
    assert tracer.start("barrier", "b") is None
    assert tracer.flush(str(tmp_path)) is None
    assert tracer.recent_spans() == []


def test_query_and_commit_link_registries_drain_once(tracer):
    q1, q2, c1 = new_trace_context(), new_trace_context(), new_trace_context()
    tracer.register_query_link("what is pathway", q1)
    tracer.register_query_link("what is pathway", q2)
    before = time.monotonic()
    tracer.register_commit_link(b"row-key-1", c1)
    got = tracer.take_query_links(["what is pathway", "absent"])
    assert {g.span_id for g in got} == {q1.span_id, q2.span_id}
    assert tracer.take_query_links(["what is pathway"]) == []
    # a commit takes the links of the row keys among its input rows, each
    # with the instant it was registered (the push instant), once
    assert tracer.take_commit_links([b"another-row"]) == []
    [(ctx, pushed)] = tracer.take_commit_links([b"row-key-1", b"another-row"])
    assert ctx.span_id == c1.span_id
    assert before <= pushed <= time.monotonic()
    assert tracer.take_commit_links([b"row-key-1"]) == []


# -- flush / merge / critical path --------------------------------------------


def _flush_two_ranks(tracer, tmp_path, *, skew_s: float = 5.0):
    """One commit trace spread over two 'ranks' (same process, reconfigured
    tracer): rank 0 holds the commit root + a groupby child + the barrier
    span with straggler attribution; rank 1's sibling commit span is stamped
    with a deliberately skewed wall clock that only the heartbeat-estimated
    offset in rank 0's _meta can undo."""
    ctx0 = commit_trace_context(0, 12, rank=0)
    with tracer.trace_span("commit", "commit 12", self_ctx=ctx0) as root:
        root.ts, root.ts_mono = 1000.0, 100.0
        root.duration_s = 0.100
        tracer.record_span(
            "operator", "groupby:words", parent=root.context(),
            ts=1000.01, ts_mono=100.01, duration_s=0.078,
        )
        with tracer.trace_span("barrier", "barrier DELTA") as bar:
            bar.ts, bar.ts_mono = 1000.05, 100.05
            bar.duration_s = 0.041
            bar.attrs["straggler_rank"] = 3
            bar.attrs["straggler_wait_s"] = 0.041
    # rank 0 measured rank 1's wall clock as skew_s ahead
    tracer.set_clock_offsets({1: skew_s})
    path0 = tracer.flush(str(tmp_path), reason="test")
    assert path0 is not None and tracer.flushes == 1
    # rank 1: sibling commit span in the SAME trace, skewed wall clock
    tracer.reset()
    tracer.configure(rank=1)
    ctx1 = commit_trace_context(0, 12, rank=1)
    with tracer.trace_span("commit", "commit 12", self_ctx=ctx1) as sib:
        sib.ts, sib.ts_mono = 1000.02 + skew_s, 200.0
        sib.duration_s = 0.055
    path1 = tracer.flush(str(tmp_path), reason="test")
    tracer.reset()
    tracer.configure(rank=0)
    return path0, path1, ctx0


def test_flush_merge_aligns_clocks_and_names_critical_path(tracer, tmp_path):
    path0, path1, ctx0 = _flush_two_ranks(tracer, tmp_path, skew_s=5.0)
    meta0, spans0 = load_trace_file(path0)
    assert meta0["rank"] == 0 and meta0["clock_offsets"] == {"1": 5.0}
    assert len(spans0) == 3
    merged = merge_trace_files([path0, path1])
    assert merged["ranks"] == [0, 1]
    by_id = {s["span_id"]: s for s in merged["spans"]}
    sib = by_id[commit_trace_context(0, 12, rank=1).span_id]
    # the 5 s skew is undone: rank 1's span lands 20 ms after rank 0's root
    assert abs(sib["ts_adj"] - 1000.02) < 1e-6
    result = critical_path(merged, ctx0.trace_id)
    assert "commit 12" in result["line"]
    assert "78% in rank 0 groupby:words" in result["line"]
    assert "barrier held 41 ms by rank 3" in result["line"]
    tree = format_trace_tree(merged, ctx0.trace_id)
    assert any("operator groupby:words" in line for line in tree)
    # rank 1's sibling has no local parent span -> renders as its own root
    assert sum("commit commit 12" in line for line in tree) == 2
    # and the directory-level convenience the supervisor post-mortem uses
    assert "commit 12" in critical_path_line(str(tmp_path))


def test_merge_tolerates_torn_tail_and_flight_partials(tracer, tmp_path):
    path0, path1, ctx0 = _flush_two_ranks(tracer, tmp_path)
    with open(path1, "a") as f:
        f.write('{"span_id": "torn-mid-wri')  # rank killed mid-write
    flight = tmp_path / "flight-rank-2.json"
    killed = {
        "trace_id": ctx0.trace_id, "span_id": "f" * 16, "parent_id": None,
        "rank": 2, "epoch": 0, "kind": "commit", "name": "commit 12",
        "ts": 1000.03, "ts_mono": 1.0, "duration_s": 0.02, "attrs": {},
        "links": [],
    }
    flight.write_text(json.dumps({"trace": {"rank": 2, "spans": [killed]}}))
    assert load_flight_spans(str(flight)) == [killed]
    merged = merge_trace_files([path0, path1], [str(flight)])
    ids = {s["span_id"] for s in merged["spans"]}
    assert "f" * 16 in ids  # the chaos-killed rank still contributed
    assert not any(i.startswith("torn") for i in ids)


def test_flush_is_atomic_and_reentrant_under_held_lock(tracer, tmp_path):
    # the SIGTERM path: flush may run while the same thread already holds
    # the tracer lock (RLock) — and a failing directory never raises
    with tracer.trace_span("rest", "GET /crash"):
        pass
    with tracer._lock:
        path = tracer.flush(str(tmp_path), reason="sigterm")
    assert path is not None and os.path.exists(path)
    assert tracer.flush(str(tmp_path / "missing" / "nested")) is None


def test_trace_counters_ride_strict_openmetrics(tracer):
    from pathway_tpu.engine.http_server import ProberStats

    from .utils import validate_openmetrics

    with tracer.trace_span("rest", "GET /metrics-check"):
        pass
    text = ProberStats().to_openmetrics()
    families = validate_openmetrics(text)
    assert 'pathway_stage_total{stage="trace.span"}' in text
    samples = families["pathway_stage"]["samples"]
    stages = {labels.get("stage") for (_, labels, _) in samples}
    assert "trace.span" in stages
