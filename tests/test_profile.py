"""Metrics plane: log-bucketed histograms, per-operator commit profiles, the
flight recorder ring, and the strict-grammar OpenMetrics exporter."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.engine.http_server import MonitoringServer, ProberStats
from pathway_tpu.engine.profile import (
    CommitProfile,
    FlightRecorder,
    LogHistogram,
    get_profiler,
    histogram,
    reset_profile,
)
from pathway_tpu.engine.runner import GraphRunner
from pathway_tpu.internals.parse_graph import G

from .utils import validate_openmetrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_small_graph():
    G.clear()
    t = pw.debug.table_from_markdown(
        """
        a
        1
        2
        3
        """
    )
    out = t.groupby(pw.this.a).reduce(pw.this.a, n=pw.reducers.count())
    pw.io.subscribe(out, lambda *a, **k: None)
    runner = GraphRunner(G._current)
    runner.run()
    return runner


# -- LogHistogram -------------------------------------------------------------


def test_log_histogram_quantiles_track_truth():
    import random

    rng = random.Random(7)
    h = LogHistogram()
    values = sorted(rng.uniform(0.0005, 0.2) for _ in range(5000))
    for v in values:
        h.observe(v)
    for q in (0.5, 0.95, 0.99):
        true = values[int(q * len(values)) - 1]
        est = h.quantile(q)
        # log2 buckets bound the error to one octave
        assert true / 2 <= est <= true * 2, (q, est, true)
    pct = h.percentiles()
    assert pct["p50"] <= pct["p95"] <= pct["p99"]
    assert h.count == 5000


def test_log_histogram_edges_and_reset():
    h = LogHistogram()
    h.observe(0.0)  # below the first bound
    h.observe(1e9)  # beyond the last bound -> +Inf overflow
    h.observe(h.bounds[3])  # exactly a bound: le is inclusive
    assert h.counts[0] == 1
    assert h.counts[-1] == 1
    assert h.counts[3] == 1
    assert h.quantile(0.5) > 0
    h.reset()
    assert h.count == 0 and h.quantile(0.5) == 0.0


def test_log_histogram_openmetrics_shape():
    h = LogHistogram()
    for v in (0.001, 0.004, 0.1, 3.0):
        h.observe(v)
    text = "\n".join(h.openmetrics_lines("x_seconds", "test hist")) + "\n# EOF\n"
    fams = validate_openmetrics(text)
    assert fams["x_seconds"]["type"] == "histogram"


# -- per-operator profiles ----------------------------------------------------


@pytest.mark.telemetry
def test_commit_profiles_capture_operator_timings():
    reset_profile()
    _run_small_graph()
    prof = get_profiler()
    assert prof.commits >= 1
    # daemon runners leaked by OTHER tests (REST servers never stop) also feed
    # the process-wide profiler — assert on THIS graph's operators existing,
    # not on exclusive ownership of the totals
    groupbys = [e for e in prof.operator_totals() if e["kind"] == "groupby"]
    inputs = [e for e in prof.operator_totals() if e["kind"] == "input"]
    assert groupbys and inputs
    assert any(e["rows"] == 3 for e in groupbys)
    assert all(e["seconds"] > 0 for e in groupbys)
    assert all(e["calls"] >= 1 for e in groupbys)
    snap = prof.snapshot()
    assert snap["commits"] >= 1
    assert snap["commit_duration_ms"]["p50"] > 0
    assert snap["operators"][0]["seconds"] >= snap["operators"][-1]["seconds"]


@pytest.mark.telemetry
def test_profile_env_gate_disables_operator_timing(monkeypatch):
    """The runner-level gate: with PATHWAY_PROFILE=0 the runner never binds
    the profiler (asserted on the runner, not on global totals — daemon
    runners leaked by other tests feed the process-wide profiler forever)."""
    monkeypatch.setenv("PATHWAY_PROFILE", "0")
    runner = _run_small_graph()
    assert runner._profiler is None
    assert runner._profile_ops is None
    monkeypatch.setenv("PATHWAY_PROFILE", "1")
    runner = _run_small_graph()
    assert runner._profiler is not None


@pytest.mark.telemetry
def test_retractions_counted_per_operator():
    reset_profile()
    t = pw.debug.table_from_markdown(
        """
        a | __time__ | __diff__
        1 | 2        | 1
        2 | 2        | 1
        1 | 4        | -1
        """
    )
    pw.io.subscribe(t, lambda *a, **k: None)
    GraphRunner(G._current).run()
    inputs = [e for e in get_profiler().operator_totals() if e["kind"] == "input"]
    assert any(e["retractions"] == 1 for e in inputs), inputs


# -- OpenMetrics exporter -----------------------------------------------------


@pytest.mark.telemetry
def test_metrics_endpoint_full_plane_passes_strict_grammar():
    """The acceptance surface: /metrics exposes per-operator time/rows series
    and commit-duration histogram buckets, all valid OpenMetrics."""
    from pathway_tpu.engine import telemetry

    reset_profile()
    telemetry.stage_reset()
    telemetry.stage_add("embed.cache_hits", 5)
    telemetry.stage_add("exchange.peer1.bytes_sent", 1024)
    histogram("pathway_rest_latency_seconds").observe(0.004)
    runner = _run_small_graph()
    stats = runner.prober_stats
    server = MonitoringServer(stats, 0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=5
        ).read().decode()
    finally:
        server.close()
    fams = validate_openmetrics(body)
    assert fams["pathway_operator_seconds"]["type"] == "counter"
    op_samples = fams["pathway_operator_seconds"]["samples"]
    kinds = {s[1]["kind"] for s in op_samples}
    assert "groupby" in kinds
    assert any(s[2] > 0 for s in op_samples)
    assert fams["pathway_operator_rows"]["samples"]
    assert fams["pathway_commit_duration_seconds"]["type"] == "histogram"
    assert fams["pathway_rest_latency_seconds"]["type"] == "histogram"
    stage_samples = {s[1]["stage"]: s[2] for s in fams["pathway_stage"]["samples"]}
    assert stage_samples["embed.cache_hits"] == 5
    assert stage_samples["exchange.peer1.bytes_sent"] == 1024


@pytest.mark.telemetry
def test_openmetrics_label_escaping():
    from pathway_tpu.engine import telemetry

    reset_profile()
    telemetry.stage_reset()
    # quotes/backslashes must escape; commas and braces are LEGAL inside a
    # quoted label value (user-settable operator names) and must round-trip
    # through the strict checker
    telemetry.stage_add('we"ird\\stage', 1)
    telemetry.stage_add("join(a,b){x}", 2)
    try:
        stats = ProberStats()
        fams = validate_openmetrics(stats.to_openmetrics())
        values = {s[1]["stage"]: s[2] for s in fams["pathway_stage"]["samples"]}
        assert values['we\\"ird\\\\stage'] == 1
        assert values["join(a,b){x}"] == 2
    finally:
        telemetry.stage_reset()


# -- /v1/statistics -----------------------------------------------------------


@pytest.mark.telemetry
def test_statistics_query_surfaces_engine_snapshot():
    from .test_xpack_llm import _store
    from .utils import capture_rows

    reset_profile()
    _run_small_graph()  # the snapshot reports PRIOR commits (it is read
    G.clear()  # mid-commit, before the current commit's profile lands)
    store = _store()
    stats_q = pw.debug.table_from_rows(pw.schema_builder({"dummy": int}), [(1,)])
    rows = capture_rows(store.statistics_query(stats_q))
    stats = rows[0]["result"].value
    assert "engine" in stats
    assert stats["engine"]["commits"] >= 1
    assert "p95" in stats["engine"]["commit_duration_ms"]
    assert any(op["kind"] == "input" for op in stats["engine"]["operators"])


# -- flight recorder ----------------------------------------------------------


def _profile_for(commit: int) -> CommitProfile:
    return CommitProfile(
        commit=commit,
        rank=0,
        duration_s=0.01 * (commit + 1),
        input_rows=commit,
        output_rows=commit,
        neu=False,
        ops=[(1, "groupby", "groupby", 0.005, commit, 0, False)],
    )


@pytest.mark.telemetry
def test_flight_recorder_ring_is_bounded_and_dump_has_summary(tmp_path, monkeypatch):
    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER_COMMITS", "4")
    rec = FlightRecorder()
    for c in range(10):
        rec.record_commit(_profile_for(c))
    rec.record_event("fence", commit=9, epoch=1)
    rec.note_barrier(b"18:3:i0")
    path = rec.dump("crash: TestError", directory=str(tmp_path))
    assert path is not None and os.path.exists(path)
    payload = json.loads(open(path).read())
    profiles = payload["profiles"]
    assert len(profiles) == 4, "ring must hold only the last N profiles"
    assert [p["commit"] for p in profiles] == [6, 7, 8, 9]
    assert payload["summary"]["last_commit"] == 9
    assert payload["summary"]["slowest_operator"]["name"] == "groupby"
    assert payload["summary"]["pending_barrier"] == "18:3:i0"
    assert payload["reason"] == "crash: TestError"
    assert payload["events"][-1]["kind"] == "fence"


@pytest.mark.telemetry
def test_flight_recorder_env_gate(tmp_path, monkeypatch):
    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER", "0")
    rec = FlightRecorder()
    rec.record_commit(_profile_for(1))
    assert rec.dump("crash", directory=str(tmp_path)) is None
    assert not list(tmp_path.iterdir())


@pytest.mark.telemetry
def test_run_crash_dumps_flight_record(tmp_path, monkeypatch):
    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER_DIR", str(tmp_path))
    reset_profile()
    t = pw.debug.table_from_markdown(
        """
        a
        1
        """
    )

    def boom(x: int) -> int:
        raise RuntimeError("operator exploded")

    out = t.select(b=pw.apply_with_type(boom, int, pw.this.a))
    pw.io.subscribe(out, lambda *a, **k: None)
    with pytest.raises(Exception):
        GraphRunner(G._current).run()
    path = tmp_path / "flight-rank-0.json"
    assert path.exists(), "a crashing run must leave its black box behind"
    payload = json.loads(path.read_text())
    assert payload["reason"].startswith("crash:")
    assert payload["rank"] == 0


@pytest.mark.telemetry
def test_noop_telemetry_path_stays_import_free():
    """Tier-1 guard for the deferred-import discipline in engine/telemetry.py:
    with telemetry off, importing pathway_tpu and running a pipeline must not
    pull opentelemetry into sys.modules (its import scans every installed
    distribution's entry points)."""
    code = (
        "import sys\n"
        "import pathway_tpu as pw\n"
        "t = pw.debug.table_from_markdown('a\\n1\\n2')\n"
        "pw.io.subscribe(t, lambda *a, **k: None)\n"
        "pw.run(monitoring_level=pw.MonitoringLevel.NONE)\n"
        "bad = [m for m in sys.modules if m.startswith('opentelemetry')]\n"
        "assert not bad, f'telemetry-off run imported {bad}'\n"
    )
    env = os.environ.copy()
    env.pop("PATHWAY_TELEMETRY", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
