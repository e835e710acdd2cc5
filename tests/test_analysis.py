"""Graph-lint tests (pathway_tpu/analysis): one deliberately-broken graph per
pass (golden diagnostics asserted by code), the PATHWAY_LINT run-time gate, the
``cli analyze`` exit-code contract, telemetry mirroring, a clean sweep over the
``examples/`` programs, and the REWIND_SAFE source audit."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.analysis import GraphLintError, Severity, analyze_graph
from pathway_tpu.internals import parse_graph as pg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(report):
    return {d.code for d in report.diagnostics}


def _ints_table():
    return pw.debug.table_from_rows(pw.schema_builder({"v": int}), [(1,), (2,), (3,)])


# ---------------------------------------------------------------------------
# PWA001 — determinism
# ---------------------------------------------------------------------------


def test_pwa001_time_udf_flagged_with_location():
    t = _ints_table()

    @pw.udf
    def stamp(a: int) -> float:
        return time.time() + a

    t.select(x=stamp(t.v))
    report = analyze_graph(pg.G._current)
    found = report.by_code("PWA001")
    assert found, report.to_json()
    d = found[0]
    assert d.severity == Severity.ERROR
    assert "time.time()" in d.message
    assert d.file is not None and d.file.endswith("test_analysis.py")
    assert d.node_kind == "rowwise"
    assert report.exit_code() == 2


def test_pwa001_random_uuid_direct_import_and_lambda():
    import random

    t = _ints_table()
    t.select(x=pw.apply(lambda a: random.random() * a, t.v))
    report = analyze_graph(pg.G._current)
    assert any(
        "random.random()" in d.message for d in report.by_code("PWA001")
    ), report.to_json()


def test_pwa001_datetime_module_chain_flagged():
    # the common spelling: ``import datetime; datetime.datetime.now()`` —
    # two attribute loads deep from the module global
    import datetime

    t = _ints_table()
    t.select(x=pw.apply(lambda a: datetime.datetime.now().timestamp() + a, t.v))
    report = analyze_graph(pg.G._current)
    assert any(
        "datetime.datetime.now()" in d.message for d in report.by_code("PWA001")
    ), report.to_json()


def test_pwa001_global_and_closure_mutation():
    t = _ints_table()

    def bump_global(a):
        global _PWA001_COUNTER  # noqa: PLW0603 - deliberate violation
        _PWA001_COUNTER = a
        return a

    seen = []

    def bump_closure(a):
        seen.append(a)
        return a

    t.select(x=pw.apply(bump_global, t.v), y=pw.apply(bump_closure, t.v))
    report = analyze_graph(pg.G._current)
    reasons = {d.details.get("reason") for d in report.by_code("PWA001")}
    assert "global_mutation" in reasons, report.to_json()
    assert "closure_mutation" in reasons, report.to_json()


def test_pwa001_local_container_with_closed_over_key_quiet():
    # a deterministic UDF that item-assigns into a LOCAL dict using a
    # closed-over KEY must not be flagged; item-assigning into a closed-over
    # CONTAINER must
    t = _ints_table()
    key = "k"
    state = {}

    def local_dict(a):
        out = {}
        out[key] = a
        return out[key]

    def shared_dict(a):
        state[a] = a
        return a

    t.select(x=pw.apply(local_dict, t.v), y=pw.apply(shared_dict, t.v))
    report = analyze_graph(pg.G._current)
    flagged = {d.details.get("udf") for d in report.by_code("PWA001")}
    assert "local_dict" not in flagged, report.to_json()
    assert "shared_dict" in flagged, report.to_json()


def test_pwa001_clean_udf_and_sink_callbacks_quiet():
    t = _ints_table()

    @pw.udf
    def pure(a: int) -> int:
        return a * 2 + 1

    r = t.select(x=pure(t.v))
    got = []
    # sink callbacks mutate closures by design; they are not dataflow UDFs
    pw.io.subscribe(r, lambda key, row, time, is_addition: got.append(row["x"]))
    report = analyze_graph(pg.G._current)
    assert not report.by_code("PWA001"), report.to_json()


# ---------------------------------------------------------------------------
# PWA002 — rewind safety
# ---------------------------------------------------------------------------


def _buffered_graph():
    t = pw.debug.table_from_markdown(
        """
        t | v | __time__ | __diff__
        1 | 1 | 0        | 1
        4 | 2 | 2        | 1
        """
    )
    return t._buffer(pw.this.t + 2, pw.this.t)


def test_pwa002_buffer_warns_under_persistence():
    _buffered_graph()
    report = analyze_graph(pg.G._current, persistence=True)
    found = report.by_code("PWA002")
    assert found and found[0].severity == Severity.WARNING, report.to_json()
    assert found[0].node_kind == "buffer"
    assert report.exit_code() == 1
    assert report.exit_code(strict=True) == 2


def test_pwa002_info_only_without_persistence():
    _buffered_graph()
    report = analyze_graph(pg.G._current, persistence=False)
    found = report.by_code("PWA002")
    assert found and found[0].severity == Severity.INFO
    assert report.exit_code() == 0


def test_pwa002_audit_draining_flushers_are_marked_rewind_unsafe():
    """Source audit: every evaluator whose process() consults runner.draining
    (a live-only flush signal replay cannot reproduce) must opt out of the
    rewind rung — the PR 6 review found the time-threshold family by hand;
    this proves the list stays complete."""
    import types

    from pathway_tpu.engine import evaluators as ev_mod
    from pathway_tpu.engine.evaluators import Evaluator

    def code_mentions_draining(cls) -> bool:
        # compiled code only — comments/docstrings about draining don't count
        for value in vars(cls).values():
            fn = getattr(value, "__func__", value)
            code = getattr(fn, "__code__", None)
            if code is None:
                continue
            stack = [code]
            while stack:
                co = stack.pop()
                if "draining" in co.co_names or "draining" in co.co_consts:
                    return True
                stack.extend(
                    c for c in co.co_consts if isinstance(c, types.CodeType)
                )
        return False

    offenders = []
    for name in dir(ev_mod):
        cls = getattr(ev_mod, name)
        if not (isinstance(cls, type) and issubclass(cls, Evaluator)):
            continue
        if code_mentions_draining(cls) and getattr(cls, "REWIND_SAFE", True):
            offenders.append(cls.__name__)
    assert not offenders, (
        f"evaluators flush on runner.draining but claim REWIND_SAFE: {offenders}"
    )


# ---------------------------------------------------------------------------
# PWA003 — unbounded state
# ---------------------------------------------------------------------------


class _EndlessSubject(pw.io.python.ConnectorSubject):
    def run(self):  # pragma: no cover - never started by the analyzer
        pass


class _StreamSchema(pw.Schema):
    v: int


def test_pwa003_streaming_groupby_flagged():
    t = pw.io.python.read(_EndlessSubject(), schema=_StreamSchema)
    t.groupby(t.v).reduce(t.v, n=pw.reducers.count())
    report = analyze_graph(pg.G._current)
    found = report.by_code("PWA003")
    assert found and found[0].severity == Severity.WARNING, report.to_json()
    assert found[0].node_kind == "groupby"


def test_pwa003_forget_upstream_suppresses():
    t = pw.io.python.read(_EndlessSubject(), schema=_StreamSchema)
    bounded = t._forget(pw.this.v + 10, pw.this.v)
    bounded.groupby(bounded.v).reduce(bounded.v, n=pw.reducers.count())
    report = analyze_graph(pg.G._current)
    assert not report.by_code("PWA003"), report.to_json()


def test_pwa003_forget_on_sibling_branch_does_not_mask():
    # a forget on the join's RIGHT branch must not mask the forget-free LEFT
    # branch from the same unbounded source
    t = pw.io.python.read(_EndlessSubject(), schema=_StreamSchema)
    raw = t.select(v=t.v)
    bounded = t._forget(pw.this.v + 10, pw.this.v)
    raw.join(bounded, raw.v == bounded.v).select(v=pw.left.v)
    report = analyze_graph(pg.G._current)
    found = [d for d in report.by_code("PWA003") if d.node_kind == "join"]
    assert found, report.to_json()


def test_pwa003_static_source_quiet():
    t = _ints_table()
    t.groupby(t.v).reduce(t.v, n=pw.reducers.count())
    report = analyze_graph(pg.G._current)
    assert not report.by_code("PWA003"), report.to_json()


# ---------------------------------------------------------------------------
# PWA004 — device placement
# ---------------------------------------------------------------------------


def test_pwa004_udf_inside_numeric_chain():
    t = _ints_table()

    @pw.udf
    def double(a: int) -> int:
        return a * 2

    t.select(y=double(t.v) + t.v * 3)
    report = analyze_graph(pg.G._current)
    found = report.by_code("PWA004")
    assert found and found[0].severity == Severity.WARNING, report.to_json()
    assert found[0].details.get("udf") == "double"


def test_pwa004_udf_alone_or_host_dtypes_quiet():
    t = pw.debug.table_from_rows(
        pw.schema_builder({"v": int, "s": str}), [(1, "a"), (2, "b")]
    )

    @pw.udf
    def double(a: int) -> int:
        return a * 2

    @pw.udf
    def tag(s: str) -> str:
        return s + "!"

    # standalone UDF column (no numeric chain) and a str chain: both fine
    t.select(y=double(t.v), z=tag(t.s) + "x")
    report = analyze_graph(pg.G._current)
    assert not report.by_code("PWA004"), report.to_json()


def test_pwa004_inconsistent_device_kwargs():
    class FakeStore:
        def __init__(self, device):
            self.device = device

    t = _ints_table()
    pg.G.add_node(pg.Node(inputs=[t], store=FakeStore("tpu:0"), name="store_a"))
    pg.G.add_node(pg.Node(inputs=[t], store=FakeStore("cpu:0"), name="store_b"))
    report = analyze_graph(pg.G._current)
    found = report.by_code("PWA004")
    assert len(found) == 2, report.to_json()
    assert {d.details.get("device") for d in found} == {"tpu:0", "cpu:0"}


def test_pwa004_consistent_devices_quiet():
    class FakeStore:
        def __init__(self, device):
            self.device = device

    t = _ints_table()
    pg.G.add_node(pg.Node(inputs=[t], store=FakeStore("tpu:0")))
    pg.G.add_node(pg.Node(inputs=[t], store=FakeStore("tpu:0")))
    report = analyze_graph(pg.G._current)
    assert not report.by_code("PWA004"), report.to_json()


# ---------------------------------------------------------------------------
# PWA005 — checkpoint compatibility
# ---------------------------------------------------------------------------


def _knn_graph():
    import numpy as np

    from pathway_tpu.stdlib.ml.index import KNNIndex

    docs = pw.debug.table_from_rows(
        pw.schema_builder({"vec": np.ndarray}),
        [(np.asarray([1.0, 0.0], dtype=np.float32),)],
    )
    queries = pw.debug.table_from_rows(
        pw.schema_builder({"qvec": np.ndarray}),
        [(np.asarray([0.9, 0.1], dtype=np.float32),)],
    )
    KNNIndex(docs.vec, docs, n_dimensions=2).get_nearest_items(queries.qvec, k=1)


def test_pwa005_external_index_under_persistence_errors():
    _knn_graph()
    report = analyze_graph(pg.G._current, persistence=True)
    found = report.by_code("PWA005")
    assert any(
        d.severity == Severity.ERROR and d.node_kind == "external_index"
        for d in found
    ), report.to_json()


def test_pwa005_quiet_without_persistence():
    _knn_graph()
    report = analyze_graph(pg.G._current, persistence=False)
    assert not report.by_code("PWA005"), report.to_json()


def test_pwa005_source_without_offset_state_warns():
    from pathway_tpu.engine.datasource import DataSource
    from pathway_tpu.internals.table import Table

    class RawSource(DataSource):
        def next_batch(self, column_names):
            raise NotImplementedError

        def is_finished(self):
            return True

    node = pg.G.add_node(pg.InputNode(source=RawSource()))
    Table(node, pw.schema_builder({"v": int}), name="raw")
    report = analyze_graph(pg.G._current, persistence=True)
    found = report.by_code("PWA005")
    assert any(d.details.get("source") == "RawSource" for d in found), report.to_json()


# ---------------------------------------------------------------------------
# run-time gate: PATHWAY_LINT=off|warn|error
# ---------------------------------------------------------------------------


def _nondet_graph_with_sink():
    t = _ints_table()

    @pw.udf
    def stamp(a: int) -> float:
        return time.time() + a

    r = t.select(x=stamp(t.v))
    got = []
    pw.io.subscribe(r, lambda key, row, time, is_addition: got.append(row["x"]))
    return got


def test_lint_error_mode_refuses_nondeterministic_graph(monkeypatch):
    monkeypatch.setenv("PATHWAY_LINT", "error")
    _nondet_graph_with_sink()
    with pytest.raises(GraphLintError) as exc_info:
        pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert "PWA001" in str(exc_info.value)


def test_lint_off_preserves_behavior(monkeypatch):
    monkeypatch.setenv("PATHWAY_LINT", "off")
    got = _nondet_graph_with_sink()
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert len(got) == 3


def test_lint_warn_default_runs_and_logs(monkeypatch, caplog):
    import logging

    monkeypatch.delenv("PATHWAY_LINT", raising=False)
    got = _nondet_graph_with_sink()
    with caplog.at_level(logging.WARNING, logger="pathway_tpu.analysis"):
        pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert len(got) == 3  # default mode never blocks a run
    assert any("PWA001" in r.message for r in caplog.records)


def test_lint_unknown_mode_warns_and_does_not_block(monkeypatch, caplog):
    import logging

    # a typo'd mode must be loud, not a silent disarm of the error gate
    monkeypatch.setenv("PATHWAY_LINT", "errors")
    got = _nondet_graph_with_sink()
    with caplog.at_level(logging.WARNING, logger="pathway_tpu.analysis"):
        pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert len(got) == 3  # fell back to warn: the run proceeds
    assert any("unrecognized PATHWAY_LINT" in r.getMessage() for r in caplog.records)


def test_lint_capture_sees_replay_storage_persistence(monkeypatch, tmp_path):
    """PATHWAY_REPLAY_STORAGE implies persistence even when run() gets no
    persistence_config — the persistence-gated passes must see it."""
    from pathway_tpu.analysis import GraphCaptureInterrupt

    monkeypatch.setenv("PATHWAY_REPLAY_STORAGE", str(tmp_path / "replay"))
    monkeypatch.setenv("PATHWAY_LINT_CAPTURE", "1")
    _ints_table()
    with pytest.raises(GraphCaptureInterrupt) as exc_info:
        pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert exc_info.value.persistence is True


def test_lint_error_mode_does_not_refuse_debug_helpers(monkeypatch, capsys):
    # pw.debug is local inspection, not a production run: a debug print of a
    # nondeterministic graph must keep working under PATHWAY_LINT=error
    monkeypatch.setenv("PATHWAY_LINT", "error")
    t = _ints_table()

    @pw.udf
    def stamp(a: int) -> float:
        return time.time() + a

    r = t.select(x=stamp(t.v))
    pw.debug.compute_and_print(r)  # must not raise GraphLintError
    assert "x" in capsys.readouterr().out


def test_lint_error_mode_refuses_run_threads_lane(monkeypatch):
    # run_threads workers build their own graphs with no parent run: rank 0
    # must still lint, so PATHWAY_LINT=error refuses the lane too
    from pathway_tpu.parallel.threads import run_threads

    monkeypatch.setenv("PATHWAY_LINT", "error")

    def program():
        t = _ints_table()

        @pw.udf
        def stamp(a: int) -> float:
            return time.time() + a

        r = t.select(x=stamp(t.v))
        got = []
        pw.io.subscribe(r, lambda key, row, time, is_addition: got.append(row["x"]))
        pw.run(monitoring_level=pw.MonitoringLevel.NONE)

    with pytest.raises(RuntimeError, match="GraphLintError"):
        run_threads(program, 2)


def test_lint_telemetry_mirrored(monkeypatch):
    from pathway_tpu.engine import telemetry
    from pathway_tpu.engine.profile import get_flight_recorder

    monkeypatch.setenv("PATHWAY_LINT", "warn")
    telemetry.stage_reset("lint.")
    recorder = get_flight_recorder()
    monkeypatch.setattr(recorder, "enabled", True)
    _nondet_graph_with_sink()
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    counters = telemetry.stage_snapshot("lint.")
    assert counters.get("lint.errors", 0) >= 1, counters
    assert counters.get("lint.diag.PWA001", 0) >= 1, counters
    assert any(
        ev.get("kind") == "lint" and ev.get("errors", 0) >= 1
        for ev in list(recorder._events)
    )


# ---------------------------------------------------------------------------
# cli analyze: exit-code contract + clean sweep over examples/
# ---------------------------------------------------------------------------


def _cli_env():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop("PATHWAY_LINT", None)
    env.pop("PATHWAY_LINT_CAPTURE", None)
    return env


def _analyze_cli(program: str, *flags: str):
    proc = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.cli", "analyze", *flags, program],
        capture_output=True,
        text=True,
        env=_cli_env(),
        timeout=120,
        cwd=REPO,
    )
    return proc


def _parse_json_stdout(stdout: str) -> dict:
    return json.loads(stdout[stdout.index("{") :])


_CLEAN_PROG = """
import pathway_tpu as pw
t = pw.debug.table_from_rows(pw.schema_builder({"v": int}), [(1,), (2,)])
r = t.select(x=t.v * 2)
pw.run(monitoring_level=pw.MonitoringLevel.NONE)
"""

_WARNING_PROG = """
import pathway_tpu as pw

class Subj(pw.io.python.ConnectorSubject):
    def run(self):
        pass

class Sch(pw.Schema):
    v: int

t = pw.io.python.read(Subj(), schema=Sch)
t.groupby(t.v).reduce(t.v, n=pw.reducers.count())
pw.run(monitoring_level=pw.MonitoringLevel.NONE)
"""

_ERROR_PROG = """
import time
import pathway_tpu as pw

t = pw.debug.table_from_rows(pw.schema_builder({"v": int}), [(1,)])

@pw.udf
def stamp(a: int) -> float:
    return time.time() + a

t.select(x=stamp(t.v))
pw.run(monitoring_level=pw.MonitoringLevel.NONE)
"""


def test_cli_analyze_exit_code_contract(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text(_CLEAN_PROG)
    warn = tmp_path / "warn.py"
    warn.write_text(_WARNING_PROG)
    err = tmp_path / "err.py"
    err.write_text(_ERROR_PROG)

    p = _analyze_cli(str(clean), "--format", "json")
    assert p.returncode == 0, p.stdout + p.stderr
    payload = _parse_json_stdout(p.stdout)
    assert payload["summary"]["errors"] == 0

    p = _analyze_cli(str(warn), "--format", "json")
    assert p.returncode == 1, p.stdout + p.stderr
    payload = _parse_json_stdout(p.stdout)
    assert payload["summary"]["errors"] == 0
    assert payload["summary"]["warnings"] >= 1
    assert any(d["code"] == "PWA003" for d in payload["diagnostics"])

    p = _analyze_cli(str(warn), "--format", "json", "--strict")
    assert p.returncode == 2, p.stdout + p.stderr

    p = _analyze_cli(str(err), "--format", "json")
    assert p.returncode == 2, p.stdout + p.stderr
    payload = _parse_json_stdout(p.stdout)
    assert any(
        d["code"] == "PWA001" and d["severity"] == "error"
        for d in payload["diagnostics"]
    )
    # text format carries the same verdict
    p = _analyze_cli(str(err))
    assert p.returncode == 2
    assert "PWA001" in p.stdout


_CRASH_PROG = """
import nonexistent_module_xyz  # crashes before any graph exists
"""

_DEBUG_THEN_ERROR_PROG = """
import time
import pathway_tpu as pw

t = pw.debug.table_from_rows(pw.schema_builder({"v": int}), [(1,)])
df = pw.debug.table_to_pandas(t)  # debug capture mid-build must not end analysis

@pw.udf
def stamp(a: int) -> float:
    return time.time() + a

t.select(x=stamp(t.v))
pw.run(monitoring_level=pw.MonitoringLevel.NONE)
"""


def test_cli_analyze_program_crash_is_exit_3(tmp_path):
    # a crashing program must not collide with the 0/1/2 diagnostic contract
    prog = tmp_path / "crash.py"
    prog.write_text(_CRASH_PROG)
    p = _analyze_cli(str(prog), "--format", "json")
    assert p.returncode == 3, p.stdout + p.stderr
    assert "crashed" in p.stderr


def test_cli_analyze_debug_helper_does_not_truncate(tmp_path):
    # pw.debug mid-program executes normally under capture; the analyzer still
    # sees the FULL graph built afterwards and reports its errors
    prog = tmp_path / "dbg.py"
    prog.write_text(_DEBUG_THEN_ERROR_PROG)
    p = _analyze_cli(str(prog), "--format", "json")
    assert p.returncode == 2, p.stdout + p.stderr
    payload = _parse_json_stdout(p.stdout)
    assert any(d["code"] == "PWA001" for d in payload["diagnostics"])


def test_cli_analyze_clean_sweep_over_examples():
    """The analyzer reports zero errors over the shipped example programs
    (06 drives a spawn cluster from a driver script and is exercised by
    test_cli instead)."""
    examples = [
        "01_streaming_wordcount.py",
        "02_etl_joins.py",
        "03_windows_and_behaviors.py",
        "04_vector_index_rag.py",
        "05_persistence_resume.py",
    ]
    for name in examples:
        p = _analyze_cli(os.path.join(REPO, "examples", name), "--format", "json")
        payload = _parse_json_stdout(p.stdout)
        assert payload["summary"]["errors"] == 0, (name, p.stdout, p.stderr)
        assert p.returncode in (0, 1), (name, p.stdout, p.stderr)


def test_bench_like_graph_clean():
    """A representative bench-engine pipeline (join + groupby + filter chain)
    carries no lint errors."""
    left = pw.debug.table_from_rows(
        pw.schema_builder({"k": int, "a": int}), [(i, i * 2) for i in range(20)]
    )
    right = pw.debug.table_from_rows(
        pw.schema_builder({"k": int, "b": int}), [(i, i * 3) for i in range(20)]
    )
    joined = left.join(right, left.k == right.k).select(
        k=pw.left.k, s=pw.left.a + pw.right.b
    )
    filtered = joined.filter(joined.s > 4)
    filtered.groupby(filtered.k).reduce(filtered.k, total=pw.reducers.sum(filtered.s))
    report = analyze_graph(pg.G._current, persistence=True)
    assert not report.errors, report.to_json()


def test_analyzer_overhead_negligible():
    """The build-time lint of a mid-sized graph stays well under a commit's
    budget (acceptance: no measurable tier-1 slowdown)."""
    t = _ints_table()
    cur = t
    for _ in range(30):
        cur = cur.select(v=cur.v + 1)
    cur.groupby(cur.v).reduce(cur.v, n=pw.reducers.count())
    t0 = time.perf_counter()
    analyze_graph(pg.G._current)
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.0, f"analysis took {elapsed:.3f}s on a 30-node chain"
