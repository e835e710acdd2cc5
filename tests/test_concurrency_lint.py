"""Concurrency-lint tests (pathway_tpu/analysis/concurrency.py): one planted
violation per pass (PWA101 lock-order cycle + call-chain self-deadlock, PWA102
unbounded waits, PWA103 unlocked shared writes with the constructor exemption,
PWA104 thread lifecycle), noqa suppression, the ``cli analyze --runtime``
exit-code contract, the clean-tree gate the acceptance criteria demand, and
telemetry mirroring."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from pathway_tpu.analysis import Severity, analyze_runtime, analyze_source
from pathway_tpu.analysis.concurrency import (
    RUNTIME_MODULES,
    LockOrderPass,
    build_runtime_context,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(report):
    return {d.code for d in report.diagnostics}


# ---------------------------------------------------------------------------
# PWA101 — lock-order cycles
# ---------------------------------------------------------------------------

_INVERSION = '''
import threading

class Inverted:
    def __init__(self):
        self.a = threading.Lock()
        self.b = threading.Lock()

    def forward(self):
        with self.a:
            with self.b:
                pass

    def backward(self):
        with self.b:
            with self.a:
                pass
'''


def test_pwa101_inversion_cycle_flagged():
    report = analyze_source(_INVERSION)
    found = report.by_code("PWA101")
    assert found, report.to_json()
    d = found[0]
    assert d.severity == Severity.ERROR
    assert "Inverted.a" in d.message and "Inverted.b" in d.message
    assert d.line is not None


def test_pwa101_consistent_order_quiet():
    consistent = _INVERSION.replace(
        "with self.b:\n            with self.a:",
        "with self.a:\n            with self.b:",
    )
    assert not analyze_source(consistent).by_code("PWA101")


def test_pwa101_call_chain_self_deadlock():
    src = '''
import threading

class SelfDead:
    def __init__(self):
        self.lk = threading.Lock()
    def outer(self):
        with self.lk:
            self.inner()
    def inner(self):
        with self.lk:
            pass
'''
    report = analyze_source(src)
    assert report.by_code("PWA101"), report.to_json()
    # an RLock is reentrant: same shape is legal
    assert not analyze_source(
        src.replace("threading.Lock()", "threading.RLock()")
    ).by_code("PWA101")


def test_pwa101_cross_method_cycle_via_calls():
    src = '''
import threading

class TwoLayers:
    def __init__(self):
        self.outer_lk = threading.Lock()
        self.inner_lk = threading.Lock()
    def path_one(self):
        with self.outer_lk:
            self.helper()
    def helper(self):
        with self.inner_lk:
            pass
    def path_two(self):
        with self.inner_lk:
            with self.outer_lk:
                pass
'''
    report = analyze_source(src)
    found = report.by_code("PWA101")
    assert found, report.to_json()
    assert "TwoLayers.inner_lk" in found[0].message


def test_pwa101_condition_alias_is_not_a_cycle():
    # Condition(self._lock) shares the mutex: with cond inside with lock must
    # not read as a two-lock cycle (it is a self-alias, caught separately)
    src = '''
import threading

class Aliased:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
    def a(self):
        with self._lock:
            pass
    def b(self):
        with self._cond:
            pass
'''
    assert not analyze_source(src).by_code("PWA101")


# ---------------------------------------------------------------------------
# PWA102 — unbounded waits
# ---------------------------------------------------------------------------

_WAITS = '''
import threading
import queue

class W:
    def __init__(self):
        self.cv = threading.Condition()
        self.done = threading.Event()
        self.q = queue.Queue()
    def bad_cv(self):
        with self.cv:
            self.cv.wait()
    def good_cv(self):
        with self.cv:
            self.cv.wait(timeout=0.5)
    def bad_queue(self):
        return self.q.get()
    def good_event(self):
        return self.done.wait(5.0)
    def bad_local(self):
        flag = threading.Event()
        flag.wait()
'''


def test_pwa102_untimed_waits_flagged():
    report = analyze_source(_WAITS)
    lines = sorted(d.line for d in report.by_code("PWA102"))
    assert len(lines) == 3, report.to_json()
    for d in report.by_code("PWA102"):
        assert d.severity == Severity.ERROR


def test_pwa102_queue_get_block_flag_is_not_a_timeout():
    # `q.get(True)` is the BLOCK flag — still an unbounded wait; only the
    # second positional (or timeout=) bounds it
    src = '''
import queue

class Q:
    def __init__(self):
        self.q = queue.Queue()
    def bad(self):
        return self.q.get(True)
    def good(self):
        return self.q.get(True, 5.0)
    def also_good(self):
        return self.q.get(block=True, timeout=5.0)
'''
    report = analyze_source(src)
    lines = sorted(d.line for d in report.by_code("PWA102"))
    assert len(lines) == 1, report.to_json()


def test_pwa102_cross_class_event_receiver():
    src = '''
import threading

class _Req:
    def __init__(self):
        self.event = threading.Event()

class Submitter:
    def submit(self, req):
        req.event.wait()
'''
    found = analyze_source(src).by_code("PWA102")
    assert found and found[0].details["primitive"] == "event"


def test_pwa102_ambiguous_attr_name_quiet():
    # `cv` is also assigned a non-primitive somewhere: the terminal-attribute
    # heuristic must not assume the receiver is the threading one
    src = '''
import threading

class RealCv:
    def __init__(self):
        self.cv = threading.Condition()

class ModelCv:
    def __init__(self, sched):
        self.cv = sched.condition()

class User:
    def go(self, thing):
        thing.cv.wait()
'''
    assert not analyze_source(src).by_code("PWA102")


# ---------------------------------------------------------------------------
# PWA103 — unlocked shared writes
# ---------------------------------------------------------------------------

_UNLOCKED = '''
import threading

class Counter:
    def __init__(self):
        self.lk = threading.Lock()
        self.count = 0
        self._wire()
    def _wire(self):
        self.count = 0
    def inc(self):
        with self.lk:
            self.count += 1
    def reset(self):
        self.count = 0
'''


def test_pwa103_inconsistent_lock_flagged_ctor_exempt():
    report = analyze_source(_UNLOCKED)
    found = report.by_code("PWA103")
    # reset() is flagged; __init__ and _wire (reachable only from __init__)
    # are exempt — no peer thread exists during construction
    assert len(found) == 1, report.to_json()
    assert found[0].details["attr"] == "count"
    assert "reset" in (found[0].function or "")


def test_pwa103_escaped_method_not_exempt():
    src = _UNLOCKED.replace(
        "self._wire()",
        "self._wire()\n        self.t = threading.Thread(target=self._wire, daemon=True)",
    )
    report = analyze_source(src)
    # _wire escapes as a thread target: its unlocked write is now flagged too
    assert len(report.by_code("PWA103")) == 2, report.to_json()


def test_pwa103_single_owner_attr_quiet():
    src = '''
import threading

class SingleOwner:
    def __init__(self):
        self.lk = threading.Lock()
        self.stats = 0
    def a(self):
        self.stats += 1
    def b(self):
        self.stats -= 1
'''
    # never written under a lock anywhere: a single-owner convention, not an
    # inconsistency — quiet
    assert not analyze_source(src).by_code("PWA103")


def test_pwa103_noqa_suppresses_with_reason():
    suppressed = _UNLOCKED.replace(
        "self.count = 0\n",
        "self.count = 0  # noqa: PWA103 (stats are advisory)\n",
    )
    assert not analyze_source(suppressed).by_code("PWA103")


# ---------------------------------------------------------------------------
# PWA104 — thread lifecycle
# ---------------------------------------------------------------------------


def test_pwa104_leaky_thread_flagged():
    src = '''
import threading

def leaky():
    t = threading.Thread(target=print)
    t.start()
'''
    found = analyze_source(src).by_code("PWA104")
    assert found and found[0].severity == Severity.ERROR


def test_pwa104_unrelated_join_does_not_mask_sibling_leak():
    # join/daemon attribution is per-variable for named threads: joining the
    # reader must not excuse the never-joined non-daemon flusher
    src = '''
import threading

def teardown():
    reader = threading.Thread(target=print)
    flusher = threading.Thread(target=print)
    reader.start()
    flusher.start()
    reader.join(timeout=5)
'''
    found = analyze_source(src).by_code("PWA104")
    assert len(found) == 1, [d.to_dict() for d in found]


def test_crashed_pass_reports_warning_not_clean():
    from pathway_tpu.analysis.concurrency import ConcurrencyPass, analyze_runtime

    class Exploder(ConcurrencyPass):
        code = "PWA101"

        def run(self, ctx):
            raise RuntimeError("parser changed under me")

    report = analyze_runtime(passes=[Exploder()])
    # a pass that silently checks nothing must not report the tree CLEAN:
    # exit 1 (2 under --strict) so CI sees the lost coverage
    assert report.exit_code() == 1
    assert report.exit_code(strict=True) == 2
    assert "NOT being checked" in report.warnings[0].message


def test_pwa104_daemon_join_and_late_daemon_quiet():
    src = '''
import threading

def daemonized():
    t = threading.Thread(target=print, daemon=True)
    t.start()

def joined():
    ts = [threading.Thread(target=print) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=5)

def late_daemon():
    t = threading.Thread(target=print)
    t.daemon = True
    t.start()
'''
    assert not analyze_source(src).by_code("PWA104")


# ---------------------------------------------------------------------------
# the tree gate (acceptance: zero PWA101-104 errors on the runtime)
# ---------------------------------------------------------------------------


def test_runtime_tree_is_clean():
    report = analyze_runtime()
    assert report.exit_code() == 0, report.to_json()
    assert not report.errors, report.to_json()


def test_runtime_lock_graph_sees_cross_module_edges():
    # the analysis is only trustworthy if it actually SEES the runtime's lock
    # nesting: the telemetry stage-counter lock taken under exchange/cache
    # locks must appear as edges (and form no cycle)
    ctx = build_runtime_context()
    edges = LockOrderPass().build_graph(ctx)
    idents = {(a, b) for (a, b) in edges}
    assert ("ClusterExchange._cv", "telemetry._stage_lock") in idents, sorted(idents)
    assert ("EmbedCache._lock", "telemetry._stage_lock") in idents, sorted(idents)


def test_runtime_modules_all_present():
    missing = [
        rel for rel in RUNTIME_MODULES if not os.path.exists(os.path.join(REPO, rel))
    ]
    assert not missing, f"RUNTIME_MODULES entries vanished: {missing}"


# ---------------------------------------------------------------------------
# cli analyze --runtime: exit-code contract + telemetry
# ---------------------------------------------------------------------------


def _cli_env():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    return env


def test_cli_analyze_runtime_gate():
    proc = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.cli", "analyze", "--runtime",
         "--format", "json"],
        capture_output=True,
        text=True,
        env=_cli_env(),
        timeout=120,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout[proc.stdout.index("{"):])
    assert payload["summary"]["errors"] == 0, proc.stdout
    assert "PWA101" in payload["summary"]["pass_seconds"]
    assert "PWA104" in payload["summary"]["pass_seconds"]


def test_cli_analyze_runtime_rejects_program_argument():
    # `analyze --runtime my_graph.py` exiting 0 with the program never linted
    # would be a silent CI hole
    proc = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.cli", "analyze", "--runtime",
         "prog.py"],
        capture_output=True,
        text=True,
        env=_cli_env(),
        timeout=60,
        cwd=REPO,
    )
    assert proc.returncode != 0
    assert "takes no PROGRAM" in proc.stderr


def test_cli_analyze_requires_program_without_runtime():
    proc = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.cli", "analyze"],
        capture_output=True,
        text=True,
        env=_cli_env(),
        timeout=60,
        cwd=REPO,
    )
    assert proc.returncode != 0
    assert "PROGRAM is required" in proc.stderr


def test_runtime_lint_gate_modes(monkeypatch):
    from pathway_tpu.analysis import concurrency
    from pathway_tpu.analysis.framework import AnalysisReport, GraphLintError
    from pathway_tpu.analysis.concurrency import runtime_gate

    planted = analyze_source(_INVERSION)  # before patching: it delegates
    assert planted.errors
    # off (default): no analysis happens at all
    monkeypatch.delenv("PATHWAY_RUNTIME_LINT", raising=False)
    monkeypatch.setattr(
        concurrency, "analyze_runtime", lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("analyzed despite off")
        )
    )
    runtime_gate()
    # error mode with a planted error report: refuses
    monkeypatch.setattr(concurrency, "_cached_report", planted)
    monkeypatch.setenv("PATHWAY_RUNTIME_LINT", "error")
    try:
        runtime_gate()
        raise AssertionError("runtime_gate did not refuse")
    except GraphLintError as exc:
        assert isinstance(exc.report, AnalysisReport)
    # warn mode logs but does not refuse
    monkeypatch.setenv("PATHWAY_RUNTIME_LINT", "warn")
    runtime_gate()


def test_runtime_gate_rides_pw_run_and_clean_tree_passes_error_mode(monkeypatch):
    import pathway_tpu as pw
    from pathway_tpu.engine import telemetry

    # error mode on a CLEAN tree must not refuse the run (and must run even
    # with the graph lint disabled — independent knobs)
    monkeypatch.setenv("PATHWAY_RUNTIME_LINT", "error")
    monkeypatch.setenv("PATHWAY_LINT", "off")
    telemetry.stage_reset("lint.")
    t = pw.debug.table_from_rows(pw.schema_builder({"v": int}), [(1,)])
    got = []
    pw.io.subscribe(t, lambda key, row, time, is_addition: got.append(row["v"]))
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert got == [1]
    counters = telemetry.stage_snapshot("lint.")
    assert counters.get("lint.runs", 0) >= 1, counters


def test_runtime_report_telemetry_counters():
    from pathway_tpu.engine import telemetry

    telemetry.stage_reset("lint.")
    report = analyze_source(_INVERSION)
    report.emit_telemetry()
    counters = telemetry.stage_snapshot("lint.")
    assert counters.get("lint.diag.PWA101", 0) >= 1, counters
    assert counters.get("lint.errors", 0) >= 1, counters
