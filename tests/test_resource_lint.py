"""Resource-lifecycle & exception-contract lint (analysis/resources.py):
planted golden violations per pass (PWA201 acquire/release incl. the
interprocedural release-via-helper corner, PWA202 typed-error swallowing,
PWA203 write-only state with the ctor exemption, PWA204 finally masking,
PWA205 telemetry drift), noqa suppression, the clean-tree gate, the
``cli analyze --runtime`` fold-in with per-pass ``checked`` flags, telemetry
mirroring through the OpenMetrics grammar, the knob-drift audit, and one-line
regressions for the findings this PR fixed on the tree."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from pathway_tpu.analysis import (
    RESOURCE_MODULES,
    Severity,
    analyze_resource_source,
    analyze_resources,
    analyze_runtime_full,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# PWA201 — acquire/release pairing
# ---------------------------------------------------------------------------

_LEAK = '''
import socket

class Wiring:
    def leak(self):
        s = socket.socket()
        s.connect(("127.0.0.1", 1))
        s.close()

    def ok_finally(self):
        s = socket.socket()
        try:
            s.connect(("127.0.0.1", 1))
        finally:
            s.close()

    def ok_with(self):
        with open("f") as f:
            return f.read()

    def ok_escape(self):
        s = socket.socket()
        return s

    def ok_tail(self):
        f = open("x")
        f.close()
'''


def test_pwa201_unprotected_release_flagged():
    report = analyze_resource_source(_LEAK)
    found = report.by_code("PWA201")
    assert len(found) == 1, report.to_json()
    d = found[0]
    assert d.severity == Severity.ERROR
    assert "leak" in (d.function or "")
    assert d.details["resource"] == "socket"


def test_pwa201_release_via_helper_interprocedural():
    # the class-attr corner: the socket is released only inside a teardown
    # helper (called from a finally elsewhere) — the pass must find the
    # release THROUGH the helper, not demand a literal close at the acquire
    src = '''
import socket

class Held:
    def start(self):
        self.sock = socket.socket()
        try:
            self.sock.connect(("127.0.0.1", 1))
        finally:
            self._teardown()

    def _teardown(self):
        self.sock.close()
'''
    assert not analyze_resource_source(src).by_code("PWA201")


def test_pwa201_class_attr_without_releaser_flagged():
    src = '''
import socket

class NeverClosed:
    def start(self):
        self.sock = socket.socket()
'''
    found = analyze_resource_source(src).by_code("PWA201")
    assert found and found[0].details["attr"] == "sock"


def test_pwa201_alias_swap_release_found():
    # the idempotent-close idiom: `h, self.h = self.h, None` then h.close()
    src = '''
import socket

class Swapped:
    def start(self):
        self.sock = socket.socket()

    def close(self):
        sock, self.sock = self.sock, None
        sock.close()
'''
    assert not analyze_resource_source(src).by_code("PWA201")


def test_pwa201_slot_store_without_finally_pop_flagged():
    src = '''
class Handler:
    def __init__(self):
        self.futures = {}

    def serve(self, key, fut):
        self.futures[key] = fut
        result = self.await_it(fut)
        self.futures.pop(key, None)
        return result

    def await_it(self, fut):
        return fut
'''
    found = analyze_resource_source(src).by_code("PWA201")
    assert found, "success-only slot pop must be flagged"
    assert found[0].details["container"] == "futures"
    fixed = src.replace(
        "        result = self.await_it(fut)\n"
        "        self.futures.pop(key, None)\n"
        "        return result",
        "        try:\n"
        "            return self.await_it(fut)\n"
        "        finally:\n"
        "            self.futures.pop(key, None)",
    )
    assert not analyze_resource_source(fixed).by_code("PWA201")


def test_pwa201_noqa_suppresses_with_reason():
    suppressed = _LEAK.replace(
        "        s = socket.socket()\n        s.connect",
        "        s = socket.socket()  # noqa: PWA201 (probe socket, process-lifetime)\n"
        "        s.connect",
    )
    assert not analyze_resource_source(suppressed).by_code("PWA201")


# ---------------------------------------------------------------------------
# PWA202 — typed-error swallowing
# ---------------------------------------------------------------------------

_SWALLOW = '''
class PeerGoneError(ConnectionError):
    pass

class Loop:
    def commit(self):
        try:
            self.exchange()
        except Exception:
            pass

    def exchange(self):
        raise PeerGoneError("peer died")
'''


def test_pwa202_typed_swallow_flagged_interprocedurally():
    report = analyze_resource_source(_SWALLOW)
    found = report.by_code("PWA202")
    assert len(found) == 1, report.to_json()
    assert found[0].severity == Severity.ERROR
    assert "PeerGoneError" in found[0].message


def test_pwa202_isinstance_triage_and_reraise_quiet():
    triaged = _SWALLOW.replace(
        "        except Exception:\n            pass",
        "        except Exception as exc:\n"
        "            if isinstance(exc, PeerGoneError):\n"
        "                raise\n"
        "            pass",
    )
    assert not analyze_resource_source(triaged).by_code("PWA202")


def test_pwa202_specific_handler_before_broad_quiet():
    narrowed = _SWALLOW.replace(
        "        except Exception:\n            pass",
        "        except PeerGoneError:\n"
        "            raise\n"
        "        except Exception:\n"
        "            pass",
    )
    assert not analyze_resource_source(narrowed).by_code("PWA202")


def test_pwa202_capture_for_transfer_quiet():
    # a worker-thread handler that SHIPS the exception to its waiters is not
    # swallowing it (the coalescer/encoder-service propagate pattern)
    shipped = _SWALLOW.replace(
        "        except Exception:\n            pass",
        "        except Exception as exc:\n            self.error = exc",
    )
    assert not analyze_resource_source(shipped).by_code("PWA202")


def test_pwa202_log_and_continue_is_still_a_swallow():
    # capture-for-transfer means STORING the exception for another consumer;
    # logging it (or `msg = str(exc)` into a local) is log-and-continue —
    # exactly the fence-wedging swallow the pass exists to catch
    logged = _SWALLOW.replace(
        "        except Exception:\n            pass",
        "        except Exception as exc:\n"
        "            import logging\n"
        '            logging.warning("failed: %s", exc)',
    )
    assert analyze_resource_source(logged).by_code("PWA202")
    localed = _SWALLOW.replace(
        "        except Exception:\n            pass",
        "        except Exception as exc:\n            msg = str(exc)",
    )
    assert analyze_resource_source(localed).by_code("PWA202")


def test_pwa202_base_exception_flagged_even_without_typed_raise():
    src = '''
class Quiet:
    def go(self):
        try:
            print("x")
        except BaseException:
            pass
'''
    found = analyze_resource_source(src).by_code("PWA202")
    assert found and "GraphCaptureInterrupt" in found[0].message


def test_pwa202_noqa_suppresses():
    suppressed = _SWALLOW.replace(
        "        except Exception:",
        "        except Exception:  # noqa: PWA202 (commit loop absorbs, fence retries)",
    )
    assert not analyze_resource_source(suppressed).by_code("PWA202")


# ---------------------------------------------------------------------------
# PWA203 — write-only / dead attribute state
# ---------------------------------------------------------------------------

_DEAD = '''
class Tracker:
    def __init__(self):
        self.parked = {}
        self.config = 7

    def park(self, rank, cont):
        self.parked[rank] = cont
'''


def test_pwa203_write_only_attr_flagged_ctor_exempt():
    report = analyze_resource_source(_DEAD)
    found = report.by_code("PWA203")
    # `parked` is written in park() and read nowhere; `config` is only
    # written in the constructor (exempt — external readers are likely)
    assert len(found) == 1, report.to_json()
    assert found[0].details["attr"] == "parked"
    assert found[0].severity == Severity.WARNING


def test_pwa203_read_anywhere_quiet():
    read = _DEAD + '''
class Restorer:
    def restore(self, tracker, rank):
        return tracker.parked.get(rank)
'''
    assert not analyze_resource_source(read).by_code("PWA203")


def test_pwa203_noqa_suppresses_with_reason():
    suppressed = _DEAD.replace(
        "        self.parked[rank] = cont",
        "        self.parked[rank] = cont  # noqa: PWA203 (read by the joiner via snapshot)",
    )
    assert not analyze_resource_source(suppressed).by_code("PWA203")


# ---------------------------------------------------------------------------
# PWA204 — exception-masking finally
# ---------------------------------------------------------------------------


def test_pwa204_raise_and_return_in_finally_flagged():
    src = '''
class Cleanup:
    def masks_with_raise(self):
        try:
            self.work()
        finally:
            raise RuntimeError("cleanup failed")

    def masks_with_return(self):
        try:
            self.work()
        finally:
            return None

    def work(self):
        pass
'''
    report = analyze_resource_source(src)
    found = report.by_code("PWA204")
    assert len(found) == 2, report.to_json()
    assert all(d.severity == Severity.ERROR for d in found)


def test_pwa204_typed_raising_call_in_finally_flagged_guard_quiet():
    src = '''
class FenceError(ConnectionError):
    pass

class Teardown:
    def bad(self):
        try:
            pass
        finally:
            self.release()

    def good(self):
        try:
            pass
        finally:
            try:
                self.release()
            except Exception as exc:
                self.last_error = exc

    def release(self):
        raise FenceError("peer gone")
'''
    report = analyze_resource_source(src)
    found = report.by_code("PWA204")
    assert len(found) == 1, report.to_json()
    assert "FenceError" in found[0].message
    assert "bad" in (found[0].function or "")


# ---------------------------------------------------------------------------
# PWA205 — telemetry-contract drift
# ---------------------------------------------------------------------------


def test_pwa205_unregistered_namespace_flagged():
    src = '''
from pathway_tpu.engine import telemetry

class Stage:
    def go(self):
        telemetry.stage_add("bogus.counter")
        telemetry.stage_add("cluster.fine")
        with telemetry.stage_timer("embed.also_fine"):
            pass
'''
    report = analyze_resource_source(src)
    found = report.by_code("PWA205")
    assert len(found) == 1, report.to_json()
    assert found[0].details["stage"] == "bogus.counter"


def test_pwa205_add_many_dict_keys_and_fstring_heads_checked():
    src = '''
from pathway_tpu.engine import telemetry

class Stage:
    def go(self, peer, kind):
        telemetry.stage_add_many({
            "exchange.barriers": 1.0,
            f"forked.peer{peer}.bytes": 2.0,
        })
        telemetry.stage_add(f"cluster.{kind}")
'''
    report = analyze_resource_source(src)
    found = report.by_code("PWA205")
    assert len(found) == 1, report.to_json()
    assert found[0].details["stage"].startswith("forked.")


def test_pwa205_truncated_complete_literal_flagged():
    # a COMPLETE literal must carry a full registered prefix — "clu" would
    # fork from /metrics even though "cluster." starts with it; only an
    # f-string HEAD may be shorter than its namespace (the tail is dynamic)
    src = '''
from pathway_tpu.engine import telemetry

class S:
    def go(self, x):
        telemetry.stage_add("clu")
        telemetry.stage_add(f"embed{x}")
'''
    found = analyze_resource_source(src).by_code("PWA205")
    assert [d.details["stage"] for d in found] == ["clu"]


def test_pwa205_add_many_via_local_dict_checked():
    src = '''
from pathway_tpu.engine import telemetry

class Stage:
    def go(self, n):
        updates = {"exchange.barriers": 1.0}
        updates[f"offbrand.peer{n}"] = 1.0
        telemetry.stage_add_many(updates)
'''
    found = analyze_resource_source(src).by_code("PWA205")
    assert len(found) == 1 and found[0].details["stage"].startswith("offbrand.")


def test_pwa205_unknown_flight_event_kind_flagged():
    src = '''
from pathway_tpu.engine.profile import get_flight_recorder

class Ev:
    def go(self):
        get_flight_recorder().record_event("fence")
        get_flight_recorder().record_event("surprise_event", detail=1)
'''
    found = analyze_resource_source(src).by_code("PWA205")
    assert len(found) == 1 and found[0].details["event"] == "surprise_event"


def test_pwa205_unknown_trace_span_kind_flagged():
    # span kinds are a closed set (telemetry.TRACE_SPAN_KINDS): the trace
    # merger and critical-path analysis key on them, so an off-registry
    # literal in trace_span()/start()/record_span() is flagged; variable
    # kinds and registered literals stay quiet
    src = '''
from pathway_tpu.engine.tracing import get_tracer, trace_span

class Sp:
    def go(self, kind):
        with trace_span("rest", "GET /v1/retrieve"):
            pass
        with get_tracer().trace_span("made_up_kind", "oops"):
            pass
        span = get_tracer().start("barrier", "b")
        with trace_span(kind):
            pass
'''
    found = analyze_resource_source(src).by_code("PWA205")
    assert len(found) == 1, [d.message for d in found]
    assert found[0].details["span_kind"] == "made_up_kind"


def test_pwa205_registry_has_no_ghost_namespaces():
    # the registry itself can drift: every registered namespace must still
    # have at least one live mention in the analyzed tree, or the registry
    # documents ghosts
    from pathway_tpu.engine.telemetry import STAGE_NAMESPACES

    joined = ""
    for rel in RESOURCE_MODULES + ("pathway_tpu/analysis/framework.py",):
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            with open(path, "r", encoding="utf-8") as f:
                joined += f.read()
    dead = [ns for ns in STAGE_NAMESPACES if ns not in joined]
    assert not dead, f"registered but unused namespaces: {dead}"


# ---------------------------------------------------------------------------
# the tree gate (acceptance: zero PWA201-205 errors on the runtime)
# ---------------------------------------------------------------------------


def test_resource_tree_is_clean():
    report = analyze_resources()
    assert report.exit_code() == 0, report.to_json()
    assert not report.errors, report.to_json()


def test_runtime_full_tree_is_clean_and_all_passes_checked():
    report = analyze_runtime_full()
    assert report.exit_code() == 0, report.to_json()
    for code in ("PWA101", "PWA102", "PWA103", "PWA104",
                 "PWA201", "PWA202", "PWA203", "PWA204", "PWA205"):
        assert report.pass_checked.get(code) is True, report.pass_checked


def test_resource_modules_all_present():
    missing = [
        rel for rel in RESOURCE_MODULES if not os.path.exists(os.path.join(REPO, rel))
    ]
    assert not missing, f"RESOURCE_MODULES entries vanished: {missing}"


def test_crashed_resource_pass_reports_warning_and_unchecked():
    from pathway_tpu.analysis.resources import ResourcePass

    class Exploder(ResourcePass):
        code = "PWA203"

        def run(self, ctx):
            raise RuntimeError("parser changed under me")

    report = analyze_resources(passes=[Exploder()])
    assert report.exit_code() == 1
    assert report.exit_code(strict=True) == 2
    assert "NOT being checked" in report.warnings[0].message
    assert report.pass_checked == {"PWA203": False}
    assert json.loads(report.to_json())["summary"]["checked"] == {"PWA203": False}


# ---------------------------------------------------------------------------
# regressions for the findings this PR fixed on today's tree
# ---------------------------------------------------------------------------


def _src(rel: str) -> str:
    with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
        return f.read()


def test_fixed_dead_state_stays_dead():
    # each was a PWA203 finding: write-only state deleted (or wired) in this PR
    assert "_membership_target" not in _src("pathway_tpu/parallel/cluster.py")
    assert "_fusion_plan" not in _src("pathway_tpu/engine/runner.py")
    assert "_ckpt_attempts" not in _src("pathway_tpu/engine/runner.py")
    assert "self._source = source" not in _src("pathway_tpu/io/http/_server.py")


def test_model_counters_are_wired_into_invariants():
    # `installed`/`stale_dropped` were write-only model state; now invariants
    src = _src("pathway_tpu/internals/protocol_models.py")
    assert "assert surv.installed" in src
    assert "surv.stale_dropped ==" in src or "+ surv.stale_dropped" in src


def test_healthz_probe_triages_typed_peer_errors():
    """A probe aborted by the epoch fence reports state=fencing (recoverable
    protocol state), not a generic degradation."""
    import urllib.request

    from pathway_tpu.engine.http_server import MonitoringServer, ProberStats
    from pathway_tpu.parallel.cluster import ClusterFenceError

    server = MonitoringServer(ProberStats(), 0)

    def fencing_source():
        raise ClusterFenceError("peer 1 died; fencing at epoch 3")

    server.health_source = fencing_source
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/healthz", timeout=5
        ) as resp:
            assert resp.status == 200
            payload = json.loads(resp.read())
    finally:
        server.close()
    assert payload["state"] == "fencing"
    assert "epoch 3" in payload["error"]


def test_retrying_store_does_not_retry_not_found():
    """A not-found raised by an inner client is definitive: the retry wrapper
    must surface it immediately instead of burning the whole backoff budget."""
    from pathway_tpu.persistence.backends import ObjectStore, RetryingObjectStore

    calls = {"n": 0}

    class NotFoundStore(ObjectStore):
        def get(self, key):
            calls["n"] += 1
            raise FileNotFoundError(key)

    store = RetryingObjectStore(NotFoundStore())
    with pytest.raises(FileNotFoundError):
        store.get("absent")
    assert calls["n"] == 1, f"not-found was retried {calls['n']} times"


def test_retrying_store_still_retries_transient():
    from pathway_tpu.persistence.backends import ObjectStore, RetryingObjectStore

    calls = {"n": 0}

    class Transient(Exception):
        pass

    class FlakyStore(ObjectStore):
        def get(self, key):
            calls["n"] += 1
            if calls["n"] < 3:
                raise Transient("throttled")
            return b"ok"

    store = RetryingObjectStore(FlakyStore())
    assert store.get("k") == b"ok"
    assert calls["n"] == 3


# ---------------------------------------------------------------------------
# cli analyze --runtime: the fold-in + checked field
# ---------------------------------------------------------------------------


def _cli_env():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    return env


def test_cli_analyze_runtime_includes_resource_passes():
    proc = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.cli", "analyze", "--runtime",
         "--format", "json"],
        capture_output=True,
        text=True,
        env=_cli_env(),
        timeout=180,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout[proc.stdout.index("{"):])
    assert payload["summary"]["errors"] == 0, proc.stdout
    for code in ("PWA101", "PWA201", "PWA202", "PWA203", "PWA204", "PWA205"):
        assert code in payload["summary"]["pass_seconds"], payload["summary"]
        assert payload["summary"]["checked"][code] is True, payload["summary"]


def test_resource_gate_modes(monkeypatch):
    from pathway_tpu.analysis import resources
    from pathway_tpu.analysis.framework import AnalysisReport, GraphLintError
    from pathway_tpu.analysis.resources import resource_gate

    planted = analyze_resource_source(_SWALLOW)
    assert planted.errors
    # off (default): no analysis happens at all
    monkeypatch.delenv("PATHWAY_RESOURCE_LINT", raising=False)
    monkeypatch.setattr(
        resources, "analyze_resources", lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("analyzed despite off")
        )
    )
    resource_gate()
    # error mode with a planted error report: refuses
    monkeypatch.setattr(resources, "_cached_report", planted)
    monkeypatch.setenv("PATHWAY_RESOURCE_LINT", "error")
    with pytest.raises(GraphLintError) as exc_info:
        resource_gate()
    assert isinstance(exc_info.value.report, AnalysisReport)
    # warn mode logs but does not refuse
    monkeypatch.setenv("PATHWAY_RESOURCE_LINT", "warn")
    resource_gate()


def test_resource_report_telemetry_counters_and_grammar():
    """lint.diag.PWA20x counters ride the stage counters and survive the
    strict OpenMetrics line grammar on /metrics."""
    from pathway_tpu.engine import telemetry
    from pathway_tpu.engine.http_server import ProberStats

    from .utils import validate_openmetrics

    telemetry.stage_reset("lint.")
    report = analyze_resource_source(_SWALLOW)
    report.emit_telemetry()
    counters = telemetry.stage_snapshot("lint.")
    assert counters.get("lint.diag.PWA202", 0) >= 1, counters
    assert counters.get("lint.errors", 0) >= 1, counters
    text = ProberStats().to_openmetrics()
    validate_openmetrics(text)
    assert 'pathway_stage_total{stage="lint.diag.PWA202"}' in text


# ---------------------------------------------------------------------------
# knob-drift audit: code PATHWAY_* reads <-> README env-knob tables
# ---------------------------------------------------------------------------

_KNOB_RE = re.compile(r"PATHWAY_[A-Z0-9_]*[A-Z0-9]")


def _code_knobs() -> set:
    out = set()
    for base, dirs, files in os.walk(os.path.join(REPO, "pathway_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(base, name), "r", encoding="utf-8") as f:
                out.update(_KNOB_RE.findall(f.read()))
    return out


def test_env_knobs_match_readme_tables():
    """The env-knob tables grew by hand across 13 PRs: every PATHWAY_* the
    code reads must appear in README.md, and every documented knob must still
    exist in code — else the docs describe a ghost."""
    with open(os.path.join(REPO, "README.md"), "r", encoding="utf-8") as f:
        documented = set(_KNOB_RE.findall(f.read()))
    in_code = _code_knobs()
    undocumented = sorted(in_code - documented)
    assert not undocumented, (
        f"PATHWAY_* knobs read in code but absent from every README table: "
        f"{undocumented} — add them to the README env-knob (or internal "
        "wiring) table"
    )
    dead = sorted(documented - in_code)
    assert not dead, (
        f"README documents knobs no code reads: {dead} — delete the rows or "
        "restore the knobs"
    )


def test_b904_raise_from_discipline_holds_without_ruff():
    """ruff.toml carries B904, but this container may not ship a ruff binary:
    the AST fallback keeps the raise-from discipline enforced either way."""
    import ast

    hits = []
    for base, dirs, files in os.walk(os.path.join(REPO, "pathway_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            with open(path, "r", encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.ExceptHandler):
                    for sub in ast.walk(node):
                        if (
                            isinstance(sub, ast.Raise)
                            and sub.exc is not None
                            and sub.cause is None
                        ):
                            hits.append(f"{os.path.relpath(path, REPO)}:{sub.lineno}")
    assert not hits, (
        f"raise without `from` inside except (B904): {hits} — chain the cause "
        "(`from exc`) or sever it explicitly (`from None`)"
    )


# ---------------------------------------------------------------------------
# dynamic leak oracle: the PWA201 model proven against the live runtime
# ---------------------------------------------------------------------------

_ORACLE_PROG = """
import json, os
import pathway_tpu as pw

tmp = os.environ["PATHWAY_TPU_TEST_DIR"]
pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

class WordSchema(pw.Schema):
    word: str

t = pw.io.fs.read(
    os.path.join(tmp, "in"), format="csv", schema=WordSchema, mode="static"
)
counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())

rows = {}
def on_change(key, row, time, is_addition):
    if is_addition:
        rows[row["word"]] = int(row["total"])
    else:
        rows.pop(row["word"], None)

pw.io.subscribe(counts, on_change)
pw.run(monitoring_level=pw.MonitoringLevel.NONE)
with open(os.path.join(tmp, f"out_{pid}.json"), "w") as f:
    json.dump(rows, f)
"""


def test_leak_oracle_around_n2_spawn_acceptance(tmp_path, leak_oracle):
    """The acceptance: an n=2 spawn run completes bit-exactly AND leaves this
    process with zero fd/socket/thread growth (the oracle fixture asserts the
    growth half after the test body)."""
    (tmp_path / "in").mkdir()
    (tmp_path / "in" / "a.csv").write_text("word\nalpha\nbeta\nalpha\n")
    (tmp_path / "in" / "b.csv").write_text("word\nbeta\ngamma\nbeta\n")
    prog = tmp_path / "prog.py"
    prog.write_text(_ORACLE_PROG)
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "--first-port", str(26000 + os.getpid() % 500 * 4),
            sys.executable, str(prog),
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
        cwd=str(tmp_path),
    )
    assert out.returncode == 0, f"spawn failed:\nstdout={out.stdout}\nstderr={out.stderr}"
    merged: dict = {}
    for p in range(2):
        merged.update(json.loads((tmp_path / f"out_{p}.json").read_text()))
    assert merged == {"alpha": 2, "beta": 3, "gamma": 1}


def test_leak_oracle_around_in_process_run_with_monitoring(leak_oracle):
    """An in-process run with the monitoring HTTP server live ALONGSIDE it
    must tear down the listener socket and serving threads completely once
    closed — the leaked-listener class PWA201 models for
    MonitoringServer.close (the server serves a real request mid-run, so a
    half-closed accept thread would show up as a leaked thread/socket)."""
    import urllib.request

    import pathway_tpu as pw
    from pathway_tpu.engine.http_server import MonitoringServer, ProberStats

    server = MonitoringServer(ProberStats(), 0)
    try:
        t = pw.debug.table_from_rows(pw.schema_builder({"v": int}), [(1,), (2,)])
        got = []
        pw.io.subscribe(t, lambda key, row, time, is_addition: got.append(row["v"]))
        pw.run(monitoring_level=pw.MonitoringLevel.NONE)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=5
        ) as resp:
            assert resp.status == 200
    finally:
        server.close()
    assert sorted(got) == [1, 2]
