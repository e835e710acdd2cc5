"""Distributed-tracing spawn acceptances (ISSUE 20): a real ``spawn -n 2``
cluster, tracing on —

- **one tree** — the deterministic ``(epoch, commit)`` trace id makes every
  rank's commit span a sibling in ONE trace with nothing riding the wire;
  the merged rank files must show a single commit trace holding spans from
  BOTH ranks with operator/barrier children parented inside it;
- **cli trace** — ``python -m pathway_tpu.cli trace <dir>`` merges the rank
  files and NAMES the critical-path span;
- **partial trace from the black box** — a chaos-SIGKILL'd rank's flight
  dump embeds its newest spans (the jsonl flush + payload ride the dump
  path), so the merger still renders the dead rank's side of the story.

Budgets mirror the other spawn acceptances: 240 s worst case, seconds on an
idle machine.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.trace


TRACED_WORDCOUNT_PROG = textwrap.dedent(
    """
    import json, os
    import pathway_tpu as pw

    tmp = os.environ["PATHWAY_TPU_TEST_DIR"]
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    words = json.load(open(os.path.join(tmp, f"input_{pid}.json")))
    # several timestamped batches -> several commits, so commit spans from
    # both ranks land in shared (epoch, commit) traces
    rows = [(w, 2 * (i // 40), 1) for i, w in enumerate(words)]
    tbl = pw.debug.table_from_rows(
        pw.schema_builder({"word": str}), rows, is_stream=True
    )
    counts = tbl.groupby(pw.this.word).reduce(
        pw.this.word, cnt=pw.reducers.count()
    )
    pw.io.subscribe(counts, lambda key, row, time, is_addition: None)
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    json.dump({"done": pid}, open(os.path.join(tmp, f"out_{pid}.json"), "w"))
    """
)


def _trace_env(trace_dir) -> dict:
    return {
        "JAX_PLATFORMS": "cpu",
        "PATHWAY_TRACE": "on",
        "PATHWAY_TRACE_SAMPLE": "1.0",
        "PATHWAY_TRACE_DIR": str(trace_dir),
        "PATHWAY_FLIGHT_RECORDER_DIR": str(trace_dir),
    }


def _spawn_blocking(n, program, tmp_path, extra_env, first_port) -> None:
    prog = tmp_path / "prog.py"
    prog.write_text(program)
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env.update(extra_env)
    out = subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", str(n), "--first-port", str(first_port),
            sys.executable, str(prog),
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
        cwd=str(tmp_path),
    )
    assert out.returncode == 0, (
        f"spawn failed:\nstdout={out.stdout}\nstderr={out.stderr}"
    )


def test_spawn_n2_commit_trace_merges_into_one_tree_and_cli_names_critical_path(
    tmp_path,
):
    """THE tracing acceptance: after a clean n=2 run, the merged rank files
    hold at least one trace whose commit spans come from BOTH ranks (the
    deterministic trace id needs no wire coordination), whose child spans all
    parent inside the trace, and ``cli trace`` names its critical path."""
    from pathway_tpu.engine.tracing import merge_trace_files

    trace_dir = tmp_path / "traces"
    trace_dir.mkdir()
    for p in range(2):
        (tmp_path / f"input_{p}.json").write_text(
            json.dumps([f"word{i % 17}" for i in range(160)])
        )
    first_port = 21000 + os.getpid() % 400 * 4
    _spawn_blocking(
        2, TRACED_WORDCOUNT_PROG, tmp_path, _trace_env(trace_dir), first_port
    )

    paths = sorted(str(p) for p in trace_dir.glob("trace-rank-*.jsonl"))
    assert len(paths) == 2, f"expected both rank flushes, got {paths}"
    merged = merge_trace_files(paths)
    spans = merged["spans"]
    assert spans, "no spans in either rank flush"

    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
    # at least one commit trace with commit spans from BOTH ranks
    shared = {
        tid: ss
        for tid, ss in by_trace.items()
        if {s["rank"] for s in ss if s["kind"] == "commit"} == {0, 1}
    }
    assert shared, (
        "no trace holds commit spans from both ranks — the deterministic "
        f"(epoch, commit) trace id broke; kinds seen: "
        f"{sorted({s['kind'] for s in spans})}"
    )
    tid, tree_spans = next(iter(sorted(shared.items())))
    ids = {s["span_id"] for s in tree_spans}
    dangling = [
        s for s in tree_spans
        if s["parent_id"] is not None and s["parent_id"] not in ids
    ]
    assert not dangling, f"spans parented OUTSIDE their own trace: {dangling}"
    # the commit spans have real children (operator / barrier substeps)
    child_kinds = {
        s["kind"] for s in tree_spans if s["parent_id"] is not None
    }
    assert child_kinds, f"commit spans have no children in trace {tid}"

    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "trace",
            str(trace_dir), "--limit", "2",
        ],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, f"cli trace failed:\n{out.stdout}\n{out.stderr}"
    assert "critical path:" in out.stdout, out.stdout
    # the critical-path line names a registered span kind
    from pathway_tpu.engine.telemetry import TRACE_SPAN_KINDS

    assert any(k in out.stdout for k in TRACE_SPAN_KINDS), out.stdout


TRACED_STREAMING_PROG = textwrap.dedent(
    """
    import os
    import pathway_tpu as pw

    tmp = os.environ["PATHWAY_TPU_TEST_DIR"]

    class WordSchema(pw.Schema):
        word: str

    t = pw.io.fs.read(
        os.path.join(tmp, "in"), format="csv", schema=WordSchema,
        mode="streaming",
    )
    counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())
    pw.io.subscribe(counts, lambda key, row, time, is_addition: None)
    cfg = pw.persistence.Config(
        pw.persistence.Backend.filesystem(os.path.join(tmp, "store"))
    )
    pw.run(persistence_config=cfg, monitoring_level=pw.MonitoringLevel.NONE)
    """
)


@pytest.mark.chaos
def test_spawn_n2_chaos_killed_rank_leaves_partial_trace_in_flight_dump(
    tmp_path,
):
    """SIGKILL rank 1 mid-run: the black box written just before the kill
    must embed rank 1's newest spans (commit spans with the shared trace id),
    and the merger accepts the flight dump as a trace source — the dead
    rank's side of the story survives its death."""
    from pathway_tpu.engine.tracing import load_flight_spans, merge_trace_files

    trace_dir = tmp_path / "traces"
    trace_dir.mkdir()
    (tmp_path / "in").mkdir()
    for i in range(2):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 2) + ["dog"] * 3) + "\n"
        )
    prog = tmp_path / "prog.py"
    prog.write_text(TRACED_STREAMING_PROG)

    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env.update(_trace_env(trace_dir))
    env["PATHWAY_CHAOS_SEED"] = "7"
    env["PATHWAY_CHAOS_PLAN"] = json.dumps(
        {"kill": [{"rank": 1, "commit": 2, "run": 0}]}
    )
    env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
    env["PATHWAY_BARRIER_TIMEOUT_S"] = "30"
    first_port = 21000 + os.getpid() % 400 * 4 + 2
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "--first-port", str(first_port),
            "--max-restarts", "1",
            sys.executable, str(prog),
        ],
        env=env,
        cwd=str(tmp_path),
        start_new_session=True,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE,
        text=True,
    )
    dump_path = trace_dir / "flight-rank-1.json"
    killed_payload = None
    try:
        deadline = time.time() + 150
        while time.time() < deadline:
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(
                    f"spawn exited early (rc={proc.returncode}): {err}"
                )
            if dump_path.exists():
                try:
                    payload = json.loads(dump_path.read_text())
                except ValueError:
                    payload = None  # racing the atomic rename
                if payload and payload.get("reason") == "chaos_kill":
                    killed_payload = payload
                    break
            time.sleep(0.3)
    finally:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.communicate(timeout=20)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.communicate()

    assert killed_payload is not None, "chaos_kill flight dump never appeared"
    spans = (killed_payload.get("trace") or {}).get("spans") or []
    assert spans, "killed rank's flight dump embeds no spans"
    assert any(s["kind"] == "commit" and s["rank"] == 1 for s in spans), (
        f"no rank-1 commit span in the dump; kinds: "
        f"{sorted({s['kind'] for s in spans})}"
    )
    # the merger accepts the dump as a trace source (partial-trace guarantee)
    flight_spans = load_flight_spans(str(dump_path))
    assert flight_spans, "merger read no spans back from the flight dump"
    merged = merge_trace_files([], flight_paths=[str(dump_path)])
    assert any(s["rank"] == 1 for s in merged["spans"])
