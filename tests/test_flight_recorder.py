"""Flight recorder end-to-end: a chaos-killed cluster leaves a dump whose last
profile is the commit before the kill (the SIGKILL itself is uncatchable — the
chaos harness dumps pre-kill), the supervisor post-mortem names the dump, and a
SIGTERM'd worker dumps from its signal hook."""

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

STREAM_PROG = textwrap.dedent(
    """
    import os
    import pathway_tpu as pw

    tmp = os.environ["PATHWAY_TPU_TEST_DIR"]

    class WordSchema(pw.Schema):
        word: str

    t = pw.io.fs.read(
        os.path.join(tmp, "in"), format="csv", schema=WordSchema, mode="streaming"
    )
    counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())
    pw.io.subscribe(counts, lambda *a, **k: None)
    open(os.path.join(tmp, f"ready-{os.environ.get('PATHWAY_PROCESS_ID', '0')}"), "w").close()
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    """
)


def _base_env(tmp_path) -> dict:
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["PATHWAY_FLIGHT_RECORDER_DIR"] = str(tmp_path / "flight")
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.chaos
@pytest.mark.telemetry
def test_chaos_kill_leaves_flight_record_and_post_mortem_names_it(tmp_path):
    """A kill at commit k yields a recorder dump whose last profile is commit
    k-1, and the supervisor post-mortem attaches the dump path + summary."""
    (tmp_path / "in").mkdir()
    (tmp_path / "flight").mkdir()
    (tmp_path / "in" / "a.csv").write_text("word\ncat\ndog\ncat\n")
    first_port = 27000 + os.getpid() % 500 * 4
    kill_commit = 3
    env = _base_env(tmp_path)
    env["PATHWAY_CHAOS_SEED"] = "1"
    env["PATHWAY_CHAOS_PLAN"] = json.dumps(
        {"kill": [{"rank": 0, "commit": kill_commit, "run": 0}]}
    )
    prog = tmp_path / "prog.py"
    prog.write_text(STREAM_PROG)
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "--first-port", str(first_port),
            sys.executable, str(prog),
        ],
        env=env,
        cwd=str(tmp_path),
        stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE,
        text=True,
    )
    try:
        _, err = proc.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        _, err = proc.communicate()
        raise AssertionError(f"spawn hung after the chaos kill:\n{err}")
    assert proc.returncode != 0

    dump_path = tmp_path / "flight" / "flight-rank-0.json"
    assert dump_path.exists(), f"no flight dump after the chaos kill:\n{err}"
    payload = json.loads(dump_path.read_text())
    assert payload["reason"] == "chaos_kill"
    assert payload["profiles"], "the ring must hold pre-kill commits"
    assert payload["profiles"][-1]["commit"] == kill_commit - 1, (
        "last recorded profile must be the commit BEFORE the kill"
    )
    assert payload["summary"]["last_commit"] == kill_commit - 1
    assert payload["events"][-1]["kind"] == "chaos_kill"
    # every profile carries per-operator entries (ops may be empty only for
    # idle commits; the ingest commit is not idle)
    assert any(p["ops"] for p in payload["profiles"])

    # the supervisor post-mortem attaches the dump path + one-line summary
    assert "flight recorder" in err, err
    assert str(dump_path) in err
    assert f"last commit {kill_commit - 1}" in err


@pytest.mark.telemetry
def test_sigterm_dumps_flight_record(tmp_path):
    (tmp_path / "in").mkdir()
    (tmp_path / "flight").mkdir()
    (tmp_path / "in" / "a.csv").write_text("word\ncat\ndog\n")
    env = _base_env(tmp_path)
    prog = tmp_path / "prog.py"
    prog.write_text(STREAM_PROG)
    proc = subprocess.Popen(
        [sys.executable, str(prog)],
        env=env,
        cwd=str(tmp_path),
        stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE,
        text=True,
    )
    try:
        deadline = time.time() + 60
        ready = tmp_path / "ready-0"
        while time.time() < deadline and not ready.exists():
            assert proc.poll() is None, proc.communicate()[1]
            time.sleep(0.05)
        assert ready.exists(), "program never reached pw.run"
        time.sleep(1.0)  # let the commit loop turn a few times
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode != 0  # SIGTERM re-raised after the dump
    dump_path = tmp_path / "flight" / "flight-rank-0.json"
    assert dump_path.exists(), proc.stderr.read() if proc.stderr else ""
    payload = json.loads(dump_path.read_text())
    assert payload["reason"] == "sigterm"
    assert payload["profiles"]
