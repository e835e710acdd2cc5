"""Chaos harness: seeded deterministic fault injection (internals/chaos.py)
driving the supervised cluster runtime.

The two spawn tests here are the PR's acceptance scenario: SIGKILL one worker
of ``spawn -n 2`` mid-run via a seeded chaos plan — with persistence on the
supervisor restarts the cluster and the final output is bit-identical to the
failure-free run; with persistence off the cluster exits with a typed peer
error within the barrier deadline. No hang in either case."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.internals import chaos as chaos_mod
from pathway_tpu.internals.chaos import Chaos, get_chaos, reset_chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- plan / schedule determinism (pure unit) ---------------------------------


def test_chaos_schedule_is_seed_deterministic():
    plan = {"frames": {"drop_prob": 0.2, "delay_prob": 0.3, "delay_ms": 5}}
    a = Chaos(7, plan)
    b = Chaos(7, plan)
    seq_a = [a.frame_action(0, 1).kind for _ in range(200)]
    seq_b = [b.frame_action(0, 1).kind for _ in range(200)]
    assert seq_a == seq_b, "same seed must replay the same schedule"
    # independent per (rank, peer) stream: draws to another peer don't shift it
    c = Chaos(7, plan)
    interleaved = []
    for _ in range(200):
        interleaved.append(c.frame_action(0, 1).kind)
        c.frame_action(0, 2)  # traffic on another link
    assert interleaved == seq_a
    d = Chaos(8, plan)
    seq_d = [d.frame_action(0, 1).kind for _ in range(200)]
    assert seq_d != seq_a, "different seed must give a different schedule"


def test_chaos_kill_matches_rank_commit_and_run(monkeypatch):
    killed = []
    monkeypatch.setattr(chaos_mod.os, "kill", lambda pid, sig: killed.append((pid, sig)))
    plan = {"kill": [{"rank": 1, "commit": 3, "run": 0}]}
    c = Chaos(0, plan)
    c.maybe_kill(0, 3)  # wrong rank
    c.maybe_kill(1, 2)  # wrong commit
    assert killed == []
    c.maybe_kill(1, 3)
    assert killed == [(os.getpid(), signal.SIGKILL)]
    # a restarted incarnation (PATHWAY_RESTART_COUNT=1) must survive the replay
    monkeypatch.setenv("PATHWAY_RESTART_COUNT", "1")
    c2 = Chaos(0, plan)
    killed.clear()
    c2.maybe_kill(1, 3)
    assert killed == []


def test_get_chaos_env_contract(monkeypatch):
    reset_chaos()
    monkeypatch.delenv("PATHWAY_CHAOS_PLAN", raising=False)
    assert get_chaos() is None
    reset_chaos()
    monkeypatch.setenv("PATHWAY_CHAOS_PLAN", json.dumps({"frames": {"drop_prob": 1.0}}))
    monkeypatch.setenv("PATHWAY_CHAOS_SEED", "42")
    try:
        c = get_chaos()
        assert c is not None and c.seed == 42
        assert c.frame_action(0, 1).kind == "drop"
    finally:
        reset_chaos()


# -- transient backend write errors retried (satellite) -----------------------


@pytest.mark.chaos
def test_chaos_transient_s3_write_errors_are_retried(tmp_path, monkeypatch):
    """Injected transient PUT failures on the S3 persistence backend are
    absorbed by ExponentialBackoffRetryStrategy — the run completes, every
    journal object lands, and a resume replays them exactly."""
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.udfs import ExponentialBackoffRetryStrategy

    from .mocks import DirS3Client

    monkeypatch.setenv("PATHWAY_CHAOS_SEED", "11")
    monkeypatch.setenv(
        "PATHWAY_CHAOS_PLAN",
        json.dumps({"backend": {"put_error_prob": 0.6, "max_errors": 5}}),
    )
    reset_chaos()
    try:
        client = DirS3Client(str(tmp_path / "fake-s3"))

        def run_once():
            from pathway_tpu.engine.runner import GraphRunner

            t = pw.debug.table_from_markdown(
                """
                word  | n
                cat   | 1
                dog   | 2
                cat   | 3
                """
            )
            counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.sum(t.n))
            rows = {}

            def on_change(key, row, time, is_addition):
                if is_addition:
                    rows[key] = row
                else:
                    rows.pop(key, None)

            pw.io.subscribe(counts, on_change)
            cfg = pw.persistence.Config(
                pw.persistence.Backend.s3(
                    "s3://bucket/chaos", _client_factory=lambda settings: client
                ),
                backend_retry_strategy=ExponentialBackoffRetryStrategy(
                    max_retries=6, initial_delay=5, backoff_factor=2, jitter_ms=2
                ),
            )
            GraphRunner(G._current).run(persistence_config=cfg)
            return {r["word"]: r["total"] for r in rows.values()}

        first = run_once()
        assert first == {"cat": 4, "dog": 2}
        harness = get_chaos()
        assert harness is not None and harness.stats["backend_errors"] > 0, (
            "the plan never injected a write error — the retry path went untested"
        )
        # resume: every frame object must exist despite the injected failures
        G.clear()
        second = run_once()
        assert second == first
    finally:
        reset_chaos()


# -- spawn acceptance scenarios ----------------------------------------------

CHAOS_PROG = textwrap.dedent(
    """
    import json, os
    import pathway_tpu as pw

    tmp = os.environ["PATHWAY_TPU_TEST_DIR"]
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class WordSchema(pw.Schema):
        word: str

    t = pw.io.fs.read(
        os.path.join(tmp, "in"), format="csv", schema=WordSchema, mode="streaming"
    )
    counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())

    out_path = os.path.join(tmp, f"out_{pid}.json")
    rows = {}
    def on_change(key, row, time, is_addition):
        if is_addition:
            rows[repr(key)] = {"word": row["word"], "total": int(row["total"])}
        else:
            rows.pop(repr(key), None)
        with open(out_path + ".tmp", "w") as f:
            json.dump(list(rows.values()), f)
        os.replace(out_path + ".tmp", out_path)

    pw.io.subscribe(counts, on_change)
    kwargs = {}
    if os.environ.get("PW_TEST_PERSIST") == "1":
        kwargs["persistence_config"] = pw.persistence.Config(
            pw.persistence.Backend.filesystem(os.path.join(tmp, "store"))
        )
    pw.run(monitoring_level=pw.MonitoringLevel.NONE, **kwargs)
    """
)


def _chaos_spawn(tmp_path, first_port, *, plan, persist, max_restarts,
                 extra_env=None, restart_mode=None):
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_CHAOS_SEED"] = "7"
    env["PATHWAY_CHAOS_PLAN"] = json.dumps(plan)
    env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
    env["PATHWAY_BARRIER_TIMEOUT_S"] = "30"
    if persist:
        env["PW_TEST_PERSIST"] = "1"
    env.update(extra_env or {})
    prog = tmp_path / "prog.py"
    prog.write_text(CHAOS_PROG)
    mode_args = ["--restart-mode", restart_mode] if restart_mode else []
    return subprocess.Popen(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "--first-port", str(first_port),
            "--max-restarts", str(max_restarts), *mode_args,
            sys.executable, str(prog),
        ],
        env=env,
        cwd=str(tmp_path),
        start_new_session=True,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE,
        text=True,
    )


def _read_merged(tmp_path) -> dict:
    merged: dict = {}
    for p in range(2):
        path = tmp_path / f"out_{p}.json"
        if not path.exists():
            continue
        try:
            for r in json.loads(path.read_text()):
                merged[r["word"]] = r["total"]
        except ValueError:
            pass
    return merged


def _terminate_group(proc) -> str:
    try:
        os.killpg(proc.pid, signal.SIGTERM)
    except ProcessLookupError:
        pass
    try:
        _, err = proc.communicate(timeout=20)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        _, err = proc.communicate()
    return err or ""


def _failure_free_counts(tmp_path) -> dict:
    """The reference output: the same pipeline, run in-process with no faults."""
    from pathway_tpu.internals.parse_graph import G

    G.clear()

    class WordSchema(pw.Schema):
        word: str

    t = pw.io.fs.read(
        str(tmp_path / "in"), format="csv", schema=WordSchema, mode="static"
    )
    counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())
    rows: dict = {}

    def on_change(key, row, time, is_addition):
        if is_addition:
            rows[key] = {"word": row["word"], "total": int(row["total"])}
        else:
            rows.pop(key, None)

    pw.io.subscribe(counts, on_change)
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    G.clear()
    return {r["word"]: r["total"] for r in rows.values()}


@pytest.mark.chaos
def test_chaos_kill_one_worker_supervisor_failover_exact(tmp_path):
    """Seeded kill of rank 0 at commit 3 (persistence on, ``--restart-mode
    all`` pinning the PR 2 rung): the supervisor restarts the cluster, the
    journal union replays, streaming continues, and the merged output is
    bit-identical to the failure-free run. (Surgical mode — the default — is
    covered by ``test_rejoin.py``.)"""
    (tmp_path / "in").mkdir()
    first_port = 28000 + os.getpid() % 500 * 4
    for i in range(4):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 1) + ["dog"] * 2) + "\n"
        )

    plan = {"kill": [{"rank": 0, "commit": 3, "run": 0}]}
    proc = _chaos_spawn(tmp_path, first_port, plan=plan, persist=True,
                        max_restarts=1, restart_mode="all")
    err = ""
    try:
        time.sleep(5)  # kill + restart window
        # data arriving AFTER the failover must still be ingested exactly once
        (tmp_path / "in" / "b.csv").write_text(
            "word\n" + "\n".join(["owl"] * 3 + ["cat"] * 1) + "\n"
        )
        expected = {"cat": 11, "dog": 8, "owl": 3}
        deadline = time.time() + 120
        merged: dict = {}
        while time.time() < deadline:
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(
                    f"spawn exited early (rc={proc.returncode}): {err}"
                )
            merged = _read_merged(tmp_path)
            if merged == expected:
                break
            time.sleep(0.3)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc)
    assert "restarting the cluster" in err, (
        f"supervisor never restarted — the chaos kill did not fire?\n{err}"
    )
    # bit-identical to the failure-free run of the same pipeline
    assert _failure_free_counts(tmp_path) == merged


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_repeated_kills_long_torture(tmp_path):
    """Long variant (excluded from tier-1 via ``slow``): BOTH ranks die across
    consecutive incarnations — rank 0 first, then the surviving rank 1 after
    the first recovery — and two supervised failovers still converge to exact
    totals. With ``--max-restarts`` > 0 the supervisor runs in surgical mode,
    so each death should relaunch only the dead rank (a restart-all fallback
    still counts as a recovery, but at least one rung must fire per death)."""
    (tmp_path / "in").mkdir()
    first_port = 28000 + os.getpid() % 500 * 4 + 4
    for i in range(6):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 1) + ["dog"] * 3) + "\n"
        )

    plan = {
        "kill": [
            {"rank": 0, "commit": 3, "run": 0},
            # the survivor keeps run 0 across rank 0's surgical restart, so its
            # own scheduled kill fires later at a live post-rejoin commit; the
            # run-1 companion covers the tolerated restart-all fallback, where
            # rank 1 is relaunched with a bumped restart count and the run-0
            # entry would never match again
            {"rank": 1, "commit": 9, "run": 0},
            {"rank": 1, "commit": 9, "run": 1},
        ]
    }
    # budget 3 absorbs one surgical->restart-all fallback and still leaves a
    # recovery for the second death
    proc = _chaos_spawn(tmp_path, first_port, plan=plan, persist=True, max_restarts=3)
    err = ""
    try:
        time.sleep(10)  # both kill + recovery windows
        (tmp_path / "in" / "late.csv").write_text(
            "word\n" + "\n".join(["owl"] * 5) + "\n"
        )
        expected = {"cat": sum(i + 1 for i in range(6)), "dog": 18, "owl": 5}
        deadline = time.time() + 240
        merged: dict = {}
        while time.time() < deadline:
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(
                    f"spawn exited early (rc={proc.returncode}): {err}"
                )
            merged = _read_merged(tmp_path)
            if merged == expected:
                break
            time.sleep(0.3)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc)
    recoveries = err.count("surgically relaunching") + err.count(
        "restarting the cluster"
    )
    assert recoveries >= 2, f"expected two supervised recoveries:\n{err}"
    assert "surgically relaunching" in err, (
        f"--max-restarts > 0 should exercise surgical mode:\n{err}"
    )


@pytest.mark.chaos
def test_chaos_kill_without_persistence_fails_typed_and_fast(tmp_path):
    """Same kill with persistence OFF: no restart — the surviving rank must
    fail with a typed peer error within the barrier deadline and the
    supervisor must tear down with a per-rank post-mortem. Never a hang."""
    (tmp_path / "in").mkdir()
    first_port = 28000 + os.getpid() % 500 * 4 + 2
    (tmp_path / "in" / "a.csv").write_text("word\ncat\ncat\ndog\n")

    plan = {"kill": [{"rank": 0, "commit": 3, "run": 0}]}
    t0 = time.monotonic()
    proc = _chaos_spawn(tmp_path, first_port, plan=plan, persist=False, max_restarts=1)
    try:
        _, err = proc.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        _terminate_group(proc)
        raise AssertionError("cluster HUNG after a worker SIGKILL (persistence off)")
    elapsed = time.monotonic() - t0
    assert proc.returncode != 0, "cluster reported success after losing a worker"
    # detection is socket-close driven, so teardown must beat the 30 s barrier
    # deadline by a wide margin (imports dominate the elapsed time)
    assert elapsed < 90, f"teardown took {elapsed:.0f}s — failure path is too slow"
    assert "PeerShutdownError" in err or "PeerTimeoutError" in err, (
        f"survivor did not fail with a typed peer error:\n{err}"
    )
    assert "post-mortem" in err, f"supervisor printed no post-mortem:\n{err}"
    assert "persistence is off" in err, f"missing loud no-restart reason:\n{err}"
