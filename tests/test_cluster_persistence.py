"""Multi-process persistence torture: ``spawn -n 2`` + fs persistence backend,
kill -9 each process once (mid-run), restart, EXACT global output — the
reference's wordcount torture matrix (``integration_tests/wordcount/base.py:320``,
``test_new_data.py:21-23``) at n=2 (VERDICT r3 item 6).

Cluster resume semantics: journal-only (operator snapshots are wall-clock-driven
and unsynchronized across processes, so the runner disables them under spawn);
on restart every process replays the UNION of journaled commit ids in lockstep,
so journals that differ by a trailing commit (the kill window) re-align."""

from __future__ import annotations

import collections
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROG = textwrap.dedent(
    """
    import json, os, signal, threading, time
    import pathway_tpu as pw

    tmp = os.environ["PATHWAY_TPU_TEST_DIR"]
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    kill_pid = os.environ.get("PW_TEST_KILL_PID")
    marker = os.environ.get("PW_TEST_KILL_MARKER", "")

    if kill_pid is not None and int(kill_pid) == pid:
        def _assassin():
            # progress-gated, not wall-clock: the kill must land mid-RUN (after
            # commits + journal frames + supervisor status exist), not during
            # the multi-second interpreter/jax import window. The per-rank
            # status file is per-INCARNATION (the supervisor clears it on every
            # launch and it carries this process's pid), unlike output files
            # which linger from earlier phases.
            spath = os.path.join(
                os.environ["PATHWAY_SUPERVISE_DIR"], f"rank-{pid}.status.json"
            )
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    if json.load(open(spath))["pid"] == os.getpid():
                        break
                except Exception:
                    pass
                time.sleep(0.05)
            time.sleep(0.5)
            try:
                # O_EXCL: exactly one kill per marker even across restarts
                fd = os.open(marker, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                os.close(fd)
            except FileExistsError:
                return
            os.kill(os.getpid(), signal.SIGKILL)
        threading.Thread(target=_assassin, daemon=True).start()

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
    cfg = pw.persistence.Config(
        pw.persistence.Backend.filesystem(os.path.join(tmp, "store")),
        snapshot_interval_ms=10,  # must be IGNORED under spawn (journal-only resume)
    )
    pw.run(persistence_config=cfg, monitoring_level=pw.MonitoringLevel.NONE)
    """
)


def _spawn_popen(tmp_path, first_port: int, kill_pid: int | None, marker: str,
                 max_restarts: int = 0, restart_mode: "str | None" = None):
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    if kill_pid is not None:
        env["PW_TEST_KILL_PID"] = str(kill_pid)
        env["PW_TEST_KILL_MARKER"] = marker
    prog = tmp_path / "prog.py"
    prog.write_text(PROG)
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
        start_new_session=True,  # killpg reaches the spawned children too
        stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE,
        text=True,
    )


def _read_merged(tmp_path) -> dict:
    merged: dict = {}
    owners: collections.Counter = collections.Counter()
    for p in range(2):
        path = tmp_path / f"out_{p}.json"
        if not path.exists():
            continue
        try:
            for r in json.loads(path.read_text()):
                merged[r["word"]] = r["total"]
                owners[r["word"]] += 1
        except ValueError:
            pass
    assert all(v == 1 for v in owners.values()), f"duplicate owners: {owners}"
    return merged


def _terminate_group(proc) -> None:
    try:
        os.killpg(proc.pid, signal.SIGTERM)
    except ProcessLookupError:
        pass
    try:
        proc.wait(timeout=20)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()


def test_spawn_kill9_each_process_restart_exact(tmp_path):
    (tmp_path / "in").mkdir()
    first_port = 24000 + os.getpid() % 500 * 4

    # several files so the hash-shard placement gives BOTH processes input
    for i in range(4):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 1) + ["dog"] * 2) + "\n"
        )

    # phase 1: kill -9 process 0 mid-run; the peer must fail loudly, not hang
    proc = _spawn_popen(tmp_path, first_port, 0, str(tmp_path / "marker0"))
    rc = proc.wait(timeout=120)
    assert rc != 0, "cluster survived a SIGKILL'd member without reporting failure"
    assert (tmp_path / "marker0").exists(), "kill thread never fired"

    # new data while the cluster is down
    (tmp_path / "in" / "b.csv").write_text("word\n" + "\n".join(["cat"] * 2 + ["owl"] * 4) + "\n")

    # phase 2: restart, kill -9 process 1 this time
    proc = _spawn_popen(tmp_path, first_port, 1, str(tmp_path / "marker1"))
    rc = proc.wait(timeout=120)
    assert rc != 0
    assert (tmp_path / "marker1").exists()

    (tmp_path / "in" / "c.csv").write_text("word\n" + "\n".join(["owl"] * 1 + ["elk"] * 5) + "\n")

    # phase 3: restart with no kill; resumed journals + new data -> exact totals
    expected = {
        "cat": sum(i + 1 for i in range(4)) + 2,  # 12
        "dog": 8,
        "owl": 5,
        "elk": 5,
    }
    proc = _spawn_popen(tmp_path, first_port, None, "")
    try:
        deadline = time.time() + 120
        merged: dict = {}
        while time.time() < deadline:
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(f"spawn exited early (rc={proc.returncode}): {err}")
            merged = _read_merged(tmp_path)
            if merged == expected:
                break
            time.sleep(0.3)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        _terminate_group(proc)


def test_spawn_kill9_single_worker_supervised_failover(tmp_path):
    """Single-worker failover, ONE spawn invocation: rank 0 SIGKILLs itself
    mid-run, the supervisor restarts the cluster from the journal (pinned to
    ``--restart-mode all`` — the PR 2 rung; surgical mode is covered by
    ``test_rejoin.py``), and the merged output converges to the exact totals —
    no operator in the loop."""
    (tmp_path / "in").mkdir()
    first_port = 24000 + os.getpid() % 500 * 4 + 2

    for i in range(4):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 1) + ["dog"] * 2) + "\n"
        )

    marker = str(tmp_path / "marker-failover")
    proc = _spawn_popen(tmp_path, first_port, 0, marker, max_restarts=2,
                        restart_mode="all")
    err = ""
    try:
        # wait for the SIGKILL to actually land, THEN add data only the
        # restarted cluster can count — converged pre-kill output files linger
        # on disk, so totals alone cannot prove the failover happened
        deadline = time.time() + 60
        while time.time() < deadline and not os.path.exists(marker):
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(
                    f"supervised spawn exited early (rc={proc.returncode}): {err}"
                )
            time.sleep(0.1)
        assert os.path.exists(marker), "kill thread never fired"
        (tmp_path / "in" / "late.csv").write_text(
            "word\n" + "\n".join(["owl"] * 3) + "\n"
        )
        expected = {"cat": sum(i + 1 for i in range(4)), "dog": 8, "owl": 3}
        deadline = time.time() + 120
        merged: dict = {}
        while time.time() < deadline:
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(
                    f"supervised spawn exited early (rc={proc.returncode}): {err}"
                )
            merged = _read_merged(tmp_path)
            if merged == expected:
                break
            time.sleep(0.3)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            _, err = proc.communicate(timeout=20)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            _, err = proc.communicate()
    assert "restarting the cluster" in (err or ""), (
        f"supervisor never reported the failover restart:\n{err}"
    )
