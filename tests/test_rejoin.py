"""Surgical single-rank restart: exchange epochs, rank rejoin, and per-rank
journal handoff.

Three layers under test:

- mesh (``parallel/cluster.py``): epoch-stamped frames, stale-epoch drops,
  FENCE broadcast, the rejoin acceptor/dialer, ``await_rejoin`` install,
  idempotent ``close``;
- chaos (``internals/chaos.py``): epoch-gated kill entries and the
  drop-rejoin-handshake schedule;
- runtime (spawn acceptance): SIGKILL one rank of ``spawn -n 4`` mid-run with
  persistence on — survivors never exit, exactly one rank is relaunched, and
  the final output is bit-identical to the failure-free run; a dropped rejoin
  handshake (and a second concurrent failure) degrade to PR 2 restart-all;
  persistence-off still refuses the rejoin loudly.
"""

from __future__ import annotations

import itertools
import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from pathway_tpu.internals.chaos import Chaos
from pathway_tpu.parallel.cluster import (
    ClusterExchange,
    ClusterFenceError,
    PeerShutdownError,
    PeerTimeoutError,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PORT_SLOT = itertools.count()


def _port_base() -> int:
    # distinct base per wiring so back-to-back tests never contend on TIME_WAIT
    return 30000 + os.getpid() % 150 * 40 + next(_PORT_SLOT) * 8


def _wire(n: int, first_port: int) -> dict:
    made: dict = {}
    errors: list = []

    def mk(me: int) -> None:
        try:
            made[me] = ClusterExchange(n, me, first_port)
        except BaseException as exc:
            errors.append(exc)

    threads = [threading.Thread(target=mk, args=(me,)) for me in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, f"wiring failed: {errors}"
    assert set(made) == set(range(n))
    return made


def _rejoin_exchange(n: int, me: int, first_port: int, epoch: int, monkeypatch):
    monkeypatch.setenv("PATHWAY_CLUSTER_REJOIN", "1")
    monkeypatch.setenv("PATHWAY_CLUSTER_EPOCH", str(epoch))
    try:
        return ClusterExchange(n, me, first_port)
    finally:
        monkeypatch.delenv("PATHWAY_CLUSTER_REJOIN", raising=False)
        monkeypatch.delenv("PATHWAY_CLUSTER_EPOCH", raising=False)


# -- mesh layer ---------------------------------------------------------------


def test_stale_epoch_frame_dropped_not_delivered(monkeypatch):
    monkeypatch.setenv("PATHWAY_HEARTBEAT_INTERVAL_S", "0.1")
    made = _wire(2, _port_base())
    a, b = made[0], made[1]
    try:
        # receiver moved to a newer epoch (as after a rejoin install): a data
        # frame stamped with the old epoch must be DROPPED, not delivered
        with a._cv:
            a.epoch = 1
        b._send(0, b"stale-tag", b"old-epoch-payload")
        with pytest.raises(PeerTimeoutError):
            a._recv(1, b"stale-tag", timeout=1.0)
        assert a.stale_frames_dropped >= 1
        assert (1, b"stale-tag") not in a._inbox
        # heartbeats keep flowing whatever the epoch — a peer mid-fence is
        # alive, not stale
        time.sleep(0.4)
        assert a.heartbeat_ages()[1] < 0.4
    finally:
        a.close()
        b.close()


def test_fence_broadcast_interrupts_peer_waits(monkeypatch):
    """Rank 2 dies; rank 0 notices and broadcasts the fence. Rank 1 — blocked
    waiting on rank 0, whose frame will never come — must abort with the typed
    fence error within socket latency, not sit out the barrier deadline."""
    monkeypatch.setenv("PATHWAY_HEARTBEAT_INTERVAL_S", "0.1")
    made = _wire(3, _port_base())
    try:
        made[2].close()
        deadline = time.time() + 10
        while 2 not in made[0].dead_peers() and time.time() < deadline:
            time.sleep(0.02)
        assert 2 in made[0].dead_peers()
        made[0].begin_fence()
        t0 = time.monotonic()
        with pytest.raises(ClusterFenceError) as excinfo:
            made[1]._recv(0, b"never-sent", timeout=30)
        assert time.monotonic() - t0 < 5
        assert "2" in str(excinfo.value)  # names the dead rank
        # the fence error IS a PeerShutdownError: existing isinstance-based
        # failure triage keeps working with surgical mode off
        assert isinstance(excinfo.value, PeerShutdownError)
    finally:
        for ex in made.values():
            ex.close()


def test_rejoin_replaces_dead_rank_and_drops_stale_tag_collision(monkeypatch):
    monkeypatch.setenv("PATHWAY_HEARTBEAT_INTERVAL_S", "0.1")
    port = _port_base()
    made = _wire(2, port)
    a, b = made[0], made[1]
    b2 = None
    try:
        # b sends a frame under a tag the post-rejoin protocol will REUSE,
        # then dies: the classic replayed-barrier collision
        b._send(0, b"collide", b"stale")
        b.close()
        with pytest.raises(PeerShutdownError):
            a._recv(1, b"never", timeout=10)

        res: dict = {}

        def relaunch() -> None:
            try:
                res["b2"] = _rejoin_exchange(2, 1, port, epoch=1, monkeypatch=monkeypatch)
            except BaseException as exc:  # surfaced by the assert below
                res["err"] = exc

        a.begin_fence()
        waits: list = []
        t = threading.Thread(target=relaunch)
        t.start()
        new_epoch = a.await_rejoin(timeout=30, on_wait=lambda: waits.append(1))
        t.join(timeout=10)
        assert "err" not in res, res.get("err")
        b2 = res["b2"]
        assert new_epoch == 1 and a.epoch == 1 and b2.epoch == 1
        assert 1 not in a.dead_peers()

        # the reused tag must deliver the FRESH epoch-1 payload, not the stale one
        out: dict = {}
        t2 = threading.Thread(
            target=lambda: out.setdefault(
                "b2", b2.exchange_parts(b"collide", {0: b"fresh"})
            )
        )
        t2.start()
        got = a.exchange_parts(b"collide", {1: b"fresh-from-a"})
        t2.join(timeout=10)
        assert got == {1: b"fresh"}
        assert out["b2"] == {0: b"fresh-from-a"}
        assert a.stale_frames_dropped >= 1
    finally:
        a.close()
        b.close()
        if b2 is not None:
            b2.close()


def test_future_epoch_frame_parked_until_own_install(monkeypatch):
    """The staggered-install race: survivor A installs the rejoin first and
    immediately talks at the new epoch, while survivor B has not fenced yet.
    A's frame must be PARKED at B and delivered once B's own install adopts
    the epoch — dropping it would wedge B's post-rejoin replay until the
    barrier deadline (nobody retransmits barrier parts)."""
    monkeypatch.setenv("PATHWAY_HEARTBEAT_INTERVAL_S", "0.1")
    port = _port_base()
    made = _wire(3, port)
    a, b = made[0], made[1]
    b2 = None
    try:
        made[2].close()
        deadline = time.time() + 10
        while (
            2 not in a.dead_peers() or 2 not in b.dead_peers()
        ) and time.time() < deadline:
            time.sleep(0.02)

        res: dict = {}

        def relaunch() -> None:
            try:
                res["c2"] = _rejoin_exchange(3, 2, port, epoch=1, monkeypatch=monkeypatch)
            except BaseException as exc:
                res["err"] = exc

        t = threading.Thread(target=relaunch)
        t.start()
        # A fences and installs FIRST; B deliberately lags at epoch 0
        a.begin_fence()
        assert a.await_rejoin(timeout=30) == 1
        # A races ahead: an epoch-1 frame reaches B while B is still at epoch 0
        a._send(1, b"replay:ids", b"a-part")
        deadline = time.time() + 5
        while (0, b"replay:ids") not in b._future_inbox and time.time() < deadline:
            time.sleep(0.02)
        with b._cv:
            assert (0, b"replay:ids") in b._future_inbox, "frame was dropped, not parked"
            assert (0, b"replay:ids") not in b._inbox
        # now B fences and installs: the parked frame must be delivered
        b.begin_fence()
        assert b.await_rejoin(timeout=30) == 1
        assert b._recv(0, b"replay:ids", timeout=5) == b"a-part"
        t.join(timeout=10)
        assert "err" not in res, res.get("err")
        b2 = res["c2"]
    finally:
        for ex in made.values():
            ex.close()
        if b2 is not None:
            b2.close()


def test_await_rejoin_times_out_typed(monkeypatch):
    monkeypatch.setenv("PATHWAY_HEARTBEAT_INTERVAL_S", "0.1")
    made = _wire(2, _port_base())
    a, b = made[0], made[1]
    try:
        b.close()
        deadline = time.time() + 10
        while 1 not in a.dead_peers() and time.time() < deadline:
            time.sleep(0.02)
        t0 = time.monotonic()
        with pytest.raises(PeerTimeoutError, match="no replacement"):
            a.await_rejoin(timeout=0.6)
        assert time.monotonic() - t0 < 5
    finally:
        a.close()
        b.close()


def test_rejoin_acceptor_refuses_stale_epoch(monkeypatch):
    """A zombie replacement from an abandoned attempt (epoch <= current) must
    be refused at the acceptor, never parked for install."""
    monkeypatch.setenv("PATHWAY_HEARTBEAT_INTERVAL_S", "0.1")
    port = _port_base()
    made = _wire(2, port)
    a, b = made[0], made[1]
    try:
        s = socket.create_connection(("127.0.0.1", port), timeout=5)
        try:
            s.sendall(b"PWRJ" + (1).to_bytes(4, "little") + (0).to_bytes(4, "little"))
            time.sleep(0.5)
            with a._cv:
                assert a._pending_rejoin == {}
        finally:
            s.close()
    finally:
        a.close()
        b.close()


def test_close_idempotent_and_closes_pending_rejoin(monkeypatch):
    monkeypatch.setenv("PATHWAY_HEARTBEAT_INTERVAL_S", "0")
    made = _wire(2, _port_base())
    a, b = made[0], made[1]
    # park a fake pending-rejoin socket: close() must release it (a rejoin
    # aborted mid-handshake must not leak the half-installed fd)
    fake_a, fake_b = socket.socketpair()
    with a._cv:
        a._pending_rejoin[1] = (fake_a, 7)
    a.close()
    a.close()  # idempotent: second call is a no-op, no double-close
    b.close()
    b.close()
    assert fake_a.fileno() == -1, "pending rejoin socket leaked by close()"
    fake_b.close()
    # the listener port is actually free again (no fd held by the acceptor)
    probe = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    try:
        probe.bind(("127.0.0.1", a.first_port + a.me))
    finally:
        probe.close()


# -- chaos plan ops -----------------------------------------------------------


def test_chaos_drop_rejoin_schedule(monkeypatch):
    monkeypatch.setenv("PATHWAY_RESTART_COUNT", "1")
    plan = {"rejoin": [{"rank": 0, "run": 1}, {"rank": 2}]}
    c = Chaos(0, plan)
    assert c.drop_rejoin(0) is True  # run matches PATHWAY_RESTART_COUNT
    assert c.drop_rejoin(1) is False  # unscheduled rank
    assert c.drop_rejoin(2) is True  # no run field: every attempt drops
    assert c.stats["rejoins_dropped"] == 2
    # a LATER escalation attempt is a fresh process with a bumped restart
    # count: run-gated entries stop firing there (the cross-attempt key)
    monkeypatch.setenv("PATHWAY_RESTART_COUNT", "2")
    c2 = Chaos(0, {"rejoin": [{"rank": 0, "run": 1}, {"rank": 2}]})
    assert c2.drop_rejoin(0) is False  # wrong incarnation
    assert c2.drop_rejoin(2) is True  # run-less entries keep dropping


def test_chaos_kill_epoch_gating(monkeypatch):
    killed: list = []
    from pathway_tpu.internals import chaos as chaos_mod

    monkeypatch.setattr(
        chaos_mod.os, "kill", lambda pid, sig: killed.append((pid, sig))
    )
    plan = {"kill": [{"rank": 0, "commit": 3, "run": 0, "epoch": 1}]}
    c = Chaos(0, plan)
    c.maybe_kill(0, 3, epoch=0)  # wrong epoch
    assert killed == []
    c.maybe_kill(0, 3, epoch=1)
    assert killed == [(os.getpid(), signal.SIGKILL)]
    # entries without an epoch field keep firing in any epoch
    killed.clear()
    c2 = Chaos(0, {"kill": [{"rank": 0, "commit": 3, "run": 0}]})
    c2.maybe_kill(0, 3, epoch=5)
    assert len(killed) == 1


# -- runner guard: rejoin refused loudly without persistence ------------------


def test_surgical_rejoin_refused_without_persistence():
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.internals.parse_graph import ParseGraph

    runner = GraphRunner(ParseGraph())

    class _FakeCluster:
        supports_rejoin = True
        epoch = 0

    runner._surgical = True
    runner._cluster = _FakeCluster()
    runner._supervise_dir = "/nonexistent"
    runner._persistence = None  # no journal shard: nothing to roll back to
    assert runner._surgical_rejoin(PeerShutdownError("peer died")) is False
    # and with surgical mode off, even a persistent runner declines
    runner._persistence = object()
    runner._surgical = False
    assert runner._surgical_rejoin(PeerShutdownError("peer died")) is False


def test_health_payload_exposes_epoch_and_rejoin_fields(monkeypatch, tmp_path):
    """Satellite: /healthz (via GraphRunner.health) and the supervisor status
    files carry cluster_epoch, restart counts, rejoin counts, last-rejoin
    duration, and the fencing state."""
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.internals.parse_graph import ParseGraph
    from pathway_tpu.parallel.supervisor import read_statuses, write_status

    monkeypatch.setenv("PATHWAY_RESTART_COUNT", "2")
    runner = GraphRunner(ParseGraph())

    class _FakeCluster:
        supports_rejoin = True
        epoch = 3

        def heartbeat_ages(self):
            return {1: 0.5}

        def dead_peers(self):
            return {}

    runner._cluster = _FakeCluster()
    runner._rejoins = 1
    runner._last_rejoin_s = 2.5
    runner._rejoin_state = "rejoining"
    health = runner.health()
    assert health["epoch"] == 3
    assert health["restarts"] == 2
    assert health["rejoins"] == 1
    assert health["last_rejoin_s"] == 2.5
    assert health["state"] == "rejoining"

    write_status(
        str(tmp_path), 0, commit=7, persistence=True, peers=health["peers"],
        epoch=health["epoch"], state=health["state"],
        restarts=health["restarts"], last_rejoin_s=health["last_rejoin_s"],
    )
    status = read_statuses(str(tmp_path), 1)[0]
    assert status["epoch"] == 3
    assert status["state"] == "rejoining"
    assert status["restarts"] == 2
    assert status["last_rejoin_s"] == 2.5


# -- spawn acceptance ---------------------------------------------------------

REJOIN_PROG = textwrap.dedent(
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
    cfg = pw.persistence.Config(
        pw.persistence.Backend.filesystem(os.path.join(tmp, "store"))
    )
    pw.run(persistence_config=cfg, monitoring_level=pw.MonitoringLevel.NONE)
    """
)


def _spawn(tmp_path, first_port, *, n, plan, max_restarts, extra_env=None):
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_CHAOS_SEED"] = "7"
    env["PATHWAY_CHAOS_PLAN"] = json.dumps(plan)
    env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
    env["PATHWAY_BARRIER_TIMEOUT_S"] = "30"
    env.update(extra_env or {})
    prog = tmp_path / "prog.py"
    prog.write_text(REJOIN_PROG)
    return subprocess.Popen(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", str(n), "--first-port", str(first_port),
            "--max-restarts", str(max_restarts),
            sys.executable, str(prog),
        ],
        env=env,
        cwd=str(tmp_path),
        start_new_session=True,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE,
        text=True,
    )


def _read_merged(tmp_path, n: int) -> dict:
    merged: dict = {}
    for p in range(n):
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


def _await_counts(proc, tmp_path, n, expected, deadline_s=150) -> tuple:
    deadline = time.time() + deadline_s
    merged: dict = {}
    while time.time() < deadline:
        if proc.poll() is not None:
            _, err = proc.communicate()
            raise AssertionError(
                f"spawn exited early (rc={proc.returncode}): {err}"
            )
        merged = _read_merged(tmp_path, n)
        if merged == expected:
            break
        time.sleep(0.3)
    return merged


def _failure_free_counts(tmp_path) -> dict:
    """Reference output: the same pipeline run in-process with no faults."""
    import pathway_tpu as pw
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
def test_surgical_failover_n4_one_relaunch_exact(tmp_path):
    """THE acceptance scenario: SIGKILL rank 2 of ``spawn -n 4`` mid-run with
    persistence on — the three survivors hold at the epoch fence (never exit),
    exactly one rank is relaunched, data arriving after the failover is still
    ingested exactly once, and the merged output is bit-identical to the
    failure-free run. No restart-all anywhere."""
    (tmp_path / "in").mkdir()
    first_port = 31000 + os.getpid() % 400 * 8
    for i in range(4):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 1) + ["dog"] * 2) + "\n"
        )

    plan = {"kill": [{"rank": 2, "commit": 3, "run": 0}]}
    proc = _spawn(tmp_path, first_port, n=4, plan=plan, max_restarts=1)
    err = ""
    try:
        time.sleep(10)  # kill + fence + rejoin window
        # post-failover data must be ingested exactly once by the healed cluster
        (tmp_path / "in" / "late.csv").write_text(
            "word\n" + "\n".join(["owl"] * 3 + ["cat"] * 1) + "\n"
        )
        expected = {"cat": 11, "dog": 8, "owl": 3}
        merged = _await_counts(proc, tmp_path, 4, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc)
    assert err.count("surgically relaunching rank 2") == 1, (
        f"expected exactly one surgical relaunch of rank 2:\n{err}"
    )
    assert "restarting the cluster" not in err, (
        f"survivors were torn down — restart-all fired instead of surgical:\n{err}"
    )
    assert "rejoined the cluster at epoch 1" in err, (
        f"rejoin never completed:\n{err}"
    )
    # bit-identical to the failure-free run of the same pipeline
    assert _failure_free_counts(tmp_path) == merged


@pytest.mark.chaos
def test_rejoin_handshake_drop_falls_back_to_restart_all(tmp_path):
    """Escalation rung 2: the chaos plan drops the replacement's rejoin
    handshake, so the surgical attempt fails typed and the supervisor degrades
    to PR 2 restart-all — which still converges to exact output."""
    (tmp_path / "in").mkdir()
    first_port = 31000 + os.getpid() % 400 * 8 + 4
    for i in range(4):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 1) + ["dog"] * 2) + "\n"
        )

    plan = {
        "kill": [{"rank": 0, "commit": 3, "run": 0}],
        # the relaunched rank 0 (restart count 1) loses its handshake once
        "rejoin": [{"rank": 0, "run": 1}],
    }
    proc = _spawn(tmp_path, first_port, n=2, plan=plan, max_restarts=2)
    err = ""
    try:
        # expected totals must REQUIRE post-recovery ingestion: with tiny
        # inputs the pipeline can converge milliseconds before the commit-3
        # kill even fires, and terminating on pre-kill convergence would race
        # the whole escalation ladder out of the test
        time.sleep(14)  # kill + failed surgical attempt + restart-all window
        (tmp_path / "in" / "late.csv").write_text(
            "word\n" + "\n".join(["owl"] * 3) + "\n"
        )
        expected = {"cat": 10, "dog": 8, "owl": 3}
        merged = _await_counts(proc, tmp_path, 2, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc)
    assert "surgically relaunching rank 0" in err, f"no surgical attempt:\n{err}"
    assert "falling back to restart-all" in err, (
        f"dropped handshake did not degrade to restart-all:\n{err}"
    )
    assert "restarting the cluster" in err, f"restart-all never ran:\n{err}"


@pytest.mark.chaos
def test_double_concurrent_failure_degrades_to_restart_all(tmp_path):
    """Two ranks die at the same commit boundary: the supervisor starts a
    surgical rejoin for the first, notices the second death while it is in
    flight, and degrades to restart-all — never a hang, exact output."""
    (tmp_path / "in").mkdir()
    first_port = 31000 + os.getpid() % 400 * 8 + 6
    for i in range(4):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 1) + ["dog"] * 2) + "\n"
        )

    plan = {
        "kill": [
            {"rank": 0, "commit": 3, "run": 0},
            {"rank": 1, "commit": 3, "run": 0},
        ]
    }
    proc = _spawn(
        tmp_path, first_port, n=2, plan=plan, max_restarts=2,
        # the doomed replacement must give up dialing the second corpse quickly
        extra_env={"PATHWAY_CONNECT_TIMEOUT_S": "8"},
    )
    err = ""
    try:
        # see test_rejoin_handshake_drop_falls_back_to_restart_all: expected
        # totals must require post-recovery ingestion or convergence can race
        # the kills
        time.sleep(16)  # both kills + failed rejoin dial + restart-all window
        (tmp_path / "in" / "late.csv").write_text(
            "word\n" + "\n".join(["owl"] * 3) + "\n"
        )
        expected = {"cat": 10, "dog": 8, "owl": 3}
        merged = _await_counts(proc, tmp_path, 2, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc)
    assert "restarting the cluster" in err, (
        f"double failure did not degrade to restart-all:\n{err}"
    )
