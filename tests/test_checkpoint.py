"""Coordinated cluster checkpoints + incremental rewind.

Three layers under test:

- persistence (``persistence/engine.py``): versioned per-rank snapshots, the
  cluster checkpoint manifest (atomic write, read-back verification, torn-
  manifest fallback, worker-count/key-derivation guards), journal compaction;
- mesh (``parallel/cluster.py``): the per-commit serve log a rewound survivor
  replays to a recovering peer (record/seal/discard/prune/depth bound);
- chaos (``internals/chaos.py``): checkpoint-phase fault entries (kill between
  snapshot and manifest, torn manifest bytes, snapshot write error) — and the
  spawn acceptance runs proving every one of them leaves the PREVIOUS
  checkpoint recoverable bit-identically.

The n=4 acceptance (kill a rank after >=2 coordinated checkpoints -> recovery
from checkpoint + journal tail, output bit-identical) carries a hand-rolled
hard timeout: a wedged rejoin SIGKILLs the process group and fails fast
instead of eating the tier-1 budget.
"""

from __future__ import annotations

import itertools
import json
import os
import pickle
import signal
import subprocess
import sys
import textwrap
import threading
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.chaos import Chaos, ChaosBackendError, reset_chaos
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.persistence.engine import (
    KEY_DERIVATION_VERSION,
    PersistenceManager,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PORT_SLOT = itertools.count()


def _port_base() -> int:
    # distinct base per wiring so back-to-back tests never contend on TIME_WAIT
    return 33000 + os.getpid() % 150 * 40 + next(_PORT_SLOT) * 8


def _manager(tmp_path) -> PersistenceManager:
    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(tmp_path / "store"))
    return PersistenceManager(cfg)


SIG = "test-graph-sig"


# -- persistence: snapshot/manifest atomicity ---------------------------------


@pytest.mark.checkpoint
def test_cluster_snapshot_manifest_roundtrip(tmp_path):
    pm = _manager(tmp_path)
    pm.open_for_append(SIG)
    blob = {"states": {1: b"abc"}, "evaluators": {}, "source_offsets": {},
            "source_deltas": {}}
    size = pm.dump_cluster_snapshot(SIG, 7, blob)
    assert size > 0
    assert pm.commit_cluster_manifest(SIG, 7, epoch=2) is True

    pm2 = _manager(tmp_path)
    manifest = pm2.load_cluster_manifest(SIG)
    assert manifest is not None
    assert manifest["commit_id"] == 7
    assert manifest["epoch"] == 2
    assert manifest["workers"] == 1
    assert manifest["key_derivation"] == KEY_DERIVATION_VERSION
    assert pm2.load_cluster_snapshot(SIG, 7) == blob


@pytest.mark.checkpoint
def test_interrupted_snapshot_write_never_corrupts_load(tmp_path):
    """A crash mid-``dump_cluster_snapshot`` leaves only a ``.tmp`` file (the
    rename never ran); a later load must see the PREVIOUS checkpoint exactly."""
    pm = _manager(tmp_path)
    pm.open_for_append(SIG)
    good = {"states": {1: b"good"}, "evaluators": {}, "source_offsets": {},
            "source_deltas": {}}
    pm.dump_cluster_snapshot(SIG, 5, good)
    assert pm.commit_cluster_manifest(SIG, 5)

    # simulated crash: half-written tmp for the NEXT attempt, no manifest
    torn = os.path.join(pm.root, "checkpoint-0000000009.pkl.tmp")
    with open(torn, "wb") as f:
        f.write(pickle.dumps({"sig": SIG})[:10])

    pm2 = _manager(tmp_path)
    manifest = pm2.load_cluster_manifest(SIG)
    assert manifest["commit_id"] == 5
    assert pm2.load_cluster_snapshot(SIG, 5) == good


@pytest.mark.checkpoint
def test_torn_manifest_falls_back_to_previous(tmp_path):
    """Torn manifest bytes (non-atomic store, crash mid-PUT): the loader skips
    the unreadable manifest with a warning and serves the previous one."""
    pm = _manager(tmp_path)
    pm.open_for_append(SIG)
    blob = {"states": {}, "evaluators": {}, "source_offsets": {}, "source_deltas": {}}
    pm.dump_cluster_snapshot(SIG, 3, blob)
    assert pm.commit_cluster_manifest(SIG, 3)

    # a NEWER manifest whose bytes tore mid-write
    raw = json.dumps({"format": 1, "sig": SIG, "commit_id": 9}).encode()
    with open(tmp_path / "store" / "cluster-manifest-0000000009.json", "wb") as f:
        f.write(raw[: len(raw) // 2])

    pm2 = _manager(tmp_path)
    manifest = pm2.load_cluster_manifest(SIG)
    assert manifest is not None and manifest["commit_id"] == 3


@pytest.mark.checkpoint
def test_manifest_name_content_mismatch_treated_as_torn(tmp_path):
    """A manifest whose body names a different commit than its filename is a
    corrupt write, not a checkpoint — skipped like torn bytes."""
    pm = _manager(tmp_path)
    pm.open_for_append(SIG)
    blob = {"states": {}, "evaluators": {}, "source_offsets": {}, "source_deltas": {}}
    pm.dump_cluster_snapshot(SIG, 3, blob)
    assert pm.commit_cluster_manifest(SIG, 3)
    meta = json.loads(
        (tmp_path / "store" / "cluster-manifest-0000000003.json").read_bytes()
    )
    (tmp_path / "store" / "cluster-manifest-0000000011.json").write_bytes(
        json.dumps(meta, sort_keys=True).encode()  # body still says commit 3
    )
    pm2 = _manager(tmp_path)
    assert pm2.load_cluster_manifest(SIG)["commit_id"] == 3


@pytest.mark.checkpoint
def test_manifest_refuses_worker_count_and_key_derivation_mismatch(tmp_path):
    """Same guards as the PWTPUJ2 journal header: a manifest from a different
    worker count or key-derivation version must refuse LOUDLY (silently
    starting from a mismatched shard layout loses data)."""
    pm = _manager(tmp_path)
    pm.open_for_append(SIG)
    blob = {"states": {}, "evaluators": {}, "source_offsets": {}, "source_deltas": {}}
    pm.dump_cluster_snapshot(SIG, 4, blob)
    assert pm.commit_cluster_manifest(SIG, 4)
    path = tmp_path / "store" / "cluster-manifest-0000000004.json"
    meta = json.loads(path.read_bytes())

    meta_bad = dict(meta, workers=4)
    path.write_bytes(json.dumps(meta_bad, sort_keys=True).encode())
    with pytest.raises(ValueError, match="worker process"):
        _manager(tmp_path).load_cluster_manifest(SIG)

    meta_bad = dict(meta, key_derivation=KEY_DERIVATION_VERSION + 1)
    path.write_bytes(json.dumps(meta_bad, sort_keys=True).encode())
    with pytest.raises(ValueError, match="key-derivation"):
        _manager(tmp_path).load_cluster_manifest(SIG)

    # and a manifest from a DIFFERENT graph is refused too
    meta_bad = dict(meta, sig="other-graph")
    path.write_bytes(json.dumps(meta_bad, sort_keys=True).encode())
    with pytest.raises(ValueError, match="different"):
        _manager(tmp_path).load_cluster_manifest(SIG)


@pytest.mark.checkpoint
def test_missing_or_corrupt_snapshot_named_by_manifest_is_loud(tmp_path):
    """The manifest promised the snapshot exists and the journal it subsumed
    is gone — treating a missing/unreadable snapshot as absent would silently
    drop all checkpointed history."""
    pm = _manager(tmp_path)
    pm.open_for_append(SIG)
    blob = {"states": {}, "evaluators": {}, "source_offsets": {}, "source_deltas": {}}
    pm.dump_cluster_snapshot(SIG, 6, blob)
    assert pm.commit_cluster_manifest(SIG, 6)

    snap = tmp_path / "store" / "checkpoint-0000000006.pkl"
    snap.write_bytes(b"\x80garbage")
    with pytest.raises(ValueError, match="unreadable"):
        _manager(tmp_path).load_cluster_snapshot(SIG, 6)
    snap.unlink()
    with pytest.raises(ValueError, match="missing"):
        _manager(tmp_path).load_cluster_snapshot(SIG, 6)


@pytest.mark.checkpoint
def test_compaction_and_cleanup_after_manifest(tmp_path):
    """Journal frames <= the manifest commit are compacted; snapshots and
    manifests older than the newest manifest are pruned; the tail-length
    counter resets."""
    from pathway_tpu.engine.columnar import Delta

    pm = _manager(tmp_path)
    pm.open_for_append(SIG)
    delta = Delta.empty(["v"])
    pm.record_commit(1, {7: delta}, {7: {"pos": 1}})
    pm.record_commit(2, {7: delta}, {7: {"pos": 2}})
    assert pm.frames_since_compact == 2
    blob = {"states": {}, "evaluators": {}, "source_offsets": {}, "source_deltas": {}}
    pm.dump_cluster_snapshot(SIG, 1, blob)
    assert pm.commit_cluster_manifest(SIG, 1)
    pm.dump_cluster_snapshot(SIG, 2, blob)
    assert pm.commit_cluster_manifest(SIG, 2)
    assert pm.compact_journal(SIG) == 2
    assert pm.frames_since_compact == 0
    pm.cleanup_cluster_checkpoints(2)

    store = tmp_path / "store"
    assert not (store / "checkpoint-0000000001.pkl").exists()
    assert (store / "checkpoint-0000000002.pkl").exists()
    assert not (store / "cluster-manifest-0000000001.json").exists()
    assert (store / "cluster-manifest-0000000002.json").exists()
    pm2 = _manager(tmp_path)
    assert pm2.load_journal(SIG) == []
    assert pm2.load_cluster_manifest(SIG)["commit_id"] == 2


def test_tail_counter_survives_relaunch(tmp_path):
    """``frames_since_compact`` is rebuilt from the loaded journal, not reset
    to 0 per process incarnation — otherwise a relaunched rank publishes
    journal_tail_frames=0 and the recovery-SLO fields claim the next recovery
    is free when it must replay the whole tail."""
    from pathway_tpu.engine.columnar import Delta

    pm = _manager(tmp_path)
    pm.open_for_append(SIG)
    delta = Delta.empty(["v"])
    for cid in (1, 2, 3):
        pm.record_commit(cid, {7: delta}, {7: {"pos": cid}})
    pm.close()

    pm2 = _manager(tmp_path)
    assert len(pm2.load_journal(SIG)) == 3
    assert pm2.frames_since_compact == 3
    # reload (the surgical-rejoin rollback path) must agree
    pm2.open_for_append(SIG)
    assert len(pm2.reload(SIG)) == 3
    assert pm2.frames_since_compact == 3
    pm2.record_commit(4, {7: delta}, {7: {"pos": 4}})
    assert pm2.frames_since_compact == 4
    assert pm2.compact_journal(SIG) == 4
    pm2.close()


# -- chaos: checkpoint-phase fault plan ---------------------------------------


def test_chaos_checkpoint_fault_gating(monkeypatch):
    """``checkpoint`` plan entries key on (op, rank, run, attempt); ``at``
    defaults to every attempt, ``run`` to every incarnation."""
    monkeypatch.setenv("PATHWAY_RESTART_COUNT", "0")
    plan = {
        "checkpoint": [
            {"op": "torn_manifest", "rank": 0, "run": 0, "at": 1},
            {"op": "snapshot_error", "rank": 1},
        ]
    }
    c = Chaos(0, plan)
    c.begin_checkpoint_attempt()  # attempt 0
    assert c.checkpoint_fault("torn_manifest", 0) is False  # wrong attempt
    assert c.checkpoint_fault("snapshot_error", 1) is True  # no at: every attempt
    assert c.checkpoint_fault("snapshot_error", 0) is False  # unscheduled rank
    c.begin_checkpoint_attempt()  # attempt 1
    assert c.checkpoint_fault("torn_manifest", 0) is True
    assert c.checkpoint_fault("post_snapshot_kill", 0) is False  # unscheduled op
    assert c.stats["checkpoint_faults"] == 2

    # a restarted incarnation (bumped PATHWAY_RESTART_COUNT) stops firing
    # run-gated entries — the replay after recovery must not re-fault
    monkeypatch.setenv("PATHWAY_RESTART_COUNT", "1")
    c2 = Chaos(0, plan)
    c2.begin_checkpoint_attempt()
    c2.begin_checkpoint_attempt()
    assert c2.checkpoint_fault("torn_manifest", 0) is False


def test_chaos_snapshot_error_fails_dump(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "PATHWAY_CHAOS_PLAN",
        json.dumps({"checkpoint": [{"op": "snapshot_error", "rank": 0, "at": 0}]}),
    )
    reset_chaos()
    try:
        pm = _manager(tmp_path)
        pm.open_for_append(SIG)
        from pathway_tpu.internals.chaos import get_chaos

        get_chaos().begin_checkpoint_attempt()
        blob = {"states": {}, "evaluators": {}, "source_offsets": {},
                "source_deltas": {}}
        with pytest.raises(ChaosBackendError):
            pm.dump_cluster_snapshot(SIG, 3, blob)
        # ChaosBackendError IS a ConnectionError: the runner's transient-ack
        # triage catches it without special-casing chaos
        assert issubclass(ChaosBackendError, ConnectionError)
        # next attempt (past `at`) succeeds and the store is uncorrupted
        get_chaos().begin_checkpoint_attempt()
        pm.dump_cluster_snapshot(SIG, 4, blob)
        assert pm.commit_cluster_manifest(SIG, 4)
        assert _manager(tmp_path).load_cluster_manifest(SIG)["commit_id"] == 4
    finally:
        monkeypatch.delenv("PATHWAY_CHAOS_PLAN")
        reset_chaos()


def test_chaos_torn_manifest_fails_commit_readback(tmp_path, monkeypatch):
    """The injected torn PUT must be caught by the read-back verification:
    ``commit_cluster_manifest`` returns False and a fresh loader still sees
    the previous checkpoint."""
    monkeypatch.setenv(
        "PATHWAY_CHAOS_PLAN",
        json.dumps({"checkpoint": [{"op": "torn_manifest", "rank": 0, "at": 1}]}),
    )
    reset_chaos()
    try:
        pm = _manager(tmp_path)
        pm.open_for_append(SIG)
        from pathway_tpu.internals.chaos import get_chaos

        blob = {"states": {}, "evaluators": {}, "source_offsets": {},
                "source_deltas": {}}
        get_chaos().begin_checkpoint_attempt()  # attempt 0: clean
        pm.dump_cluster_snapshot(SIG, 2, blob)
        assert pm.commit_cluster_manifest(SIG, 2) is True
        get_chaos().begin_checkpoint_attempt()  # attempt 1: torn
        pm.dump_cluster_snapshot(SIG, 5, blob)
        assert pm.commit_cluster_manifest(SIG, 5) is False
        assert _manager(tmp_path).load_cluster_manifest(SIG)["commit_id"] == 2
    finally:
        monkeypatch.delenv("PATHWAY_CHAOS_PLAN")
        reset_chaos()


# -- mesh: incremental-rewind serve log ---------------------------------------


def _wire_pair(first_port):
    from pathway_tpu.parallel.cluster import ClusterExchange

    made: dict = {}
    errors: list = []

    def mk(me: int) -> None:
        try:
            made[me] = ClusterExchange(2, me, first_port)
        except BaseException as exc:
            errors.append(exc)

    threads = [threading.Thread(target=mk, args=(me,)) for me in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, f"wiring failed: {errors}"
    return made[0], made[1]


def test_serve_log_records_seals_and_serves(monkeypatch):
    monkeypatch.setenv("PATHWAY_HEARTBEAT_INTERVAL_S", "0.1")
    a, b = _wire_pair(_port_base())
    try:
        a.commit_log_depth = b.commit_log_depth = 4
        for cid in range(3):
            a.begin_commit_log(cid)
            b.begin_commit_log(cid)
            done: dict = {}
            t = threading.Thread(
                target=lambda c=cid: done.setdefault(
                    "b", b.exchange_parts(b"neu:%d" % c, {0: b"from-b-%d" % c})
                )
            )
            t.start()
            got = a.exchange_parts(b"neu:%d" % cid, {1: b"from-a-%d" % cid})
            t.join(timeout=10)
            assert got == {1: b"from-b-%d" % cid}
            a.end_commit_log()
            b.end_commit_log()
        assert a.commit_log_covers([0, 1, 2])
        assert not a.commit_log_covers([0, 3])

        # serving commit 1 re-sends the ORIGINAL logged parts: the peer
        # (simulating a tail-replaying replacement) recomputes the same tag
        # live and must receive exactly what the original barrier carried
        out: dict = {}
        t = threading.Thread(
            target=lambda: out.setdefault(
                "b", b.exchange_parts(b"neu:1", {0: b"recomputed-live"})
            )
        )
        t.start()
        assert a.serve_commit_log(1) == 1
        t.join(timeout=10)
        assert out["b"] == {0: b"from-a-1"}
    finally:
        a.close()
        b.close()


def test_serve_log_depth_discard_and_prune(monkeypatch):
    monkeypatch.setenv("PATHWAY_HEARTBEAT_INTERVAL_S", "0")
    from pathway_tpu.parallel.cluster import ClusterExchange

    a, b = _wire_pair(_port_base())
    try:
        a.commit_log_depth = 2
        for cid in range(4):
            a.begin_commit_log(cid)
            a._commit_log[cid].append((b"tag:%d" % cid, {1: b"p"}))
            a.end_commit_log()
        # depth bound: only the newest 2 sealed entries survive
        assert list(a._commit_log) == [2, 3]

        # an interrupted commit's PARTIAL entry is discarded, never served
        a.begin_commit_log(9)
        a._commit_log[9].append((b"tag:9", {1: b"partial"}))
        a.discard_open_commit_log()
        assert 9 not in a._commit_log
        assert a.serve_commit_log(9) == 0

        # a durable checkpoint prunes everything at or behind its commit
        a.prune_commit_log(2)
        assert list(a._commit_log) == [3]
    finally:
        a.close()
        b.close()

    # ThreadExchange never rejoins: its serve log stays disabled
    tx = ClusterExchange.__new__(ClusterExchange)  # no sockets needed
    from pathway_tpu.parallel.cluster import ThreadExchange

    assert ThreadExchange.supports_rejoin is False


# -- runner: REWIND_SAFE gating ----------------------------------------------


def test_rewind_safe_flag_gates_undo_ring():
    """A graph holding an operator with ``REWIND_SAFE = False`` (e.g. the
    external-index evaluator, whose in-place pages would cost more to snapshot
    per commit than the tail replay saves, or the drain-sensitive time-column
    family, whose ``runner.draining`` flush a rejoin replay cannot reproduce)
    must skip the rewind rung."""
    from pathway_tpu.engine.evaluators import (
        BufferEvaluator,
        Evaluator,
        ExternalIndexEvaluator,
        ForgetEvaluator,
        FreezeEvaluator,
    )

    assert Evaluator.REWIND_SAFE is True
    assert ExternalIndexEvaluator.REWIND_SAFE is False
    for cls in (BufferEvaluator, FreezeEvaluator, ForgetEvaluator):
        assert cls.REWIND_SAFE is False, cls.__name__


# -- spawn acceptance ---------------------------------------------------------

CKPT_PROG = textwrap.dedent(
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

# a wedged rejoin must fail fast, not eat the tier-1 budget
HARD_TIMEOUT_S = 120


def _spawn_ckpt(tmp_path, first_port, *, n, plan, max_restarts, extra_env=None):
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_CHAOS_SEED"] = "7"
    env["PATHWAY_CHAOS_PLAN"] = json.dumps(plan)
    env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
    env["PATHWAY_BARRIER_TIMEOUT_S"] = "30"
    env["PATHWAY_CHECKPOINT_INTERVAL_S"] = "0.4"
    env.update(extra_env or {})
    prog = tmp_path / "prog.py"
    prog.write_text(CKPT_PROG)
    proc = subprocess.Popen(
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
    # hard-timeout watchdog: a wedged rejoin is SIGKILLed as a group so the
    # test fails in bounded time with the stderr it produced so far
    watchdog = threading.Timer(
        HARD_TIMEOUT_S, lambda: _killpg_quiet(proc.pid)
    )
    watchdog.daemon = True
    watchdog.start()
    return proc, watchdog


def _killpg_quiet(pid: int) -> None:
    try:
        os.killpg(pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


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


def _terminate_group(proc, watchdog) -> str:
    watchdog.cancel()
    try:
        os.killpg(proc.pid, signal.SIGTERM)
    except ProcessLookupError:
        pass
    try:
        _, err = proc.communicate(timeout=20)
    except subprocess.TimeoutExpired:
        _killpg_quiet(proc.pid)
        _, err = proc.communicate()
    return err or ""


def _await_counts(proc, tmp_path, n, expected, deadline_s=90) -> dict:
    deadline = time.time() + deadline_s
    merged: dict = {}
    while time.time() < deadline:
        if proc.poll() is not None:
            _, err = proc.communicate()
            raise AssertionError(f"spawn exited early (rc={proc.returncode}): {err}")
        merged = _read_merged(tmp_path, n)
        if merged == expected:
            break
        time.sleep(0.3)
    return merged


def _drip_feed(tmp_path, seconds: float, rows_per_file: int = 2) -> int:
    """Write a small ``drip`` csv every 0.2s for ``seconds``, returning the
    number of rows written. Checkpoint attempts ride the per-commit allgather,
    so an IDLE cluster stops checkpointing: the initial files drain in well
    under a second, and without a live commit stream an attempt-gated chaos
    fault (``at`` >= 2) would never fire — the run converges failure-free and
    the test flakes on ingest-speed jitter. The drip keeps commits (and the
    wall-clock attempt counter) ticking through the kill window."""
    rows = 0
    i = 0
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        (tmp_path / "in" / f"drip{i:04d}.csv").write_text(
            "word\n" + "drip\n" * rows_per_file
        )
        rows += rows_per_file
        i += 1
        time.sleep(0.2)
    return rows


def _failure_free_counts(tmp_path) -> dict:
    """Reference output: the same pipeline run in-process with no faults."""
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


def _manifests(tmp_path) -> list:
    store = tmp_path / "store"
    if not store.exists():
        return []
    return sorted(
        int(f.name[len("cluster-manifest-"):-len(".json")])
        for f in store.iterdir()
        if f.name.startswith("cluster-manifest-") and f.name.endswith(".json")
    )


@pytest.mark.chaos
@pytest.mark.checkpoint
def test_coordinated_checkpoint_failover_n4_exact(tmp_path):
    """THE acceptance scenario: with coordinated checkpoints every 0.4s,
    SIGKILL rank 2 of ``spawn -n 4`` well after >=2 checkpoints have landed —
    the replacement recovers from the latest checkpoint + journal tail (never
    a full-history replay), survivors rewind in place, post-failover data is
    ingested exactly once, and the merged output is bit-identical to the
    failure-free run."""
    (tmp_path / "in").mkdir()
    first_port = _port_base()
    for i in range(4):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 1) + ["dog"] * 2) + "\n"
        )

    # attempt-gated (attempts tick at commit boundaries on the 0.4s cadence,
    # kept alive by the drip feed below): kill at the start of checkpoint
    # attempt 3, i.e. after exactly 3 checkpoints landed — a commit-id-gated
    # kill can lose the race against fast convergence on a loaded test host
    plan = {
        "checkpoint": [{"op": "pre_snapshot_kill", "rank": 2, "run": 0, "at": 3}]
    }
    proc, watchdog = _spawn_ckpt(tmp_path, first_port, n=4, plan=plan, max_restarts=1)
    err = ""
    try:
        # keep commits flowing so attempt 3 (the kill) is actually reached,
        # and keep dripping through the fence/rejoin so recovery is exercised
        # with data crossing the failure window
        dripped = _drip_feed(tmp_path, 8.0)
        (tmp_path / "in" / "late.csv").write_text(
            "word\n" + "\n".join(["owl"] * 3 + ["cat"] * 1) + "\n"
        )
        expected = {"cat": 11, "dog": 8, "owl": 3, "drip": dripped}
        merged = _await_counts(proc, tmp_path, 4, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc, watchdog)
    assert err.count("surgically relaunching rank 2") == 1, (
        f"expected exactly one surgical relaunch of rank 2:\n{err}"
    )
    assert "restarting the cluster" not in err, (
        f"survivors were torn down — restart-all fired instead of surgical:\n{err}"
    )
    assert "rejoined the cluster at epoch 1" in err, f"rejoin never completed:\n{err}"
    # the rejoin used a bounded-recovery rung, not a full-history replay
    assert ("via incremental rewind" in err) or ("via checkpoint+tail replay" in err), (
        f"recovery fell back to full journal replay despite checkpoints:\n{err}"
    )
    # >=1 durable manifest exists and the compacted journal stayed bounded
    assert _manifests(tmp_path), "no cluster checkpoint manifest was committed"
    # bit-identical to the failure-free run of the same pipeline
    assert _failure_free_counts(tmp_path) == merged


@pytest.mark.chaos
@pytest.mark.checkpoint
def test_kill_mid_checkpoint_protocol_recovers_from_previous(tmp_path):
    """Chaos satellite: SIGKILL rank 1 BETWEEN its snapshot write and the
    manifest commit (attempt 4 — after earlier checkpoints landed). The
    half-finished checkpoint must be invisible: recovery uses the previous
    manifest + journal tail and the output stays bit-identical."""
    (tmp_path / "in").mkdir()
    first_port = _port_base()
    for i in range(2):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 2) + ["dog"] * 3) + "\n"
        )

    plan = {
        "checkpoint": [{"op": "post_snapshot_kill", "rank": 1, "run": 0, "at": 4}]
    }
    proc, watchdog = _spawn_ckpt(tmp_path, first_port, n=2, plan=plan, max_restarts=1)
    err = ""
    try:
        # the commit stream must stay alive for attempt 4 to be reached
        dripped = _drip_feed(tmp_path, 7.0)
        (tmp_path / "in" / "late.csv").write_text(
            "word\n" + "\n".join(["owl"] * 2 + ["dog"] * 1) + "\n"
        )
        expected = {"cat": 5, "dog": 7, "owl": 2, "drip": dripped}
        merged = _await_counts(proc, tmp_path, 2, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc, watchdog)
    assert "surgically relaunching rank 1" in err, f"no surgical relaunch:\n{err}"
    assert "rejoined the cluster at epoch 1" in err, f"rejoin never completed:\n{err}"
    assert _failure_free_counts(tmp_path) == merged


@pytest.mark.chaos
@pytest.mark.checkpoint
def test_torn_manifest_mid_run_previous_checkpoint_stands(tmp_path):
    """Chaos satellite: rank 0 tears the manifest bytes on checkpoint attempt
    2. The read-back verification turns the torn write into a clean "attempt
    failed" — no compaction happens for it, the run continues, later attempts
    succeed, and a SIGKILL after that still recovers bit-identically."""
    (tmp_path / "in").mkdir()
    first_port = _port_base()
    for i in range(2):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word\n" + "\n".join(["cat"] * (i + 1) + ["dog"] * 2) + "\n"
        )

    # both faults are attempt-gated (attempts tick at commit boundaries on the
    # 0.4s cadence, kept alive by the drip feed below): a commit-id-gated kill
    # can lose the race against fast convergence on a loaded test host
    plan = {
        "checkpoint": [
            {"op": "torn_manifest", "rank": 0, "run": 0, "at": 2},
            {"op": "post_snapshot_kill", "rank": 1, "run": 0, "at": 5},
        ],
    }
    proc, watchdog = _spawn_ckpt(tmp_path, first_port, n=2, plan=plan, max_restarts=1)
    err = ""
    try:
        # the commit stream must stay alive for attempts 2 (torn) and 5 (kill)
        dripped = _drip_feed(tmp_path, 8.0)
        (tmp_path / "in" / "late.csv").write_text("word\nowl\nowl\n")
        expected = {"cat": 3, "dog": 4, "owl": 2, "drip": dripped}
        merged = _await_counts(proc, tmp_path, 2, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc, watchdog)
    assert "rejoined the cluster at epoch 1" in err, f"rejoin never completed:\n{err}"
    # the torn write was caught by the read-back verification, loudly
    assert "torn/unreadable" in err, f"torn manifest was never detected:\n{err}"
    # torn manifest never became the recovery point: every surviving manifest
    # on disk parses clean and the newest one loads
    for commit in _manifests(tmp_path):
        raw = (tmp_path / "store" / f"cluster-manifest-{commit:010d}.json").read_bytes()
        json.loads(raw)
    assert _failure_free_counts(tmp_path) == merged
