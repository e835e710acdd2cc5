"""Elastic mesh membership: grow/shrink the cluster under traffic.

Four layers under test:

- membership plumbing (``parallel/membership.py``): the typed
  ``MembershipMismatchError`` (manifest_n/current_n/epoch + remediation
  hint), the supervisor<->worker directive file, reshard-policy analysis
  refusals;
- state handoff: ``StateTable.reshard_partition`` and the
  ``GroupbyEvaluator`` keyed export/import round-trip (the array
  redistribution at the heart of the reshard);
- chaos (``internals/chaos.py``): the ``scale_join_kill`` /
  ``scale_drain_kill`` / ``handoff_torn`` / ``dropped_scale_handshake``
  plan ops;
- spawn acceptance: a ``spawn -n 2`` cluster scaled 2 -> 4 -> 2 UNDER LIVE
  INGESTION, final output bit-identical to a static n=2 run; joiner catch-up
  from the membership manifest + fragments only (no journal replay,
  asserted on the joiner's own log line); each chaos op recovering via the
  escalation ladder without hanging.
"""

from __future__ import annotations

import itertools
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals.chaos import Chaos
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.parallel.membership import (
    MembershipDirective,
    MembershipMismatchError,
    clear_directive,
    read_directive,
    write_directive,
)

pytestmark = pytest.mark.elastic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PORT_SLOT = itertools.count()


def _port_base() -> int:
    return 36000 + os.getpid() % 150 * 40 + next(_PORT_SLOT) * 8


# -- typed mismatch + directive plumbing --------------------------------------


def test_membership_mismatch_error_is_typed_and_actionable(tmp_path):
    """Satellite: a worker-count mismatch carries (manifest_n, current_n,
    epoch) and a --scale-vs-corrupt-store remediation hint, and stays a
    ValueError for pre-elastic refusal handling."""
    from pathway_tpu.persistence.engine import PersistenceManager

    cfg = pw.persistence.Config(
        pw.persistence.Backend.filesystem(tmp_path / "store")
    )
    pm = PersistenceManager(cfg)
    with pytest.raises(MembershipMismatchError) as excinfo:
        pm._check_meta({"key_derivation": 2, "workers": 4, "epoch": 3}, "journal")
    err = excinfo.value
    assert isinstance(err, ValueError)  # pre-elastic triage keeps working
    assert err.manifest_n == 4
    assert err.current_n == 1
    assert err.epoch == 3
    assert "--scale" in str(err) or "spawn --scale" in str(err)
    assert "clear the persistence" in str(err)


def test_directive_file_roundtrip(tmp_path):
    d = MembershipDirective(generation=3, target_n=4, epoch=7, from_n=2)
    write_directive(str(tmp_path), d)
    got = read_directive(str(tmp_path))
    assert got == d
    clear_directive(str(tmp_path))
    assert read_directive(str(tmp_path)) is None
    # malformed files read as "no directive", never crash the commit loop
    (tmp_path / "membership.json").write_text("{not json")
    assert read_directive(str(tmp_path)) is None


def test_store_meta_self_heals_when_manifest_agrees(tmp_path, monkeypatch):
    """Crash window between the membership manifest (the commit point) and
    the store-meta update: a relaunch at the manifest's count rewrites the
    stale meta instead of refusing."""
    from pathway_tpu.persistence.engine import PersistenceManager

    root = tmp_path / "store"
    monkeypatch.setenv("PATHWAY_PROCESSES", "2")
    monkeypatch.setenv("PATHWAY_PROCESS_ID", "0")
    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(root))
    pm = PersistenceManager(cfg)
    pm.open_for_append("sig")  # meta written with workers=2
    pm.dump_cluster_snapshot("sig", 5, {"states": {}, "evaluators": {},
                                        "source_offsets": {}, "source_deltas": {}})
    # the membership manifest commits workers=4 but the meta update is lost
    assert pm.commit_membership_manifest(
        "sig", 5, epoch=1, from_n=2, to_n=4, generation=1
    )
    meta = json.loads((root / "store.meta").read_text())
    assert meta["workers"] == 2  # set_workers never ran (crash window)
    monkeypatch.setenv("PATHWAY_PROCESSES", "4")
    pm4 = PersistenceManager(cfg)
    pm4.open_for_append("sig")  # self-heals: manifest names 4
    assert json.loads((root / "store.meta").read_text())["workers"] == 4
    # a count agreeing with NEITHER still refuses typed
    monkeypatch.setenv("PATHWAY_PROCESSES", "3")
    pm3 = PersistenceManager(cfg)
    with pytest.raises(MembershipMismatchError):
        pm3.open_for_append("sig")


# -- state handoff: the array redistribution ----------------------------------


def test_state_table_reshard_partition_by_key():
    from pathway_tpu.engine.columnar import Delta, StateTable
    from pathway_tpu.internals.keys import sequential_keys, shard_of

    table = StateTable(["v"])
    keys = sequential_keys(100, 16)
    table.apply(Delta(keys, np.ones(16, dtype=np.int64),
                      {"v": np.arange(16, dtype=np.int64)}))
    parts = table.reshard_partition(lambda k: shard_of(k, 4))
    total = 0
    for dest, (pkeys, pdiffs, pcols) in parts.items():
        assert (shard_of(pkeys, 4) == dest).all()
        assert (pdiffs == 1).all()
        total += len(pkeys)
        # rebuild on the "new owner": values survive the move
        t2 = StateTable(["v"])
        t2.apply(Delta(pkeys, pdiffs, pcols))
        assert len(t2) == len(pkeys)
    assert total == 16


def _groupby_runner(rows):
    """A real single-process groupby run, returning (runner, node_id)."""
    from pathway_tpu.engine.runner import GraphRunner

    G.clear()
    t = pw.debug.table_from_rows(
        pw.schema_builder({"word": str}), [(w,) for w in rows]
    )
    counts = t.groupby(t.word).reduce(t.word, n=pw.reducers.count())
    runner = GraphRunner(G._current)
    runner.lint_exempt = True
    runner.run(monitoring_level=pw.MonitoringLevel.NONE, max_commits=4)
    nid = counts._node.id
    return runner, nid


def test_groupby_reshard_export_import_roundtrip():
    """The donor's full export, re-imported into fresh evaluators, carries
    every group's aggregates exactly (counts keep counting correctly)."""
    from pathway_tpu.engine.evaluators import GroupbyEvaluator

    rows = ["cat"] * 3 + ["dog"] * 2 + ["owl"] * 5 + ["elk"]
    runner, nid = _groupby_runner(rows)
    ev = runner.evaluators[nid]
    assert isinstance(ev, GroupbyEvaluator)
    assert ev.reshard_check() is None
    exports = ev.reshard_export(
        lambda keys: (keys["lo"] % np.uint64(2)).astype(np.int64), 2
    )
    assert sum(len(p["gkeys"]) for p in exports.values()) == 4  # 4 groups
    # two fresh importers, one per new rank; re-query their aggregates by
    # re-running an incremental delta through them
    runner2, nid2 = _groupby_runner([])  # empty: fresh evaluator shells
    fresh = runner2.evaluators[nid2]
    for payload in exports.values():
        fresh.reshard_import(payload)
    # all groups present with the exact leaf values
    gkeys, slots = fresh.gindex.items()
    assert len(gkeys) == 4
    counts = {
        int(k["lo"]): int(fresh.leaf_states[0].values(np.array([s]))[0])
        for k, s in zip(gkeys, slots)
    }
    src_gkeys, src_slots = runner.evaluators[nid].gindex.items()
    want = {
        int(k["lo"]): int(
            runner.evaluators[nid].leaf_states[0].values(np.array([s]))[0]
        )
        for k, s in zip(src_gkeys, src_slots)
    }
    assert counts == want
    assert sorted(want.values()) == [1, 2, 3, 5]


def test_groupby_reshard_import_refuses_overlapping_fragments():
    rows = ["cat", "dog"]
    runner, nid = _groupby_runner(rows)
    ev = runner.evaluators[nid]
    full = ev.reshard_export(
        lambda keys: np.zeros(len(keys), dtype=np.int64), 1
    )
    runner2, nid2 = _groupby_runner([])
    fresh = runner2.evaluators[nid2]
    fresh.reshard_import(full[0])
    with pytest.raises(RuntimeError, match="disjoint"):
        fresh.reshard_import(full[0])


# -- observability + plan refusals --------------------------------------------


def test_health_payload_exposes_membership_fields(tmp_path):
    """Satellite: /healthz (via GraphRunner.health) and the status files
    carry target_workers / current_workers / membership_state plus the
    commit/refusal/mismatch markers the supervisor steers by."""
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.internals.parse_graph import ParseGraph
    from pathway_tpu.parallel.supervisor import read_statuses, write_status

    runner = GraphRunner(ParseGraph())

    class _FakeCluster:
        supports_rejoin = True
        epoch = 1
        n = 4

        def heartbeat_ages(self):
            return {}

        def dead_peers(self):
            return {}

    runner._cluster = _FakeCluster()
    runner._membership_state = "resharding"
    runner._member_pending = MembershipDirective(2, 4, 1, 2)
    runner._member_committed_gen = 2
    health = runner.health()
    assert health["membership_state"] == "resharding"
    assert health["current_workers"] == 4
    assert health["target_workers"] == 4
    assert health["membership_committed"] == 2

    write_status(
        str(tmp_path), 0, commit=7, persistence=True,
        extra={
            "membership_state": health["membership_state"],
            "current_workers": health["current_workers"],
            "target_workers": health["target_workers"],
            "membership_committed": health["membership_committed"],
        },
    )
    status = read_statuses(str(tmp_path), 1)[0]
    assert status["membership_state"] == "resharding"
    assert status["target_workers"] == 4
    assert status["membership_committed"] == 2


def test_reshard_plan_accepts_join_graphs():
    """Join arrangements now export by join key and join OUTPUT rows are
    re-exchanged by their output row key, so a join graph plans clean —
    the refusal that used to live here is gone (ROADMAP item closed)."""
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.parallel.membership import compute_reshard_plan

    G.clear()
    left = pw.debug.table_from_rows(
        pw.schema_builder({"k": int, "a": int}), [(1, 10), (2, 20)]
    )
    right = pw.debug.table_from_rows(
        pw.schema_builder({"k": int, "b": int}), [(1, 100)]
    )
    joined = left.join(right, left.k == right.k).select(left.a, right.b)
    got: list = []
    pw.io.subscribe(joined, lambda *a, **k: got.append(1))
    runner = GraphRunner(G._current)
    runner.lint_exempt = True
    runner.run(monitoring_level=pw.MonitoringLevel.NONE, max_commits=3)
    # stamp the cluster policies the plan reads (single-process runs skip it)
    for node in runner._nodes:
        ev = runner.evaluators[node.id]
        ev._cluster_policies = tuple(
            ev.cluster_input_policy(i) for i in range(len(node.inputs))
        )
    plan = compute_reshard_plan(runner)
    assert plan.ok, plan.refusals
    join_nids = [n.id for n in runner._nodes if n.kind == "join"]
    assert join_nids and all(plan.policies[nid] == "bykey" for nid in join_nids)
    G.clear()


def test_reshard_plan_refusal_is_typed_and_structured():
    """A genuine refusal (join evaluator holding a populated UDF replay memo,
    which is keyed by pre-exchange row keys) surfaces as BOTH a formatted
    string and a structured {node, kind, reason} record for /healthz."""
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.parallel.membership import compute_reshard_plan

    G.clear()
    left = pw.debug.table_from_rows(
        pw.schema_builder({"k": int, "a": int}), [(1, 10)]
    )
    right = pw.debug.table_from_rows(
        pw.schema_builder({"k": int, "b": int}), [(1, 100)]
    )
    joined = left.join(right, left.k == right.k).select(left.a, right.b)
    pw.io.subscribe(joined, lambda *a, **k: None)
    runner = GraphRunner(G._current)
    runner.lint_exempt = True
    runner.run(monitoring_level=pw.MonitoringLevel.NONE, max_commits=3)
    for node in runner._nodes:
        ev = runner.evaluators[node.id]
        ev._cluster_policies = tuple(
            ev.cluster_input_policy(i) for i in range(len(node.inputs))
        )
    join_nid = next(n.id for n in runner._nodes if n.kind == "join")
    runner.evaluators[join_nid]._udf_memo = {b"stale": 1}
    plan = compute_reshard_plan(runner)
    assert not plan.ok
    assert any("memo" in r for r in plan.refusals)
    assert plan.refused_nodes and plan.refused_nodes[0]["kind"] == "join"
    assert plan.refused_nodes[0]["node"] == join_nid
    G.clear()


def test_reshard_plan_accepts_groupby_pipeline():
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.parallel.membership import compute_reshard_plan

    runner, nid = _groupby_runner(["cat", "dog", "cat"])
    for node in runner._nodes:
        ev = runner.evaluators[node.id]
        ev._cluster_policies = tuple(
            ev.cluster_input_policy(i) for i in range(len(node.inputs))
        )
    plan = compute_reshard_plan(runner)
    assert plan.ok, plan.refusals
    assert plan.policies[nid] == "bykey"
    G.clear()


# -- chaos plan ops -----------------------------------------------------------


def test_chaos_scale_fault_gating(monkeypatch):
    monkeypatch.setenv("PATHWAY_RESTART_COUNT", "0")
    plan = {
        "scale": [
            {"op": "handoff_torn", "rank": 1, "at": 0},
            {"op": "dropped_scale_handshake", "rank": 2},
            {"op": "scale_drain_kill", "rank": 3, "run": 1},
        ]
    }
    c = Chaos(0, plan)
    c.begin_scale_attempt()  # attempt 0
    assert c.scale_fault("handoff_torn", 1) is True
    assert c.scale_fault("handoff_torn", 0) is False  # wrong rank
    c.begin_scale_attempt()  # attempt 1: `at: 0` no longer fires
    assert c.scale_fault("handoff_torn", 1) is False
    assert c.scale_fault("dropped_scale_handshake", 2) is True  # every attempt
    assert c.scale_fault("scale_drain_kill", 3) is False  # wrong run
    assert c.stats["scale_faults"] == 2


def test_chaos_scale_kill_fires_sigkill(monkeypatch):
    killed: list = []
    from pathway_tpu.internals import chaos as chaos_mod

    monkeypatch.setattr(
        chaos_mod.os, "kill", lambda pid, sig: killed.append((pid, sig))
    )
    c = Chaos(0, {"scale": [{"op": "scale_join_kill", "rank": 2, "run": 0}]})
    c.begin_scale_attempt()
    c.maybe_scale_kill(1, "scale_join_kill")
    assert killed == []
    c.maybe_scale_kill(2, "scale_join_kill")
    assert killed == [(os.getpid(), signal.SIGKILL)]


# -- spawn acceptance ---------------------------------------------------------

ELASTIC_PROG = textwrap.dedent(
    """
    import json, os
    import pathway_tpu as pw

    tmp = os.environ["PATHWAY_TPU_TEST_DIR"]
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class WordSchema(pw.Schema):
        word: str

    t = pw.io.fs.read(
        os.path.join(tmp, "in"), format="csv", schema=WordSchema,
        mode="streaming",
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


def _spawn_elastic(
    tmp_path, first_port, *, n, scale_plan, plan=None, max_restarts=0,
    extra_env=None,
):
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_SCALE_PLAN"] = json.dumps(scale_plan)
    if plan is not None:
        env["PATHWAY_CHAOS_SEED"] = "7"
        env["PATHWAY_CHAOS_PLAN"] = json.dumps(plan)
    env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
    env["PATHWAY_BARRIER_TIMEOUT_S"] = "30"
    env["PATHWAY_FENCE_TIMEOUT_S"] = "30"
    env["PATHWAY_MEMBERSHIP_DEADLINE_S"] = "60"
    env.update(extra_env or {})
    prog = tmp_path / "prog.py"
    prog.write_text(ELASTIC_PROG)
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


def _await_counts(proc, tmp_path, n, expected, deadline_s=240) -> dict:
    # generous deadline: convergence itself is asserted EXACTLY by the
    # caller — under full-suite load on the shared 2-core host, a chaos
    # recovery (restart-all + journal replay) can legitimately take minutes,
    # and a tight wait here reads as a spurious row-loss failure
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
    """Reference output: the same pipeline run in-process, statically, at
    n=1 — the bit-identity baseline for the scaled cluster."""
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


def _write_files(tmp_path, prefix: str, spec: dict) -> None:
    for name, words in spec.items():
        (tmp_path / "in" / f"{prefix}{name}.csv").write_text(
            "word\n" + "\n".join(words) + "\n"
        )


@pytest.mark.chaos
def test_elastic_grow_shrink_cycle_exact(tmp_path):
    """THE acceptance scenario: n=2 -> 4 -> 2 under live ingestion. Data
    lands before, between, and after the transitions; the final merged
    output is bit-identical to a static n=2 (and n=1) run; joiners catch up
    from the membership manifest + fragments only (no journal replay —
    asserted on the joiner's own log line); leavers drain as planned
    exits."""
    (tmp_path / "in").mkdir()
    first_port = _port_base()
    _write_files(tmp_path, "a", {
        "0": ["cat"] * 3 + ["dog"] * 2,
        "1": ["cat"] * 2 + ["owl"] * 1,
        "2": ["dog"] * 4,
        "3": ["elk"] * 2 + ["cat"] * 1,
    })
    scale_plan = [
        {"after_commit": 4, "n": 4},
        {"after_commit": 14, "n": 2},
    ]
    proc = _spawn_elastic(tmp_path, first_port, n=2, scale_plan=scale_plan)
    err = ""
    try:
        time.sleep(8)  # let the grow transition land under traffic
        _write_files(tmp_path, "b", {
            "0": ["fox"] * 3 + ["cat"] * 2,
            "1": ["owl"] * 2,
        })
        time.sleep(8)  # shrink window
        _write_files(tmp_path, "c", {"0": ["cat"] * 1 + ["bee"] * 2})
        expected = {"cat": 9, "dog": 6, "owl": 3, "elk": 2, "fox": 3, "bee": 2}
        merged = _await_counts(proc, tmp_path, 4, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc)
    assert "membership change complete: cluster is n=4" in err, (
        f"grow transition never completed:\n{err}"
    )
    assert "membership change complete: cluster is n=2" in err, (
        f"shrink transition never completed:\n{err}"
    )
    assert "joined the cluster" in err and "no journal replay" in err, (
        f"joiner catch-up was not manifest+fragments:\n{err}"
    )
    assert "drained for scale-down" in err, (
        f"leavers were not drained cleanly:\n{err}"
    )
    assert "restarting the cluster" not in err, (
        f"a transition fell back to restart-all:\n{err}"
    )
    # bit-identical to the failure-free static run of the same pipeline
    assert _failure_free_counts(tmp_path) == merged


@pytest.mark.chaos
def test_elastic_scale_join_kill_recovers(tmp_path):
    """Chaos: a joiner is SIGKILLed before it installs. The transition
    cannot complete surgically — the supervisor recovers down the ladder
    (restart-all at the committed topology) without hanging, and the final
    output stays exact."""
    (tmp_path / "in").mkdir()
    first_port = _port_base()
    _write_files(tmp_path, "a", {
        "0": ["cat"] * 3 + ["dog"] * 2,
        "1": ["owl"] * 2,
    })
    plan = {"scale": [{"op": "scale_join_kill", "rank": 2, "run": 0}]}
    proc = _spawn_elastic(
        tmp_path, first_port, n=2,
        scale_plan=[{"after_commit": 4, "n": 4}],
        plan=plan, max_restarts=3,
        extra_env={"PATHWAY_MEMBERSHIP_DEADLINE_S": "20",
                   "PATHWAY_CONNECT_TIMEOUT_S": "8"},
    )
    err = ""
    try:
        time.sleep(12)
        _write_files(tmp_path, "b", {"0": ["fox"] * 3})
        expected = {"cat": 3, "dog": 2, "owl": 2, "fox": 3}
        merged = _await_counts(proc, tmp_path, 4, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc)
    assert "restarting the cluster" in err, (
        f"the joiner kill did not recover via restart-all:\n{err}"
    )


@pytest.mark.chaos
def test_elastic_handoff_torn_retries_and_completes(tmp_path):
    """Chaos: the first transition attempt's handoff fragment write tears.
    Read-back verification fails the ack barrier, the attempt aborts
    cleanly (previous topology stands), and the NEXT attempt completes —
    output exact, no restart."""
    (tmp_path / "in").mkdir()
    first_port = _port_base()
    _write_files(tmp_path, "a", {
        "0": ["cat"] * 3 + ["dog"] * 2,
        "1": ["owl"] * 2,
    })
    plan = {"scale": [{"op": "handoff_torn", "rank": 0, "at": 0, "run": 0}]}
    proc = _spawn_elastic(
        tmp_path, first_port, n=2,
        scale_plan=[{"after_commit": 4, "n": 3}],
        plan=plan, max_restarts=2,
    )
    err = ""
    try:
        time.sleep(8)
        _write_files(tmp_path, "b", {"0": ["fox"] * 3})
        expected = {"cat": 3, "dog": 2, "owl": 2, "fox": 3}
        merged = _await_counts(proc, tmp_path, 3, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc)
    assert "aborted (transient" in err or "will retry" in err, (
        f"the torn handoff never aborted an attempt:\n{err}"
    )
    assert "membership change complete: cluster is n=3" in err, (
        f"the retry never completed the transition:\n{err}"
    )
    assert "restarting the cluster" not in err, (
        f"the torn handoff escalated to restart-all:\n{err}"
    )


@pytest.mark.chaos
def test_elastic_dropped_scale_handshake_recovers(tmp_path):
    """Chaos: the joiner's membership hello is dropped — its wiring fails
    typed, the transition cannot converge, and the supervisor recovers
    (deadline -> restart-all at the committed topology) without hanging."""
    (tmp_path / "in").mkdir()
    first_port = _port_base()
    _write_files(tmp_path, "a", {
        "0": ["cat"] * 2 + ["dog"] * 1,
        "1": ["owl"] * 2,
    })
    plan = {"scale": [{"op": "dropped_scale_handshake", "rank": 2, "run": 0}]}
    proc = _spawn_elastic(
        tmp_path, first_port, n=2,
        scale_plan=[{"after_commit": 4, "n": 3}],
        plan=plan, max_restarts=3,
        extra_env={"PATHWAY_MEMBERSHIP_DEADLINE_S": "15",
                   "PATHWAY_CONNECT_TIMEOUT_S": "6",
                   "PATHWAY_FENCE_TIMEOUT_S": "12"},
    )
    err = ""
    try:
        time.sleep(14)
        _write_files(tmp_path, "b", {"0": ["fox"] * 2})
        expected = {"cat": 2, "dog": 1, "owl": 2, "fox": 2}
        merged = _await_counts(proc, tmp_path, 3, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc)
    assert "restarting the cluster" in err, (
        f"the dropped handshake did not recover via restart-all:\n{err}"
    )


@pytest.mark.chaos
def test_elastic_scale_drain_kill_recovers(tmp_path):
    """Chaos: a donor rank is SIGKILLed mid-handoff (after the quiesce vote,
    before its fragments are durable). The manifest never commits, so the
    ladder recovers at the OLD topology and the re-issued transition is not
    required for exactness — output stays exact either way."""
    (tmp_path / "in").mkdir()
    first_port = _port_base()
    _write_files(tmp_path, "a", {
        "0": ["cat"] * 2 + ["dog"] * 1,
        "1": ["owl"] * 2,
    })
    plan = {"scale": [{"op": "scale_drain_kill", "rank": 1, "run": 0, "at": 0}]}
    proc = _spawn_elastic(
        tmp_path, first_port, n=2,
        scale_plan=[{"after_commit": 4, "n": 4}],
        plan=plan, max_restarts=3,
        extra_env={"PATHWAY_MEMBERSHIP_DEADLINE_S": "20",
                   "PATHWAY_CONNECT_TIMEOUT_S": "8",
                   "PATHWAY_FENCE_TIMEOUT_S": "12"},
    )
    err = ""
    try:
        time.sleep(14)
        _write_files(tmp_path, "b", {"0": ["fox"] * 2})
        expected = {"cat": 2, "dog": 1, "owl": 2, "fox": 2}
        merged = _await_counts(proc, tmp_path, 4, expected)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        err = _terminate_group(proc)
    assert "restarting the cluster" in err, (
        f"the drain kill did not recover via restart-all:\n{err}"
    )
