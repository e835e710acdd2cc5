"""Tiered IVF index (ISSUE 15): device-hot / host-cold / frozen-spill page
residency, EWMA-driven promotion with async prefetch, incremental centroid
maintenance, and the fence-riding background rebuild + generation swap
(``ops/knn_tiers.py``). The prefetch/rebuild/swap protocol's model checks live
in ``test_modelcheck.py`` (``tiered_index_model``)."""

from __future__ import annotations

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
from pathway_tpu.engine.brownout import get_brownout, reset_brownout
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.ops.knn_tiers import (
    DirSpillStore,
    TieredIvfKnnStore,
    tiering_enabled,
)

pytestmark = pytest.mark.tiered

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clustered(n, dim, n_centers, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(scale=5.0, size=(n_centers, dim)).astype(np.float32)
    docs = (
        centers[rng.integers(0, n_centers, n)] + rng.normal(size=(n, dim))
    ).astype(np.float32)
    return centers, docs


def _exact_top(docs, queries, k):
    qn = np.sum(queries * queries, axis=1)[:, None]
    dn = np.sum(docs * docs, axis=1)[None, :]
    dist = qn + dn - 2.0 * queries @ docs.T
    return np.argsort(dist, axis=1)[:, :k]


# -- residency / scoring ------------------------------------------------------


def test_tiered_full_probe_matches_exact():
    _, docs = _clustered(3000, 24, 12, seed=1)
    store = TieredIvfKnnStore(24, n_clusters=12, n_probe=12)
    store.add_many([f"d{i}" for i in range(3000)], docs)
    q = docs[:40]
    _s, idx, valid = store.search_batch(q, 10)
    assert valid[:, 0].all()
    exact = _exact_top(docs, q, 10)
    for r in range(40):
        got = {store.key_of[int(i)] for i in idx[r] if i >= 0}
        want = {f"d{j}" for j in exact[r]}
        assert got == want
    store.close()


def test_residency_never_changes_results_bitwise(tmp_path):
    """The tier-honesty contract: the same corpus + queries return BITWISE
    identical scores/slots whether everything is hot or the store runs a
    tiny HBM budget with a frozen spill tier."""
    centers, docs = _clustered(4000, 16, 8, seed=2)
    keys = [f"d{i}" for i in range(4000)]
    rng = np.random.default_rng(3)
    q = (centers[np.zeros(16, dtype=int)] + rng.normal(size=(16, 16))).astype(
        np.float32
    )
    tiered = TieredIvfKnnStore(
        16, n_clusters=8, n_probe=2, hbm_budget_bytes=30_000,
        spill_store=DirSpillStore(str(tmp_path / "spill")),
    )
    allhot = TieredIvfKnnStore(16, n_clusters=8, n_probe=2)
    tiered.add_many(keys, docs)
    allhot.add_many(keys, docs)
    for _ in range(6):  # settle the EWMA; spill + demotion engage
        rt = tiered.search_batch(q, 10)
        rh = allhot.search_batch(q, 10)
    time.sleep(0.3)  # the prefetch worker drains its staging queue
    rt = tiered.search_batch(q, 10)
    rh = allhot.search_batch(q, 10)
    stats = tiered.tier_stats()
    assert stats["spilled"] > 0 or stats["spills"] > 0, stats
    np.testing.assert_array_equal(rt[0], rh[0])
    np.testing.assert_array_equal(rt[1], rh[1])
    tiered.close()
    allhot.close()


def test_hot_tier_respects_budget_with_demotions():
    _, docs = _clustered(4000, 16, 8, seed=4)
    budget = 50_000
    store = TieredIvfKnnStore(
        16, n_clusters=8, n_probe=8, hbm_budget_bytes=budget
    )
    store.add_many([f"d{i}" for i in range(4000)], docs)
    q = docs[:16]
    for _ in range(8):
        store.search_batch(q, 5)
    time.sleep(0.5)  # promotions are async; let them land and evict
    assert store.tiers.hot_bytes <= budget, store.tier_stats()
    # full-probe traffic over 8 clusters cannot all fit: something demoted
    assert store.tiers.counts()["hot"] < 8, store.tier_stats()
    store.close()


def test_spill_prefetch_and_stall_accounting(tmp_path):
    from pathway_tpu.engine import telemetry
    from pathway_tpu.engine.profile import histograms

    centers, docs = _clustered(4000, 16, 8, seed=5)
    store = TieredIvfKnnStore(
        16, n_clusters=8, n_probe=2, hbm_budget_bytes=30_000,
        spill_store=DirSpillStore(str(tmp_path / "spill")),
    )
    store.add_many([f"d{i}" for i in range(4000)], docs)
    rng = np.random.default_rng(6)
    q0 = (centers[np.zeros(8, dtype=int)] + rng.normal(size=(8, 16))).astype(
        np.float32
    )
    for _ in range(6):
        store.search_batch(q0, 5)  # narrow working set: the rest freezes
    assert store.tier_stats()["spilled"] > 0, store.tier_stats()
    # now probe EVERY cluster: frozen ones must come back (prefetch/unspill)
    _s, idx, valid = store.search_batch(docs[:32], 5)
    assert valid[:, 0].all()
    stats = store.tier_stats()
    assert stats["probe_spilled"] > 0, stats
    stages = telemetry.stage_snapshot("index.")
    assert stages.get("index.probes", 0) > 0
    assert "pathway_ivf_prefetch_stall_seconds" in histograms()
    assert "pathway_ivf_tier_hit_ratio" in histograms()
    assert "pathway_ivf_tier_occupancy_ratio" in histograms()
    store.close()


# -- incremental maintenance / background rebuild -----------------------------


def test_churn_is_incremental_not_stop_the_world():
    """Mutation batches below the rebuild-drift threshold touch only their
    clusters: the generation never bumps, no rebuild is scheduled, and both
    added and removed rows are immediately visible."""
    _, docs = _clustered(2000, 16, 8, seed=7)
    store = TieredIvfKnnStore(16, n_clusters=8, n_probe=8)
    store.add_many([f"d{i}" for i in range(2000)], docs)
    store.search_batch(docs[:4], 3)  # initial train
    gen0 = store.generation
    rng = np.random.default_rng(8)
    for wave in range(4):
        fresh = (docs[rng.integers(0, 2000, 40)]).astype(np.float32)
        store.add_many([f"w{wave}-{i}" for i in range(40)], fresh)
        for i in range(20):
            store.remove(f"w{wave}-{i}") if wave else store.remove(f"d{i}")
        _s, idx, _v = store.search_batch(fresh[:2], 1)
    assert store.generation == gen0
    assert not store._rebuild_inflight(), store.tier_stats()
    # a just-added row is findable, a just-removed row is not
    probe_vec = docs[150:151]
    store.add("fresh-row", probe_vec[0])
    _s, idx, _v = store.search_batch(probe_vec, 1)
    assert store.key_of.get(int(idx[0, 0])) == "fresh-row"
    store.remove("fresh-row")
    _s, idx, _v = store.search_batch(probe_vec, 1)
    assert store.key_of.get(int(idx[0, 0])) != "fresh-row"
    store.close()


def test_drifted_cluster_splits_without_global_retrain():
    """Concentrated churn into one region splits/recenters THAT cluster
    (bounded per-cluster work) — n_clusters can grow, generation stays."""
    _, docs = _clustered(800, 8, 4, seed=9)
    store = TieredIvfKnnStore(8, n_clusters=4, n_probe=4)
    store.add_many([f"d{i}" for i in range(800)], docs)
    store.search_batch(docs[:4], 3)
    gen0, c0 = store.generation, store.n_clusters
    # pile one tight blob onto a single cluster (far corner of the space)
    blob = (np.full((600, 8), 40.0) + np.random.default_rng(10).normal(
        size=(600, 8)
    )).astype(np.float32)
    for s in range(0, 600, 100):
        store.add_many([f"b{i}" for i in range(s, s + 100)], blob[s : s + 100])
        store.search_batch(blob[:2], 1)
    assert store.generation == gen0
    assert store.n_clusters > c0 or store.stats["splits"] > 0, store.tier_stats()
    store.close()


def test_background_rebuild_swaps_at_commit_boundary():
    _, docs = _clustered(1500, 16, 8, seed=11)
    store = TieredIvfKnnStore(16, n_clusters=8, n_probe=8)
    store.add_many([f"d{i}" for i in range(1500)], docs)
    store.search_batch(docs[:4], 3)
    gen0 = store.generation
    # churn past the rebuild-drift threshold (replace the whole corpus)
    for i in range(1500):
        store.remove(f"d{i}")
    _, fresh = _clustered(1600, 16, 8, seed=12)
    store.add_many([f"n{i}" for i in range(1600)], fresh)
    r_old = store.search_batch(fresh[:8], 5)
    assert store._rebuild_inflight() or store.generation > gen0
    # the OLD generation answered while the rebuild ran — and correctly
    assert np.isfinite(r_old[0][:, 0]).all()
    deadline = time.monotonic() + 30
    while store._rebuild_inflight() and time.monotonic() < deadline:
        time.sleep(0.05)
    store.search_batch(fresh[:1], 1)  # the commit boundary that swaps
    store.search_batch(fresh[:1], 1)
    assert store.generation == gen0 + 1, store.tier_stats()
    exact = _exact_top(fresh, fresh[:20], 10)
    _s, idx, _v = store.search_batch(fresh[:20], 10)
    hits = 0
    for r in range(20):
        got = {store.key_of.get(int(i)) for i in idx[r] if i >= 0}
        hits += len(got & {f"n{j}" for j in exact[r]})
    assert hits / 200 >= 0.95
    # the swapped-in generation locates rows the way every consumer reads them:
    # a removal after the swap works and the row stops being served
    store.remove("n0")
    _s, idx, _v = store.search_batch(fresh[:1], 3)
    assert "n0" not in {store.key_of.get(int(i)) for i in idx[0] if i >= 0}
    # pause accounting: the swap took ONE bounded pause, not a retrain stall
    assert store.stats["swaps"] == 1
    assert store.stats["max_pause_s"] < 5.0
    store.close()


def test_rebuild_dirty_churn_reconciled_at_swap():
    """Rows added/removed WHILE the rebuild runs land in the swapped
    generation exactly once (the dirty-set reconcile)."""
    _, docs = _clustered(1200, 16, 8, seed=13)
    store = TieredIvfKnnStore(16, n_clusters=8, n_probe=8)
    store.add_many([f"d{i}" for i in range(1200)], docs)
    store.search_batch(docs[:4], 3)
    for i in range(1200):
        store.remove(f"d{i}")
    _, fresh = _clustered(1200, 16, 8, seed=14)
    store.add_many([f"n{i}" for i in range(1200)], fresh)
    store.search_batch(fresh[:1], 1)  # schedules the rebuild
    assert store._rebuild_inflight()
    # churn DURING the rebuild: late adds + a late removal
    late = fresh[:5] + 0.25
    store.add_many([f"late{i}" for i in range(5)], late)
    store.remove("n0")
    deadline = time.monotonic() + 30
    while store._rebuild_inflight() and time.monotonic() < deadline:
        time.sleep(0.05)
    store.search_batch(fresh[:1], 1)
    assert store.generation >= 1
    _s, idx, _v = store.search_batch(late, 1)
    got = {store.key_of.get(int(i)) for i in idx[:, 0]}
    assert got == {f"late{i}" for i in range(5)}, got
    _s, idx, _v = store.search_batch(fresh[:1], 3)
    assert "n0" not in {store.key_of.get(int(i)) for i in idx[0] if i >= 0}
    store.close()


# -- chaos: torn swap + rebuild kill ------------------------------------------


@pytest.mark.chaos
def test_torn_tier_swap_old_generation_intact_then_retries(monkeypatch):
    """Injected ``tier_swap_torn`` at rebuild attempt 0: the pending
    generation is DISCARDED at the commit boundary, the old generation keeps
    serving correct results, and the next maintenance pass schedules a fresh
    rebuild (attempt 1, not gated) that swaps cleanly."""
    from pathway_tpu.internals.chaos import reset_chaos

    monkeypatch.setenv(
        "PATHWAY_CHAOS_PLAN",
        json.dumps({"index": [{"op": "tier_swap_torn", "rank": 0, "at": 0}]}),
    )
    monkeypatch.setenv("PATHWAY_CHAOS_SEED", "3")
    reset_chaos()
    try:
        _, docs = _clustered(1000, 16, 8, seed=15)
        store = TieredIvfKnnStore(16, n_clusters=8, n_probe=8)
        store.add_many([f"d{i}" for i in range(1000)], docs)
        store.search_batch(docs[:4], 3)
        for i in range(1000):
            store.remove(f"d{i}")
        _, fresh = _clustered(1000, 16, 8, seed=16)
        store.add_many([f"n{i}" for i in range(1000)], fresh)
        store.search_batch(fresh[:1], 1)  # schedules rebuild attempt 0
        deadline = time.monotonic() + 30
        while store._rebuild_inflight() and time.monotonic() < deadline:
            time.sleep(0.05)
        r_torn = store.search_batch(fresh[:10], 5)  # the torn swap boundary
        assert store.stats["swaps_torn"] == 1, store.tier_stats()
        assert store.generation == 0  # OLD generation intact and serving
        assert np.isfinite(r_torn[0][:, 0]).all()
        exact = _exact_top(fresh, fresh[:10], 5)
        for r in range(10):
            got = {store.key_of.get(int(i)) for i in r_torn[1][r] if i >= 0}
            assert got == {f"n{j}" for j in exact[r]}
        # drift is still over threshold: the retry rebuild (attempt 1) swaps
        store.search_batch(fresh[:1], 1)
        deadline = time.monotonic() + 30
        while store._rebuild_inflight() and time.monotonic() < deadline:
            time.sleep(0.05)
        store.search_batch(fresh[:1], 1)
        assert store.generation == 1, store.tier_stats()
        assert store.stats["swaps"] == 1
        store.close()
    finally:
        reset_chaos()


TIERED_CHAOS_PROG = textwrap.dedent(
    """
    import hashlib, json, os
    import numpy as np
    import pathway_tpu as pw

    tmp = os.environ["PATHWAY_TPU_TEST_DIR"]
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class DocSchema(pw.Schema):
        text: str

    @pw.udf
    def embed(text: str) -> np.ndarray:
        digest = hashlib.sha256(str(text).encode()).digest()
        rng = np.random.default_rng(int.from_bytes(digest[:8], "little"))
        v = rng.normal(size=8).astype(np.float32)
        return v / np.linalg.norm(v)

    docs = pw.io.fs.read(
        os.path.join(tmp, "in"), format="csv", schema=DocSchema,
        mode="streaming",
    )
    from pathway_tpu.stdlib.indexing import IvfKnnFactory

    # full probe: results are EXACT whatever generation answers, so the
    # output is bit-identical across any rebuild/kill/replay interleaving
    factory = IvfKnnFactory(
        dimensions=8, n_clusters=4, n_probe=4, embedder=embed
    )
    index = factory.build_index(docs.text, docs)
    queries = pw.debug.table_from_rows(
        pw.schema_builder({"q": str}), [("doc-7",), ("doc-23",), ("doc-41",)]
    )
    res = index.query(queries.q, number_of_matches=1, collapse_rows=True)
    out_path = os.path.join(tmp, f"out_{pid}.json")
    rows = {}
    def on_change(key, row, time, is_addition):
        if is_addition:
            rows[repr(key)] = {"q": row["q"], "text": list(row["text"])}
        else:
            rows.pop(repr(key), None)
        with open(out_path + ".tmp", "w") as f:
            json.dump(list(rows.values()), f)
        os.replace(out_path + ".tmp", out_path)

    pw.io.subscribe(res.select(pw.this.q, pw.this.text), on_change)
    pw.run(
        monitoring_level=pw.MonitoringLevel.NONE,
        persistence_config=pw.persistence.Config(
            pw.persistence.Backend.filesystem(os.path.join(tmp, "store"))
        ),
    )
    """
)


@pytest.mark.chaos
def test_rebuild_kill_spawn_n2_recovers_bit_identical(tmp_path):
    """The n=2 acceptance: a chaos ``rebuild_kill`` SIGKILLs rank 0 while its
    background index rebuild is mid-build; the supervisor ladder recovers
    (persistence on), the torn new generation is simply gone, and the final
    retrieve output is bit-identical to a failure-free run."""
    (tmp_path / "in").mkdir()
    # wave 1 trains; wave 2's churn crosses the rebuild-drift threshold
    (tmp_path / "in" / "a.csv").write_text(
        "text\n" + "\n".join(f"doc-{i}" for i in range(30)) + "\n"
    )
    prog = tmp_path / "prog.py"
    prog.write_text(TIERED_CHAOS_PROG)
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_IVF_TIERED"] = "on"
    env["PATHWAY_IVF_REBUILD_DRIFT"] = "0.5"
    env["PATHWAY_CHAOS_SEED"] = "7"
    env["PATHWAY_CHAOS_PLAN"] = json.dumps(
        {"index": [{"op": "rebuild_kill", "rank": 0, "run": 0}]}
    )
    env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
    env["PATHWAY_BARRIER_TIMEOUT_S"] = "30"
    first_port = 26200 + os.getpid() % 500 * 4
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "--first-port", str(first_port),
            "--max-restarts", "2",
            sys.executable, str(prog),
        ],
        env=env, cwd=str(tmp_path), start_new_session=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
    )

    def _answers():
        merged = {}
        for p in range(2):
            path = tmp_path / f"out_{p}.json"
            if path.exists():
                try:
                    for r in json.loads(path.read_text()):
                        merged[r["q"]] = r["text"]
                except ValueError:
                    pass
        return merged

    try:
        # wave 2 lands mid-run: the add churn schedules the rebuild the
        # chaos op kills
        time.sleep(2.0)
        (tmp_path / "in" / "b.csv").write_text(
            "text\n" + "\n".join(f"doc-{i}" for i in range(30, 60)) + "\n"
        )
        want = {"doc-7": ["doc-7"], "doc-23": ["doc-23"], "doc-41": ["doc-41"]}
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(
                    f"spawn exited early rc={proc.returncode}: {err[-2000:]}"
                )
            if _answers() == want:
                break
            time.sleep(0.5)
        assert _answers() == want, _answers()
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
    # the kill actually fired (rank 0 died mid-rebuild and was relaunched)
    assert "restart" in (err or "").lower() or "rejoin" in (err or "").lower(), (
        err or ""
    )[-2000:]


# -- brownout interplay -------------------------------------------------------


def test_brownout_rung2_probe_never_triggers_promotion_churn():
    """The satellite contract: rung 2 halves ``n_probe`` at query time AND a
    browned-out probe set must not promote/demote — degradation protects the
    tiers, it must not thrash them."""
    reset_brownout()
    try:
        _, docs = _clustered(2000, 16, 8, seed=17)
        store = TieredIvfKnnStore(
            16, n_clusters=8, n_probe=8, hbm_budget_bytes=60_000
        )
        store.add_many([f"d{i}" for i in range(2000)], docs)
        store.search_batch(docs[:2], 1)  # train off the brownout clock
        time.sleep(0.3)
        from pathway_tpu.engine import telemetry

        before = telemetry.stage_snapshot("index.").get(
            "index.prefetch_requests", 0.0
        )
        get_brownout().observe_occupancy(0.95)  # engage rung 2
        assert get_brownout().nprobe_shift() == 1
        assert store._effective_n_probe() == 4
        for _ in range(4):
            store.search_batch(docs[:8], 3)
        after = telemetry.stage_snapshot("index.").get(
            "index.prefetch_requests", 0.0
        )
        assert after == before, (before, after)
        store.close()
    finally:
        reset_brownout()


# -- selection / descriptor / membership --------------------------------------


def test_tiering_enabled_knob(monkeypatch):
    monkeypatch.delenv("PATHWAY_IVF_TIERED", raising=False)
    monkeypatch.delenv("PATHWAY_IVF_HBM_BUDGET_MB", raising=False)
    assert not tiering_enabled()
    monkeypatch.setenv("PATHWAY_IVF_HBM_BUDGET_MB", "64")
    assert tiering_enabled()  # auto: budget implies tiered
    monkeypatch.setenv("PATHWAY_IVF_TIERED", "off")
    assert not tiering_enabled()
    monkeypatch.setenv("PATHWAY_IVF_TIERED", "on")
    monkeypatch.delenv("PATHWAY_IVF_HBM_BUDGET_MB", raising=False)
    assert tiering_enabled()
    from pathway_tpu.ops.knn import IvfKnnIndex

    index = IvfKnnIndex(8, n_clusters=4, n_probe=2)
    assert isinstance(index.store, TieredIvfKnnStore)
    index.store.close()


def test_rebuild_descriptor_roundtrip():
    from pathway_tpu.ops.knn import IvfKnnIndex

    _, docs = _clustered(500, 8, 4, seed=18)
    src = IvfKnnIndex(8, n_clusters=4, n_probe=4, tiered=True)
    src.add_many(
        [f"d{i}" for i in range(500)], list(docs),
        filter_data=[{"n": i} if i % 2 == 0 else None for i in range(500)],
    )
    src.search_many([docs[0]], [1])  # train
    desc = src.rebuild_descriptor()
    assert desc is not None and len(desc["keys"]) == 500
    dst = IvfKnnIndex(8, n_clusters=4, n_probe=4, tiered=True)
    dst.install_rebuild_descriptor(desc)
    a = src.search_many(list(docs[:20]), [3] * 20)
    b = dst.search_many(list(docs[:20]), [3] * 20)
    for ra, rb in zip(a, b):
        assert {k for k, _ in ra} == {k for k, _ in rb}
    assert dst.filter_data.get("d0") == {"n": 0}
    src.store.close()
    dst.store.close()


def test_reshard_plan_accepts_descriptor_capable_external_index():
    """The membership-preflight half of the new contract: an external index
    whose store exports a rebuildable descriptor plans as ``replicate``
    instead of the blanket device-resident refusal."""
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.parallel.membership import compute_reshard_plan
    from pathway_tpu.stdlib.indexing import IvfKnnFactory

    from .mocks import fake_embedding

    @pw.udf
    def embed(text: str) -> np.ndarray:
        return fake_embedding(text, 8)

    G.clear()
    docs = pw.debug.table_from_rows(
        pw.schema_builder({"text": str}), [("alpha",), ("beta",), ("gamma",)]
    )
    factory = IvfKnnFactory(dimensions=8, n_clusters=2, n_probe=2, embedder=embed)
    index = factory.build_index(docs.text, docs)
    queries = pw.debug.table_from_rows(pw.schema_builder({"q": str}), [("alpha",)])
    res = index.query_as_of_now(queries.q, number_of_matches=1, collapse_rows=True)
    got: list = []
    pw.io.subscribe(res, lambda *a, **k: got.append(1))
    runner = GraphRunner(G._current)
    runner.lint_exempt = True
    runner.run(monitoring_level=pw.MonitoringLevel.NONE, max_commits=4)
    for node in runner._nodes:
        ev = runner.evaluators[node.id]
        ev._cluster_policies = tuple(
            ev.cluster_input_policy(i) for i in range(len(node.inputs))
        )
    plan = compute_reshard_plan(runner)
    # the external-index node itself plans as "replicate" — the blanket
    # device-resident refusal is GONE for descriptor-capable indexes (the
    # collapsed as-of-now reply downstream is keyed by query and plans by key)
    ext = [
        nid for nid, pol in plan.policies.items() if pol == "replicate"
    ]
    assert ext, (plan.policies, plan.refusals)
    assert not any(
        "external index" in r or "snapshot protocol" in r for r in plan.refusals
    ), plan.refusals
    # descriptor round-trips through the evaluator surface the fragments use
    ev = runner.evaluators[ext[0]]
    desc = ev.rebuild_descriptor()
    assert desc is not None and len(desc["keys"]) == 3
    G.clear()


def test_reshard_plan_keeps_typed_refusal_without_descriptor():
    """An index type with no export contract still refuses — loudly."""
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.parallel.membership import compute_reshard_plan
    from pathway_tpu.stdlib.indexing.nearest_neighbors import LshKnn
    from pathway_tpu.stdlib.indexing.data_index import DataIndex

    from .mocks import fake_embedding

    @pw.udf
    def embed(text: str) -> np.ndarray:
        return fake_embedding(text, 8)

    G.clear()
    docs = pw.debug.table_from_rows(
        pw.schema_builder({"text": str}), [("alpha",), ("beta",)]
    )
    index = DataIndex(
        docs, LshKnn(docs.text, None, dimensions=8, embedder=embed)
    )
    queries = pw.debug.table_from_rows(pw.schema_builder({"q": str}), [("alpha",)])
    res = index.query_as_of_now(queries.q, number_of_matches=1, collapse_rows=True)
    got: list = []
    pw.io.subscribe(res, lambda *a, **k: got.append(1))
    runner = GraphRunner(G._current)
    runner.lint_exempt = True
    runner.run(monitoring_level=pw.MonitoringLevel.NONE, max_commits=4)
    for node in runner._nodes:
        ev = runner.evaluators[node.id]
        ev._cluster_policies = tuple(
            ev.cluster_input_policy(i) for i in range(len(node.inputs))
        )
    plan = compute_reshard_plan(runner)
    assert not plan.ok
    assert any("rebuildable descriptor" in r for r in plan.refusals), plan.refusals
    G.clear()


def test_index_counters_on_openmetrics():
    from pathway_tpu.engine.http_server import ProberStats

    from .utils import validate_openmetrics

    _, docs = _clustered(500, 8, 4, seed=19)
    store = TieredIvfKnnStore(8, n_clusters=4, n_probe=2)
    store.add_many([f"d{i}" for i in range(500)], docs)
    store.search_batch(docs[:4], 3)
    text = ProberStats().to_openmetrics()
    validate_openmetrics(text)
    assert 'pathway_stage_total{stage="index.probes"}' in text
    assert "pathway_ivf_tier_occupancy_ratio" in text
    store.close()
