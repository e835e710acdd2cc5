"""Quantized retrieval tower (ISSUE 16): per-page symmetric int8 payloads in
every tier with an exact fp32 rescore epilogue (``ops/knn_quant.py`` +
``ops/knn_tiers.py``). The contracts pinned here:

- returned scores are BITWISE what :func:`knn_quant.rescore_pairs` computes
  over the returned (query, slot) pairs from the fp32 source rows — the
  approximate int8 pass builds shortlists only;
- residency moves stay bitwise-invariant under int8 (exact integer dots in
  f32 — accumulation order cannot matter);
- sidecars (per-page scale/zero-point) survive frozen-spill serialization and
  rebuild-descriptor replication bit-exactly, and a recalibrated scale WINS
  over append-time re-derivation across the round-trip;
- mode mismatches are typed refusals (``QuantConfigError``), never silent
  fp32 fallbacks;
- scale recalibration rides the churn/maintenance path, and a ``quant`` chaos
  kill mid-recalibration leaves the OLD scales serving intact.

The recalibration protocol's schedule-exhaustive model checks live in
``test_modelcheck.py`` (``quant_recalibration_model``)."""

from __future__ import annotations

import json

import numpy as np
import pytest

from pathway_tpu.ops import knn_quant
from pathway_tpu.ops.knn_quant import (
    PAGE,
    QuantConfigError,
    quant_mode,
    quantize_queries,
    rescore_pairs,
)
from pathway_tpu.ops.knn_tiers import (
    DirSpillStore,
    TieredIvfKnnStore,
    _ClusterPages,
)

pytestmark = pytest.mark.quant


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


def _int8_store(dim, n_clusters, n_probe, **kw):
    return TieredIvfKnnStore(
        dim, n_clusters=n_clusters, n_probe=n_probe, quant="int8", **kw
    )


def _assert_rescore_bitwise(store, queries, scores, idx):
    """The external honesty recompute: every
    returned score must equal the pinned epilogue over the returned pair's
    fp32 source row, bit for bit."""
    qn = np.sum(queries * queries, axis=1)
    for r in range(len(queries)):
        m = idx[r] >= 0
        slots = idx[r][m].astype(int)
        if slots.size == 0:
            continue
        vecs = np.stack([store._vector_of(int(s)) for s in slots]).astype(
            np.float32
        )
        norms = np.sum(vecs * vecs, axis=1)
        exact = rescore_pairs(
            np.repeat(queries[r : r + 1], slots.size, axis=0),
            vecs,
            norms,
            np.repeat(qn[r : r + 1], slots.size),
            store.metric,
        ).astype(np.float32)
        np.testing.assert_array_equal(exact, scores[r][m])


# -- mode resolution ----------------------------------------------------------


def test_quant_mode_resolution_and_typed_refusals(monkeypatch):
    assert quant_mode("int8") == "int8"
    for off in (None, "", "off", "0", "false", "none", "No"):
        assert quant_mode(off) == "off" or off is None
    monkeypatch.delenv("PATHWAY_IVF_QUANT", raising=False)
    assert quant_mode() == "off"
    monkeypatch.setenv("PATHWAY_IVF_QUANT", "int8")
    assert quant_mode() == "int8"
    # fp8 is reserved sidecar format, not a silent fallback
    with pytest.raises(QuantConfigError, match="reserved"):
        quant_mode("fp8")
    # a typo'd mode must not silently serve fp32 under an int8 budget
    with pytest.raises(QuantConfigError, match="unknown"):
        quant_mode("int4")


def test_quant_opt_in_resolves_tiered_store_under_auto(monkeypatch):
    """``PATHWAY_IVF_QUANT=int8`` alone must engage the tiered store that
    hosts the tower — silently serving fp32 under an int8 opt-in would
    violate the loud-refusal contract. Explicit ``PATHWAY_IVF_TIERED=off``
    still wins, and no knobs at all keeps the untiered store bit-for-bit."""
    from pathway_tpu.ops.knn_tiers import tiering_enabled

    monkeypatch.delenv("PATHWAY_IVF_TIERED", raising=False)
    monkeypatch.delenv("PATHWAY_IVF_HBM_BUDGET_MB", raising=False)
    monkeypatch.delenv("PATHWAY_IVF_QUANT", raising=False)
    assert not tiering_enabled()
    monkeypatch.setenv("PATHWAY_IVF_QUANT", "int8")
    assert tiering_enabled()
    from pathway_tpu.ops.knn import IvfKnnIndex
    from pathway_tpu.ops.knn_tiers import TieredIvfKnnStore

    idx = IvfKnnIndex(8, n_clusters=2, n_probe=2)
    assert isinstance(idx.store, TieredIvfKnnStore)
    assert idx.store.quant == "int8"
    monkeypatch.setenv("PATHWAY_IVF_TIERED", "off")
    assert not tiering_enabled()


# -- recall + the pinned rescore epilogue -------------------------------------


def test_int8_full_probe_matches_exact_topk():
    _, docs = _clustered(3000, 24, 12, seed=31)
    store = _int8_store(24, 12, 12)
    store.add_many([f"d{i}" for i in range(3000)], docs)
    q = docs[:40]
    scores, idx, valid = store.search_batch(q, 10)
    assert valid.all()
    exact = _exact_top(docs, q, 10)
    for r in range(40):
        got = {store.key_of[int(i)] for i in idx[r] if i >= 0}
        assert got == {f"d{j}" for j in exact[r]}
    _assert_rescore_bitwise(store, q, scores, idx)
    store.close()


def test_rescore_bitwise_after_churn_and_dead_rows_masked():
    _, docs = _clustered(4000, 16, 8, seed=32)
    keys = [f"d{i}" for i in range(4000)]
    store = _int8_store(16, 8, 8)
    store.add_many(keys, docs)
    store.search_batch(docs[:4], 5)
    for i in range(0, 1500):
        store.remove(f"d{i}")
    q = docs[2000:2032]
    scores, idx, _v = store.search_batch(q, 10)
    dead = {f"d{i}" for i in range(1500)}
    for r in range(len(q)):
        got = {store.key_of.get(int(i)) for i in idx[r] if i >= 0}
        assert not (got & dead)
        assert None not in got
    _assert_rescore_bitwise(store, q, scores, idx)
    store.close()


def test_rescore_depth_follows_env_and_clamps_to_k(monkeypatch):
    """``PATHWAY_IVF_RESCORE_K`` sets the shortlist depth — but k always
    wins when it is deeper (the shortlist never truncates below what the
    caller asked for). Pinned via the rescore-depth histogram the epilogue
    observes, not via recall: at depth 4 near-ties in a crowded dim-8 set
    legitimately land outside the shortlist, which is WHY the default is
    64 — recall-at-depth is a measurement, not a unit invariant."""
    from pathway_tpu.engine.profile import histogram

    monkeypatch.setenv("PATHWAY_IVF_RESCORE_K", "4")
    assert knn_quant.rescore_k() == 4
    _, docs = _clustered(600, 8, 4, seed=33)
    store = _int8_store(8, 4, 4)
    store.add_many([f"d{i}" for i in range(600)], docs)
    hist = histogram("pathway_ivf_quant_rescore_depth")

    def observed_depth(k):
        c0, s0 = hist.count, hist.sum
        scores, idx, valid = store.search_batch(docs[:8], k)
        assert valid.all()
        # the query is its own document: the self-match dominates every
        # shortlist, so the top hit is exact even at starvation depth
        for r in range(8):
            assert store.key_of[int(idx[r][0])] == f"d{r}"
            assert np.count_nonzero(idx[r] >= 0) == k
        _assert_rescore_bitwise(store, docs[:8], scores, idx)
        assert hist.count == c0 + 1
        return hist.sum - s0

    assert observed_depth(2) == 4.0  # env floor applies above k
    assert observed_depth(12) == 12.0  # k wins when deeper than the env
    store.close()


# -- residency + spill round-trips --------------------------------------------


def test_residency_moves_bitwise_invariant_under_int8(tmp_path):
    import time

    centers, docs = _clustered(4000, 16, 8, seed=34)
    keys = [f"d{i}" for i in range(4000)]
    rng = np.random.default_rng(35)
    q = (centers[np.zeros(16, dtype=int)] + rng.normal(size=(16, 16))).astype(
        np.float32
    )
    tiered = _int8_store(
        16, 8, 2,
        hbm_budget_bytes=30_000,
        spill_store=DirSpillStore(str(tmp_path / "spill")),
    )
    allhot = _int8_store(16, 8, 2)
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


def test_sidecars_survive_blob_roundtrip_bit_exact():
    rng = np.random.default_rng(36)
    n = PAGE + 40  # two pages, second partial
    vecs = rng.normal(scale=3.0, size=(n, 12)).astype(np.float32)
    norms = np.sum(vecs * vecs, axis=1)
    block = _ClusterPages(12, cap=2 * PAGE, quant=True)
    block.append(np.arange(n, dtype=np.int64), vecs, norms)
    thawed = _ClusterPages.from_blob(12, block.to_blob(), quant=True)
    np.testing.assert_array_equal(thawed.qvecs[:n], block.qvecs[:n])
    np.testing.assert_array_equal(thawed.qscale, block.qscale)
    np.testing.assert_array_equal(thawed.qzero, block.qzero)


def test_recalibrated_scale_wins_blob_roundtrip():
    """A recalibration that tightened the scales pre-freeze must survive the
    spill round-trip by COPY: the thawed block serves the recalibrated codes,
    not an append-time re-derivation from the fp32 rows."""
    rng = np.random.default_rng(37)
    n = PAGE
    vecs = rng.normal(size=(n, 12)).astype(np.float32)
    norms = np.sum(vecs * vecs, axis=1)
    block = _ClusterPages(12, cap=PAGE, quant=True)
    block.append(np.arange(n, dtype=np.int64), vecs, norms)
    derived_scale = float(block.qscale[0])
    # recalibrate to a DIFFERENT (tighter) scale than append would derive —
    # e.g. after the max-magnitude row died; install codes to match
    tight = np.float32(derived_scale / 2.0)
    block.qscale[0] = tight
    block.qvecs[:n] = knn_quant.quantize_rows(vecs, float(tight))
    block._drop_quant_caches()
    thawed = _ClusterPages.from_blob(12, block.to_blob(), quant=True)
    assert thawed.qscale[0] == tight != np.float32(derived_scale)
    np.testing.assert_array_equal(thawed.qvecs[:n], block.qvecs[:n])


def test_pre_quant_blob_thaws_into_quant_store():
    """A blob frozen BEFORE quantization was enabled carries no sidecars:
    thawing it under quant=True re-derives codes instead of failing."""
    rng = np.random.default_rng(38)
    vecs = rng.normal(size=(PAGE, 12)).astype(np.float32)
    norms = np.sum(vecs * vecs, axis=1)
    plain = _ClusterPages(12, cap=PAGE, quant=False)
    plain.append(np.arange(PAGE, dtype=np.int64), vecs, norms)
    thawed = _ClusterPages.from_blob(12, plain.to_blob(), quant=True)
    assert thawed.quant
    want_codes, want_scale, _ = knn_quant.quantize_block(thawed.vecs)
    np.testing.assert_array_equal(thawed.qvecs[:PAGE], want_codes[:PAGE])
    np.testing.assert_array_equal(thawed.qscale, want_scale)


# -- descriptor / membership replication --------------------------------------


def test_rebuild_descriptor_carries_quant_state_and_roundtrips(monkeypatch):
    from pathway_tpu.ops.knn import IvfKnnIndex

    monkeypatch.setenv("PATHWAY_IVF_QUANT", "int8")
    monkeypatch.setenv("PATHWAY_IVF_TIERED", "on")
    _, docs = _clustered(1200, 16, 6, seed=39)
    keys = [f"d{i}" for i in range(1200)]
    src = IvfKnnIndex(16, n_clusters=6, n_probe=6, tiered=True)
    for key, vec in zip(keys, docs):
        src.add(key, vec)
    src.store.search_batch(docs[:4], 5)
    desc = src.rebuild_descriptor()
    assert desc is not None
    assert desc["quant"]["mode"] == "int8"
    assert desc["quant"]["dtype"] == "int8"
    clusters = desc["quant"]["clusters"]
    assert clusters, "resident clusters must publish their sidecars"
    for entry in clusters.values():
        assert entry["qscale"].dtype == np.float32
        assert entry["qzero"].dtype == np.float32
        assert entry["rows"] > 0
    dst = IvfKnnIndex(16, n_clusters=6, n_probe=6, tiered=True)
    dst.install_rebuild_descriptor(desc)
    q = docs[:16]
    exact = _exact_top(docs, q, 5)
    scores, idx, _valid = dst.store.search_batch(q, 5)
    for r in range(16):
        got = {dst.store.key_of[int(i)] for i in idx[r] if i >= 0}
        assert got == {f"d{j}" for j in exact[r]}
    _assert_rescore_bitwise(dst.store, q, scores, idx)


def test_rebuild_descriptor_mode_mismatch_is_typed_refusal(monkeypatch):
    from pathway_tpu.ops.knn import IvfKnnIndex

    monkeypatch.setenv("PATHWAY_IVF_QUANT", "int8")
    _, docs = _clustered(400, 8, 4, seed=40)
    src = IvfKnnIndex(8, n_clusters=4, n_probe=4, tiered=True)
    for i in range(400):
        src.add(f"d{i}", docs[i])
    desc = src.rebuild_descriptor()
    assert desc["quant"]["mode"] == "int8"
    monkeypatch.setenv("PATHWAY_IVF_QUANT", "off")
    plain = IvfKnnIndex(8, n_clusters=4, n_probe=4, tiered=True)
    with pytest.raises(QuantConfigError, match="quant mode"):
        plain.install_rebuild_descriptor(desc)


def test_sharded_store_aggregates_quant_state():
    from pathway_tpu.parallel import ShardedIvfKnnStore, make_mesh

    mesh = make_mesh(8)
    _, docs = _clustered(600, 16, 4, seed=41)
    keys = [f"d{i}" for i in range(600)]
    sharded = ShardedIvfKnnStore(
        mesh, 16, n_clusters=4, n_probe=4, tiered=True, quant="int8"
    )
    assert sharded.quant == "int8"
    sharded.add_many(keys, docs)
    sharded.search_batch(docs[:4], 5)
    state = sharded.quant_state()
    assert state["mode"] == "int8"
    assert state["clusters"], "per-shard sidecars must aggregate"
    assert all(":" in cid for cid in state["clusters"])  # shard-prefixed
    # search through the quantized shards still matches exact top-k
    q = docs[:12]
    exact = _exact_top(docs, q, 5)
    _s, idx, valid = sharded.search_batch(q, 5)
    assert valid.all()
    for r in range(12):
        got = {sharded.key_of[int(x)] for x in idx[r] if x >= 0}
        assert got == {f"d{j}" for j in exact[r]}
    # the flat (non-tiered) sharded store has no quantized blocks: the
    # resolved mode must SAY so, not pretend
    flat = ShardedIvfKnnStore(
        mesh, 16, n_clusters=4, n_probe=4, tiered=False, quant="int8"
    )
    assert flat.quant == "off"
    assert flat.quant_state() == {"mode": "off"}


# -- recalibration + chaos ----------------------------------------------------


def test_scale_recalibration_rides_maintenance_after_churn():
    _, docs = _clustered(2000, 16, 4, seed=42)
    keys = [f"d{i}" for i in range(2000)]
    store = _int8_store(16, 4, 4)
    store.add_many(keys, docs)
    store.search_batch(docs[:4], 5)
    # kill a third of every cluster: dead rows may pin page scales
    for i in range(0, 2000, 3):
        store.remove(f"d{i}")
    for cid in range(store.n_clusters):
        store._maintain_cluster(cid)
    assert store.stats["quant_recalibrations"] >= 1, store.stats
    q = docs[1:33]
    live = [i for i in range(2000) if i % 3 != 0]
    exact = _exact_top(docs[live], q, 5)
    scores, idx, _v = store.search_batch(q, 5)
    for r in range(32):
        got = {store.key_of.get(int(i)) for i in idx[r] if i >= 0}
        assert got == {f"d{live[j]}" for j in exact[r]}
    _assert_rescore_bitwise(store, q, scores, idx)
    store.close()


@pytest.mark.chaos
def test_chaos_quant_kill_serves_old_scales_then_recovers(monkeypatch):
    """Injected ``quant`` chaos op at recalibration attempt 0: the freshly
    computed sidecars are discarded BEFORE anything re-points, the old scales
    keep serving (results still exact — the fp32 rescore is untouched), and
    the next maintenance pass recalibrates cleanly."""
    from pathway_tpu.internals.chaos import reset_chaos

    monkeypatch.setenv(
        "PATHWAY_CHAOS_PLAN",
        json.dumps({"index": [{"op": "quant", "rank": 0, "at": 0}]}),
    )
    monkeypatch.setenv("PATHWAY_CHAOS_SEED", "5")
    reset_chaos()
    try:
        _, docs = _clustered(1200, 16, 4, seed=43)
        keys = [f"d{i}" for i in range(1200)]
        store = _int8_store(16, 4, 4)
        store.add_many(keys, docs)
        store.search_batch(docs[:4], 5)
        # churn enough rows that maintenance wants to recalibrate; the plan
        # gates on rebuild attempt 0, so EVERY recalibration in this window
        # aborts before install (drift-triggered ones from remove() included)
        for i in range(0, 1200, 2):
            store.remove(f"d{i}")
        for cid in range(store.n_clusters):
            store._maintain_cluster(cid)
        assert store.stats["quant_chaos_aborts"] >= 1, store.stats
        assert store.stats["quant_recalibrations"] == 0, store.stats
        # old scales keep serving: results stay EXACT (the fp32 rescore
        # epilogue never depended on the sidecars that got discarded)
        q = docs[1:17]
        live = [i for i in range(1200) if i % 2 == 1]
        exact = _exact_top(docs[live], q, 5)
        scores, idx, _v = store.search_batch(q, 5)
        for r in range(16):
            got = {store.key_of.get(int(i)) for i in idx[r] if i >= 0}
            assert got == {f"d{live[j]}" for j in exact[r]}
        _assert_rescore_bitwise(store, q, scores, idx)
        # chaos lifted (process restarted / plan expired): the next
        # maintenance pass recalibrates and installs cleanly
        aborts = store.stats["quant_chaos_aborts"]
        monkeypatch.setenv("PATHWAY_CHAOS_PLAN", "{}")
        reset_chaos()
        for cid in range(store.n_clusters):
            store._maintain_cluster(cid)
        assert store.stats["quant_chaos_aborts"] == aborts
        assert store.stats["quant_recalibrations"] >= 1, store.stats
        scores, idx, _v = store.search_batch(q, 5)
        for r in range(16):
            got = {store.key_of.get(int(i)) for i in idx[r] if i >= 0}
            assert got == {f"d{live[j]}" for j in exact[r]}
        store.close()
    finally:
        reset_chaos()


# -- kernels / caches / observability -----------------------------------------


def test_quant_kernels_registered_in_cache_sizes():
    from pathway_tpu.ops.knn import kernel_cache_sizes

    sizes = kernel_cache_sizes()
    assert "quant_probe" in sizes
    assert "quant_score" in sizes


def test_device_kernel_parity_with_host_path():
    """The jitted block kernel and the host epilogue run the same operations
    in the same order — but the COMPILER may still contract the epilogue's
    multiply+add into an FMA (XLA-CPU does, for the l2sq branch), which is a
    1-ulp divergence numpy cannot reproduce. That is precisely why the store
    runs a FIRST-USE PARITY PROBE instead of trusting the lockstep: any byte
    of disagreement permanently downgrades that store to the host path, so
    served scores stay pinned to the host bytes either way. Here we pin the
    contract the probe relies on: agreement within 1 ulp everywhere (same
    math), and bitwise where no mul+add contraction is available to fuse."""
    import jax.numpy as jnp

    rng = np.random.default_rng(44)
    cap, dim, nq = PAGE, 16, 8
    vecs = rng.normal(scale=3.0, size=(cap, dim)).astype(np.float32)
    norms = np.sum(vecs * vecs, axis=1)
    qvecs, qscale, _qzero = knn_quant.quantize_block(vecs)
    srow = knn_quant.row_scales(qscale, cap)
    mask = np.where(rng.random(cap) < 0.9, np.float32(0.0), np.float32(-np.inf))
    queries = rng.normal(size=(nq, dim)).astype(np.float32)
    q_codes, q_scales = quantize_queries(queries)
    qn = np.sum(queries * queries, axis=1)
    for metric in ("l2sq", "cos", "ip"):
        host = knn_quant.approx_scores(
            q_codes.astype(np.float32), q_scales, qn,
            qvecs.astype(np.float32), srow, norms, metric, maskadd=mask,
        )
        dev = np.asarray(
            knn_quant.quant_score_block_kernel(
                jnp.asarray(qvecs), jnp.asarray(srow), jnp.asarray(norms),
                jnp.asarray(mask), jnp.asarray(q_codes),
                jnp.asarray(q_scales), jnp.asarray(qn), metric,
            )
        )
        finite = np.isfinite(host)
        assert np.array_equal(finite, np.isfinite(dev)), metric
        ulp = np.spacing(np.maximum(np.abs(host[finite]), np.abs(dev[finite])))
        assert np.all(np.abs(host[finite] - dev[finite]) <= ulp), metric
        np.testing.assert_array_equal(host[~finite], dev[~finite])
        if metric == "ip":  # scale*dot then separate mask add: nothing to fuse
            np.testing.assert_array_equal(host, dev)


def test_device_parity_probe_downgrades_or_matches_end_to_end():
    """Whatever the compiler does, a store WITH a hot device mirror must
    serve byte-identical results to a host-only store: either the kernel
    agrees bitwise, or the first-use probe flags it and the store scores on
    host forever after. Both branches land on the same bytes."""
    _, docs = _clustered(1500, 16, 4, seed=49)
    keys = [f"d{i}" for i in range(1500)]
    mirrored = _int8_store(16, 4, 4)  # default budget: everything hot-mirrors
    hostonly = _int8_store(16, 4, 4, hbm_budget_bytes=0)
    mirrored.add_many(keys, docs)
    hostonly.add_many(keys, docs)
    q = docs[:24]
    for _ in range(4):  # settle: give mirrors time to stage + probe to fire
        rm = mirrored.search_batch(q, 10)
        rh = hostonly.search_batch(q, 10)
    np.testing.assert_array_equal(rm[0], rh[0])
    np.testing.assert_array_equal(rm[1], rh[1])
    mirrored.close()
    hostonly.close()


def test_negnorm_fused_epilogue_bitwise_equals_unfused():
    rng = np.random.default_rng(45)
    cap, dim, nq = 64, 12, 4
    vecs = rng.normal(size=(cap, dim)).astype(np.float32)
    norms = np.sum(vecs * vecs, axis=1)
    qvecs, qscale, _ = knn_quant.quantize_block(vecs)
    srow = knn_quant.row_scales(qscale, cap)
    mask = np.where(rng.random(cap) < 0.8, np.float32(0.0), np.float32(-np.inf))
    queries = rng.normal(size=(nq, dim)).astype(np.float32)
    q_codes, q_scales = quantize_queries(queries)
    qn = np.sum(queries * queries, axis=1)
    qf = q_codes.astype(np.float32)
    df = qvecs.astype(np.float32)
    unfused = knn_quant.approx_scores(
        qf, q_scales, qn, df, srow, norms, "l2sq", maskadd=mask
    )
    fused = knn_quant.approx_scores(
        qf, q_scales, qn, df, srow, norms, "l2sq",
        negnorm=(mask - norms).astype(np.float32),
    )
    np.testing.assert_array_equal(unfused, fused)


def test_block_maskadd_and_negn_caches_invalidate_on_mutation():
    rng = np.random.default_rng(46)
    vecs = rng.normal(size=(PAGE, 8)).astype(np.float32)
    norms = np.sum(vecs * vecs, axis=1)
    block = _ClusterPages(8, cap=PAGE, quant=True)
    block.append(np.arange(PAGE, dtype=np.int64), vecs, norms)
    m0 = block.maskadd(PAGE)
    n0 = block.negn(PAGE)
    assert block.maskadd(PAGE) is m0  # cached handle, no rebuild
    assert block.negn(PAGE) is n0
    assert np.all(m0 == 0.0)
    # kill a row the way the store does: validity flip + mutation bump
    block.valid[3] = False
    block.n_live -= 1
    block.mutations += 1
    m1 = block.maskadd(PAGE)
    n1 = block.negn(PAGE)
    assert m1 is not m0 and n1 is not n0
    assert m1[3] == -np.inf and np.isneginf(n1[3])
    np.testing.assert_array_equal(
        np.delete(n1, 3), np.delete((m1 - norms).astype(np.float32), 3)
    )


def test_quant_metrics_on_openmetrics_strict():
    from pathway_tpu.engine import telemetry
    from pathway_tpu.engine.http_server import ProberStats
    from pathway_tpu.engine.profile import histograms

    from .utils import validate_openmetrics

    _, docs = _clustered(800, 8, 4, seed=47)
    store = _int8_store(8, 4, 4)
    store.add_many([f"d{i}" for i in range(800)], docs)
    store.search_batch(docs[:8], 5)
    ratio = store.quant_recall_audit(docs[:16], k=5)
    assert ratio == 1.0
    assert histograms()["pathway_ivf_quant_rescore_depth"].count > 0
    assert histograms()["pathway_ivf_quant_recall_ratio"].count > 0
    text = ProberStats().to_openmetrics()
    validate_openmetrics(text)
    assert "pathway_ivf_quant_rescore_depth" in text
    assert "pathway_ivf_quant_recall_ratio" in text
    assert 'pathway_stage_total{stage="index.quant.batches"}' in text
    assert telemetry.stage_snapshot().get("index.quant.batches", 0) > 0
    store.close()


# -- quantized query encode ---------------------------------------------------


def test_quant_encode_gating_follows_index_mode(monkeypatch):
    from pathway_tpu.models.encoder import quant_encode_enabled

    monkeypatch.delenv("PATHWAY_IVF_QUANT_ENCODE", raising=False)
    monkeypatch.setenv("PATHWAY_IVF_QUANT", "int8")
    assert quant_encode_enabled()  # auto follows the index mode
    monkeypatch.setenv("PATHWAY_IVF_QUANT", "off")
    assert not quant_encode_enabled()
    monkeypatch.setenv("PATHWAY_IVF_QUANT_ENCODE", "on")
    assert quant_encode_enabled()  # forced on, index fp32
    monkeypatch.setenv("PATHWAY_IVF_QUANT", "int8")
    monkeypatch.setenv("PATHWAY_IVF_QUANT_ENCODE", "off")
    assert not quant_encode_enabled()  # forced off, index int8


def test_lattice_encoded_queries_requantize_code_stable():
    """The encoder's quantized tower folds ``round(v/s) * s`` into the
    forward; re-quantizing those lattice rows must reproduce the codes
    EXACTLY (the row max is itself a lattice point) — zero added rounding
    between the encode and the int8 scorer."""
    rng = np.random.default_rng(48)
    raw = rng.normal(size=(32, 24)).astype(np.float32)
    codes1, scales1 = quantize_queries(raw)
    lattice = (codes1.astype(np.float32) * scales1[:, None]).astype(np.float32)
    codes2, _scales2 = quantize_queries(lattice)
    np.testing.assert_array_equal(codes1, codes2)


def test_embed_and_semantic_caches_key_on_quant_mode():
    from pathway_tpu.models.embed_pipeline import EmbedCache
    from pathway_tpu.models.encoder_service import SemanticQueryCache

    vec = np.arange(4, dtype=np.float32)
    plain = EmbedCache(16, model="m")
    tagged = EmbedCache(16, model="m|quant:int8")
    plain.put("hello", vec)
    assert plain.get("hello") is not None
    assert tagged.get("hello") is None  # geometry flip misses, never serves
    sem_plain = SemanticQueryCache(16, mode="exact")
    sem_tagged = SemanticQueryCache(16, mode="exact", key_tag="quant:int8")
    sem_plain.put("hello world", vec)
    assert sem_plain.get("hello world") is not None
    assert sem_tagged.get("hello world") is None
