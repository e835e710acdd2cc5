"""North-star benchmarks (BASELINE configs #1-#5 + engine throughput).

Headline: brute-force KNN retrieval at 1M docs x 128 dims on the TPU — the replacement
for the reference's ``src/external_integration/brute_force_knn_integration.rs:113``
(ndarray matmul + partial sort via ``src/mat_mul.rs:5``) — against a CPU numpy
implementation of the same computation (BLAS matmul + ``argpartition``), an in-process
stand-in for the reference's Rust kernel. Sub-benches cover the rest of BASELINE:

  #2 embedder     — Flax MiniLM batch-encode throughput (``models/encoder.py``)
  #3 vectorstore  — VectorStoreServer end-to-end over REST: ingest->index docs/s and
                    single-query p50 (embed + KNN + join pipeline per request)
  #4 streaming    — timed stream -> tumbling window aggregation, rows/s
  #5 sharded      — ShardedKNNStore on an 8-virtual-device mesh (subprocess, CPU mesh)
  engine          — streaming wordcount + incremental hash join vs vectorized-numpy
                    CPU proxies that maintain the same per-commit outputs

Run contract:

  * the ORCHESTRATOR process never imports jax: a chip belongs to one process,
    and a parent that has touched JAX would hold it against its own children;
  * each sub-bench runs in its own subprocess under its own deadline, one at a
    time, so one hung section cannot eat the round;
  * there is NO fallback: every section reports the device it ran on
    (``platform``, ``device_kind``, count, as JAX reports them) and refuses the
    CPU platform — the run stops non-zero at the first section that finds no
    accelerator. The one exception is the toy-scale correctness mode, asked for
    explicitly with ``PW_BENCH_SMOKE=1 JAX_PLATFORMS=cpu``, whose output says
    ``device: cpu`` and whose numbers mean nothing;
  * a section that raises, exits non-zero or runs out its deadline makes the
    whole run exit non-zero;
  * after every completed sub-bench the CUMULATIVE result line is printed and
    flushed — the driver's tail capture keeps partial results on timeout; the
    final line is the full aggregate (the ONE-JSON-line contract).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

# toy-scale correctness mode: exercises every code path in seconds; numbers at
# this scale are meaningless for the BASELINE targets and never comparable
SMOKE = bool(os.environ.get("PW_BENCH_SMOKE"))

N_DOCS = 1_000_000
DIM = 128
N_QUERIES = 1024
K = 10
CPU_SUBSET = 64
INGEST_CHUNK = 50_000  # one staged scatter per chunk, constant shape -> single compile

if SMOKE:
    N_DOCS = 20_000
    N_QUERIES = 64
    CPU_SUBSET = 16
    INGEST_CHUNK = 5_000


# Published per-chip bf16 peak in TFLOP/s, keyed by the ``device_kind`` JAX
# reports (Google Cloud documentation, "TPU v5e": 197). A utilization is only
# quoted against a peak this table holds: an unknown device is a KeyError, not
# a default — add it here with its source first.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}  # device_kind of a v5e chip


def _run_cpu(data: np.ndarray, norms: np.ndarray, q: np.ndarray) -> np.ndarray:
    scores = q @ data.T
    qn = np.sum(q * q, axis=1, keepdims=True)
    dist = qn + norms[None, :] - 2.0 * scores
    idx = np.argpartition(dist, K, axis=1)[:, :K]
    part = np.take_along_axis(dist, idx, axis=1)
    order = np.argsort(part, axis=1)
    return np.take_along_axis(idx, order, axis=1)


def bench_knn() -> dict:
    import jax

    from pathway_tpu.ops.knn import DenseKNNStore

    rng = np.random.default_rng(0)
    data = rng.normal(size=(N_DOCS, DIM)).astype(np.float32)
    queries = rng.normal(size=(N_QUERIES, DIM)).astype(np.float32)

    store = DenseKNNStore(DIM, metric="l2sq", initial_capacity=N_DOCS)

    t0 = time.perf_counter()
    for i in range(0, N_DOCS, INGEST_CHUNK):
        store.add_many(list(range(i, i + INGEST_CHUNK)), data[i : i + INGEST_CHUNK])
        store._flush()
    jax.block_until_ready(store._data)
    ingest_s = time.perf_counter() - t0

    store.search_batch(queries, K)  # warmup / compile

    reps = [rng.normal(size=(N_QUERIES, DIM)).astype(np.float32) for _ in range(4)]
    latencies = []
    for q in [queries] + reps:
        t1 = time.perf_counter()
        store.search_batch(q, K)
        latencies.append(time.perf_counter() - t1)
    med = float(np.median(latencies))

    norms = np.sum(data * data, axis=1)
    t0 = time.perf_counter()
    cpu_idx = _run_cpu(data, norms, queries[:CPU_SUBSET])
    cpu_qps = CPU_SUBSET / (time.perf_counter() - t0)

    _, tpu_idx, _ = store.search_batch(queries[:CPU_SUBSET], K)
    tpu_keys = np.vectorize(lambda s: store.key_of.get(int(s), -1))(tpu_idx)
    recall = float(
        np.mean([len(set(tpu_keys[r]) & set(cpu_idx[r])) / K for r in range(CPU_SUBSET)])
    )

    # IVF-Flat (the ANN slot): measured on a CLUSTERED corpus — the distribution
    # embedding vectors actually have, and the workload ANN indexes exist for
    # (uniform random data defeats every ANN structure, HNSW included). Recall
    # is against exact numpy search over the SAME corpus.
    from pathway_tpu.ops.knn_ivf import IvfKnnStore

    n_centers = 1024
    centers = rng.normal(scale=4.0, size=(n_centers, DIM)).astype(np.float32)
    cdata = (
        centers[rng.integers(0, n_centers, N_DOCS)]
        + rng.normal(size=(N_DOCS, DIM)).astype(np.float32)
    ).astype(np.float32)
    ivf_clusters = min(1024, max(16, N_DOCS // 256))
    ivf = IvfKnnStore(
        DIM, metric="l2sq", initial_capacity=N_DOCS,
        n_clusters=ivf_clusters, n_probe=max(8, ivf_clusters // 16),
    )
    for i in range(0, N_DOCS, INGEST_CHUNK):
        ivf.add_many(list(range(i, i + INGEST_CHUNK)), cdata[i : i + INGEST_CHUNK])
    cqueries = (
        centers[rng.integers(0, n_centers, N_QUERIES)]
        + rng.normal(size=(N_QUERIES, DIM)).astype(np.float32)
    ).astype(np.float32)
    ivf.search_batch(cqueries, K)  # train + compile off the clock
    creps = [
        (
            centers[rng.integers(0, n_centers, N_QUERIES)]
            + rng.normal(size=(N_QUERIES, DIM)).astype(np.float32)
        ).astype(np.float32)
        for _ in range(4)
    ]
    ivf_lat = []
    for q in [cqueries] + creps:  # distinct batches, same protocol as dense KNN
        t1 = time.perf_counter()
        ivf.search_batch(q, K)
        ivf_lat.append(time.perf_counter() - t1)
    ivf_med = float(np.median(ivf_lat))
    cnorms = np.sum(cdata * cdata, axis=1)
    ivf_cpu_idx = _run_cpu(cdata, cnorms, cqueries[:CPU_SUBSET])
    _, ivf_idx, _ = ivf.search_batch(cqueries[:CPU_SUBSET], K)
    ivf_keys = np.vectorize(lambda s: ivf.key_of.get(int(s), -1))(ivf_idx)
    ivf_recall = float(
        np.mean(
            [len(set(ivf_keys[r]) & set(ivf_cpu_idx[r])) / K for r in range(CPU_SUBSET)]
        )
    )

    return {
        "knn_qps": round(N_QUERIES / med, 1),
        "knn_vs_cpu": round((N_QUERIES / med) / cpu_qps, 1),
        "knn_ingest_docs_per_s": round(N_DOCS / ingest_s, 1),
        "knn_p50_batch1024_ms": round(med * 1000.0, 2),
        "recall_at_10": round(recall, 4),
        "ivf_qps": round(N_QUERIES / ivf_med, 1),
        "ivf_p50_batch1024_ms": round(ivf_med * 1000.0, 2),
        "ivf_recall_at_10": round(ivf_recall, 4),
    }


def bench_ivf_scale() -> dict:
    """Tentpole check (ISSUE 15): the TIERED IVF index must sustain >= 10x
    more docs than the device-hot tier alone holds, at recall@10 >= 0.95 vs
    exact, with churn absorbed incrementally and the background rebuild never
    blocking queries for more than one bounded commit pause.

    CPU-honest like the engine sections: residency management, hit rates,
    prefetch stalls, maintenance/rebuild pauses and recall are all measured
    the same on any host (the "device-hot" tier is bookkeeping + resident
    blocks on CPU; the same code path device_puts on TPU); only
    PW_BENCH_SMOKE shrinks it.

    Honesty keys: ``ivfscale_docs_over_hot_budget`` (>= 10x by construction,
    reported measured), ``ivfscale_recall_honest`` (recall@10 vs exact numpy
    over the live corpus), ``ivfscale_bitwise_residency`` (the same queries
    through an all-hot twin store return BITWISE identical scores/slots —
    residency must never change results), ``ivfscale_rebuild_nonblocking``
    (a full background rebuild committed while serving, with the max pause
    bounded and NO stop-the-world rebuild on the churn path)."""
    import shutil
    import tempfile

    from pathway_tpu.engine.profile import histograms
    from pathway_tpu.ops.knn_tiers import DirSpillStore, TieredIvfKnnStore

    dim = 64
    stages = [15_000, 30_000, 60_000] if SMOKE else [60_000, 120_000, 240_000]
    n_docs = stages[-1]
    n_queries, k = 256, 10
    n_centers = 256
    n_clusters = max(16, n_docs // 1024)
    rng = np.random.default_rng(11)
    centers = rng.normal(scale=4.0, size=(n_centers, dim)).astype(np.float32)

    def clustered(n: int, seed: int) -> np.ndarray:
        r = np.random.default_rng(seed)
        return (
            centers[r.integers(0, n_centers, n)]
            + r.normal(size=(n, dim)).astype(np.float32)
        ).astype(np.float32)

    data = clustered(n_docs, 12)
    queries = clustered(n_queries, 13)
    # hot budget = 1/10 of the FINAL corpus bytes: by the last stage the
    # store provably holds 10x what the hot tier can
    corpus_bytes = n_docs * dim * 4
    budget = max(1, corpus_bytes // 10)
    results: dict = {
        "ivfscale_docs": n_docs,
        "ivfscale_hot_budget_mb": round(budget / (1 << 20), 1),
    }
    spill_dir = tempfile.mkdtemp(prefix="pw-ivfscale-spill-")
    store = TieredIvfKnnStore(
        dim, metric="l2sq", n_clusters=n_clusters,
        n_probe=max(8, n_clusters // 16), hbm_budget_bytes=budget,
        spill_store=DirSpillStore(spill_dir),
    )
    keys = [f"d{i}" for i in range(n_docs)]
    ingest_t0 = time.perf_counter()
    fed = 0
    for stage_docs in stages:
        while fed < stage_docs:
            end_i = min(fed + 20_000, stage_docs)
            store.add_many(keys[fed:end_i], data[fed:end_i])
            fed = end_i
        store.search_batch(queries[:8], k)  # train/maintain off the clock
        lat = []
        for _ in range(3):
            t1 = time.perf_counter()
            store.search_batch(queries, k)
            lat.append(time.perf_counter() - t1)
        med = float(np.median(lat))
        results[f"ivfscale_qps_at_{stage_docs}"] = round(n_queries / med, 1)
    results["ivfscale_ingest_docs_per_s"] = round(
        n_docs / (time.perf_counter() - ingest_t0), 1
    )
    stats = store.tier_stats()
    probes = stats["probe_hot"] + stats["probe_cold"] + stats["probe_spilled"]
    results["ivfscale_tier_hit_rate"] = round(
        (stats["probe_hot"] + stats["probe_cold"]) / max(probes, 1), 4
    )
    results["ivfscale_hot_clusters"] = stats["hot"]
    results["ivfscale_occupancy"] = round(stats["occupancy"], 3)
    # per-slot footprint MEASURED from the resident blocks (payload dtype +
    # sidecars), not an assumed fp32 row width — the assumption misprices
    # the store whenever the payload dtype differs (PATHWAY_IVF_QUANT)
    blocks = list(store.tiers.pages.values())
    slot_bytes = sum(b.nbytes for b in blocks) / max(
        sum(b.vecs.shape[0] for b in blocks), 1
    )
    results["ivfscale_docs_over_hot_budget"] = round(
        n_docs * slot_bytes / budget, 1
    )

    # -- churn phase: sustained replace traffic while serving ------------------
    # enough waves to cross the rebuild-drift threshold: the full re-train
    # must run in the BACKGROUND and swap at one commit boundary
    import collections

    churn_rows = 0
    churn_t0 = time.perf_counter()
    wave = max(2000, n_docs // 24)
    waves = 0
    pool = collections.deque(keys)  # live keys, oldest removed first
    swaps_before = store.stats["swaps"]  # growth during the ramp may already
    # have committed one background rebuild; the churn phase must observe ITS
    # OWN rebuild land
    while waves < 40 and store.stats["swaps"] == swaps_before:
        new_keys = [f"r{waves}-{i}" for i in range(wave)]
        store.add_many(new_keys, clustered(wave, 100 + waves))
        pool.extend(new_keys)
        for _ in range(wave):
            store.remove(pool.popleft())
        churn_rows += 2 * wave
        store.search_batch(queries[:32], k)  # serving continues through churn
        if store._rebuild_inflight():
            # keep serving while the rebuild runs; the swap lands at a later
            # commit boundary
            deadline = time.perf_counter() + 120
            while store._rebuild_inflight() and time.perf_counter() < deadline:
                store.search_batch(queries[:32], k)
                time.sleep(0.02)
            store.search_batch(queries[:8], k)  # the swapping boundary
        waves += 1
    churn_s = time.perf_counter() - churn_t0
    results["ivfscale_churn_rows_per_s"] = round(churn_rows / max(churn_s, 1e-9), 1)
    results["ivfscale_rebuilds"] = int(store.stats["rebuilds"])
    results["ivfscale_rebuild_pause_max_ms"] = round(
        store.stats["max_pause_s"] * 1000.0, 1
    )
    results["ivfscale_rebuild_nonblocking"] = bool(
        store.stats["swaps"] >= 1 and store.stats["max_pause_s"] < 10.0
    )

    # -- recall + bitwise residency honesty ------------------------------------
    live_keys = list(store.slot_of.keys())
    live = np.stack([store._vector_of(store.slot_of[kk]) for kk in live_keys])
    sub = queries[:128]
    qn = np.sum(sub * sub, axis=1)[:, None]
    dn = np.sum(live * live, axis=1)[None, :]
    exact_idx = np.argsort(qn + dn - 2.0 * sub @ live.T, axis=1)[:, :k]
    # probe autotune to the recall target (the operating point is reported)
    while True:
        _s, got_idx, _v = store.search_batch(sub, k)
        hits = 0
        for r in range(len(sub)):
            got = {store.key_of.get(int(x)) for x in got_idx[r] if x >= 0}
            want = {live_keys[j] for j in exact_idx[r]}
            hits += len(got & want)
        recall = hits / (len(sub) * k)
        if recall >= 0.95 or store.n_probe >= min(store.n_clusters, 256):
            break
        store.n_probe = min(store.n_probe * 2, min(store.n_clusters, 256))
    results["ivfscale_n_probe"] = store.n_probe
    results["ivfscale_recall_at_10"] = round(recall, 4)
    results["ivfscale_recall_honest"] = bool(recall >= 0.95)
    lat = []
    for _ in range(3):
        t1 = time.perf_counter()
        store.search_batch(queries, k)
        lat.append(time.perf_counter() - t1)
    med = float(np.median(lat))
    results["ivfscale_qps"] = round(n_queries / med, 1)
    results["ivfscale_p50_batch_ms"] = round(med * 1000.0, 2)
    # bitwise residency honesty: the SAME store, the SAME queries, with the
    # residency forced from tiered (budget-bounded hot set + spill) to
    # all-hot — scores and slots must be byte-identical, or the tiers are
    # changing results
    a_s, a_i, _ = store.search_batch(sub, k)
    store.tiers.budget_bytes = 0  # lift the budget: everything is promotable
    for cid in range(store.n_clusters):
        if store.tiers.residency(cid) == "spilled":
            store.tiers.unspill(cid)
        store.tiers.promote(cid)
    b_s, b_i, _ = store.search_batch(sub, k)
    results["ivfscale_bitwise_residency"] = bool(
        np.array_equal(a_s, b_s) and np.array_equal(a_i, b_i)
    )
    # prefetch stalls: the frozen clusters probed across the churn + recall
    # sweeps (0.0 when every load hid inside the overlap window)
    results["ivfscale_spill_freezes"] = int(store.stats["spills"])  # cumulative
    results["ivfscale_frozen_clusters_end"] = int(store.tier_stats()["spilled"])
    # jit-cache regression keys (the pow2 padding discipline): ragged
    # cluster sizes must land in O(log) compile buckets, not one program per
    # cluster — the 18x ingest regression class this PR hit and fixed
    from pathway_tpu.ops.knn import kernel_cache_sizes

    caches = kernel_cache_sizes()
    results["ivfscale_assign_kernel_compiles"] = caches["tiered_assign"]
    results["ivfscale_score_kernel_compiles"] = caches["tiered_score"]
    stall = histograms().get("pathway_ivf_prefetch_stall_seconds")
    if stall is not None and stall.count:
        results["ivfscale_prefetch_stall_p50_ms"] = round(
            stall.quantile(0.50) * 1000.0, 3
        )
        results["ivfscale_prefetch_stall_p95_ms"] = round(
            stall.quantile(0.95) * 1000.0, 3
        )
        results["ivfscale_prefetch_stalls"] = int(stall.count)
    else:
        results["ivfscale_prefetch_stall_p50_ms"] = 0.0
        results["ivfscale_prefetch_stall_p95_ms"] = 0.0
        results["ivfscale_prefetch_stalls"] = 0
    store.close()
    shutil.rmtree(spill_dir, ignore_errors=True)
    return results


def bench_quant() -> dict:
    """Quantized retrieval tower (``PATHWAY_IVF_QUANT=int8``): the SAME
    corpus in an fp32-payload and an int8-payload tiered store at the SAME
    hot budget. The capacity multiple is MEASURED from actual block bytes
    (never an assumed row width), the recall cost is measured against brute
    force with the exact-rescore epilogue on, and the rescore contract is
    re-proven from outside the store: every returned score must be bitwise
    equal to ``rescore_pairs`` recomputed over the returned (query, slot)
    pairs from the fp32 source rows. CPU-honest — every key is a real
    measurement that degrades loudly, never a skip."""
    from pathway_tpu.engine.profile import histograms
    from pathway_tpu.ops.knn_quant import rescore_pairs
    from pathway_tpu.ops.knn_tiers import TieredIvfKnnStore

    dim = 128
    n_docs = 6_000 if SMOKE else 24_000
    n_queries, k = 128, 10
    n_centers = 128
    rng = np.random.default_rng(21)
    centers = rng.normal(scale=4.0, size=(n_centers, dim)).astype(np.float32)

    def clustered(n: int, seed: int) -> np.ndarray:
        r = np.random.default_rng(seed)
        return (
            centers[r.integers(0, n_centers, n)]
            + r.normal(size=(n, dim)).astype(np.float32)
        ).astype(np.float32)

    data = clustered(n_docs, 22)
    queries = clustered(n_queries, 23)
    n_clusters = max(16, n_docs // 512)
    budget = max(1, (n_docs * dim * 4) // 10)
    keys = [f"d{i}" for i in range(n_docs)]
    results: dict = {"quant_docs": n_docs, "quant_dim": dim}

    def build(quant: str) -> TieredIvfKnnStore:
        # full probe isolates the payload-dtype cost: recall differences are
        # then quantization, not probe luck
        store = TieredIvfKnnStore(
            dim, metric="l2sq", n_clusters=n_clusters, n_probe=n_clusters,
            hbm_budget_bytes=budget, quant=quant,
        )
        for s in range(0, n_docs, 4000):
            store.add_many(keys[s : s + 4000], data[s : s + 4000])
        store.search_batch(queries[:8], k)  # train/maintain off the clock
        return store

    f32 = build("off")
    q8 = build("int8")

    qn_full = np.sum(queries * queries, axis=1)
    dn = np.sum(data * data, axis=1)[None, :]
    exact_idx = np.argsort(
        qn_full[:, None] + dn - 2.0 * queries @ data.T, axis=1
    )[:, :k]
    want = [{f"d{j}" for j in exact_idx[r]} for r in range(n_queries)]

    def recall(store: TieredIvfKnnStore) -> float:
        _s, idx, _v = store.search_batch(queries, k)
        hits = 0
        for r in range(n_queries):
            got = {store.key_of.get(int(x)) for x in idx[r] if x >= 0}
            hits += len(got & want[r])
        return hits / (n_queries * k)

    recall_f = recall(f32)
    recall_q = recall(q8)
    ratio = recall_q / max(recall_f, 1e-12)
    results["quant_recall_at_10_fp32"] = round(recall_f, 4)
    results["quant_recall_at_10_int8"] = round(recall_q, 4)
    results["quant_recall_ratio"] = round(ratio, 4)
    results["quant_recall_honest"] = bool(ratio >= 0.99)
    # the store's own online audit (also populates the /metrics histogram)
    results["quant_recall_audit"] = round(
        float(q8.quant_recall_audit(queries[:64], k=k)), 4
    )

    # -- rescore-epilogue bitwise honesty --------------------------------------
    # recompute OUTSIDE the store: gather each returned slot's fp32 source
    # row, rebuild its norm with the store's own expression, push the pairs
    # through the pinned epilogue — bitwise equality or the key goes false
    s_q, i_q, _ = q8.search_batch(queries, k)
    bitwise = True
    for r in range(n_queries):
        m = i_q[r] >= 0
        slots = i_q[r][m].astype(int)
        if slots.size == 0:
            continue
        vecs = np.stack([q8._vector_of(int(s)) for s in slots]).astype(np.float32)
        norms = np.sum(vecs * vecs, axis=1)
        qi = np.full(slots.size, r)
        exact = rescore_pairs(
            queries[qi], vecs, norms, qn_full[qi], "l2sq"
        ).astype(np.float32)
        bitwise = bitwise and np.array_equal(exact, s_q[r][m])
    results["quant_rescore_bitwise"] = bool(bitwise)

    # -- measured capacity multiple at the same budget -------------------------
    def slot_bytes(store: TieredIvfKnnStore) -> float:
        blocks = list(store.tiers.pages.values())
        return sum(b.nbytes for b in blocks) / max(
            sum(b.vecs.shape[0] for b in blocks), 1
        )

    multiple = slot_bytes(f32) / max(slot_bytes(q8), 1e-12)
    results["quant_capacity_multiple"] = round(multiple, 2)
    results["quant_capacity_honest"] = bool(multiple >= 3.5)

    # -- solo-retrieve p50 ----------------------------------------------------
    # per-query interleave + min-of-medians: the two stores alternate on every
    # single query (order flipped each rep) so host drift, frequency scaling,
    # and cache-warmth hit both code paths identically instead of whichever
    # store happened to run second
    f32.search_batch(queries[:1], k)  # warm both jit/BLAS paths
    q8.search_batch(queries[:1], k)
    rounds_f, rounds_q = [], []
    for rep in range(3):
        lat_f, lat_q = [], []
        for r in range(64):
            pair = ((f32, lat_f), (q8, lat_q))
            if (rep + r) % 2:
                pair = pair[::-1]
            for store, lat in pair:
                t1 = time.perf_counter()
                store.search_batch(queries[r : r + 1], k)
                lat.append(time.perf_counter() - t1)
        rounds_f.append(float(np.median(lat_f)))
        rounds_q.append(float(np.median(lat_q)))
    p50_f = min(rounds_f)
    p50_q = min(rounds_q)
    results["quant_solo_p50_ms"] = round(p50_q * 1000.0, 3)
    results["quant_solo_p50_fp32_ms"] = round(p50_f * 1000.0, 3)
    # 10% tolerance absorbs host timer noise at sub-ms latencies
    results["quant_solo_p50_no_worse"] = bool(p50_q <= p50_f * 1.10)

    # -- residency moves stay bitwise-invariant under int8 ---------------------
    sub = queries[:64]
    a_s, a_i, _ = q8.search_batch(sub, k)
    q8.tiers.budget_bytes = 0  # lift the budget: everything is promotable
    for cid in range(q8.n_clusters):
        if q8.tiers.residency(cid) == "spilled":
            q8.tiers.unspill(cid)
        q8.tiers.promote(cid)
    b_s, b_i, _ = q8.search_batch(sub, k)
    results["quant_bitwise_residency"] = bool(
        np.array_equal(a_s, b_s) and np.array_equal(a_i, b_i)
    )

    depth = histograms().get("pathway_ivf_quant_rescore_depth")
    results["quant_rescore_batches"] = int(depth.count) if depth is not None else 0
    f32.close()
    q8.close()
    return results


def bench_embedder() -> dict:
    """BASELINE #2: SentenceTransformer batch-embed throughput on the TPU.

    Steady-state measurement: fixed 1024-doc chunks (the serving batch size), with
    the SAME shape warmed up first so one-time XLA compilation is excluded — the
    engine reuses a compiled shape for every production batch. Reports the
    host-side (tokenize) vs device-side split."""
    from pathway_tpu.models.encoder import JaxSentenceEncoder

    enc = JaxSentenceEncoder("sentence-transformers/all-MiniLM-L6-v2")
    bs = 64 if SMOKE else 1024
    texts = [
        f"document number {i} about topic {i % 37} and theme {i % 11}"
        for i in range(4 * bs)
    ]
    enc.encode(texts[:bs])  # warmup / compile at the production shape
    # token count + host-tokenize share measured separately (untimed pre-pass)
    n_tokens = 0
    tok_s = 0.0
    for start in range(0, len(texts), bs):
        t1 = time.perf_counter()
        _ids, mask = enc._tokenize(texts[start : start + bs])
        tok_s += time.perf_counter() - t1
        n_tokens += int(mask.sum())
    t0 = time.perf_counter()
    for start in range(0, len(texts), bs):
        enc.encode(texts[start : start + bs])
    dt = time.perf_counter() - t0

    # analytic matmul FLOPs per PADDED token (the shapes actually executed):
    # per layer qkv+out = 4h^2, ffn = 2*h*ffn, x2 for multiply-add; attention
    # scores/values add 4*s*h per token. MFU is quoted against the published
    # bf16 peak of the device_kind it ran on (PEAK_BF16_TFLOPS).
    from pathway_tpu.models.encoder import _next_pow2

    cfg = enc.config
    mm_flops_per_token = 2 * cfg.num_layers * (
        4 * cfg.hidden_size**2 + 2 * cfg.hidden_size * cfg.intermediate_size
    )
    total_flops = 0
    for start in range(0, len(texts), bs):
        ids, _m = enc._tokenize(texts[start : start + bs])
        # the same bucketing encode_device applies — the shapes actually executed
        p2 = _next_pow2(ids.shape[1])
        b2 = _next_pow2(min(bs, len(texts) - start))
        attn_flops_per_token = cfg.num_layers * 4 * p2 * cfg.hidden_size
        total_flops += b2 * p2 * (mm_flops_per_token + attn_flops_per_token)
    tflops = total_flops / dt / 1e12
    out = {
        "embed_docs_per_s": round(len(texts) / dt, 1),
        "embed_tokens_per_s": round(n_tokens / dt, 1),
        "embed_host_tokenize_ms_per_batch": round(tok_s / (len(texts) / bs) * 1000, 2),
        "embed_dim": enc.dim,
        "embed_tflops_per_s": round(tflops, 2),
    }
    import jax

    dev = jax.devices()[0]
    if dev.platform == "tpu":
        out["embed_mfu_pct"] = round(100.0 * tflops / PEAK_BF16_TFLOPS[dev.device_kind], 2)
    return out


def bench_embedpipe() -> dict:
    """EmbedPipeline (ISSUE 4): overlapped+length-sorted ingest vs the
    synchronous encode, coalesced concurrent-query p50 vs solo dispatch, and
    the content-hash cache on re-ingest — all three measured on the SAME host
    with the SAME encoder (absolute docs/s is device-bound). Also reports the padded-token waste ratio both ways and a
    bitwise-equality check of pipelined vs synchronous embeddings (which is
    the recall@10-unchanged guarantee: identical vectors, identical search).
    Pipelines here pin ``service_mode=False``: this section measures the PR-4
    deadline-coalescer mechanics; the persistent encoder service has its own
    ``encsvc`` section."""
    import concurrent.futures
    import threading

    from pathway_tpu.models.embed_pipeline import EmbedPipeline
    from pathway_tpu.models.encoder import JaxSentenceEncoder, _next_pow2

    enc = JaxSentenceEncoder("sentence-transformers/all-MiniLM-L6-v2")
    bs = 128 if SMOKE else 1024
    n_chunks = 2 if SMOKE else 4
    rng = np.random.default_rng(9)
    # serving-shaped corpus: mostly short chunks, a long tail of big ones — the
    # distribution where pad-to-longest burns FLOPs on the short majority
    def make_text(i: int) -> str:
        r = rng.random()
        n_words = int(rng.integers(4, 11)) if r < 0.7 else (
            int(rng.integers(20, 41)) if r < 0.95 else int(rng.integers(80, 121))
        )
        return " ".join(f"tok{(i * 131 + j * 17) % 5000}" for j in range(n_words))

    texts = [make_text(i) for i in range(n_chunks * bs)]
    sub_batch = max(16, bs // 8)  # 8 length-sorted sub-batches per commit batch

    # warm both shape families off the clock (sync longest bucket + the sorted
    # sub-batch buckets)
    enc.encode(texts[:bs])
    warm_pipe = EmbedPipeline(enc, cache_size=0, sub_batch=sub_batch, service_mode=False)
    warm_pipe.encode_batch(texts[:bs])

    out: dict = {}
    t0 = time.perf_counter()
    sync_parts = [enc.encode(texts[s : s + bs]) for s in range(0, len(texts), bs)]
    sync_s = time.perf_counter() - t0
    out["embedpipe_sync_docs_per_s"] = round(len(texts) / sync_s, 1)
    # sync-path waste: every row pays the batch-longest pow2 bucket
    padded = real = 0
    for s in range(0, len(texts), bs):
        ids, mask = enc._tokenize(texts[s : s + bs])
        padded += _next_pow2(ids.shape[0]) * _next_pow2(ids.shape[1])
        real += int(mask.sum())
    out["embedpipe_pad_waste_sync"] = round(1.0 - real / max(padded, 1), 4)

    pipe = EmbedPipeline(enc, cache_size=0, sub_batch=sub_batch, service_mode=False)  # overlap only
    t0 = time.perf_counter()
    over_parts = [pipe.encode_batch(texts[s : s + bs]) for s in range(0, len(texts), bs)]
    over_s = time.perf_counter() - t0
    out["embedpipe_overlap_docs_per_s"] = round(len(texts) / over_s, 1)
    out["embedpipe_overlap_speedup"] = round(sync_s / over_s, 2)
    out["embedpipe_pad_waste_sorted"] = round(pipe.pad_waste_ratio(), 4)
    out["embedpipe_bitwise_equal"] = bool(
        all(
            np.array_equal(a, b) for a, b in zip(sync_parts, over_parts)
        )
    )

    # -- coalesced vs solo concurrent queries --------------------------------
    n_clients = 16
    per_client = 2 if SMOKE else 4
    # warm every (batch, seq) bucket the comparison can hit — query texts all
    # land in one seq bucket; solo pads batch to 8, coalesced to 8/16 — so the
    # timed section measures dispatch+compute, not XLA compiles
    warm_q = [f"client {90 + c} warmup {c} about topic {c}" for c in range(16)]
    enc.encode(warm_q[:1])
    enc.encode(warm_q)
    qpipe = EmbedPipeline(enc, max_wait_ms=4.0, cache_size=0, service_mode=False)
    qpipe.embed_query_rows(warm_q[:1])
    qpipe.embed_query_rows(warm_q)

    def run_clients(embed_one) -> list:
        lats: list = []
        lock = threading.Lock()

        def client(c: int) -> None:
            for q in range(per_client):
                t1 = time.perf_counter()
                embed_one(f"client {c} question {q} about topic {c * 7 + q}")
                dt = time.perf_counter() - t1
                with lock:
                    lats.append(dt)

        with concurrent.futures.ThreadPoolExecutor(n_clients) as pool:
            list(pool.map(client, range(n_clients)))
        return lats

    # solo baseline = the pre-pipeline serving path: the engine evaluates one
    # query commit at a time on ONE thread, so 16 concurrent clients' embeds
    # serialize as 16 padded batch-of-1 dispatches (a lock models the engine's
    # single evaluation thread; unserialized parallel encodes would measure a
    # deployment that does not exist)
    solo_gate = threading.Lock()

    def solo_embed(q: str) -> None:
        with solo_gate:
            enc.encode([q])

    solo_lat = run_clients(solo_embed)
    coal_lat = run_clients(
        lambda q: np.asarray(qpipe.embed_query_rows([q])[0])
    )
    solo_p50 = float(np.median(solo_lat)) * 1000.0
    coal_p50 = float(np.median(coal_lat)) * 1000.0
    out["embedpipe_solo_q_p50_ms"] = round(solo_p50, 2)
    out["embedpipe_coalesced_q_p50_ms"] = round(coal_p50, 2)
    out["embedpipe_coalesce_speedup"] = round(solo_p50 / max(coal_p50, 1e-9), 2)
    cstats = qpipe.coalescer.stats()
    out["embedpipe_coalesce_avg_batch"] = round(
        cstats["coalesce_rows"] / max(cstats["coalesce_batches"], 1), 2
    )

    # -- content-hash cache: unchanged-corpus re-ingest ----------------------
    cpipe = EmbedPipeline(enc, cache_size=len(texts) + 16, sub_batch=sub_batch, service_mode=False)
    t0 = time.perf_counter()
    for s in range(0, len(texts), bs):
        cpipe.encode_batch(texts[s : s + bs])
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for s in range(0, len(texts), bs):
        cpipe.encode_batch(texts[s : s + bs])
    re_s = time.perf_counter() - t0
    stats = cpipe.cache.stats()
    out["embedpipe_first_ingest_docs_per_s"] = round(len(texts) / first_s, 1)
    out["embedpipe_reingest_docs_per_s"] = round(len(texts) / re_s, 1)
    out["embedpipe_cache_reingest_speedup"] = round(first_s / max(re_s, 1e-9), 2)
    out["embedpipe_cache_hit_rate"] = round(
        stats["cache_hits"] / max(stats["cache_hits"] + stats["cache_misses"], 1), 4
    )
    return out


def bench_encsvc() -> dict:
    """Persistent encoder service (ISSUE 11): solo-query p50 through the
    always-warm continuously-batched service vs the PR-4 deadline coalescer
    and vs a bare ``encode_device`` dispatch; tick occupancy under 16
    concurrent clients; semantic-cache hit speedup; and a TRUE bitwise-
    equality honesty key (exact mode) against a direct encode. The jit
    pre-warm runs — and is reported as ``encsvc_prewarm_s`` — BEFORE any timed
    request, so compilation never pollutes request latency."""
    import concurrent.futures
    import threading

    from pathway_tpu.models.embed_pipeline import EmbedPipeline
    from pathway_tpu.models.encoder import JaxSentenceEncoder

    if SMOKE:
        # fewer pre-warm compiles at toy scale: the full bucket matrix is a
        # device start-up cost
        os.environ.setdefault("PATHWAY_ENCSVC_PREWARM_MAX_BATCH", "16")
    enc = JaxSentenceEncoder("sentence-transformers/all-MiniLM-L6-v2")
    out: dict = {}

    # -- startup: pre-warm every reachable (batch, seq) bucket ---------------
    pipe = EmbedPipeline(enc, cache_size=0, service_mode=True, prewarm=True)
    svc = pipe.service
    out["encsvc_prewarm_ok"] = bool(svc.wait_warm(timeout_s=420.0))
    out["encsvc_prewarm_s"] = round(svc.prewarm_s, 2)
    out["encsvc_prewarm_compiles"] = svc.prewarm_compiles

    n_solo = 16 if SMOKE else 64

    def q(i: int) -> str:
        return f"solo retrieval question {i} about topic {i % 7}"

    # settle both paths once so the timed section is steady-state dispatch
    np.asarray(pipe.embed_query_rows([q(10_001)])[0])
    np.asarray(enc.encode_device([q(10_002)]))

    # -- solo p50: the ROADMAP item-2 headline (pre-warm excluded) -----------
    lat = []
    for i in range(n_solo):
        t0 = time.perf_counter()
        np.asarray(pipe.embed_query_rows([q(i)])[0])
        lat.append(time.perf_counter() - t0)
    solo_p50 = float(np.median(lat)) * 1000.0
    out["encsvc_solo_p50_ms"] = round(solo_p50, 2)
    out["encsvc_solo_sub15ms"] = bool(solo_p50 < 15.0)

    dlat = []
    for i in range(n_solo):
        t0 = time.perf_counter()
        np.asarray(enc.encode_device([q(i + n_solo)]))
        dlat.append(time.perf_counter() - t0)
    out["encsvc_direct_p50_ms"] = round(float(np.median(dlat)) * 1000.0, 2)

    legacy = EmbedPipeline(enc, cache_size=0, service_mode=False, max_wait_ms=2.0)
    np.asarray(legacy.embed_query_rows([q(10_003)])[0])
    llat = []
    for i in range(n_solo):
        t0 = time.perf_counter()
        np.asarray(legacy.embed_query_rows([q(i + 2 * n_solo)])[0])
        llat.append(time.perf_counter() - t0)
    legacy.coalescer.close()
    out["encsvc_legacy_solo_p50_ms"] = round(float(np.median(llat)) * 1000.0, 2)
    out["encsvc_solo_speedup_vs_legacy"] = round(
        float(np.median(llat)) / max(float(np.median(lat)), 1e-9), 2
    )

    # -- honesty key: service row bitwise == a direct encode of the same text
    probe = "bitwise honesty probe query"
    svc_row = np.asarray(pipe.embed_query_rows([probe])[0], dtype=np.float32)
    direct_row = np.asarray(enc.encode_device([probe]), dtype=np.float32)[0]
    out["encsvc_bitwise_equal"] = bool(np.array_equal(svc_row, direct_row))

    # -- occupancy under 16 concurrent clients -------------------------------
    n_clients = 16
    per_client = 2 if SMOKE else 4
    ticks0, rows0 = svc.ticks, svc.total_rows
    clat: list = []
    lock = threading.Lock()

    def client(c: int) -> None:
        for k in range(per_client):
            t1 = time.perf_counter()
            np.asarray(
                pipe.embed_query_rows([f"client {c} burst {k} topic {c * 7 + k}"])[0]
            )
            dt = time.perf_counter() - t1
            with lock:
                clat.append(dt)

    with concurrent.futures.ThreadPoolExecutor(n_clients) as pool:
        list(pool.map(client, range(n_clients)))
    ticks = svc.ticks - ticks0
    rows = svc.total_rows - rows0
    out["encsvc_concurrent_p50_ms"] = round(float(np.median(clat)) * 1000.0, 2)
    out["encsvc_ticks_16c"] = ticks
    out["encsvc_avg_tick_rows_16c"] = round(rows / max(ticks, 1), 2)
    out["encsvc_occupancy_16c"] = round(rows / max(ticks * n_clients, 1), 4)

    # -- semantic-cache hit speedup (exact mode: bitwise-honest hits) --------
    sem = EmbedPipeline(enc, cache_size=4096, service_mode=True, prewarm=False)
    primes = [f"semantic prime question {i} about topic {i}" for i in range(8)]
    mlat = []
    for p in primes:
        t0 = time.perf_counter()
        np.asarray(sem.embed_query_rows([p])[0])
        mlat.append(time.perf_counter() - t0)
    # wait on the SEMANTIC layer (the one being measured): its fill lands
    # after the content-cache fill on the worker thread
    deadline = time.monotonic() + 30.0
    while len(sem.semantic_cache) < len(primes) and time.monotonic() < deadline:
        time.sleep(0.01)
    hlat = []
    for i, p in enumerate(primes):
        variant = f"  Semantic PRIME question {i}  about topic {i} "
        t0 = time.perf_counter()
        np.asarray(sem.embed_query_rows([variant])[0])
        hlat.append(time.perf_counter() - t0)
    miss_p50 = float(np.median(mlat)) * 1000.0
    hit_p50 = float(np.median(hlat)) * 1000.0
    out["encsvc_semantic_miss_p50_ms"] = round(miss_p50, 3)
    out["encsvc_semantic_hit_p50_ms"] = round(hit_p50, 3)
    out["encsvc_semantic_hit_speedup"] = round(miss_p50 / max(hit_p50, 1e-9), 2)
    out["encsvc_semantic_hits"] = sem.semantic_cache.stats()["semantic_exact_hits"]
    svc.close()
    sem.service.close()
    return out


def _vs_corpus(n_docs: int) -> list:
    """The vector-store bench corpus — ONE construction shared by the main
    serving bench and the non-embed floor bench (they must measure the same
    workload for the decomposition to mean anything)."""
    import json as _json

    rng = np.random.default_rng(1)
    words = [f"term{i}" for i in range(500)]
    return [
        (" ".join(words[j] for j in rng.integers(0, 500, 12)), _json.dumps({"path": f"doc{i}"}))
        for i in range(n_docs)
    ]


def _vs_poster(port: int):
    import json as _json
    import urllib.request

    def post(route: str, payload: dict, timeout: float = 60.0) -> dict:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{route}",
            data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return _json.loads(resp.read())

    return post


def bench_vector_store(port: int = 18715) -> dict:
    """BASELINE #3: VectorStoreServer end-to-end over REST (ingest + query p50)."""
    import json as _json
    import urllib.request

    import pathway_tpu as pw
    from pathway_tpu.internals import parse_graph as pg
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    pg.G.clear()
    n_docs = 2_000 if SMOKE else 20_000
    docs = _vs_corpus(n_docs)
    doc_table = pw.debug.table_from_rows(
        pw.schema_builder({"data": str, "_metadata": str}), docs
    )
    embedder = SentenceTransformerEmbedder(batch_size=64 if SMOKE else 1024)
    # compile the production batch shape off the clock (the engine reuses one
    # compiled shape for every ingest batch; cold-start XLA compilation is a
    # per-process constant, not a per-document cost)
    embedder.encoder.encode(["warm up"] * (64 if SMOKE else 1024))
    # single-query model cost, measured BEFORE the server's commit loop can
    # compete for the host (decomposes query p50 into embed vs engine+REST)
    embed_times = []
    embedder.encoder.encode(["warm single"])
    for _ in range(10):
        t1 = time.perf_counter()
        embedder.encoder.encode(["a single query string"])
        embed_times.append(time.perf_counter() - t1)
    embed_ms = float(np.median(embed_times)) * 1000.0
    server = VectorStoreServer(doc_table, embedder=embedder)
    t_start = time.perf_counter()
    server.run_server(host="127.0.0.1", port=port, threaded=True, terminate_on_error=False)
    post = _vs_poster(port)

    # ingest time: until statistics reports the corpus indexed
    deadline = time.perf_counter() + 600
    ingest_s = None
    while time.perf_counter() < deadline:
        try:
            stats = post("/v1/statistics", {}, timeout=5)
            if int(stats.get("file_count", 0)) >= 1:
                ingest_s = time.perf_counter() - t_start
                break
        except Exception:
            pass
        time.sleep(0.25)
    if ingest_s is None:
        raise TimeoutError("vectorstore: corpus not indexed within 600 s")

    post("/v1/retrieve", {"query": "term1 term2", "k": 3})  # warmup
    lat = []
    for i in range(30):
        t1 = time.perf_counter()
        post("/v1/retrieve", {"query": f"term{i} term{i+40} term{i+80}", "k": 3})
        lat.append(time.perf_counter() - t1)

    # latency floor diagnostic: one device round-trip (a trivial jit + fetch).
    # The serving path is engineered down to ONE round-trip (device-resident
    # query embeddings chained into the search kernel), so p50 ~= rtt + engine
    # overhead.
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((8, 8))
    np.asarray(f(x))
    rtts = []
    for _ in range(10):
        t1 = time.perf_counter()
        np.asarray(f(x))
        rtts.append(time.perf_counter() - t1)
    rtt_ms = float(np.median(rtts)) * 1000.0
    p50_ms = float(np.median(lat)) * 1000.0
    # decomposition: the single-query model forward (embed_ms, measured above
    # pre-server) is reported alongside p50, NOT subtracted from it — the two
    # are measured under different host contention so the difference is not a
    # measurement (r5 artifact carried a negative "nonembed" residual). The
    # MEASURED non-embed floor is bench_vs_floor's vs_query_nonembed_p50_ms.
    return {
        "vs_ingest_docs_per_s": round(n_docs / ingest_s, 1),
        "vs_query_p50_ms": round(p50_ms, 2),
        "vs_query_p95_ms": round(float(np.percentile(lat, 95)) * 1000.0, 2),
        "device_roundtrip_p50_ms": round(rtt_ms, 2),
        "vs_query_p50_minus_rtt_ms": round(p50_ms - rtt_ms, 2),
        "vs_query_embed1_ms": round(embed_ms, 2),
    }


def bench_vs_floor(port: int = 18731) -> dict:
    """MEASURED non-embed serving floor (r4 verdict: a residual computed as
    p50 - batched_embed_amortization is not a measurement): the IDENTICAL
    REST -> engine -> KNN serving path with an instant deterministic hash
    embedder — no model forward anywhere in the loop, so this p50 IS the
    REST + engine + search floor. Runs as its own section/subprocess so the
    model server's background threads don't inflate it."""
    import hashlib
    import json as _json
    import urllib.request

    import pathway_tpu as pw
    from pathway_tpu.internals import parse_graph as pg
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    pg.G.clear()
    n_docs = 2_000 if SMOKE else 20_000
    rng = np.random.default_rng(1)
    words = [f"term{i}" for i in range(500)]
    docs = [
        (" ".join(words[j] for j in rng.integers(0, 500, 12)), _json.dumps({"path": f"doc{i}"}))
        for i in range(n_docs)
    ]

    @pw.udf
    def _instant_embed(text: str) -> np.ndarray:
        # same 384-dim as the production encoder: the KNN matmul/norm cost
        # scales with dim, so a smaller floor embedding would understate the
        # search share of the floor
        h = np.frombuffer(
            hashlib.md5(text.encode()).digest() * 24, dtype=np.uint8
        ).astype(np.float32)
        return h / (np.linalg.norm(h) + 1e-9)

    doc_table = pw.debug.table_from_rows(
        pw.schema_builder({"data": str, "_metadata": str}), docs
    )
    server = VectorStoreServer(doc_table, embedder=_instant_embed)
    server.run_server(host="127.0.0.1", port=port, threaded=True, terminate_on_error=False)
    post = _vs_poster(port)

    deadline = time.perf_counter() + 240
    while time.perf_counter() < deadline:
        try:
            stats = post("/v1/statistics", {}, timeout=5)
            if int(stats.get("file_count", 0)) >= 1:
                break
        except Exception:
            pass
        time.sleep(0.25)
    else:
        raise TimeoutError("vsfloor: corpus not indexed within its deadline")

    post("/v1/retrieve", {"query": "term1 term2", "k": 3})  # warmup
    lat = []
    for i in range(50):
        t1 = time.perf_counter()
        post("/v1/retrieve", {"query": f"term{i} term{i+11}", "k": 3})
        lat.append(time.perf_counter() - t1)
    return {
        "vs_query_nonembed_p50_ms": round(float(np.median(lat)) * 1000.0, 2),
        "vs_query_nonembed_p95_ms": round(float(np.percentile(lat, 95)) * 1000.0, 2),
    }


def bench_streaming_window() -> dict:
    """BASELINE #4: timed stream -> tumbling window aggregation."""
    import pathway_tpu as pw
    from pathway_tpu.internals import parse_graph as pg
    from pathway_tpu.engine.runner import GraphRunner

    pg.G.clear()
    rng = np.random.default_rng(2)
    n = 200_000
    n_commits = 20
    per = n // n_commits
    rows = []
    for c in range(n_commits):
        ts = rng.integers(c * 100, (c + 1) * 100, per)
        sensors = rng.integers(0, 64, per)
        for t, s in zip(ts.tolist(), sensors.tolist()):
            rows.append((s, t, float(t % 7), 2 * c, 1))
    schema = pw.schema_builder({"sensor": int, "t": int, "value": float})
    tbl = pw.debug.table_from_rows(schema, rows, is_stream=True)
    win = tbl.windowby(
        tbl.t, window=pw.temporal.tumbling(duration=50), instance=tbl.sensor
    ).reduce(
        sensor=pw.this._pw_instance,
        start=pw.this._pw_window_start,
        total=pw.reducers.sum(pw.this.value),
        n=pw.reducers.count(),
    )
    cnt = [0]
    pw.io.subscribe(win, lambda key, row, time, is_addition: cnt.__setitem__(0, cnt[0] + 1))
    t0 = time.perf_counter()
    GraphRunner(pg.G._current).run(monitoring_level=pw.MonitoringLevel.NONE)
    dt = time.perf_counter() - t0
    return {"window_rows_per_s": round(n / dt, 1), "window_updates": cnt[0]}


def bench_telemetry() -> dict:
    """Metrics-plane overhead: streaming wordcount with per-operator
    profiling toggled PER COMMIT (even commits profiled, odd not) inside one
    run, so machine noise — which on a cpu-shared host dwarfs the true
    overhead at whole-run granularity (±20-50% between identical runs) —
    decorrelates from the measurement: adjacent commits see the same machine.
    Per-arm MEDIANS (durations are heavy-tailed), median-of-3 passes, GC off
    during the measured run (allocation-triggered pauses otherwise land on
    one parity), and a NULL calibration (same toggle bookkeeping, profiling
    off for both parities) subtracted to cancel the estimator's own parity
    bias. Contract: <2% commit-throughput delta on the headline regime.

    Two regimes: headline ``telemetry_overhead_pct`` on engine-bench-sized
    commits (~8k rows, multi-ms — what production batches look like;
    lands <1% + measurement floor), and
    ``telemetry_overhead_small_commits_pct`` on sub-millisecond few-hundred-
    row commits — the ADVERSARIAL bound where fixed per-commit bookkeeping
    (~18 µs measured standalone: per-op perf_counter pairs + one-pass
    retraction counts + the ring/fold appends) is largest relative to real
    work; expect a few percent there, by design of the regime. CPU-vs-CPU on
    any host, no device keys. Also reports the profiled commits' duration
    percentiles from the live log-bucketed histogram (what /metrics serves,
    measured not mocked).

    The tracing plane rides the same estimator: ``trace_overhead_pct``
    toggles ``PATHWAY_TRACE`` span bookkeeping per commit at the default 1%
    head-sampling rate on the headline regime (same <2% contract), and
    ``trace_output_bitwise_identical`` replays one stream traced at
    sample=1.0 vs tracing off and compares every delivered batch bitwise —
    tracing must observe, never perturb."""
    import pathway_tpu as pw
    from pathway_tpu.engine.profile import get_profiler, reset_profile
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.internals import parse_graph as pg

    rng = np.random.default_rng(11)
    words_pool = np.array([f"word{i}" for i in range(4_000)])

    class ToggleRunner(GraphRunner):
        """Profiling on for even commits, off for odd — the per-commit A/B.
        With ``null=True`` profiling is off for BOTH parities while commits
        are still classified even/odd: that run measures the estimator's own
        parity bias (allocator drift, cache effects, throttle phase), which
        is subtracted from the toggle estimate."""

        def __init__(self, graph, *, null: bool = False):
            super().__init__(graph)
            self.null = null
            self.durations_on: list = []
            self.durations_off: list = []

        def step(self) -> bool:
            even = self._commit % 2 == 0
            profiled = even and not self.null
            saved = self._profiler
            if not profiled:
                self._profiler = None
            t0 = time.perf_counter()
            try:
                out = super().step()
            finally:
                dt = time.perf_counter() - t0
                self._profiler = saved
            (self.durations_on if even else self.durations_off).append(dt)
            return out

    class TraceToggleRunner(GraphRunner):
        """Tracing on for even commits, off for odd — the tracing plane's
        per-commit A/B (same estimator as the profiler toggle above). "On"
        means the full commit-span path at the DEFAULT head-sampling rate:
        deterministic commit context, span open/close, link drain; operator
        child spans only synthesize for the sampled ~1%."""

        def __init__(self, graph, *, null: bool = False):
            super().__init__(graph)
            self.null = null
            self.durations_on: list = []
            self.durations_off: list = []

        def step(self) -> bool:
            from pathway_tpu.engine.tracing import get_tracer

            even = self._commit % 2 == 0
            traced = even and not self.null
            tracer = get_tracer()
            saved = tracer.enabled
            if not traced:
                tracer.enabled = False
            t0 = time.perf_counter()
            try:
                out = super().step()
            finally:
                dt = time.perf_counter() - t0
                tracer.enabled = saved
            (self.durations_on if even else self.durations_off).append(dt)
            return out

    def typical(values: list) -> float:
        """Median: commit durations are heavy-tailed (GC, scheduler, state
        growth spikes run 5-10x the median) and the overhead under test is
        percent-level — a mean would be set by the tail, not the signal."""
        values = sorted(values)
        mid = len(values) // 2
        return values[mid] if len(values) % 2 else (values[mid - 1] + values[mid]) / 2

    def measure(
        n: int, n_commits: int, *, null: bool = False, runner_cls=ToggleRunner
    ) -> tuple:
        import gc

        per = n // n_commits
        words = words_pool[rng.integers(0, len(words_pool), n)]
        rows = [(w, 2 * (i // per), 1) for i, w in enumerate(words.tolist())]
        pg.G.clear()
        tbl = pw.debug.table_from_rows(
            pw.schema_builder({"word": str}), rows, is_stream=True
        )
        out = tbl.groupby(pw.this.word).reduce(pw.this.word, cnt=pw.reducers.count())
        pw.io.subscribe(out, on_batch=lambda *a: None)
        runner = runner_cls(pg.G._current, null=null)
        # GC pauses (~100 µs) are allocation-count-triggered: the profiled
        # arm's slightly higher allocation rate SHIFTS which parity pays
        # them, turning GC timing into a systematic A/B bias either way.
        # Collect up front, keep GC off for the measured run.
        gc.collect()
        gc.disable()
        try:
            runner.run(monitoring_level=pw.MonitoringLevel.NONE)
        finally:
            gc.enable()
        # drop per-arm warmup (first profiled + first unprofiled commit pay
        # first-touch costs) before the medians
        on_mean = typical(runner.durations_on[1:])
        off_mean = typical(runner.durations_off[1:])
        return (on_mean - off_mean) / off_mean * 100.0, on_mean, off_mean

    def calibrated(n: int, n_commits: int, *, runner_cls=ToggleRunner) -> tuple:
        """Bias-corrected overhead: median-of-3 toggle passes MINUS
        median-of-3 null passes (same runner, profiling off for both
        parities). The null measures everything the estimator picks up that
        is NOT profiling — even/odd parity bias from allocator drift, cache
        phase, and the host's cpu-share throttle — which in this container
        runs ±1-3%, the same order as the effect under test."""
        toggles = sorted(
            measure(n, n_commits, runner_cls=runner_cls) for _ in range(3)
        )
        nulls = sorted(
            measure(n, n_commits, null=True, runner_cls=runner_cls)[0]
            for _ in range(3)
        )
        pct, on_t, off_t = toggles[1]
        return pct - nulls[1], on_t, off_t

    prev = os.environ.get("PATHWAY_PROFILE")
    os.environ["PATHWAY_PROFILE"] = "1"
    try:
        scale = 4 if SMOKE else 1
        reset_profile()
        # representative: engine-bench-sized commit batches (~8k rows/commit,
        # multi-ms commits) — the regime the <2% contract is about; per-commit
        # bookkeeping (~18 µs measured standalone) amortizes to well under 1%
        rep_n = 400_000 if SMOKE else 800_000
        rep_pct, rep_on, rep_off = calibrated(rep_n, rep_n // 8_000)
        totals = get_profiler().operator_totals()  # folds pending profiles
        pct = get_profiler().commit_hist.percentiles()
        # by NAME, like the flight-recorder summary and /v1/statistics — kind
        # alone cannot distinguish two groupby nodes
        slowest = max(totals, key=lambda e: e["seconds"])["name"] if totals else ""
        reset_profile()
        # adversarial: the regime is DEFINED by its ~500-row sub-ms commits —
        # scaling rows down further would measure a regime nothing runs in
        small_pct, _on, _off = calibrated(200_000 // scale, 400 // scale)
        # tracing plane: the same bias-corrected per-commit estimator, span
        # bookkeeping at the default head-sampling rate vs PATHWAY_TRACE=off
        # — the distributed-tracing README row shares the <2% contract
        trace_prev = {
            k: os.environ.get(k)
            for k in ("PATHWAY_TRACE", "PATHWAY_TRACE_SAMPLE")
        }
        os.environ["PATHWAY_TRACE"] = "on"
        os.environ["PATHWAY_TRACE_SAMPLE"] = "0.01"
        from pathway_tpu.engine.tracing import reset_tracing

        reset_tracing()
        try:
            # full headline regime: the per-commit trace path costs ~15 µs
            # standalone (two sha1 context derivations + pending-buffer
            # routing), percent-level on multi-ms commits. PAIRED estimator
            # here rather than `calibrated`: host cpu-share drift between the
            # toggle group and the null group reads as ±5-10% bias at this
            # arm's position late in the bench, so each toggle pass is
            # corrected by the null pass run immediately after it, and the
            # median of the paired differences is reported.
            trace_pairs = []
            for _ in range(3):
                t_pct, t_on, t_off = measure(
                    rep_n, rep_n // 8_000, runner_cls=TraceToggleRunner
                )
                null_pct, _, _ = measure(
                    rep_n, rep_n // 8_000, null=True,
                    runner_cls=TraceToggleRunner,
                )
                trace_pairs.append((t_pct - null_pct, t_on, t_off))
            trace_pairs.sort()
            trace_pct, _t_on, _t_off = trace_pairs[1]

            # honesty: tracing must not perturb results — the SAME stream,
            # traced at sample=1.0 (every commit spanned, operator child
            # spans synthesized) and with tracing off, must agree BITWISE
            def final_batches(trace_env: str) -> list:
                os.environ["PATHWAY_TRACE"] = trace_env
                os.environ["PATHWAY_TRACE_SAMPLE"] = "1.0"
                reset_tracing()
                cap_rng = np.random.default_rng(17)
                words = words_pool[
                    cap_rng.integers(0, len(words_pool), 60_000)
                ]
                rows = [
                    (w, 2 * (i // 6_000), 1)
                    for i, w in enumerate(words.tolist())
                ]
                pg.G.clear()
                tbl = pw.debug.table_from_rows(
                    pw.schema_builder({"word": str}), rows, is_stream=True
                )
                out = tbl.groupby(pw.this.word).reduce(
                    pw.this.word, cnt=pw.reducers.count()
                )
                captured: list = []

                def on_batch(keys, diffs, columns, time):
                    captured.append((
                        keys.tobytes(),
                        diffs.tobytes(),
                        tuple(
                            (nm, np.asarray(col).tobytes())
                            if np.asarray(col).dtype != object
                            else (nm, repr(np.asarray(col).tolist()).encode())
                            for nm, col in sorted(columns.items())
                        ),
                    ))

                pw.io.subscribe(out, on_batch=on_batch)
                pw.run(monitoring_level=pw.MonitoringLevel.NONE)
                return captured

            trace_bitwise = final_batches("on") == final_batches("off")
        finally:
            for k, v in trace_prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            reset_tracing()
    finally:
        if prev is None:
            os.environ.pop("PATHWAY_PROFILE", None)
        else:
            os.environ["PATHWAY_PROFILE"] = prev
    reset_profile()
    return {
        "telemetry_overhead_pct": round(rep_pct, 2),
        "telemetry_overhead_small_commits_pct": round(small_pct, 2),
        "telemetry_profiled_commit_ms": round(rep_on * 1000, 3),
        "telemetry_unprofiled_commit_ms": round(rep_off * 1000, 3),
        "telemetry_commit_p50_ms": round(pct["p50"] * 1000, 3),
        "telemetry_commit_p99_ms": round(pct["p99"] * 1000, 3),
        "telemetry_slowest_operator": slowest,
        "trace_overhead_pct": round(trace_pct, 2),
        "trace_output_bitwise_identical": bool(trace_bitwise),
    }


def bench_engine() -> dict:
    """Streaming wordcount + incremental join vs vectorized-numpy CPU proxies
    maintaining identical per-commit results.

    Fairness contract, both sides: data preparation (row lists / numpy arrays,
    sorted build sides) happens OFF the clock; the timed region is per-commit
    incremental processing + delivery of the update batches.

    Reading the ratios: wordcount/join (string keys) are the headline bars
    (>= 1.0x). join_int is secondary and sits ~0.6x (r5: single-int keys now
    derive via an identity mix instead of xxh3, and the inner all-matched emit
    path skips its splicing — up from ~0.47): the proxy is a non-incremental
    branchless binary search over sorted int64s near the memory-bandwidth
    floor, while the engine maintains a fully incremental, retraction-capable
    arrangement and gathers object-cell outputs; closing the rest needs typed
    (non-object) string columns. The join_churn metric is the same workload
    once the build side actually churns: there incrementality wins ~2.5x,
    which is the workload this engine exists for. The engine delivers
    through the vectorized ``pw.io.subscribe(on_batch=...)`` sink (columnar arrays,
    the TPU-native delivery path); the proxies consume by updating their own
    result state. Join keys are string entity ids (the representative ETL join);
    the int-key variant is reported as a secondary metric."""
    import pathway_tpu as pw
    from pathway_tpu.internals import parse_graph as pg
    from pathway_tpu.engine.runner import GraphRunner

    def _warmup() -> None:
        # Compile the jit'd groupby/join/consolidation kernels off the clock: the
        # timed region measures steady-state throughput (compiles amortize away in
        # any real deployment; the numpy proxy has no compile step to pay either).
        rngw = np.random.default_rng(0)
        ww = [f"w{i}" for i in range(256)]
        rows = [(ww[j], 2 * (i // 2048), 1) for i, j in enumerate(rngw.integers(0, 256, 8192).tolist())]
        pg.G.clear()
        t = pw.debug.table_from_rows(pw.schema_builder({"word": str}), rows, is_stream=True)
        out = t.groupby(pw.this.word).reduce(pw.this.word, cnt=pw.reducers.count())
        pw.io.subscribe(out, on_batch=lambda *a: None)
        GraphRunner(pg.G._current).run(monitoring_level=pw.MonitoringLevel.NONE)
        pg.G.clear()
        lt = pw.debug.table_from_rows(
            pw.schema_builder({"k": str}),
            [(ww[j], 2 * (i // 2048), 1) for i, j in enumerate(rngw.integers(0, 256, 8192).tolist())],
            is_stream=True,
        )
        rt = pw.debug.table_from_rows(
            pw.schema_builder({"k2": str, "name": str}), [(w, w.upper()) for w in ww]
        )
        j = lt.join(rt, lt.k == rt.k2).select(lt.k, rt.name)
        pw.io.subscribe(j, on_batch=lambda *a: None)
        GraphRunner(pg.G._current).run(monitoring_level=pw.MonitoringLevel.NONE)

    _warmup()

    rng = np.random.default_rng(3)
    n = 400_000
    n_commits = 20
    words_pool = np.array([f"word{i}" for i in range(20_000)])
    word_ids = rng.integers(0, len(words_pool), n)
    words = words_pool[word_ids]

    # numpy proxy: per commit np.unique + count accumulation + changed-group emission
    per = n // n_commits
    t0 = time.perf_counter()
    counts: dict = {}
    emitted = 0
    for c in range(n_commits):
        batch = words[c * per : (c + 1) * per]
        uniq, cnt = np.unique(batch, return_counts=True)
        for w, k in zip(uniq.tolist(), cnt.tolist()):
            counts[w] = counts.get(w, 0) + k
        emitted += len(uniq)
    proxy_wc_s = time.perf_counter() - t0

    pg.G.clear()
    rows = [
        (w, 2 * (i // per), 1) for i, w in enumerate(words.tolist())
    ]
    tbl = pw.debug.table_from_rows(pw.schema_builder({"word": str}), rows, is_stream=True)
    out = tbl.groupby(pw.this.word).reduce(pw.this.word, cnt=pw.reducers.count())
    delivered = [0]
    pw.io.subscribe(
        out, on_batch=lambda keys, diffs, columns, time: delivered.__setitem__(
            0, delivered[0] + len(keys)
        )
    )
    t0 = time.perf_counter()
    GraphRunner(pg.G._current).run(monitoring_level=pw.MonitoringLevel.NONE)
    engine_wc_s = time.perf_counter() - t0

    # join: 200k probe rows against a 20k-row build side, streamed in 10 commits.
    # Keys are string ids; the proxy probes a pre-sorted build side via searchsorted
    # (numpy's fastest honest string lookup), the engine runs its full incremental
    # hash join (both sides arranged, retraction-capable).
    nj = 200_000
    build_n = 20_000
    per_j = nj // 10
    probe_pos = rng.integers(0, build_n, nj)
    build_keys = np.array([f"user_{i:08d}" for i in range(build_n)])
    build_names = np.array([f"name{i}" for i in range(build_n)])
    probe_keys = build_keys[probe_pos]

    def proxy_join(build_k: np.ndarray, probe_k: np.ndarray) -> float:
        import gc

        gc.collect()
        order = np.argsort(build_k)
        sb, sn = build_k[order], build_names[order]
        t0 = time.perf_counter()
        for c in range(10):
            keys = probe_k[c * per_j : (c + 1) * per_j]
            pos = np.searchsorted(sb, keys)
            _ = keys, sn[pos]  # emitted join rows (key, name)
        return time.perf_counter() - t0

    def engine_join(schema_k: type, build_vals: list, probe_vals: list) -> float:
        import gc

        gc.collect()  # isolate from the previous sub-measurement's garbage
        pg.G.clear()
        lrows = [(k, 2 * (i // per_j), 1) for i, k in enumerate(probe_vals)]
        lt = pw.debug.table_from_rows(
            pw.schema_builder({"k": schema_k}), lrows, is_stream=True
        )
        rt = pw.debug.table_from_rows(
            pw.schema_builder({"k2": schema_k, "name": str}),
            [(k, f"name{i}") for i, k in enumerate(build_vals)],
        )
        j = lt.join(rt, lt.k == rt.k2).select(lt.k, rt.name)
        pw.io.subscribe(j, on_batch=lambda keys, diffs, columns, time: None)
        t0 = time.perf_counter()
        GraphRunner(pg.G._current).run(monitoring_level=pw.MonitoringLevel.NONE)
        return time.perf_counter() - t0

    proxy_join_s = proxy_join(build_keys, probe_keys)
    engine_join_s = engine_join(str, build_keys.tolist(), probe_keys.tolist())
    proxy_join_int_s = proxy_join(np.arange(build_n), probe_pos)
    engine_join_int_s = engine_join(
        int, list(range(build_n)), [int(k) for k in probe_pos]
    )

    # -- incremental join under build-side churn ---------------------------------
    # After every second probe commit, 2k build rows change their name; every
    # previously-arrived probe row joining a changed key must emit a retract+insert
    # pair (the defining obligation of an INCREMENTAL join). The proxy does the same
    # with the best vectorized numpy available: sorted-build searchsorted for probe
    # lookups, np.isin over the accumulated probe history for retro updates.
    churn_rounds = [(2 * r + 1, rng.integers(0, build_n, 2_000)) for r in range(5)]

    def proxy_churn() -> float:
        order = np.argsort(build_keys)
        sb = build_keys[order]
        names_cur = build_names[order].copy()
        hist: list = []
        churn = {t: pos for t, pos in churn_rounds}
        t0 = time.perf_counter()
        for c in range(10):
            keys = probe_keys[c * per_j : (c + 1) * per_j]
            pos = np.searchsorted(sb, keys)
            _ = keys, names_cur[pos]  # emitted join rows
            hist.append(keys)
            if c in churn:
                changed_pos = np.unique(churn[c])
                changed_keys = build_keys[changed_pos]
                sc = np.sort(changed_keys)
                h = np.concatenate(hist)
                hit = h[np.isin(h, sc)]
                hp = np.searchsorted(sb, hit)
                old = names_cur[hp]  # retractions carry old values
                bp = np.searchsorted(sb, changed_keys)
                names_cur[bp] = np.char.add(build_names[changed_pos], f"_v{c}")
                new = names_cur[hp]  # re-inserts carry new values
                _ = hit, old, new  # emitted retract+insert update pairs
        return time.perf_counter() - t0

    def engine_churn() -> float:
        pg.G.clear()
        lrows = [(k, 4 * (i // per_j), 1) for i, k in enumerate(probe_keys.tolist())]
        lt = pw.debug.table_from_rows(
            pw.schema_builder({"k": str}), lrows, is_stream=True
        )
        rrows: list = [
            (k, f"name{i}", 0, 1) for i, k in enumerate(build_keys.tolist())
        ]
        current = {k: f"name{i}" for i, k in enumerate(build_keys.tolist())}
        for c, pos in churn_rounds:
            t = 4 * c + 2  # between probe commits c and c+1
            for p in np.unique(pos).tolist():
                k = build_keys[p]
                rrows.append((k, current[k], t, -1))
                current[k] = f"name{p}_v{c}"
                rrows.append((k, current[k], t, 1))
        rt = pw.debug.table_from_rows(
            pw.schema_builder({"k2": str, "name": str}), rrows, is_stream=True
        )
        j = lt.join(rt, lt.k == rt.k2).select(lt.k, rt.name)
        pw.io.subscribe(j, on_batch=lambda keys, diffs, columns, time: None)
        t0 = time.perf_counter()
        GraphRunner(pg.G._current).run(monitoring_level=pw.MonitoringLevel.NONE)
        return time.perf_counter() - t0

    proxy_churn_s = proxy_churn()
    engine_churn_s = engine_churn()

    return {
        "wordcount_rows_per_s": round(n / engine_wc_s, 1),
        "wordcount_vs_numpy": round(proxy_wc_s / engine_wc_s, 3),
        "wordcount_updates_delivered": delivered[0],
        "join_rows_per_s": round(nj / engine_join_s, 1),
        "join_vs_numpy": round(proxy_join_s / engine_join_s, 3),
        "join_int_rows_per_s": round(nj / engine_join_int_s, 1),
        "join_int_vs_numpy": round(proxy_join_int_s / engine_join_int_s, 3),
        "join_churn_rows_per_s": round(nj / engine_churn_s, 1),
        "join_churn_vs_numpy": round(proxy_churn_s / engine_churn_s, 3),
    }


def bench_fusion() -> dict:
    """Whole-commit fusion A/B: the join/groupby chain workload with the
    fusion compiler toggled PER COMMIT inside one run (even commits fused, odd
    per-node dispatch — the telemetry section's parity discipline, because
    whole-run timing swings ±20-50% on this shared host), medians per arm,
    median-of-3 passes, GC off during the measured region.

    Workload: a wide integer feature-derivation chain (the shape of a
    production feature pipeline — money in cents, timestamps, categorical
    codes; ~150 elementwise ops across 20 derivation stages — feature-store width), a selectivity filter,
    an incremental hash join against a dimension table, a short post-join
    derivation chain, and a groupby summing two int columns. The numpy proxy
    performs the same per-commit computation the obvious vectorized way
    (op-at-a-time temporaries, pre-sorted searchsorted join, ``np.add.at``
    aggregation) and maintains the same per-commit group outputs.

    Keys: ``fused_join_speedup`` (unfused/fused commit medians),
    ``join_vs_numpy`` (numpy proxy / FUSED engine — the ROADMAP trajectory
    metric, engine now ahead of numpy instead of 0.7-1.1x parity),
    ``fusion_join_vs_numpy_unfused`` (same ratio, fusion off — the before
    picture), ``bitwise_equal`` (fused vs unfused sink bytes, XLA path forced,
    the honesty key), and the recompile discipline counters
    (``fusion_jit_compiles``/``fusion_shape_buckets`` from a ragged
    commit-size sweep — pow2 bucketing must hold compiles at O(log) of the
    size spread). CPU-vs-CPU on any host; no device-only keys."""
    import gc

    import pathway_tpu as pw
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.internals import parse_graph as pg

    n_commits = 8
    per = 50_000 if SMOKE else 200_000
    build_n = 4_000
    n = per * n_commits
    rng = np.random.default_rng(17)
    uids = rng.integers(0, build_n, n)
    amounts = rng.integers(1, 10**6, n)
    qtys = rng.integers(1, 50, n)
    tss = rng.integers(0, 10**9, n)
    cats = rng.integers(0, 32, n)
    b_region = np.arange(build_n) % 7
    b_tier = (np.arange(build_n) * 13) % 1000

    # ONE chain definition consumed by both sides: `c` maps feature name ->
    # column (pw expression or numpy array), `W` is if_else/np.where. Values
    # are re-bounded with mods so 10 stages stay in int64 range either way.
    # ops are deliberately the memory-bound mix (mul/add/sub/xor/shift/where/
    # compare) a feature pipeline compiles to — the regime where one fused XLA
    # pass beats numpy's one-temporary-per-op; the single ``// 86400`` is the
    # realistic timestamp normalization (integer division is ALU-bound, fusion
    # neither helps nor hurts it)
    def _derive(c: dict, W) -> dict:
        return {
            "total": c["amount"] * c["qty"],
            "day": c["ts"] // 86400,
            "hod": (c["ts"] >> 7) & 31,
            "dow": (c["ts"] >> 12) & 7,
        }

    def _seed_feats(c: dict, W) -> dict:
        return {
            "net": W(c["total"] > 10**7, c["total"] - (c["total"] >> 4), c["total"]),
            "bucket": c["dow"] * 32 + c["cat"],
            "fa": c["total"] & 0xFFFFF,
            "fb": c["day"] * 24 + c["hod"],
            "fc": (c["total"] >> 3) & 0xFFFFF,
            "fd": c["hod"] * 3600 + c["dow"],
        }

    def _stage(c: dict, W) -> dict:
        return {
            "fa": (c["fb"] * 3 + c["fc"]) & 0xFFFFF,
            "fb": W(c["fa"] > c["fd"], c["fa"] - c["fd"], c["fd"] - c["fa"]),
            "fc": ((c["fc"] >> 3) ^ (c["fa"] * 7)) + c["bucket"],
            "fd": (c["fd"] + (c["fa"] & 0x3FF)) ^ (c["fb"] >> 5),
        }

    def _gate(c: dict):
        return (c["net"] > 500_000) & ((c["fa"] & 3) != 0)

    def _finalize(c: dict, W) -> dict:
        return {
            "final": (c["fa"] + c["fb"]) >> 3,
            "cap": W(c["fc"] > 10**8, 10**8, c["fc"]),
        }

    N_STAGES = 20

    def build_graph(rows: list, capture=None):
        pg.G.clear()
        t = pw.debug.table_from_rows(
            pw.schema_builder(
                {"uid": int, "amount": int, "qty": int, "ts": int, "cat": int}
            ),
            rows,
            is_stream=True,
        )
        dim = pw.debug.table_from_rows(
            pw.schema_builder({"uid2": int, "region": int, "tier": int}),
            [(int(i), int(r), int(ti)) for i, (r, ti) in enumerate(zip(b_region, b_tier))],
        )

        def cols_of(tbl, names):
            return {nm: getattr(tbl, nm) for nm in names}

        c0 = cols_of(t, ["uid", "cat", "amount", "qty", "ts"])
        t1 = t.select(t.uid, t.cat, **_derive(c0, pw.if_else))
        c1 = cols_of(t1, ["uid", "cat", "total", "day", "hod", "dow"])
        cur = t1.select(t1.uid, **_seed_feats(c1, pw.if_else))
        for _s in range(N_STAGES):
            c = cols_of(cur, ["uid", "net", "bucket", "fa", "fb", "fc", "fd"])
            cur = cur.select(
                cur.uid, cur.net, cur.bucket, **_stage(c, pw.if_else)
            )
        cg = cols_of(cur, ["net", "fa"])
        kept = cur.filter(_gate(cg))
        ck = cols_of(kept, ["fa", "fb", "fc"])
        t_fin = kept.select(kept.uid, kept.net, kept.bucket, **_finalize(ck, pw.if_else))
        j = t_fin.join(dim, t_fin.uid == dim.uid2).select(
            t_fin.final, t_fin.net, t_fin.cap, t_fin.bucket, dim.region, dim.tier
        )
        p1 = j.select(
            j.region, j.net, j.cap, j.bucket,
            boosted=j.final * (j.tier + 1),
        )
        p2 = p1.select(
            p1.region, p1.net,
            margin=p1.boosted - (p1.cap // 2 + p1.bucket),
        )
        out = p2.groupby(p2.region).reduce(
            p2.region,
            s=pw.reducers.sum(p2.net),
            m=pw.reducers.sum(p2.margin),
            cnt=pw.reducers.count(),
        )
        if capture is None:
            pw.io.subscribe(out, on_batch=lambda *a: None)
        else:
            def on_batch(keys, diffs, columns, time):
                capture.append(
                    (
                        keys.tobytes(),
                        diffs.tobytes(),
                        tuple(
                            (nm, np.asarray(col).tobytes())
                            if np.asarray(col).dtype != object
                            else (nm, repr(np.asarray(col).tolist()).encode())
                            for nm, col in sorted(columns.items())
                        ),
                    )
                )

            pw.io.subscribe(out, on_batch=on_batch)

    def make_rows(sizes: list) -> list:
        rows = []
        pos = 0
        for ci, sz in enumerate(sizes):
            for i in range(pos, pos + sz):
                rows.append(
                    (int(uids[i]), int(amounts[i]), int(qtys[i]), int(tss[i]),
                     int(cats[i]), 2 * ci, 1)
                )
            pos += sz
        return rows

    class ToggleRunner(GraphRunner):
        """Fusion on for even commits, off for odd — per-commit A/B over the
        SAME evaluator state (outputs are identical either way, so the state
        evolution is shared and adjacent commits see the same machine)."""

        def __init__(self, graph):
            super().__init__(graph)
            self.fused_t: list = []
            self.unfused_t: list = []

        def step(self) -> bool:
            fused = self._commit % 2 == 0
            saved = self._fusion_schedule
            if not fused:
                self._fusion_schedule = None
            t0 = time.perf_counter()
            try:
                return super().step()
            finally:
                dt = time.perf_counter() - t0
                self._fusion_schedule = saved
                (self.fused_t if fused else self.unfused_t).append(dt)

    def typical(values: list) -> float:
        values = sorted(values)
        mid = len(values) // 2
        return values[mid] if len(values) % 2 else (values[mid - 1] + values[mid]) / 2

    rows_even = make_rows([per] * n_commits)
    prev_fusion = os.environ.get("PATHWAY_FUSION")
    prev_profile = os.environ.get("PATHWAY_PROFILE")
    os.environ["PATHWAY_FUSION"] = "on"
    # per-operator profiling off for the measured arms (it costs the same in
    # both, but the A/B is about the dispatch path, not the metrics plane)
    os.environ["PATHWAY_PROFILE"] = "0"

    def ab_pass() -> tuple:
        build_graph(rows_even)
        runner = ToggleRunner(pg.G._current)
        gc.collect()
        gc.disable()
        try:
            runner.run(monitoring_level=pw.MonitoringLevel.NONE)
        finally:
            gc.enable()
        stats = [
            it.stats()
            for it in (runner._fusion_schedule or [])
            if hasattr(it, "stats")
        ]
        # drop per-arm warmup (the first fused commit pays every jit compile,
        # the first unfused commit pays first-touch state growth) and, in BOTH
        # arms symmetrically, the near-zero trailing drain steps the run loop
        # appends after sources finish — falling back to the raw samples if a
        # very fast host filters an arm empty
        def arm(samples: list) -> list:
            kept = [x for x in samples[1:] if x > 1e-4]
            return kept or samples[1:] or samples
        return typical(arm(runner.fused_t)), typical(arm(runner.unfused_t)), stats

    # -- numpy proxy: same per-commit computation, vectorized the obvious way
    def proxy_pass() -> float:
        group_sums: dict = {}
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            for ci in range(n_commits):
                sl = slice(ci * per, (ci + 1) * per)
                c = {
                    "uid": uids[sl], "amount": amounts[sl], "qty": qtys[sl],
                    "ts": tss[sl], "cat": cats[sl],
                }
                c.update(_derive(c, np.where))
                c.update(_seed_feats(c, np.where))
                for _s in range(N_STAGES):
                    c.update(_stage(c, np.where))
                keep = np.asarray(_gate(c))
                kept = {k: v[keep] for k, v in c.items()
                        if k in ("uid", "net", "bucket", "fa", "fb", "fc")}
                kept.update(_finalize(kept, np.where))
                reg = b_region[kept["uid"]]
                tier = b_tier[kept["uid"]]
                boosted = kept["final"] * (tier + 1)
                margin = boosted - (kept["cap"] // 2 + kept["bucket"])
                s = np.zeros(7, dtype=np.int64)
                m = np.zeros(7, dtype=np.int64)
                cnt = np.zeros(7, dtype=np.int64)
                np.add.at(s, reg, kept["net"])
                np.add.at(m, reg, margin)
                np.add.at(cnt, reg, 1)
                for g in range(7):
                    prev = group_sums.get(g, (0, 0, 0))
                    group_sums[g] = (
                        prev[0] + int(s[g]), prev[1] + int(m[g]), prev[2] + int(cnt[g]),
                    )
            return (time.perf_counter() - t0) / n_commits
        finally:
            gc.enable()

    # engine A/B passes and proxy passes INTERLEAVE so each (engine, proxy)
    # pair sees the same phase of this host's cpu-share throttle, and the
    # headline numbers are MEDIANS OF PER-PASS RATIOS: a ratio computed inside
    # one pass compares like with like even while absolute times drift ±30%
    # between passes (a proxy measured minutes after the engine would
    # effectively compare across different machines)
    pairs = []
    for _ in range(3):
        fused_i, unfused_i, stats_i = ab_pass()
        proxy_i = proxy_pass()
        pairs.append((fused_i, unfused_i, proxy_i, stats_i))
    speedup = sorted(u / f for f, u, _p, _s in pairs)[1]
    vs_numpy = sorted(p / f for f, _u, p, _s in pairs)[1]
    vs_numpy_unfused = sorted(p / u for _f, u, p, _s in pairs)[1]
    fused_s, unfused_s, numpy_s, chain_stats = sorted(pairs, key=lambda p: p[0])[1]

    # -- bitwise honesty: fused (XLA path FORCED down to small batches) vs
    # unfused sink bytes over a seeded multi-commit stream
    prev_jit_rows = os.environ.get("PATHWAY_FUSION_JIT_ROWS")
    os.environ["PATHWAY_FUSION_JIT_ROWS"] = "512"
    bit_rows = make_rows([4_000] * 4)
    captures: dict = {}
    for mode in ("on", "off"):
        os.environ["PATHWAY_FUSION"] = mode
        got: list = []
        build_graph(bit_rows, capture=got)
        GraphRunner(pg.G._current).run(monitoring_level=pw.MonitoringLevel.NONE)
        captures[mode] = got
    bitwise_equal = captures["on"] == captures["off"]

    # -- ragged commit sizes: pow2 bucketing must bound recompiles
    os.environ["PATHWAY_FUSION"] = "on"
    os.environ["PATHWAY_FUSION_JIT_ROWS"] = "1024"
    ragged_sizes = [3_000, 5_000, 9_000, 3_500, 6_500, 12_000, 4_100, 7_900]
    build_graph(make_rows(ragged_sizes))
    runner = GraphRunner(pg.G._current)
    runner.run(monitoring_level=pw.MonitoringLevel.NONE)
    ragged_stats = [
        it.stats() for it in (runner._fusion_schedule or []) if hasattr(it, "stats")
    ]
    if prev_jit_rows is None:
        os.environ.pop("PATHWAY_FUSION_JIT_ROWS", None)
    else:
        os.environ["PATHWAY_FUSION_JIT_ROWS"] = prev_jit_rows
    if prev_fusion is None:
        os.environ.pop("PATHWAY_FUSION", None)
    else:
        os.environ["PATHWAY_FUSION"] = prev_fusion
    if prev_profile is None:
        os.environ.pop("PATHWAY_PROFILE", None)
    else:
        os.environ["PATHWAY_PROFILE"] = prev_profile

    chain_ops = sum(len(s["nodes"]) for s in chain_stats)
    return {
        "fused_join_speedup": round(speedup, 3),
        "join_vs_numpy": round(vs_numpy, 3),
        "fusion_join_vs_numpy_unfused": round(vs_numpy_unfused, 3),
        "fusion_fused_commit_ms": round(fused_s * 1000, 2),
        "fusion_unfused_commit_ms": round(unfused_s * 1000, 2),
        "fusion_numpy_commit_ms": round(numpy_s * 1000, 2),
        "fusion_rows_per_commit": per,
        "fusion_ops_fused": chain_ops,
        "fusion_chains": len(chain_stats),
        "fusion_jit_compiles": sum(s["jit_compiles"] for s in chain_stats),
        "fusion_jit_verified": sum(s["jit_verified"] for s in chain_stats),
        "fusion_parity_rejects": sum(s["jit_disabled"] for s in chain_stats),
        "bitwise_equal": bool(bitwise_equal),
        "fusion_ragged_commits": len(ragged_sizes),
        "fusion_ragged_jit_compiles": sum(s["jit_compiles"] for s in ragged_stats),
        "fusion_ragged_shape_buckets": len(
            {b for s in ragged_stats for b in s["jit_buckets"]}
        ),
    }


def bench_scale() -> dict:
    """Honest at-scale run (BASELINE north star): ~10M x 384 vectors with REAL
    MiniLM embedding geometry through ingest -> index -> query.

    Corpus construction is reported in the keys, not hidden: ``scale_real_docs``
    texts are embedded with the production encoder; the remainder is
    manifold-sampled from those embeddings (real vector + gaussian noise at 25%
    of the measured mean nearest-neighbor distance, re-normalized) — the
    distribution ANN indexes face, unlike gaussian-cluster toys. Vectors are
    stored bfloat16 so the full corpus fits one v5e chip's HBM (10M x 384 x 2B
    = 7.7 GB); recall@10 is IVF measured against the exact dense search over
    the SAME corpus. At toy scale (PW_BENCH_SMOKE) the numbers only prove
    the code path."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import JaxSentenceEncoder
    from pathway_tpu.ops.knn import DenseKNNStore
    from pathway_tpu.ops.knn_ivf import IvfKnnStore

    n_total = 50_000 if SMOKE else 10_000_000
    n_real = 2_000 if SMOKE else 200_000
    n_queries = 256 if SMOKE else 1024
    dim = 384
    k = 10
    chunk = 10_000 if SMOKE else 100_000

    enc = JaxSentenceEncoder()
    rng = np.random.default_rng(7)
    topics = [f"topic{i}" for i in range(997)]

    def texts(start: int, count: int) -> list:
        return [
            f"document {start + i} about {topics[(start + i) % 997]} and "
            f"{topics[(start + i * 31) % 997]} with detail {(start + i) % 89}"
            for i in range(count)
        ]

    t0 = time.perf_counter()
    bs = 512 if SMOKE else 2048
    base_parts = []
    for s in range(0, n_real, bs):
        base_parts.append(enc.encode(texts(s, min(bs, n_real - s))))
    base = np.concatenate(base_parts).astype(np.float32)
    embed_s = time.perf_counter() - t0

    # noise scale from the real corpus's own geometry: mean NN distance on a
    # sample. The 25%-of-NN-distance budget is the DISPLACEMENT NORM, so the
    # per-coordinate std divides by sqrt(dim) — passing the norm directly as the
    # coordinate std (the r4 bug) inflates displacement by sqrt(384) ~ 19.6x and
    # turns the corpus into near-uniform sphere noise, which has no manifold
    # structure (nothing like real embeddings) and is the degenerate worst case
    # for any ANN index.
    sample = base[rng.choice(n_real, size=min(2048, n_real), replace=False)]
    d2 = (
        np.sum(sample * sample, axis=1)[:, None]
        + np.sum(sample * sample, axis=1)[None, :]
        - 2.0 * sample @ sample.T
    )
    np.fill_diagonal(d2, np.inf)
    nn_dist = float(np.mean(np.sqrt(np.maximum(d2.min(axis=1), 0.0))))
    sigma = 0.25 * nn_dist / float(np.sqrt(dim))

    def corpus_chunk(start: int, count: int) -> np.ndarray:
        take = rng.integers(0, n_real, count)
        out = base[take] + rng.normal(scale=sigma, size=(count, dim)).astype(np.float32)
        out /= np.maximum(np.linalg.norm(out, axis=1, keepdims=True), 1e-9)
        return out.astype(np.float32)

    qtexts = texts(10_000_000_000, n_queries)
    queries = np.concatenate(
        [enc.encode(qtexts[s : s + bs]) for s in range(0, n_queries, bs)]
    ).astype(np.float32)

    results: dict = {
        "scale_docs": n_total,
        "scale_real_docs": n_real,
        "scale_embed_docs_per_s": round(n_real / embed_s, 1),
        "scale_nn_dist": round(nn_dist, 4),
        "scale_noise_norm": round(0.25 * nn_dist, 4),
    }

    # corpus held on host in f16 (7.7 GB at full scale) so dense and IVF ingest
    # the IDENTICAL vectors without doubling device HBM
    corpus = np.empty((n_total, dim), dtype=np.float16)
    for s in range(0, n_total, chunk):
        corpus[s : s + chunk] = corpus_chunk(s, min(chunk, n_total - s))

    store = DenseKNNStore(dim, metric="l2sq", initial_capacity=n_total, dtype=jnp.bfloat16)
    t0 = time.perf_counter()
    for s in range(0, n_total, chunk):
        end = min(s + chunk, n_total)
        store.add_many(list(range(s, end)), corpus[s:end].astype(np.float32))
        store._flush()
    jax.block_until_ready(store._data)
    results["scale_ingest_docs_per_s"] = round(n_total / (time.perf_counter() - t0), 1)

    store.search_batch(queries, k)  # compile off the clock
    lat = []
    for _ in range(5):
        t1 = time.perf_counter()
        dense_scores, dense_idx, _ = store.search_batch(queries, k)
        lat.append(time.perf_counter() - t1)
    med = float(np.median(lat))
    results["scale_dense_qps"] = round(n_queries / med, 1)
    results["scale_dense_p50_batch_ms"] = round(med * 1000.0, 2)
    dense_keys = np.vectorize(lambda s_: store.key_of.get(int(s_), -1))(dense_idx)
    del store  # free HBM before the IVF copy

    # cluster count: pow2 with ~640 docs/cluster, so probe=8 touches < 1% of the
    # corpus at 10M (16384 clusters) — bytes gathered per query stay under the
    # per-query share of a full dense scan, which is where the qps win comes from
    n_clusters = 64
    while n_clusters * 640 < n_total and n_clusters < 16384:
        n_clusters *= 2
    ivf = IvfKnnStore(
        dim, metric="l2sq", initial_capacity=n_total,
        n_clusters=n_clusters, n_probe=8,
        dtype=jnp.bfloat16,
    )
    t0 = time.perf_counter()
    for s in range(0, n_total, chunk):
        end = min(s + chunk, n_total)
        ivf.add_many(list(range(s, end)), corpus[s:end].astype(np.float32))
        ivf._flush()  # per-chunk: ONE staged mega-flush would pad 10M rows to 16M f32
    ivf.search_batch(queries, k)  # train + compile off the clock
    results["scale_ivf_train_plus_ingest_s"] = round(time.perf_counter() - t0, 1)

    # auto-tune n_probe (faiss-style): smallest probe count reaching >=0.95
    # recall@10 on a query subsample, then measure qps at that operating point.
    # The chosen probe is REPORTED — recall and speed are both in the artifact.
    tune_n = min(128, n_queries)

    def _recall(idx_rows: np.ndarray, n_rows: int) -> float:
        keys = np.vectorize(lambda s_: ivf.key_of.get(int(s_), -1))(idx_rows)
        return float(
            np.mean(
                [len(set(keys[r]) & set(dense_keys[r])) / k for r in range(n_rows)]
            )
        )

    probe_cap = min(ivf.n_clusters, 256)
    probe = ivf.n_probe
    while True:
        ivf.n_probe = probe
        _s, tune_idx, _v = ivf.search_batch(queries[:tune_n], k)
        r = _recall(tune_idx, tune_n)
        if r >= 0.95 or probe >= probe_cap:
            break
        probe = min(probe * 2, probe_cap)
    results["scale_ivf_n_probe"] = probe

    lat = []
    for _ in range(5):
        t1 = time.perf_counter()
        _sc, ivf_idx, _v = ivf.search_batch(queries, k)
        lat.append(time.perf_counter() - t1)
    med = float(np.median(lat))
    results["scale_ivf_qps"] = round(n_queries / med, 1)
    results["scale_ivf_p50_batch_ms"] = round(med * 1000.0, 2)
    results["scale_ivf_recall_at_10_vs_exact"] = round(_recall(ivf_idx, n_queries), 4)
    return results


def bench_sharded() -> dict:
    """BASELINE #5: sharded index with all-gather top-k merge over the devices
    this process sees (the chips of one host; the 8 virtual CPU devices in the
    toy-scale mode). One device cannot shard: the section then says it was not
    run instead of printing numbers from somewhere else."""
    import jax
    from jax.sharding import Mesh

    from pathway_tpu.parallel.knn_sharded import ShardedKNNStore

    devices = np.array(jax.devices())
    if len(devices) < 2:
        return {"sharded": f"not run ({len(devices)} device)"}
    mesh = Mesh(devices, ("data",))
    rng = np.random.default_rng(0)
    n, dim, q, k = (20_000, 64, 64, 10) if SMOKE else (100_000, 64, 256, 10)
    data = rng.normal(size=(n, dim)).astype(np.float32)
    store = ShardedKNNStore(mesh, dim, metric="l2sq", initial_capacity=n)
    t0 = time.perf_counter()
    store.add_many(list(range(n)), data)
    store._flush()
    jax.block_until_ready(store._data)
    ingest_s = time.perf_counter() - t0
    queries = rng.normal(size=(q, dim)).astype(np.float32)
    store.search_batch(queries, k)  # warmup / compile
    lat = []
    for _ in range(5):
        t1 = time.perf_counter()
        store.search_batch(queries, k)  # returns host arrays: the fetch blocks
        lat.append(time.perf_counter() - t1)
    med = float(np.median(lat))
    return {
        "sharded_devices": len(devices),
        "sharded_qps": round(q / med, 1),
        "sharded_ingest_docs_per_s": round(n / ingest_s, 1),
    }


# -- rejoin: bounded-time recovery at any journal length ----------------------

_REJOIN_PROG = """
import json, os, signal, threading, time
import pathway_tpu as pw

tmp = os.environ["PW_BENCH_TMP"]
pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

class WordSchema(pw.Schema):
    word: str

t = pw.io.fs.read(
    os.path.join(tmp, "in"), format="csv", schema=WordSchema,
    mode="streaming", refresh_interval=0.02,
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

# assassin: the FIRST incarnation of rank 1 SIGKILLs itself when the bench
# drops the marker (time-controlled kills; commit-id gating would race the
# feed). The relaunched incarnation (bumped restart count) must not re-die.
if pid == 1 and int(os.environ.get("PATHWAY_RESTART_COUNT", "0")) == 0:
    marker = os.path.join(tmp, "kill-marker")
    def _assassin():
        while not os.path.exists(marker):
            time.sleep(0.05)
        os.kill(os.getpid(), signal.SIGKILL)
    threading.Thread(target=_assassin, daemon=True).start()

cfg = pw.persistence.Config(
    pw.persistence.Backend.filesystem(os.path.join(tmp, "store"))
)
pw.run(persistence_config=cfg, monitoring_level=pw.MonitoringLevel.NONE)
"""


def _journal_frames(path: str) -> int:
    """Count complete frames in one journal shard (magic line + json meta
    line, then 8-byte-BE-length-prefixed frames).

    Standalone copy of the PWTPUJ2 framing from persistence/engine.py —
    the orchestrator never imports pathway_tpu (the jax import chain is what
    the TPU-probe honesty machinery keeps OUT of this process), so it cannot
    call load_journal. The magic check keeps the copy honest: a journal
    format bump fails the bench loudly instead of silently counting garbage
    into the rejoin headline ratios."""
    import struct as _struct

    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return 0
    if not data.startswith(b"PWTPUJ2\n"):
        raise RuntimeError(
            f"journal {path!r} does not start with the PWTPUJ2 magic this "
            "parser understands — persistence/engine.py changed the on-disk "
            "format; update _journal_frames to match"
        )
    off = data.find(b"\n", data.find(b"\n") + 1) + 1
    if off <= 0:
        return 0
    n = 0
    while off + 8 <= len(data):
        (ln,) = _struct.unpack(">Q", data[off:off + 8])
        off += 8 + ln
        if off <= len(data):
            n += 1
    return n


_REJOIN_PORT_SALT = [0]  # distinct port block per run: no TIME_WAIT collisions


def _rejoin_run(tag: str, feed_s: float, ckpt_interval_s: float) -> dict:
    """One measured failover: spawn -n 2, feed the journal for ``feed_s``
    seconds (one tiny csv per source poll -> journal frames grow with feed
    time), SIGKILL rank 1 via the in-program assassin, and parse the
    survivor's rejoin duration + recovery mode from stderr."""
    import re
    import shutil
    import tempfile

    tmp = tempfile.mkdtemp(prefix=f"pw-bench-rejoin-{tag}-")
    out: dict = {}
    proc = None
    try:
        os.makedirs(os.path.join(tmp, "in"))
        prog = os.path.join(tmp, "prog.py")
        with open(prog, "w") as f:
            f.write(_REJOIN_PROG)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = (
            os.path.dirname(os.path.abspath(__file__))
            + os.pathsep + env.get("PYTHONPATH", "")
        )
        env["PW_BENCH_TMP"] = tmp
        env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
        env["PATHWAY_BARRIER_TIMEOUT_S"] = "120"
        env["PATHWAY_CHECKPOINT_INTERVAL_S"] = str(ckpt_interval_s)
        if not ckpt_interval_s:
            # pre-checkpoint baseline (the PR 3 path): no coordinated
            # checkpoints AND no undo ring — survivors full-replay too
            env["PATHWAY_UNDO_RING_DEPTH"] = "0"
        _REJOIN_PORT_SALT[0] += 1
        first_port = 27000 + (os.getpid() * 16 + _REJOIN_PORT_SALT[0] * 4) % 2600
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "pathway_tpu.cli", "spawn",
                "-n", "2", "--first-port", str(first_port),
                "--max-restarts", "1",
                sys.executable, prog,
            ],
            env=env, cwd=tmp, start_new_session=True,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
        )

        def _merged() -> dict:
            merged: dict = {}
            for p in range(2):
                path = os.path.join(tmp, f"out_{p}.json")
                try:
                    with open(path) as f:
                        for r in json.load(f):
                            merged[r["word"]] = r["total"]
                except (OSError, ValueError):
                    pass
            return merged

        def _await(expected: dict, deadline_s: float) -> None:
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    raise RuntimeError(f"spawn exited early rc={proc.returncode}")
                if _merged() == expected:
                    return
                time.sleep(0.1)
            raise RuntimeError(f"no convergence to {expected}, got {_merged()}")

        # feed: one file per source poll window grows the journal by roughly
        # one frame per poll — journal length is proportional to feed_s. Each
        # frame carries a realistic row batch (2-row frames would make replay
        # look artificially free next to the fixed relaunch cost)
        cats = 0
        i = 0
        deadline = time.monotonic() + feed_s
        while time.monotonic() < deadline:
            with open(os.path.join(tmp, "in", f"f{i:06d}.csv"), "w") as f:
                f.write("word\n" + "cat\n" * 60)
            cats += 60
            i += 1
            time.sleep(0.02)
        _await({"cat": cats}, 90)
        # journal length AT THE KILL (late data lands after recovery)
        frames = sum(
            _journal_frames(os.path.join(tmp, "store", f"process-{p}", "journal.bin"))
            for p in range(2)
        )
        with open(os.path.join(tmp, "kill-marker"), "w") as f:
            f.write("now")
        # post-failover convergence proves the heal, not just the relaunch
        time.sleep(1.0)
        with open(os.path.join(tmp, "in", "late.csv"), "w") as f:
            f.write("word\nowl\nowl\nowl\n")
        _await({"cat": cats, "owl": 3}, 150)
        # convergence proves the engine healed; give the supervisor a beat to
        # observe the epoch flip in the status files and log the rejoin line
        # this bench parses for its latency number
        time.sleep(2.0)
        out["frames"] = frames
    finally:
        err = ""
        if proc is not None:
            try:
                os.killpg(proc.pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
            try:
                _, err = proc.communicate(timeout=20)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                _, err = proc.communicate()
        shutil.rmtree(tmp, ignore_errors=True)
    # the SUPERVISOR's wall clock is the honest rejoin latency: relaunch of the
    # killed rank -> every status file reports the new epoch. It covers the
    # replacement's journal-proportional recovery, which is what this bench
    # sweeps (a survivor's own rejoin line would mix in the O(1) rewind rung)
    m = re.search(
        r"rank 1 rejoined the cluster at epoch 1 in ([0-9.]+)s", err or ""
    )
    if not m:
        raise RuntimeError(f"no supervisor rejoin line in stderr:\n{(err or '')[-2000:]}")
    out["rejoin_s"] = float(m.group(1))
    out["mode"] = (
        "checkpoint+tail replay"
        if "cold-starting from cluster checkpoint manifest" in (err or "")
        else "full journal replay"
    )
    return out


def bench_rejoin() -> dict:
    """Recovery-SLO headline: survivor rejoin latency vs journal length, with
    coordinated checkpoints OFF (pre-checkpoint path: full journal-union
    replay, grows linearly) and ON (checkpoint + bounded tail: flat). The
    acceptance claim is the ckpt ratio staying within 2x while the journal
    grows ~10x. CPU-only (localhost cluster) — honest on any host."""
    feed_1x, feed_10x = (2.0, 20.0) if SMOKE else (3.0, 30.0)
    res: dict = {}
    runs = {
        ("replay", "1x"): (feed_1x, 0.0),
        ("replay", "10x"): (feed_10x, 0.0),
        ("ckpt", "1x"): (feed_1x, 0.3),
        ("ckpt", "10x"): (feed_10x, 0.3),
    }
    for (kind, scale), (feed_s, interval) in runs.items():
        r = _rejoin_run(f"{kind}-{scale}", feed_s, interval)
        res[f"rejoin_{kind}_{scale}_s"] = round(r["rejoin_s"], 2)
        res[f"rejoin_{kind}_{scale}_frames"] = r["frames"]
        res[f"rejoin_{kind}_{scale}_mode"] = r["mode"]
    res["rejoin_journal_growth"] = round(
        res["rejoin_replay_10x_frames"] / max(1, res["rejoin_replay_1x_frames"]), 1
    )
    res["rejoin_replay_growth_ratio"] = round(
        res["rejoin_replay_10x_s"] / max(1e-9, res["rejoin_replay_1x_s"]), 2
    )
    res["rejoin_ckpt_flat_ratio"] = round(
        res["rejoin_ckpt_10x_s"] / max(1e-9, res["rejoin_ckpt_1x_s"]), 2
    )
    # the acceptance headline: checkpointed rejoin stays flat (within 2x)
    # while the journal grows ~10x
    res["rejoin_ckpt_flat"] = bool(res["rejoin_ckpt_flat_ratio"] <= 2.0)
    return res


_ELASTIC_PROG = """
import json, os
import pathway_tpu as pw

tmp = os.environ["PW_BENCH_TMP"]
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


def _elastic_cycle(
    prog_text: str,
    prefix: str,
    *,
    feed_total_s: float,
    rows_per_file: int,
    port_base: int,
    scale_plan: list,
) -> dict:
    """One spawn n=2 -> 4 -> 2 scale cycle under live ingestion for
    ``prog_text``; returns ``{prefix}_*`` keys (pause p50/max, rows handed
    off/s, throughput dip, exactness + joiner-catch-up honesty keys)."""
    import re
    import shutil
    import statistics
    import tempfile

    tmp = tempfile.mkdtemp(prefix=f"pw-bench-{prefix}-")
    res: dict = {}
    proc = None
    try:
        os.makedirs(os.path.join(tmp, "in"))
        prog = os.path.join(tmp, "prog.py")
        with open(prog, "w") as f:
            f.write(prog_text)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = (
            os.path.dirname(os.path.abspath(__file__))
            + os.pathsep + env.get("PYTHONPATH", "")
        )
        env["PW_BENCH_TMP"] = tmp
        env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
        env["PATHWAY_BARRIER_TIMEOUT_S"] = "120"
        env["PATHWAY_MEMBERSHIP_DEADLINE_S"] = "90"
        env["PATHWAY_SCALE_PLAN"] = json.dumps(scale_plan)
        _REJOIN_PORT_SALT[0] += 1
        first_port = port_base + (os.getpid() * 16 + _REJOIN_PORT_SALT[0] * 4) % 2600
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "pathway_tpu.cli", "spawn",
                "-n", "2", "--first-port", str(first_port),
                "--max-restarts", "2",
                sys.executable, prog,
            ],
            env=env, cwd=tmp, start_new_session=True,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
        )

        def _total() -> int:
            total = 0
            for p in range(4):
                try:
                    with open(os.path.join(tmp, f"out_{p}.json")) as f:
                        total += sum(r["total"] for r in json.load(f))
                except (OSError, ValueError):
                    pass
            return total

        # steady feed; sample delivered-output totals on a fixed clock so the
        # transition windows show up as rate dips in the timeline
        fed = 0
        i = 0
        samples: list = []  # (t, delivered_total)
        deadline = time.monotonic() + feed_total_s
        t0 = time.monotonic()
        while time.monotonic() < deadline:
            with open(os.path.join(tmp, "in", f"f{i:06d}.csv"), "w") as f:
                f.write("word\n" + f"w{i % 23}\n" * rows_per_file)
            fed += rows_per_file
            i += 1
            samples.append((time.monotonic() - t0, _total()))
            time.sleep(0.05)
        # convergence: everything fed is delivered exactly once
        conv_deadline = time.monotonic() + 60
        while time.monotonic() < conv_deadline:
            if proc.poll() is not None:
                raise RuntimeError(f"spawn exited early rc={proc.returncode}")
            if _total() == fed:
                break
            time.sleep(0.1)
        if _total() != fed:
            raise RuntimeError(f"no convergence: fed {fed}, got {_total()}")
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        _out, err = proc.communicate(timeout=30)
        proc = None
        # per-rank transition durations ("reshard pause": the commit loop's
        # time inside MEMBERSHIP_CHANGE) + rows handed off
        pauses = [
            float(m)
            for m in re.findall(
                r"membership transition to n=\d+ complete .* in ([0-9.]+)s", err
            )
        ]
        drains = [
            float(m)
            for m in re.findall(r"drained for scale-down .* in ([0-9.]+)s", err)
        ]
        handed = [
            int(m) for m in re.findall(r"(\d+) row\(s\) handed off", err)
        ]
        tails = [
            int(m)
            for m in re.findall(
                r"membership manifest \+ handoff fragments at commit \d+ "
                r"\(\+(\d+) journal tail frame\(s\)\)",
                err,
            )
        ]
        if not pauses:
            raise RuntimeError(f"no completed transitions in stderr:\n{err[-2000:]}")
        all_pauses = pauses + drains
        res[f"{prefix}_reshard_pause_p50_s"] = round(
            statistics.median(all_pauses), 3
        )
        res[f"{prefix}_reshard_pause_max_s"] = round(max(all_pauses), 3)
        res[f"{prefix}_rows_handed_off"] = int(sum(handed))
        res[f"{prefix}_rows_handed_off_per_s"] = round(
            sum(handed) / max(1e-9, sum(all_pauses)), 1
        )
        # throughput dip: delivered-rows/s in the worst 2 s window vs the
        # overall steady rate (the transitions are the stalls)
        rates: list = []
        for a in range(len(samples)):
            b = a
            while b + 1 < len(samples) and samples[b + 1][0] - samples[a][0] < 2.0:
                b += 1
            if b > a:
                dt = samples[b][0] - samples[a][0]
                rates.append((samples[b][1] - samples[a][1]) / dt)
        steady = statistics.median(rates) if rates else 0.0
        worst = min(rates) if rates else 0.0
        res[f"{prefix}_throughput_dip_pct"] = (
            round(100.0 * (1.0 - worst / steady), 1) if steady > 0 else None
        )
        res[f"{prefix}_ingest_rows_per_s"] = round(steady, 1)
        # honesty keys: both transitions completed, joiners caught up from
        # manifest + fragments with a near-empty tail, and never a restart
        res[f"{prefix}_transitions_complete"] = (
            "membership change complete: cluster is n=4" in err
            and "membership change complete: cluster is n=2" in err
        )
        res[f"{prefix}_join_tail_frames_max"] = max(tails) if tails else None
        res[f"{prefix}_join_no_replay"] = bool(
            tails
            and max(tails) <= 2
            and err.count("no journal replay") >= 2
            and "restarting the cluster" not in err
        )
        res[f"{prefix}_exact"] = _total() == fed
        return res
    finally:
        if proc is not None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.communicate()
        shutil.rmtree(tmp, ignore_errors=True)


_ELASTIC_JOINDEDUP_PROG = """
import json, os
import pathway_tpu as pw

tmp = os.environ["PW_BENCH_TMP"]
pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

class WordSchema(pw.Schema):
    word: str

t = pw.io.fs.read(
    os.path.join(tmp, "in"), format="csv", schema=WordSchema, mode="streaming"
)
counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())
joined = t.join(counts, t.word == counts.word).select(t.word, total=counts.total)
best = joined.deduplicate(
    value=joined.total, instance=joined.word, acceptor=lambda new, old: new >= old
)
final = best.with_id_from(best.word)

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

pw.io.subscribe(final, on_change)
cfg = pw.persistence.Config(
    pw.persistence.Backend.filesystem(os.path.join(tmp, "store"))
)
pw.run(persistence_config=cfg, monitoring_level=pw.MonitoringLevel.NONE)
"""


def _bench_handoff_rss_sweep() -> dict:
    """Peak-handoff-memory honesty key: partition the same join+dedup graph's
    state at 1x / 2x / 4x size through BOTH transports and report the peak
    transport allocation (tracemalloc, donor side, state excluded via
    reset_peak). The chunked schedule must stay flat (<= 1.5x across the 4x
    sweep) while the gather baseline grows ~linearly with state — in-process
    and CPU-only, honest on any host."""
    import pickle as _pickle
    import tracemalloc

    import pathway_tpu as pw
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.internals import parse_graph as pg
    from pathway_tpu.parallel.membership import (
        build_fragment_chunks,
        build_fragments,
        compute_reshard_plan,
    )

    # production-shaped rows: a few-hundred-byte payload per row, so state is
    # payload-dominated (the regime the chunked transport bounds); the O(rows)
    # int owner metadata the exporters scan is second-order and amortizes
    # under the chunk budget
    # (the budget scales with the profile: state must exceed several chunks
    # at the smallest sweep point or the sweep never leaves the 1-chunk
    # regime and measures nothing)
    chunk_bytes = 1 << 18 if SMOKE else 1 << 20
    payload = "x" * 400

    def runner_with_rows(n_rows: int) -> GraphRunner:
        pg.G.clear()
        left = pw.debug.table_from_rows(
            pw.schema_builder({"k": int, "a": int, "p": str}),
            [(i, i * 3, payload + str(i)) for i in range(n_rows)],
        )
        right = pw.debug.table_from_rows(
            pw.schema_builder({"k": int, "b": int}),
            [(i, i * 7) for i in range(n_rows)],
        )
        joined = left.join(right, left.k == right.k).select(
            left.a, left.p, right.b
        )
        best = joined.deduplicate(
            value=joined.b, instance=joined.a, acceptor=lambda new, old: new >= old
        )
        pw.io.subscribe(best, lambda *a, **kw: None)
        runner = GraphRunner(pg.G._current)
        runner.lint_exempt = True
        runner.run(monitoring_level=pw.MonitoringLevel.NONE, max_commits=3)
        return runner

    base_rows = 1500 if SMOKE else 4000
    sizes = [base_rows, base_rows * 2, base_rows * 4]
    chunk_peaks: list = []
    gather_peaks: list = []
    for n_rows in sizes:
        runner = runner_with_rows(n_rows)
        for node in runner._nodes:
            ev = runner.evaluators[node.id]
            ev._cluster_policies = tuple(
                ev.cluster_input_policy(i) for i in range(len(node.inputs))
            )
        plan = compute_reshard_plan(runner)
        if not plan.ok:
            raise RuntimeError(f"reshard plan refused: {plan.refusals}")
        tracemalloc.start()
        try:
            # chunked: the donor only ever holds open chunks + one pickle
            tracemalloc.reset_peak()
            chunk_iter, _stats = build_fragment_chunks(
                runner, plan, 2, commit=3, generation=1, chunk_bytes=chunk_bytes
            )
            for _dest, chunk in chunk_iter:
                _pickle.dumps(chunk, protocol=_pickle.HIGHEST_PROTOCOL)
            chunk_peaks.append(tracemalloc.get_traced_memory()[1])
            # gather baseline: every destination's full fragment materializes
            # at once before any write
            tracemalloc.reset_peak()
            frags, _stats = build_fragments(runner, plan, 2, commit=3, generation=1)
            for _dest, frag in sorted(frags.items()):
                _pickle.dumps(frag, protocol=_pickle.HIGHEST_PROTOCOL)
            del frags
            gather_peaks.append(tracemalloc.get_traced_memory()[1])
        finally:
            tracemalloc.stop()
        pg.G.clear()
    return {
        "elastic_handoff_state_rows": sizes,
        "elastic_handoff_chunk_bytes": chunk_bytes,
        "elastic_handoff_chunked_peak_mb": [
            round(p / 1e6, 2) for p in chunk_peaks
        ],
        "elastic_handoff_gather_peak_mb": [
            round(p / 1e6, 2) for p in gather_peaks
        ],
        "elastic_chunk_peak_growth_x": round(
            chunk_peaks[-1] / max(1, chunk_peaks[0]), 2
        ),
        "elastic_gather_peak_growth_x": round(
            gather_peaks[-1] / max(1, gather_peaks[0]), 2
        ),
        # the honesty key: chunked flat across a 4x state sweep, gather is not
        "elastic_chunk_peak_flat": bool(
            chunk_peaks[-1] <= 1.5 * chunk_peaks[0]
            and gather_peaks[-1] >= 2.0 * gather_peaks[0]
        ),
    }


def bench_elastic() -> dict:
    """Elastic-membership headline: n=2 -> 4 -> 2 scale cycles under live
    ingestion, for a groupby pipeline AND a join+dedup-heavy pipeline (the
    graphs the preflight refused before universal reshardability). Measures
    the reshard pause (per-rank transition duration, the window the commit
    loop spends inside MEMBERSHIP_CHANGE), the ingest throughput dip around
    the transitions, rows handed off per second, and two honesty families:
    every joiner catches up from the membership manifest + handoff fragments
    with a near-empty journal tail (never a full-history replay), and the
    chunked transport's peak handoff memory stays FLAT across a 4x
    state-size sweep while the gather baseline grows ~linearly. The cluster
    ranks run on the CPU platform (localhost cluster)."""
    res = _elastic_cycle(
        _ELASTIC_PROG,
        "elastic",
        feed_total_s=10.0 if SMOKE else 18.0,
        rows_per_file=40 if SMOKE else 80,
        port_base=29200,
        scale_plan=[{"after_commit": 8, "n": 4}, {"after_commit": 30, "n": 2}],
    )
    res.update(
        _elastic_cycle(
            _ELASTIC_JOINDEDUP_PROG,
            "elastic_joindedup",
            feed_total_s=8.0 if SMOKE else 12.0,
            rows_per_file=30 if SMOKE else 60,
            port_base=30100,
            scale_plan=[
                {"after_commit": 8, "n": 4},
                {"after_commit": 24, "n": 2},
            ],
        )
    )
    res.update(_bench_handoff_rss_sweep())
    return res


def bench_autoscale() -> dict:
    """Closed-loop autoscaler headline: a ramping synthetic load at n=2 must
    scale the cluster to 4 and back to 2 with NO operator input. The load
    profile is a chaos-plan ``load_spike`` (deterministic; the same op the
    tests replay), fed as CSV files whose rate follows ``Chaos.load_rate``.
    Reports time-to-scale (spike start -> cluster stable at n=4, observed
    through the supervisor control endpoint's ``status`` command), the shed
    rate the controller saw during the scale window, the reshard pauses, and
    a NO-FLAP honesty key: exactly one transition per direction, flap lock
    never engaged, final delivered counts exact. CPU-only (localhost
    cluster) — honest on any host."""
    import re
    import shutil
    import socket as socket_mod
    import tempfile

    from pathway_tpu.internals.chaos import Chaos

    base_rate = 80.0 if SMOKE else 140.0
    spike_rate = 650.0 if SMOKE else 1100.0
    spike_at_s, spike_len_s = 4.0, 9.0
    feed_total_s = 20.0
    rows_per_worker = 180.0 if SMOKE else 300.0
    load = Chaos(0, {"load": {
        "op": "load_spike", "at_s": spike_at_s, "duration_s": spike_len_s,
        "low": base_rate, "high": spike_rate,
    }})
    tmp = tempfile.mkdtemp(prefix="pw-bench-autoscale-")
    res: dict = {}
    proc = None
    try:
        os.makedirs(os.path.join(tmp, "in"))
        prog = os.path.join(tmp, "prog.py")
        with open(prog, "w") as f:
            f.write(_ELASTIC_PROG)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = (
            os.path.dirname(os.path.abspath(__file__))
            + os.pathsep + env.get("PYTHONPATH", "")
        )
        env["PW_BENCH_TMP"] = tmp
        env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
        env["PATHWAY_BARRIER_TIMEOUT_S"] = "120"
        env["PATHWAY_MEMBERSHIP_DEADLINE_S"] = "90"
        env["PATHWAY_AUTOSCALE"] = "on"
        env["PATHWAY_AUTOSCALE_MIN"] = "2"
        env["PATHWAY_AUTOSCALE_MAX"] = "4"
        env["PATHWAY_AUTOSCALE_ROWS_PER_WORKER"] = str(rows_per_worker)
        env["PATHWAY_AUTOSCALE_SAMPLE_S"] = "0.5"
        env["PATHWAY_AUTOSCALE_UP_SAMPLES"] = "2"
        env["PATHWAY_AUTOSCALE_DOWN_SAMPLES"] = "4"
        env["PATHWAY_AUTOSCALE_UP_COOLDOWN_S"] = "2"
        env["PATHWAY_AUTOSCALE_DOWN_COOLDOWN_S"] = "4"
        env["PATHWAY_AUTOSCALE_FLAP_WINDOW_S"] = "60"
        env["PATHWAY_AUTOSCALE_FLAP_REVERSALS"] = "3"
        _REJOIN_PORT_SALT[0] += 1
        first_port = 23400 + (os.getpid() * 16 + _REJOIN_PORT_SALT[0] * 4) % 2600
        control_port = first_port + 1299
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "pathway_tpu.cli", "spawn",
                "-n", "2", "--first-port", str(first_port),
                "--max-restarts", "2",
                "--control-port", str(control_port),
                sys.executable, prog,
            ],
            env=env, cwd=tmp, start_new_session=True,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
        )

        def _control_status() -> dict:
            try:
                with socket_mod.create_connection(
                    ("127.0.0.1", control_port), timeout=2.0
                ) as conn:
                    conn.sendall(b"status\n")
                    buf = b""
                    while not buf.endswith(b"\n"):
                        chunk = conn.recv(4096)
                        if not chunk:
                            break
                        buf += chunk
                return json.loads(buf.decode())
            except (OSError, ValueError):
                return {}

        def _total() -> int:
            total = 0
            for p in range(4):
                try:
                    with open(os.path.join(tmp, f"out_{p}.json")) as f:
                        total += sum(r["total"] for r in json.load(f))
                except (OSError, ValueError):
                    pass
            return total

        # feed at the chaos-plan load profile; observe topology through the
        # control endpoint's status command on a fixed clock
        fed = 0
        i = 0
        t0 = time.monotonic()
        seen_n: list = []  # (elapsed, n, max_shed_rate)
        carry = 0.0
        last_tick = 0.0
        while True:
            elapsed = time.monotonic() - t0
            if elapsed >= feed_total_s:
                break
            rate = load.load_rate(elapsed)
            if rate is None:  # 0.0 is a legitimate idle rate, not "no profile"
                rate = base_rate
            carry += rate * max(0.0, elapsed - last_tick)
            last_tick = elapsed
            rows = int(carry)
            if rows > 0:
                carry -= rows
                with open(os.path.join(tmp, "in", f"f{i:06d}.csv"), "w") as f:
                    f.write("word\n" + f"w{i % 23}\n" * rows)
                fed += rows
                i += 1
            status = _control_status()
            if status:
                ctrl = status.get("autoscaler") or {}
                signals = ctrl.get("signals") or {}
                seen_n.append((
                    elapsed,
                    int(status.get("n") or 0),
                    float(signals.get("shed_rate") or 0.0),
                ))
            time.sleep(0.1)
        # convergence: everything fed is delivered exactly once (and the
        # cluster is back at n=2 — the scale-in under the fading load)
        conv_deadline = time.monotonic() + 90
        back_to_2 = None
        while time.monotonic() < conv_deadline:
            if proc.poll() is not None:
                raise RuntimeError(f"spawn exited early rc={proc.returncode}")
            status = _control_status()
            n_now = int(status.get("n") or 0) if status else 0
            if back_to_2 is None and n_now == 2 and any(
                n == 4 for _t, n, _s in seen_n
            ):
                back_to_2 = time.monotonic() - t0
            if _total() == fed and n_now == 2 and not status.get(
                "transition_in_flight"
            ):
                break
            time.sleep(0.2)
        if _total() != fed:
            raise RuntimeError(f"no convergence: fed {fed}, got {_total()}")
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        _out, err = proc.communicate(timeout=30)
        proc = None
        first_at_4 = next((t for t, n, _s in seen_n if n >= 4), None)
        res["autoscale_time_to_scale_s"] = (
            round(first_at_4 - spike_at_s, 2) if first_at_4 is not None else None
        )
        res["autoscale_scale_in_at_s"] = (
            round(back_to_2, 2) if back_to_2 is not None else None
        )
        res["autoscale_shed_rate_window_max"] = round(
            max((s for _t, _n, s in seen_n), default=0.0), 2
        )
        pauses = [
            float(m)
            for m in re.findall(
                r"membership transition to n=\d+ complete .* in ([0-9.]+)s", err
            )
        ]
        res["autoscale_reshard_pause_max_s"] = (
            round(max(pauses), 3) if pauses else None
        )
        res["autoscale_ingest_rows_per_s"] = round(fed / feed_total_s, 1)
        requested = re.findall(r"membership change requested: n=\d+ -> n=(\d+)", err)
        # honesty keys: scaled out AND back with no operator input, exactly
        # one transition per direction, the flap lock never engaged, counts
        # exact — an autoscaler that oscillates or loses rows fails loudly
        res["autoscale_transitions"] = len(requested)
        res["autoscale_no_flap"] = bool(
            len(requested) == 2
            and "FLAP-LOCKED" not in err
            and "membership change complete: cluster is n=4" in err
            and "membership change complete: cluster is n=2" in err
            and "restarting the cluster" not in err
        )
        res["autoscale_exact"] = _total() == fed
        return res
    finally:
        if proc is not None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.communicate()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_replicas() -> dict:
    """Read-replica serving fleet: bootstrap cost, query scaling, feed tax.

    All in-process (followers + HTTP servers + the client router), CPU-only —
    honest on any host. Reports:

    - bootstrap wall time + rows/s for a bounded-fragment cold start;
    - the BITWISE honesty key: the replica's results at the same commit id
      must equal the primary's exactly (keys AND float scores) — a replica
      that drifts is worse than no replica;
    - router queries/s at 1 vs 2 replicas (the independent-scaling claim);
    - kill-invisibility: one replica server closed mid-load, zero client
      errors (every query answered by the survivor or the primary);
    - the feed tax: primary ingest commits/s with frame recording on vs off.
    """
    import shutil
    import tempfile
    import threading

    import numpy as np

    from pathway_tpu.ops.knn import BruteForceKnnIndex
    from pathway_tpu.parallel.replica import (
        ReplicaFollower,
        ReplicaRouter,
        ReplicaServer,
        default_index_factory,
    )
    from pathway_tpu.persistence.replica_feed import ReplicaFeed

    dim = 64 if SMOKE else 128
    n_rows = 4_000 if SMOKE else 40_000
    n_queries = 64
    load_s = 1.5 if SMOKE else 3.0
    rng = np.random.default_rng(7)
    rows = rng.normal(size=(n_rows, dim)).astype(np.float32)
    queries = rng.normal(size=(n_queries, dim)).astype(np.float32)
    keys = [f"d{i}" for i in range(n_rows)]

    primary = BruteForceKnnIndex(dim)
    primary.add_many(keys, rows)
    primary.search_many(list(queries[:1]), [1])  # warm the kernel

    tmp = tempfile.mkdtemp(prefix="pw-bench-replicas-")
    res: dict = {}
    servers = []
    try:
        feed = ReplicaFeed(os.path.join(tmp, "feed"))
        t0 = time.perf_counter()
        feed.export_bootstrap(1, primary, rows_per_fragment=4096)
        res["replicas_export_s"] = round(time.perf_counter() - t0, 3)

        followers = []
        t0 = time.perf_counter()
        for rid in range(2):
            f = ReplicaFollower(
                feed, default_index_factory, replica_id=rid, poll_s=0.02
            )
            f.bootstrap()
            followers.append(f)
        boot_s = time.perf_counter() - t0
        res["replicas_bootstrap_s"] = round(boot_s / 2, 3)
        res["replicas_bootstrap_rows_per_s"] = round(2 * n_rows / boot_s, 1)

        # tail catch-up: 20 frames of 32 rows each
        extra = rng.normal(size=(20 * 32, dim)).astype(np.float32)
        for c in range(20):
            feed.record_commit(
                2 + c,
                [f"t{c}_{j}" for j in range(32)],
                extra[c * 32 : (c + 1) * 32],
            )
        primary.add_many(
            [f"t{c}_{j}" for c in range(20) for j in range(32)], extra
        )
        t0 = time.perf_counter()
        for f in followers:
            f.poll_frames()
        res["replicas_catchup_frames_per_s"] = round(
            2 * 20 / (time.perf_counter() - t0), 1
        )

        # -- BITWISE honesty key: replica == primary at the same commit -----
        k = 10
        want = primary.search_many(list(queries), [k] * n_queries)
        bitwise = True
        for f in followers:
            commit, got = f.search_many(list(queries), [k] * n_queries)
            bitwise = bitwise and commit == 21 and got == want
        res["replicas_bitwise_equal"] = bool(bitwise)

        servers = [ReplicaServer(f) for f in followers]
        endpoints = [f"http://127.0.0.1:{s.port}" for s in servers]

        def primary_serve(vectors, kk, filters):
            return 21, primary.search_many(
                list(vectors), [kk] * len(vectors), filters
            )

        payload = [[float(x) for x in queries[0]]]

        def hammer(router, duration_s, errors):
            done = time.perf_counter() + duration_s
            count = 0
            while time.perf_counter() < done:
                try:
                    router.retrieve(payload, k)
                    count += 1
                except Exception:
                    errors.append(1)
            return count

        def measure_qps(eps) -> float:
            router = ReplicaRouter(eps, primary=primary_serve, timeout_s=10.0)
            counts = []
            errors: list = []
            threads = [
                threading.Thread(
                    target=lambda: counts.append(
                        hammer(router, load_s, errors)
                    )
                )
                for _ in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors
            return sum(counts) / load_s

        qps_1 = measure_qps(endpoints[:1])
        qps_2 = measure_qps(endpoints)
        res["replicas_qps_n1"] = round(qps_1, 1)
        res["replicas_qps_n2"] = round(qps_2, 1)
        res["replicas_qps_scaling_x"] = round(qps_2 / max(qps_1, 1e-9), 2)

        # -- kill-invisibility under load -----------------------------------
        router = ReplicaRouter(
            endpoints, primary=primary_serve, timeout_s=10.0
        )
        errors: list = []
        counts: list = []
        threads = [
            threading.Thread(
                target=lambda: counts.append(hammer(router, load_s, errors))
            )
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        time.sleep(load_s / 3)
        servers[0].close()  # half the fleet vanishes mid-load
        for t in threads:
            t.join()
        res["replicas_kill_queries"] = int(sum(counts))
        res["replicas_kill_client_errors"] = len(errors)  # honesty: must be 0
        res["replicas_kill_failovers"] = int(router.stats["failovers"])

        # -- the feed tax on primary ingest ---------------------------------
        batch = rng.normal(size=(256, dim)).astype(np.float32)
        bkeys = [f"f{j}" for j in range(256)]

        def ingest(commits: int, with_feed: bool) -> float:
            t0 = time.perf_counter()
            for c in range(commits):
                primary.add_many(bkeys, batch)  # upserts: steady-state size
                if with_feed:
                    feed.record_commit(100 + c, bkeys, batch)
            return commits / (time.perf_counter() - t0)

        commits = 20 if SMOKE else 60
        ingest(3, False)  # warm
        off = ingest(commits, False)
        on = ingest(commits, True)
        res["replicas_ingest_commits_per_s_feed_off"] = round(off, 1)
        res["replicas_ingest_commits_per_s_feed_on"] = round(on, 1)
        res["replicas_feed_tax_frac"] = round(max(0.0, 1.0 - on / off), 3)
        return res
    finally:
        for s in servers:
            s.close()
        shutil.rmtree(tmp, ignore_errors=True)


# -- section registry ---------------------------------------------------------
#
# One registration per section derives the runner table AND both deadline
# tables — a section cannot be added without deadlines (a missing entry used
# to KeyError the orchestrator at run time).

SUB_BENCHES: dict = {}
# per-sub-bench wall deadlines (seconds): generous at full scale, tight at toy scale
_DEADLINES_FULL: dict = {}
_DEADLINES_SMALL: dict = {}


def _register_section(name: str, fn, *, full: int = 600, small: int = 300) -> None:
    SUB_BENCHES[name] = fn
    _DEADLINES_FULL[name] = full
    _DEADLINES_SMALL[name] = small


_register_section("knn", lambda: bench_knn(), full=600, small=300)
_register_section("ivfscale", lambda: bench_ivf_scale(), full=900, small=900)
_register_section("quant", lambda: bench_quant(), full=600, small=300)
_register_section("embedder", lambda: bench_embedder(), full=420, small=240)
_register_section("embedpipe", lambda: bench_embedpipe(), full=600, small=420)
_register_section("encsvc", lambda: bench_encsvc(), full=600, small=420)
_register_section("window", lambda: bench_streaming_window(), full=300, small=300)
_register_section("engine", lambda: bench_engine(), full=600, small=600)
_register_section("fusion", lambda: bench_fusion(), full=600, small=420)
_register_section("telemetry", lambda: bench_telemetry(), full=420, small=420)
_register_section("vectorstore", lambda: bench_vector_store(), full=600, small=300)
_register_section("vsfloor", lambda: bench_vs_floor(), full=300, small=300)
_register_section("sharded", lambda: bench_sharded(), full=660, small=660)
_register_section("scale", lambda: bench_scale(), full=1500, small=420)
_register_section("rejoin", lambda: bench_rejoin(), full=420, small=300)
_register_section("elastic", lambda: bench_elastic(), full=480, small=360)
_register_section("autoscale", lambda: bench_autoscale(), full=360, small=300)
_register_section("replicas", lambda: bench_replicas(), full=360, small=240)

# a section child's exit code when JAX found no accelerator: the orchestrator
# stops the round there instead of failing every remaining section one by one
_NO_ACCELERATOR_RC = 4


def _device_or_exit() -> dict:
    """The device this section's numbers are taken on, as JAX reports it. The
    CPU platform is refused unless the toy-scale correctness mode was asked for
    explicitly (``PW_BENCH_SMOKE=1 JAX_PLATFORMS=cpu``); there is no fallback."""
    import jax

    dev = jax.devices()[0]
    explicit_cpu = SMOKE and os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if dev.platform == "cpu" and not explicit_cpu:
        print(
            "bench.py: JAX found no accelerator (platform 'cpu'); a measurement "
            "does not fall back to the CPU. For the toy-scale correctness mode "
            "ask for it explicitly: PW_BENCH_SMOKE=1 JAX_PLATFORMS=cpu",
            file=sys.stderr,
        )
        sys.exit(_NO_ACCELERATOR_RC)
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def _run_with_deadline(cmd: list, env: dict, deadline: float) -> tuple[int, str]:
    """Run one section child (stderr passes through, so a failure shows its
    traceback); returns (exit code, stdout), with exit code -1 for a child
    stopped at its deadline."""
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, text=True, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    try:
        out, _ = proc.communicate(timeout=deadline)
        return proc.returncode, out or ""
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            proc.wait(timeout=15.0)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        return -1, ""


def _final_line(results: dict, device: "dict | None") -> str:
    return json.dumps(
        {
            "metric": "knn_query_qps_1Mx128",
            "value": results.get("knn_qps", 0.0),
            "unit": "queries/s",
            "vs_baseline": results.get("knn_vs_cpu", 0.0),
            "baseline": "numpy BLAS matmul+argpartition (reference rust-kernel proxy)",
            "device": device,
            **{k: v for k, v in results.items() if k not in ("knn_qps", "knn_vs_cpu")},
        }
    )


def _child_main(name: str) -> None:
    """One section in this process: refuse a missing accelerator, run, print one
    JSON line that carries the device. An exception propagates (traceback on
    stderr, non-zero exit) — it is never turned into a result key."""
    device = _device_or_exit()
    out = SUB_BENCHES[name]()
    print(json.dumps({**out, "_device": device}), flush=True)


def main() -> int:
    results: dict = {}
    failed: list = []
    device: "dict | None" = None
    deadlines = _DEADLINES_SMALL if SMOKE else _DEADLINES_FULL
    env = dict(os.environ)
    if SMOKE:
        # the full jit pre-warm bucket matrix is a device start-up cost — cap it
        # so toy-scale sections don't burn their deadline compiling buckets they
        # never dispatch
        env.setdefault("PATHWAY_ENCSVC_PREWARM_MAX_BATCH", "16")
        if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
            # the virtual 8-device CPU mesh the tests use, so the sharded
            # section has something to shard over (no effect on an accelerator)
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
            ).strip()
    me = os.path.abspath(__file__)
    for name in SUB_BENCHES:
        t0 = time.perf_counter()
        rc, out = _run_with_deadline(
            [sys.executable, me, "--sub", name], env, deadlines[name]
        )
        if rc == _NO_ACCELERATOR_RC:
            print(f"bench.py: stopped at section {name!r}: no accelerator", file=sys.stderr)
            return 1
        if rc == 0 and out.strip():
            try:
                section = json.loads(out.strip().splitlines()[-1])
                device = section.pop("_device")
                results.update(section)
            except (ValueError, KeyError) as exc:
                results[f"{name}_error"] = f"unparseable output: {exc!r}"[:200]
                failed.append(name)
        elif rc == -1:
            results[f"{name}_error"] = (
                f"deadline {deadlines[name]}s exceeded after {time.perf_counter() - t0:.0f}s"
            )
            failed.append(name)
        else:
            results[f"{name}_error"] = f"exit code {rc}"
            failed.append(name)
        # cumulative flushed line after EVERY section: a driver timeout keeps
        # everything completed so far, and the LAST line is always the most
        # complete aggregate (the one the driver parses)
        print(_final_line(results, device), flush=True)
    if failed:
        print(f"bench.py: failed sections: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--sub":
        _child_main(sys.argv[2])
    elif len(sys.argv) == 2 and sys.argv[1] in SUB_BENCHES:
        # `bench.py NAME` is an alias for `--sub NAME` — it used to silently
        # ignore the name and run EVERY section
        _child_main(sys.argv[1])
    elif len(sys.argv) >= 2:
        print(
            f"bench.py: unknown section {sys.argv[1]!r}\n"
            f"usage: bench.py [NAME | --sub NAME]   (no args = all sections)\n"
            f"sections: {', '.join(sorted(SUB_BENCHES))}",
            file=sys.stderr,
        )
        sys.exit(2)
    else:
        sys.exit(main())
