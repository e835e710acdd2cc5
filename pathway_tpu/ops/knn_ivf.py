"""IVF-Flat approximate KNN over the HBM-resident store.

The reference serves approximate search through USearch's HNSW
(``src/external_integration/usearch_integration.rs:20``). A pointer-chasing graph
is the wrong shape for a TPU; the TPU-native equivalent of "sublinear candidate
selection + exact re-scoring" is IVF-Flat:

- **coarse quantizer**: k-means centroids live on device; probing is one small
  ``queries @ centroids.T`` matmul + ``top_k`` (MXU work, no host round-trip);
- **inverted lists**: a CSR layout over live slots (see below); probing selects
  fixed-size candidate *pages*, streams their vectors, scores them exactly, and
  merges top-k — the whole probe→gather→score→top-k chain is ONE jit'd kernel,
  one dispatch and one host fetch per query batch;
- **training**: k-means iterations are themselves matmul + segment-sum on device;
  the index retrains when the corpus doubles, and assignments rebuild in one
  assign pass.

Recall is tunable via ``n_probe`` (``n_probe == n_clusters`` degenerates to exact
brute force). Search cost scales with the probed fraction of the corpus instead
of the corpus size — the sublinearity HNSW buys the reference, bought the TPU way.

CSR bucket layout
-----------------
Inverted lists are stored as a host-side CSR pair — ``_csr_offsets`` (C+1,) and
``_csr_rows`` (n_live,), live slot ids sorted cluster-major — plus a *paged*
device mirror: each cluster's member list is padded up to a multiple of
``PAGE`` (128) rows and packed into a contiguous ``(n_pages * PAGE,)`` int32
``_page_rows`` array (-1 pads), with ``_first_page``/``_n_pages`` per cluster.
The page count is padded to a power of two (the last page is an all-pad
sentinel), so the packed geometry only changes shape when the corpus doubles —
every other mutation batch rebuilds *contents*, not shapes, and the query
kernel's jit cache keeps hitting. Oversized clusters are split at train time
and spill overflow members to their second-nearest centroid at rebuild time,
so the per-cluster page budget (``_max_pages``) tracks ~1.5x the mean
occupancy, not the most bloated cluster.

Shape-bucketing policy
----------------------
Query batches and ``k`` are padded to the next power of two (floor 8 queries)
before entering the jit'd query kernel, and results are sliced back. Together
with the pow2-padded page count this bounds the number of XLA compilations for
a store at steady geometry to O(log(max batch) * log(max k)) regardless of how
ragged the serving traffic is. ``search_shape_buckets`` records the distinct
(q_pow2, k_pow2) buckets a store has seen; ``pathway_tpu.ops.knn.
kernel_cache_sizes()`` exposes the actual jit cache sizes for regression tests
and the bench recompile counter.

Pallas / XLA fallback contract
------------------------------
The candidate scoring stage — the bandwidth-bound heart of the query — has two
implementations selected by the ``impl`` static of ``_ivf_query_fused``:

- ``"pallas"``: a ``pl.pallas_call`` TPU kernel (ragged-paged-attention shape:
  ``arxiv 2604.15464``). Per-query page indices are scalar-prefetched into
  SMEM; the grid walks (query group of 8, page-slot, query in group) and each
  step DMAs ONE ``(PAGE, dim)`` candidate page HBM→VMEM, dots it against the
  group's query block, and keeps that query's row of the ``(8, PAGE)`` score
  tile (every block is (8, 128)-aligned, which is what Mosaic accepts).
  Candidate vectors are never materialized
  as a ``(q, n_probe * bucket_width, dim)`` gather — they stream through VMEM
  page by page. ``"pallas_interpret"`` runs the same kernel through the Pallas
  interpreter on any backend (used by the parity tests).
- ``"xla"``: a composite fallback — ``lax.scan`` over page slots, gathering one
  ``(q, PAGE, dim)`` tile per step. Bit-for-bit the same scoring math (f32
  accumulation, identical metric epilogue, identical -inf masking), so the two
  implementations are interchangeable; tests assert parity.

Both paths bound peak memory to one candidate tile instead of the full
candidate volume. On CPU backends ``search_batch`` instead takes a numpy path
that walks the SAME CSR cluster-major (one BLAS GEMM per probed cluster), which
beats XLA's CPU gather by orders of magnitude while computing the identical
probe → exact-score → top-k result.
"""

from __future__ import annotations

import functools
from typing import Any, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.ops.knn import DenseKNNStore, next_pow2, pad_queries_pow2, topk_rows
from pathway_tpu.ops.knn_quant import host_metric_scores

_KMEANS_CHUNK = 4096

# rows per packed candidate page: one MXU-width tile of candidates, and the
# granularity of the HBM→VMEM stream in both scoring implementations
PAGE = 128


@functools.partial(jax.jit, static_argnames=("n_iters",))
def _kmeans_kernel(vectors: jax.Array, valid: jax.Array, centroids: jax.Array, n_iters: int):
    """Lloyd iterations fully on device, memory-safe at large cluster counts:
    each iteration scans (chunk, d) blocks accumulating per-centroid sums and
    counts (one-hot matmul — MXU work, no scatter), so peak extra memory is
    O(chunk * C) instead of O(n * C). Callers pad ``vectors``/``valid`` to a
    multiple of ``_KMEANS_CHUNK`` with ``valid=False`` rows."""
    n, d = vectors.shape
    C = centroids.shape[0]
    vb = vectors.reshape(n // _KMEANS_CHUNK, _KMEANS_CHUNK, d)
    mb = valid.reshape(n // _KMEANS_CHUNK, _KMEANS_CHUNK)

    def step(cents, _):
        cn = jnp.sum(cents * cents, axis=1)
        cb = cents.astype(jnp.bfloat16)

        def acc(carry, blk):
            sums, counts = carry
            v, m = blk
            sim = 2.0 * (v.astype(jnp.bfloat16) @ cb.T).astype(jnp.float32) - cn[None, :]
            sim = jnp.where(m[:, None], sim, -jnp.inf)
            a = jnp.argmax(sim, axis=1)
            oh = jax.nn.one_hot(a, C, dtype=jnp.bfloat16) * m[:, None].astype(jnp.bfloat16)
            sums = sums + jnp.einsum(
                "nc,nd->cd", oh, v.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )
            counts = counts + jnp.sum(oh.astype(jnp.float32), axis=0)
            return (sums, counts), None

        init = (jnp.zeros((C, d), jnp.float32), jnp.zeros((C,), jnp.float32))
        (sums, counts), _ = lax.scan(acc, init, (vb, mb))
        new = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), cents
        )
        return new, None

    centroids, _ = lax.scan(step, centroids, None, length=n_iters)
    return centroids


@jax.jit
def _assign2_kernel(block: jax.Array, centroids: jax.Array) -> jax.Array:
    """Top-2 nearest centroids per row (primary + spill candidate), bf16
    affinity with f32 correction — near-ties may swap, which is harmless for
    coarse quantization (both clusters are close)."""
    cn = jnp.sum(centroids * centroids, axis=1)
    sim = (
        2.0 * (block.astype(jnp.bfloat16) @ centroids.astype(jnp.bfloat16).T).astype(jnp.float32)
        - cn[None, :]
    )
    _, idx = lax.top_k(sim, 2)
    return idx.astype(jnp.int32)


@jax.jit
def _pack_pages_kernel(
    data: jax.Array, norms: jax.Array, valid: jax.Array, page_rows: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Materialize the paged device mirror of the CSR: candidate vectors packed
    cluster-major into (n_pages * PAGE, d), their norms and an additive -inf
    mask reshaped (n_pages, PAGE) so the scoring stage addresses them by page
    id. One fused gather per index rebuild (amortized over mutation batches)."""
    safe = jnp.maximum(page_rows, 0)
    packed = data[safe]
    pn = norms[safe].reshape(-1, PAGE)
    ok = (page_rows >= 0) & valid[safe]
    pm = jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32).reshape(-1, PAGE)
    return packed, pn, pm


def _page_scores_epilogue(dot, pn, pm, qn, metric: str):
    """Shared metric epilogue — MUST stay identical between the Pallas kernel
    and the XLA composite (the parity tests pin this)."""
    if metric == "l2sq":
        s = 2.0 * dot - pn - qn
    elif metric == "cos":
        s = dot / jnp.maximum(jnp.sqrt(pn * qn), 1e-30)
    else:  # ip
        s = dot
    return s + pm


def _score_pages_xla(packed, pn, pm, queries, page_ids, metric: str) -> jax.Array:
    """Composite fallback: scan page slots, gathering ONE (q, PAGE, d) tile per
    step — peak memory is a single candidate tile, never the full
    (q, n_probe * bucket_width, d) volume."""
    qf = queries.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=1)[:, None]  # (q, 1)
    qc = queries.astype(packed.dtype)

    def step(_, pid):  # pid: (q,) page id for this slot
        rows = pid[:, None] * PAGE + jnp.arange(PAGE)[None, :]
        vecs = packed[rows]  # (q, PAGE, d) — one streamed tile
        dot = jnp.einsum(
            "qd,qpd->qp", qc, vecs, preferred_element_type=jnp.float32
        )
        return 0, _page_scores_epilogue(dot, pn[pid], pm[pid], qn, metric)

    _, stacked = lax.scan(step, 0, page_ids.T)  # (P, q, PAGE)
    q = queries.shape[0]
    return stacked.transpose(1, 0, 2).reshape(q, -1)


# rows per query / norm / mask / output block: the f32 sublane tile. Mosaic
# takes a block only if its last two dims are multiples of (8, 128) or the full
# dimension, so the kernel moves 8-row blocks and picks its row with a mask.
_QROWS = 8

# page ids one Pallas call may prefetch: 64 KiB of int32 in scalar memory (the
# chunk rounds up to a power of two, so at most twice that)
_SMEM_PAGE_IDS = 1 << 14


def _score_pages_pallas(
    packed, pn, pm, queries, page_ids, metric: str, interpret: bool
) -> jax.Array:
    """Fused probe→gather→score streaming kernel (TPU): per-query page ids are
    scalar-prefetched, the grid walks (query group, page slot, query in group),
    and each step DMAs one (PAGE, d) candidate page into VMEM via the
    prefetched index map — the ragged-gather-by-pages shape of Ragged Paged
    Attention. A step scores its group's 8 queries against the page of ONE of
    them and keeps that query's row; the (8, PAGE) output block stays resident
    across the group's 8 steps and is written back once."""
    q, d = queries.shape
    n_slots = page_ids.shape[1]
    assert q % _QROWS == 0, "callers pad the query batch (pad_queries_pow2)"
    # the page count is a power of two, so an 8-row block divides it (a store
    # under 8 pages moves the whole array)
    prows = min(_QROWS, pn.shape[0])

    def page_of(g, j, r, ids):
        return ids[(g * _QROWS + r) * n_slots + j]

    def kernel(ids_ref, q_ref, data_ref, pn_ref, pm_ref, out_ref):
        g, j, r = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        qv = q_ref[...]  # (_QROWS, d) f32
        page = data_ref[...].astype(jnp.float32)  # (PAGE, d)
        dot = lax.dot_general(
            qv, page, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (_QROWS, PAGE)
        qn = jnp.sum(qv * qv, axis=1, keepdims=True)  # (_QROWS, 1)
        # this page's norm / mask row out of its block: a masked sublane sum
        # (one value plus zeros is exact, and -inf + 0 stays -inf)
        in_block = lax.rem(page_of(g, j, r, ids_ref), prows)
        sel = lax.broadcasted_iota(jnp.int32, (prows, PAGE), 0) == in_block
        pn_row = jnp.sum(jnp.where(sel, pn_ref[...], 0.0), axis=0, keepdims=True)
        pm_row = jnp.sum(jnp.where(sel, pm_ref[...], 0.0), axis=0, keepdims=True)
        scores = _page_scores_epilogue(dot, pn_row, pm_row, qn, metric)
        # only row r met ITS page; the other rows keep what their steps wrote
        mine = lax.broadcasted_iota(jnp.int32, (_QROWS, PAGE), 0) == r
        out_ref[...] = jnp.where(mine, scores, out_ref[...])

    # the block of norm / mask rows that holds this step's page
    page_rows = pl.BlockSpec(
        (prows, PAGE), lambda g, j, r, ids: (lax.div(page_of(g, j, r, ids), prows), 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q // _QROWS, n_slots, _QROWS),
        in_specs=[
            pl.BlockSpec((_QROWS, d), lambda g, j, r, ids: (g, 0)),
            pl.BlockSpec((PAGE, d), lambda g, j, r, ids: (page_of(g, j, r, ids), 0)),
            page_rows,  # pn
            page_rows,  # pm
        ],
        out_specs=pl.BlockSpec((_QROWS, PAGE), lambda g, j, r, ids: (g, j)),
    )
    score = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q, n_slots * PAGE), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # the output block is revisited along the innermost axis only
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ivf_score_pages",
    )
    # page ids ride SMEM flat: a 2-D SMEM array pads its minor dim to 128 words
    return score(page_ids.reshape(-1), queries.astype(jnp.float32), packed, pn, pm)


@functools.partial(
    jax.jit, static_argnames=("k", "n_probe", "max_pages", "metric", "impl")
)
def _ivf_query_fused(
    centroids: jax.Array,   # (C, d) f32
    first_page: jax.Array,  # (C,) int32
    n_pages: jax.Array,     # (C,) int32
    packed: jax.Array,      # (n_pages_pow2 * PAGE, d) corpus dtype
    pn: jax.Array,          # (n_pages_pow2, PAGE) f32 row norms
    pm: jax.Array,          # (n_pages_pow2, PAGE) f32 additive mask (0 / -inf)
    packed_rows: jax.Array, # (n_pages_pow2 * PAGE,) int32 packed pos -> slot
    queries: jax.Array,     # (q, d) f32
    k: int,
    n_probe: int,
    max_pages: int,
    metric: str,
    impl: str,
) -> Tuple[jax.Array, jax.Array]:
    """ONE fused pass: probe clusters -> expand probed clusters to candidate
    pages -> stream-score the pages -> top-k -> map positions back to slots.
    Single device round-trip per query batch."""
    cn = jnp.sum(centroids * centroids, axis=1)
    aff = 2.0 * queries @ centroids.T - cn[None, :]  # L2 affinity to centroids
    _, probe = lax.top_k(aff, n_probe)  # (q, n_probe)
    base = first_page[probe]  # (q, n_probe)
    cnt = n_pages[probe]
    span = jnp.arange(max_pages, dtype=jnp.int32)
    ids = base[..., None] + span[None, None, :]  # (q, n_probe, max_pages)
    sentinel = pn.shape[0] - 1  # last page is all-pad by construction
    page_ids = jnp.where(span[None, None, :] < cnt[..., None], ids, sentinel)
    page_ids = page_ids.reshape(queries.shape[0], -1).astype(jnp.int32)
    if impl == "xla":
        scores = _score_pages_xla(packed, pn, pm, queries, page_ids, metric)
    else:
        scores = _score_pages_pallas(
            packed, pn, pm, queries, page_ids, metric,
            interpret=(impl == "pallas_interpret"),
        )
    k_eff = min(k, scores.shape[1])
    top_scores, pos = lax.top_k(scores, k_eff)
    pg = jnp.take_along_axis(page_ids, pos // PAGE, axis=1)
    top_slots = packed_rows[pg * PAGE + pos % PAGE]
    top_slots = jnp.where(jnp.isfinite(top_scores), top_slots, -1)
    return top_scores, top_slots


class IvfKnnStore(DenseKNNStore):
    """Keyed IVF-Flat store: ``DenseKNNStore``'s storage management (staged
    scatters, capacity doubling, slot recycling) plus centroid assignments and
    the CSR/paged inverted lists maintained through the flush/grow hooks."""

    def __init__(
        self,
        dim: int,
        metric: str = "l2sq",
        initial_capacity: int = 1024,
        n_clusters: int = 64,
        n_probe: int = 8,
        train_iters: int = 8,
        dtype: Any = jnp.float32,
        device: Any = None,
    ):
        super().__init__(
            dim, metric=metric, initial_capacity=initial_capacity, dtype=dtype,
            device=device,
        )
        self.n_clusters = max(2, n_clusters)
        self.n_probe = min(n_probe, self.n_clusters)
        # configured cluster count: retrains restart from it — n_clusters grows
        # via splits within ONE train, and must not compound across retrains
        # (the probed fraction would silently shrink every corpus doubling).
        # n_probe is NOT reset: it is the caller's tuning knob.
        self._n_clusters_base = self.n_clusters
        self.train_iters = train_iters
        self._centroids: jax.Array | None = None
        # host mirrors: primary assignment + spill candidate (2nd-nearest)
        self._assign = np.full(self.capacity, -1, dtype=np.int32)
        self._assign2 = np.full(self.capacity, -1, dtype=np.int32)
        self._bucket_cap: int | None = None  # set by _split_oversized at train
        self._trained_at = 0  # corpus size at last (re)train
        self._host_cache: "tuple | None" = None  # f32 mirrors for the CPU path
        # CSR + paged layout (built lazily by _ensure_index)
        self._index_dirty = True
        self._csr_offsets: np.ndarray | None = None
        self._csr_rows: np.ndarray | None = None
        self._first_page: np.ndarray | None = None
        self._n_pages: np.ndarray | None = None
        self._page_rows: np.ndarray | None = None
        self._max_pages = 1
        self._packed: "tuple | None" = None  # device mirror (packed, pn, pm, rows)
        # distinct (q_pow2, k_pow2) shape buckets this store has served — the
        # recompile-observability counter (bench + jit-cache regression test)
        self.search_shape_buckets: set = set()

    # -- DenseKNNStore hooks -------------------------------------------------

    def _after_grow(self, old_capacity: int, extra: int) -> None:
        pad = np.full(extra, -1, dtype=np.int32)
        self._assign = np.concatenate([self._assign, pad])
        self._assign2 = np.concatenate([self._assign2, pad.copy()])
        self._invalidate_index()  # geometry changed; rebuild lazily

    def _after_flush_adds(self, padded_slots: np.ndarray, vecs: jax.Array) -> None:
        # assign the new rows to centroids (chunked device passes) unless a
        # retrain will re-assign everything anyway
        if self._centroids is not None:
            top2 = self._assign_rows(vecs)
            self._assign[padded_slots] = top2[:, 0]
            self._assign2[padded_slots] = top2[:, 1]
        self._invalidate_index()

    def _after_flush_removals(self) -> None:
        self._invalidate_index()

    def _invalidate_index(self) -> None:
        self._index_dirty = True
        self._packed = None
        self._host_cache = None

    # training runs on a SAMPLE (faiss-style): k-means cost and its (chunk, C)
    # intermediates stay bounded however large the corpus grows
    _TRAIN_SAMPLE_PER_CLUSTER = 32

    def _assign_rows(self, rows: jax.Array) -> np.ndarray:
        """Top-2 centroid assignment for ``rows``, chunked so BOTH the
        (chunk, C) affinity and the (chunk, dim) block stay within a fixed
        memory budget at any cluster count / dimensionality."""
        chunk = max(1024, (1 << 28) // max(self.n_clusters, self.dim, 1))
        parts = []
        for start in range(0, rows.shape[0], chunk):
            parts.append(
                np.asarray(_assign2_kernel(rows[start : start + chunk], self._centroids))
            )
        return np.concatenate(parts) if parts else np.zeros((0, 2), dtype=np.int32)

    def _maybe_train(self) -> None:
        n = len(self.slot_of)
        if n == 0:
            return
        needs = self._centroids is None or n >= 2 * max(self._trained_at, 1)
        if not needs:
            return
        self.n_clusters = self._n_clusters_base
        rng = np.random.default_rng(0)
        live = np.fromiter(self.slot_of.values(), dtype=np.int64)
        seeds = rng.choice(live, size=self.n_clusters, replace=len(live) < self.n_clusters)
        # k-means accumulates means: always train in f32 even over a bf16 corpus
        init = self._data[jnp.asarray(seeds)].astype(jnp.float32)
        sample_cap = self.n_clusters * self._TRAIN_SAMPLE_PER_CLUSTER
        if len(live) > sample_cap:
            sample = np.sort(rng.choice(live, size=sample_cap, replace=False))
        else:
            # gather LIVE rows only: casting the whole preallocated buffer to
            # f32 would materialize capacity x dim (multi-GB for a large store)
            sample = np.sort(live)
        train_vecs = self._data[jnp.asarray(sample)].astype(jnp.float32)
        n_train = len(sample)
        pad = (-n_train) % _KMEANS_CHUNK
        if pad:
            train_vecs = jnp.concatenate(
                [train_vecs, jnp.zeros((pad, self.dim), jnp.float32)]
            )
        train_valid = jnp.arange(n_train + pad) < n_train
        self._centroids = _kmeans_kernel(train_vecs, train_valid, init, self.train_iters)
        # assign the FULL corpus to the trained centroids (chunked device passes)
        top2 = self._assign_rows(self._data)
        self._assign = top2[:, 0].copy()
        self._assign2 = top2[:, 1].copy()
        self._split_oversized(live)
        self._trained_at = n
        self._invalidate_index()

    @staticmethod
    def _cap_for(n_live: int, n_clusters: int) -> int:
        """Target per-cluster occupancy: ~1.5x the mean, rounded up to pow2 —
        the padded page budget search pays for."""
        mean = max(1, n_live // max(n_clusters, 1))
        cap = 8
        while cap < (3 * mean + 1) // 2:
            cap *= 2
        return cap

    def _split_oversized(self, live: np.ndarray) -> None:
        """Bound the bucket width by SPLITTING oversized clusters instead of
        letting the per-cluster page budget track the most bloated one: each
        cluster past the cap gets a host-side 2-means over its members, the
        centroid is replaced by the pair, and siblings cross-link as each
        other's spill target. k-means over manifold-clustered corpora routinely
        leaves a handful of clusters at 3-4x the mean; without splits the whole
        candidate volume doubles for them."""
        if not len(live):
            return
        cap = self._cap_for(len(live), self.n_clusters)
        self._bucket_cap = cap
        limit = 2 * self.n_clusters  # at most double the cluster count
        cents = np.array(self._centroids, dtype=np.float32)
        for _ in range(6):  # each round halves offenders; 6 covers 64x skew
            al = self._assign[live]
            counts = np.bincount(al, minlength=self.n_clusters)
            over = np.where(counts > cap)[0]
            if not len(over) or self.n_clusters + len(over) > limit:
                break
            order = np.argsort(al, kind="stable")
            starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
            new_rows: List[np.ndarray] = []
            for c in over:
                mem = live[order[starts[c] : starts[c] + counts[c]]]
                vecs = np.asarray(
                    self._data[jnp.asarray(mem)].astype(jnp.float32)
                )
                # 2-means, host-side (members are a few thousand rows at most)
                c0, c1 = vecs[0], vecs[len(vecs) // 2]
                for _it in range(6):
                    d0 = np.sum((vecs - c0) ** 2, axis=1)
                    d1 = np.sum((vecs - c1) ** 2, axis=1)
                    g1 = d1 < d0
                    if g1.all() or (~g1).all():
                        break
                    c0 = vecs[~g1].mean(axis=0)
                    c1 = vecs[g1].mean(axis=0)
                new_id = self.n_clusters
                self.n_clusters += 1
                self._assign[mem[g1]] = new_id
                self._assign2[mem[g1]] = c
                self._assign2[mem[~g1]] = new_id
                cents[c] = c0
                new_rows.append(c1[None, :])
            if new_rows:
                cents = np.concatenate([cents] + new_rows)
        self._centroids = jnp.asarray(cents)
        self.n_probe = min(self.n_probe, self.n_clusters)

    def _ensure_index(self) -> None:
        """Pack live slots into the CSR (+ paged) inverted-list layout — one
        vectorized sort + fancy-index pass (this reruns after every mutation
        batch, so it must not walk the corpus in Python).

        The per-cluster page budget is what search pays for (candidates per
        probe = max_pages * PAGE), so oversized clusters are rebalanced first:
        overflow members past ~1.5x the mean spill to their 2nd-nearest
        centroid. A spilled point sits in a cluster whose centroid is nearly as
        close, so probes still find it; the win is a bounded budget instead of
        one tracking the most bloated cluster."""
        if not self._index_dirty:
            return
        live = np.fromiter(self.slot_of.values(), dtype=np.int64)
        C = self.n_clusters
        counts = np.zeros(C, dtype=np.int64)
        a = np.zeros(0, dtype=np.int64)
        if len(live):
            a = self._assign[live].astype(np.int64)
            a2 = self._assign2[live]
            counts = np.bincount(a, minlength=C)
            cap = self._bucket_cap or self._cap_for(len(live), C)
            over = np.where(counts > cap)[0]
            if len(over):
                a = a.copy()
                order = np.argsort(a, kind="stable")
                starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
                for c in over:
                    tail = order[starts[c] + cap : starts[c] + counts[c]]
                    mv = tail[a2[tail] != c]
                    a[mv] = a2[mv]
                counts = np.bincount(a, minlength=C)
        offsets = np.zeros(C + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        order = np.argsort(a, kind="stable")
        sorted_a = a[order]
        sorted_slots = live[order].astype(np.int32)
        self._csr_offsets = offsets
        self._csr_rows = sorted_slots
        # paged mirror: per-cluster member lists padded to PAGE multiples and
        # packed contiguously; total page count padded pow2 with a trailing
        # all-pad sentinel page so the kernel shapes only change on doubling
        n_pages_c = -(-counts // PAGE)  # ceil; empty clusters get 0 pages
        first_page = np.zeros(C, dtype=np.int32)
        if C:
            np.cumsum(n_pages_c[:-1], out=first_page[1:])
        total = int(n_pages_c.sum()) + 1
        pages_pow2 = next_pow2(total)
        page_rows = np.full(pages_pow2 * PAGE, -1, dtype=np.int32)
        if len(live):
            within = np.arange(len(live), dtype=np.int64) - offsets[sorted_a]
            dest = first_page[sorted_a].astype(np.int64) * PAGE + within
            page_rows[dest] = sorted_slots
        self._first_page = first_page
        self._n_pages = n_pages_c.astype(np.int32)
        self._page_rows = page_rows
        self._max_pages = int(max(1, n_pages_c.max() if C else 1))
        self._index_dirty = False
        self._packed = None

    def _ensure_packed(self) -> None:
        """Device mirror of the paged layout (skipped entirely on the CPU
        numpy path): one fused gather per rebuild."""
        if self._packed is not None:
            return
        rows = jnp.asarray(self._page_rows)
        packed, pn, pm = _pack_pages_kernel(self._data, self._norms, self._valid, rows)
        # first_page/n_pages ride along so steady-state queries re-upload
        # nothing: the hot path stays one device round-trip per batch
        self._packed = (
            packed, pn, pm, rows,
            jnp.asarray(self._first_page), jnp.asarray(self._n_pages),
        )

    # -- query paths ---------------------------------------------------------

    def _effective_n_probe(self) -> int:
        """``n_probe`` after the brownout ladder's degradation shift
        (``engine/brownout.py``): under rung 2 the serving plane halves the
        probed clusters — recall degrades honestly instead of the embed/query
        queue growing without bound. Level 0 (the steady state) returns
        ``n_probe`` unchanged, so normal serving is bit-identical to the
        pre-brownout build. On the device path each shift level is one extra
        jit bucket (``n_probe`` is a static kernel argument) — bounded at the
        ladder's two rungs."""
        from pathway_tpu.engine.brownout import get_brownout

        return max(1, self.n_probe >> get_brownout().nprobe_shift())

    def _search_numpy(
        self, queries: np.ndarray, k_eff: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Host BLAS path for CPU backends, walking the CSR cluster-major: for
        every probed cluster, ONE GEMM of the queries probing it against that
        cluster's member block. Candidate vectors are read once per batch
        through BLAS instead of being materialized per query (XLA:CPU's
        (q, n_probe * bucket_width, dim) gather was far slower)."""
        if self._host_cache is None:
            self._host_cache = (
                np.asarray(self._data.astype(jnp.float32)),
                np.asarray(self._norms),
                np.asarray(self._centroids, dtype=np.float32),
            )
        data, norms, cents = self._host_cache
        offsets, rows = self._csr_offsets, self._csr_rows
        counts_all = offsets[1:] - offsets[:-1]
        cn = np.sum(cents * cents, axis=1)
        n_probe = self._effective_n_probe()
        nq_total = queries.shape[0]
        out_scores = np.full((nq_total, k_eff), -np.inf, dtype=np.float32)
        out_slots = np.full((nq_total, k_eff), -1, dtype=np.int64)
        # chunk queries so the (chunk, worst-case candidates) buffers stay
        # within a fixed budget however skewed the cluster sizes are
        w_est = n_probe * int(max(counts_all.max() if len(counts_all) else 1, 1))
        CH = int(max(64, min(1024, (1 << 28) // max(8 * w_est, 1))))
        for start in range(0, nq_total, CH):
            q = queries[start : start + CH]
            nq = q.shape[0]
            aff = 2.0 * q @ cents.T - cn[None, :]
            probe = np.argpartition(aff, -n_probe, axis=1)[:, -n_probe:]
            pc = counts_all[probe]  # (nq, n_probe) candidate counts
            col0 = np.zeros_like(pc)
            np.cumsum(pc[:, :-1], axis=1, out=col0[:, 1:])
            W = int(pc.sum(axis=1).max()) if nq else 0
            if W == 0:
                continue
            buf_s = np.full((nq, W), -np.inf, dtype=np.float32)
            buf_i = np.full((nq, W), -1, dtype=np.int32)  # slots fit int32
            qn = np.sum(q * q, axis=1)
            # cluster-major iteration: group (query, probe) pairs by cluster
            flatc = probe.ravel()
            flatq = np.repeat(np.arange(nq), probe.shape[1])
            flats = col0.ravel()
            order = np.argsort(flatc, kind="stable")
            fc, fq, fs = flatc[order], flatq[order], flats[order]
            uniq, first = np.unique(fc, return_index=True)
            bounds = np.append(first, len(fc))
            for g in range(len(uniq)):
                c = int(uniq[g])
                mem = rows[offsets[c] : offsets[c + 1]]
                mc = len(mem)
                if mc == 0:
                    continue
                sel = slice(bounds[g], bounds[g + 1])
                qs, ds = fq[sel], fs[sel]
                sub = host_metric_scores(q[qs], data[mem], norms[mem], qn[qs], self.metric)
                cols = ds[:, None] + np.arange(mc)[None, :]
                buf_s[qs[:, None], cols] = sub
                buf_i[qs[:, None], cols] = mem
            ts, ti = topk_rows(buf_s, buf_i, k_eff)
            out_scores[start : start + nq] = ts
            out_slots[start : start + nq] = ti
        return out_scores, out_slots

    def _search_device_launch(
        self, queries: Any, k_eff: int, impl: str | None = None
    ) -> Tuple[jax.Array, jax.Array]:
        """Dispatch the fused device path WITHOUT blocking on the result — the
        sharded store launches every shard's kernel before fetching any, so
        query latency is max-over-shards, not sum. ``impl`` overrides the
        scoring implementation (tests force ``"xla"``/``"pallas_interpret"``)."""
        self._ensure_packed()
        if impl is None:
            impl = "pallas" if jax.default_backend() == "tpu" else "xla"
        packed, pn, pm, rows, first_page, n_pages = self._packed
        if isinstance(queries, jax.Array):
            q_dev = queries.astype(jnp.float32)
        else:
            q_dev = jnp.asarray(np.asarray(queries, dtype=np.float32))
        nq = q_dev.shape[0]
        n_probe = self._effective_n_probe()
        cand = n_probe * self._max_pages * PAGE
        k_used = min(next_pow2(max(1, k_eff)), cand)
        # chunk the query batch so the streamed tile + the (chunk, cand) score
        # matrix stay within a fixed HBM budget
        q_cap = (1 << 26) // max(cand, 1)
        if impl != "xla":
            # the Pallas kernel prefetches every (query, slot) page id into SMEM
            q_cap = min(q_cap, _SMEM_PAGE_IDS // (n_probe * self._max_pages))
        q_chunk = next_pow2(max(8, min(nq, q_cap)))
        parts = []
        for start in range(0, max(nq, 1), q_chunk):
            sl, _n = pad_queries_pow2(q_dev[start : start + q_chunk], self.dim)
            self.search_shape_buckets.add((sl.shape[0], k_used))
            parts.append(
                _ivf_query_fused(
                    self._centroids, first_page, n_pages, packed, pn, pm, rows,
                    sl, k_used, n_probe, self._max_pages, self.metric, impl,
                )
            )
        top_scores = jnp.concatenate([p[0] for p in parts])[:nq, :k_eff]
        top_slots = jnp.concatenate([p[1] for p in parts])[:nq, :k_eff]
        return top_scores, top_slots

    def _search_device(
        self, queries: Any, k_eff: int, impl: str | None = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        top_scores, top_slots = self._search_device_launch(queries, k_eff, impl)
        scores, idx = jax.device_get((top_scores, top_slots))
        return scores, idx.astype(np.int64)

    def _prepare_search(self) -> bool:
        """Flush mutations, (re)train if due, build the CSR/paged layout.
        False while the store is empty (nothing trained to search)."""
        self._flush()
        self._maybe_train()
        if self._centroids is None:
            return False
        self._ensure_index()
        return True

    def search_batch(self, queries: Any, k: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        if not self._prepare_search():
            n = int(np.asarray(queries).shape[0]) if not isinstance(queries, jax.Array) else queries.shape[0]
            return (
                np.full((n, max(1, k)), -np.inf, dtype=np.float32),
                np.full((n, max(1, k)), -1, dtype=np.int64),
                np.zeros((n, max(1, k)), dtype=bool),
            )
        k_eff = max(1, k)
        if jax.default_backend() == "cpu":
            q_np = np.asarray(queries, dtype=np.float32).reshape(-1, self.dim)
            self.search_shape_buckets.add(
                (next_pow2(max(8, q_np.shape[0])), next_pow2(k_eff))
            )
            scores, idx = self._search_numpy(q_np, k_eff)
        else:
            if not isinstance(queries, jax.Array):
                queries = np.asarray(queries, dtype=np.float32).reshape(-1, self.dim)
            elif queries.ndim != 2 or queries.shape[-1] != self.dim:
                queries = queries.reshape(-1, self.dim)
            scores, idx = self._search_device(queries, k_eff)
        valid = np.isfinite(scores)
        if scores.shape[1] < k_eff:  # fewer candidates than k: pad result shape
            pad = k_eff - scores.shape[1]
            scores = np.pad(scores, ((0, 0), (0, pad)), constant_values=-np.inf)
            idx = np.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
            valid = np.pad(valid, ((0, 0), (0, pad)), constant_values=False)
        return scores, idx, valid
