"""Brute-force & LSH KNN over an HBM-resident vector store.

TPU-native replacement for the reference's engine KNN: ``src/external_integration/
brute_force_knn_integration.rs:113`` (ndarray matmul + partial sort via ``src/mat_mul.rs:5``)
and ``stdlib/ml/classifiers/_knn_lsh.py`` (random-projection LSH). Design:

- the vector store is ONE dense ``(capacity, dim)`` jax array in HBM with a validity mask;
  capacity doubles amortized so jit re-traces are rare (static shapes for XLA);
- search = one jit'd kernel: ``queries @ data.T`` on the MXU (bf16 accumulate-f32 by default)
  fused with masking + ``lax.top_k`` — XLA fuses the elementwise mask into the matmul epilogue;
- adds/removes stage host-side and flush as one scatter (``data.at[slots].set(batch)``) per
  commit, so ingest cost is one device round-trip per batch, not per row.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from pathway_tpu.engine import tracing
from pathway_tpu.internals.shapes import next_pow2 as _next_pow2_shared


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _search_kernel(
    data: jax.Array, valid: jax.Array, norms: jax.Array, queries: jax.Array, k: int, metric: str
) -> Tuple[jax.Array, jax.Array]:
    """Top-k over the full store: (q, cap) score matrix on the MXU, masked, top_k."""
    with jax.named_scope("knn_score"):
        scores = jnp.dot(
            queries, data.T, preferred_element_type=jnp.float32
        )  # (q, cap) — MXU path (bf16 operands accumulate in f32)
        # query norms in f32 regardless of storage dtype: a bf16 self-product
        # loses ~3 decimal digits, which skews l2 distances near ties
        qf = queries.astype(jnp.float32)
        if metric == "l2sq":
            qn = jnp.sum(qf * qf, axis=1, keepdims=True)
            scores = -(qn + norms[None, :] - 2.0 * scores)  # -(||q-d||^2), higher is better
        elif metric == "cos":
            qn = jnp.linalg.norm(qf, axis=1, keepdims=True)
            scores = scores / jnp.maximum(qn * jnp.sqrt(norms)[None, :], 1e-30)
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
    with jax.named_scope("knn_top_k"):
        top_scores, top_idx = lax.top_k(scores, k)
    return top_scores, top_idx


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1): the shape-bucketing unit — every
    jit'd search/scatter kernel sees pow2-padded batch shapes so its cache is
    keyed by O(log) distinct buckets instead of one entry per raw size.
    Delegates to the ONE shared rule in ``internals/shapes.py`` (also used by
    the encoder and segment reductions)."""
    return _next_pow2_shared(n, floor=1)


def pad_queries_pow2(queries: Any, dim: int) -> Tuple[Any, int]:
    """Pad a query batch with zero rows to the next pow2 count (floor 8) —
    the ONE bucketing policy shared by the dense and IVF search paths. A host
    batch is padded in numpy (no program runs); a device batch on the device
    (a concatenate keyed by its row count). Returns (padded batch, original
    row count) for slicing results back."""
    nq = queries.shape[0]
    q_pad = next_pow2(max(8, nq))
    if q_pad == nq:
        return queries, nq
    if isinstance(queries, jax.Array):
        return jnp.concatenate([queries, jnp.zeros((q_pad - nq, dim), queries.dtype)]), nq
    padded = np.zeros((q_pad, dim), dtype=queries.dtype)
    padded[:nq] = queries
    return padded, nq


def kernel_cache_sizes() -> Dict[str, int]:
    """Entries in each search kernel's jit cache — the recompile counter the
    bench artifact reports and the jit-cache regression tests bound."""
    from pathway_tpu.ops import knn_ivf

    def sz(fn: Any) -> int:
        try:
            return int(fn._cache_size())
        except Exception:
            return -1

    from pathway_tpu.ops import knn_quant, knn_tiers

    return {
        "dense_search": sz(_search_kernel),
        "ivf_query": sz(knn_ivf._ivf_query_fused),
        "ivf_pack": sz(knn_ivf._pack_pages_kernel),
        # tiered store: assignment batches and hot blocks pad to pow2, so
        # both caches must stay O(log) over ragged cluster sizes (an unpadded
        # shape per cluster was an 18x ingest regression)
        "tiered_assign": sz(knn_ivf._assign2_kernel),
        "tiered_score": sz(knn_tiers._score_block_kernel),
        # quantized tower: int8 coarse probe and block scorer (pow2-padded
        # centroid counts / block capacities / query buckets, same O(log)
        # cache discipline)
        "quant_probe": sz(knn_quant.quant_probe_kernel),
        "quant_score": sz(knn_quant.quant_score_block_kernel),
    }


def topk_rows(
    scores: np.ndarray, ids: np.ndarray, k: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row host top-k over (n, m) candidate arrays: (n, k) scores sorted
    descending + their ids, padded with -inf / -1 when m < k; ids of non-finite
    scores are -1. The ONE merge contract shared by the CPU IVF path and the
    sharded top-k merge."""
    n, m = scores.shape
    kk = min(k, m)
    if kk > 0:
        part = np.argpartition(scores, -kk, axis=1)[:, -kk:]
        psc = np.take_along_axis(scores, part, axis=1)
        order = np.argsort(-psc, axis=1)
        top = np.take_along_axis(part, order, axis=1)
        out_s = np.take_along_axis(scores, top, axis=1).astype(np.float32)
        out_i = np.take_along_axis(ids, top, axis=1).astype(np.int64)
    else:
        out_s = np.zeros((n, 0), dtype=np.float32)
        out_i = np.zeros((n, 0), dtype=np.int64)
    if kk < k:
        out_s = np.pad(out_s, ((0, 0), (0, k - kk)), constant_values=-np.inf)
        out_i = np.pad(out_i, ((0, 0), (0, k - kk)), constant_values=-1)
    out_i[~np.isfinite(out_s)] = -1
    return out_s, out_i


def pad_pow2(slots: np.ndarray, vecs: "np.ndarray | None" = None, extras: "np.ndarray | None" = None):
    """Pad a scatter batch to a power-of-two bucket so the update kernel compiles
    once per (bucket, capacity) pair; padding repeats row 0 (duplicate scatter
    indices with identical values are no-ops)."""
    n = len(slots)
    if n == 0:
        return slots, vecs, extras
    bucket = _next_pow2_shared(n, floor=8)
    if bucket != n:
        pad = bucket - n
        slots = np.concatenate([slots, np.full(pad, slots[0], slots.dtype)])
        if vecs is not None:
            vecs = np.concatenate([vecs, np.repeat(vecs[:1], pad, axis=0)])
        if extras is not None:
            extras = np.concatenate([extras, np.repeat(extras[:1], pad, axis=0)])
    return slots, vecs, extras


def pow2_target(capacity: int, target: "int | None") -> int:
    """Next capacity: at least double, jumping straight past ``target`` (every
    distinct capacity costs an XLA compile of the resize/scatter shapes)."""
    new_capacity = capacity * 2
    if target is not None:
        while new_capacity < target:
            new_capacity *= 2
    return new_capacity


class SlotIngestMixin:
    """Host-staged keyed slot assignment shared by the dense and sharded stores.

    Requires the host class to provide ``dim``, ``slot_of``, ``key_of``, ``_free``,
    ``_staged_slots``, ``_staged_vecs``, ``_staged_invalid`` and ``_grow()``.
    """

    def add(self, key: Any, vector: np.ndarray) -> None:
        vector = np.asarray(vector, dtype=np.float32).reshape(-1)
        assert vector.shape[0] == self.dim, f"dim mismatch: {vector.shape[0]} != {self.dim}"
        if key in self.slot_of:
            self.remove(key)
        if not self._free:
            self._grow()
        slot = self._free.pop()
        self.slot_of[key] = slot
        self.key_of[slot] = key
        self._staged_slots.append(slot)
        self._staged_vecs.append(vector)

    def add_many(self, keys: List[Any], vectors: np.ndarray) -> None:
        """Bulk insert: one staging append for the whole batch (no per-row Python work
        beyond the key dict updates)."""
        vectors = np.asarray(vectors, dtype=np.float32).reshape(len(keys), self.dim)
        last = {k: i for i, k in enumerate(keys)}  # intra-batch dedup: last write wins
        if len(last) != len(keys):
            keep = sorted(last.values())
            keys = [keys[i] for i in keep]
            vectors = vectors[keep]
        for k in [k for k in keys if k in self.slot_of]:
            self.remove(k)
        if len(self._free) < len(keys):
            self._grow(target=self.capacity + len(keys) - len(self._free))
        slots = [self._free.pop() for _ in range(len(keys))]
        self.slot_of.update(zip(keys, slots))
        self.key_of.update(zip(slots, keys))
        self._staged_slots.extend(slots)
        self._staged_vecs.extend(vectors)

    def remove(self, key: Any) -> None:
        slot = self.slot_of.pop(key, None)
        if slot is None:
            return
        self.key_of.pop(slot, None)
        self._free.append(slot)
        self._staged_invalid.append(slot)
        # drop a staged add for the same slot if still pending
        if slot in self._staged_slots:
            i = self._staged_slots.index(slot)
            del self._staged_slots[i]
            del self._staged_vecs[i]


class DenseKNNStore(SlotIngestMixin):
    """Keyed dense vector store with amortized-capacity device residency."""

    def __init__(
        self,
        dim: int,
        metric: str = "l2sq",
        dtype: Any = jnp.float32,
        initial_capacity: int = 1024,
        device: Any = None,
    ):
        assert metric in ("l2sq", "cos", "ip")
        self.dim = dim
        self.metric = metric
        self.dtype = dtype
        self.capacity = initial_capacity
        self.device = device
        # explicit placement pins the store to one chip of a mesh (the sharded
        # wrappers place one sub-store per device); computations on committed
        # arrays stay on that device, so only the three roots need the put
        def _place(x):
            return jax.device_put(x, device) if device is not None else x

        self._data = _place(jnp.zeros((self.capacity, dim), dtype=dtype))
        self._valid = _place(jnp.zeros((self.capacity,), dtype=bool))
        self._norms = _place(jnp.zeros((self.capacity,), dtype=jnp.float32))
        self.slot_of: Dict[Any, int] = {}
        self.key_of: Dict[int, Any] = {}
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        # staged updates applied lazily before the next search
        self._staged_vecs: List[np.ndarray] = []
        self._staged_slots: List[int] = []
        self._staged_invalid: List[int] = []

    def __len__(self) -> int:
        return len(self.slot_of)

    def _grow(self, target: int | None = None) -> None:
        new_capacity = pow2_target(self.capacity, target)
        self._flush()
        extra = new_capacity - self.capacity
        self._data = jnp.concatenate(
            [self._data, jnp.zeros((extra, self.dim), dtype=self.dtype)]
        )
        self._valid = jnp.concatenate([self._valid, jnp.zeros((extra,), dtype=bool)])
        self._norms = jnp.concatenate(
            [self._norms, jnp.zeros((extra,), dtype=jnp.float32)]
        )
        self._free.extend(range(new_capacity - 1, self.capacity - 1, -1))
        old_capacity, self.capacity = self.capacity, new_capacity
        self._after_grow(old_capacity, extra)

    def _after_grow(self, old_capacity: int, extra: int) -> None:
        """Subclass hook: capacity geometry just changed."""

    def _flush(self) -> None:
        # staged batches pad to power-of-two buckets so the scatter kernels compile
        # once per (bucket, capacity) pair instead of once per batch size (padding
        # rows re-write slot[0] with its own values — a no-op)
        if self._staged_slots:
            slots_np = np.array(self._staged_slots, dtype=np.int32)
            vecs_np = np.stack(self._staged_vecs).astype(np.float32)
            slots_np, vecs_np, _ = pad_pow2(slots_np, vecs_np)
            slots = jnp.asarray(slots_np)
            vecs = jnp.asarray(vecs_np)
            self._data = self._data.at[slots].set(vecs.astype(self.dtype))
            self._norms = self._norms.at[slots].set(jnp.sum(vecs * vecs, axis=1))
            self._valid = self._valid.at[slots].set(True)
            self._staged_slots, self._staged_vecs = [], []
            self._after_flush_adds(slots_np, vecs)
        if self._staged_invalid:
            inv = sorted(set(self._staged_invalid))
            flags_np = np.array([s in self.key_of for s in inv], dtype=bool)
            slots_np = np.array(inv, dtype=np.int32)
            slots_np, _, flags_np = pad_pow2(slots_np, extras=flags_np)
            self._valid = self._valid.at[jnp.asarray(slots_np)].set(jnp.asarray(flags_np))
            self._staged_invalid = []
            self._after_flush_removals()

    def _after_flush_adds(self, padded_slots: np.ndarray, vecs: jax.Array) -> None:
        """Subclass hook: a staged add batch just scattered into the device
        arrays (IVF assigns the new rows to centroids here)."""

    def _after_flush_removals(self) -> None:
        """Subclass hook: staged invalidations just applied."""

    def export_rows(self) -> Tuple[List[Any], np.ndarray]:
        """Every live (key, vector) pair as host arrays — the *rebuildable
        descriptor* contract: an index over this store can be reconstructed
        on another process from this export alone (membership handoff,
        background rebuilds). One device gather for the whole corpus."""
        self._flush()
        keys = list(self.slot_of.keys())
        if not keys:
            return keys, np.zeros((0, self.dim), dtype=np.float32)
        slots = np.fromiter(self.slot_of.values(), dtype=np.int64)
        vecs = np.asarray(self._data[jnp.asarray(slots)].astype(jnp.float32))
        return keys, vecs

    def search_batch(self, queries: Any, k: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Returns (scores (q,k), slots (q,k), valid_mask (q,k)); slots map via key_of."""
        with tracing.trace_span("search.prepare"):
            top_scores, top_idx, nq, k_eff = self._dispatch_search(queries, k)
        # one batched host fetch for scores and ids together: the one place
        # where a search blocks on the device. The bucket's padding rows and
        # columns are cut off here, on the host
        with tracing.trace_span("search.device_wait"):
            scores, idx = jax.device_get((top_scores, top_idx))
        scores, idx = scores[:nq, :k_eff], idx[:nq, :k_eff]
        valid = np.isfinite(scores)
        return scores, idx, valid

    def _dispatch_search(self, queries: Any, k: int) -> Tuple[jax.Array, jax.Array, int, int]:
        """Flush, cast, pad to the bucket and enqueue the search program;
        nothing here waits for the device. Returns the PADDED (query bucket,
        k bucket) results and the (rows, columns) that are real. A host batch
        is cast and padded in numpy and crosses with the call, so the search
        program is the only one that runs; a device batch is cast and padded
        on the device."""
        self._flush()
        # bf16-resident corpus (HBM capacity: 10M x 384 fits one v5e chip):
        # the MXU consumes bf16 natively with f32 accumulation — cast the
        # QUERIES down instead of materializing an f32 copy of the corpus
        bf16 = self._data.dtype == jnp.bfloat16
        want = jnp.bfloat16 if bf16 else jnp.float32
        if isinstance(queries, jax.Array):
            if queries.ndim != 2 or queries.shape[-1] != self.dim:
                queries = queries.reshape(-1, self.dim)
            if queries.dtype != want:
                queries = queries.astype(want)
        else:
            queries = np.asarray(queries, dtype=np.float32).reshape(-1, self.dim)
            queries = queries.astype(want, copy=False)
        # a padded host batch is handed to the program as it is: the call
        # transfers it
        q_pad, nq = pad_queries_pow2(queries, self.dim)
        # pow2 shape bucketing: serving traffic arrives at ragged batch sizes
        # and per-request k; padding both to the next power of two bounds the
        # kernel's jit cache at O(log) entries instead of one compile per size
        k_eff = max(1, min(k, self.capacity))
        k_pad = min(next_pow2(k_eff), self.capacity)
        data = (
            self._data
            if bf16 or self._data.dtype == jnp.float32
            else self._data.astype(jnp.float32)
        )
        top_scores, top_idx = _search_kernel(
            data, self._valid, self._norms, q_pad, k_pad, self.metric
        )
        return top_scores, top_idx, nq, k_eff


class BruteForceKnnIndex:
    """ExternalIndex-protocol adapter over DenseKNNStore (engine-facing).

    Parity: reference ``BruteForceKNNIndex`` (``brute_force_knn_integration.rs:22``) with its
    auxiliary filter data support (jmespath replaced by a python callable / jsonpath-lite).
    """

    def __init__(
        self,
        dim: int,
        metric: str = "l2sq",
        initial_capacity: int = 1024,
        mesh: Any = None,
        _store: Any = None,
    ):
        if _store is not None:
            # subclass-provided store (IvfKnnIndex): every other attribute
            # initializes here so subclasses never copy this tail
            self.store: Any = _store
        elif mesh is not None:
            from pathway_tpu.parallel.knn_sharded import ShardedKNNStore

            self.store = ShardedKNNStore(
                mesh, dim, metric=metric, initial_capacity=initial_capacity
            )
        else:
            self.store = DenseKNNStore(
                dim, metric=metric, initial_capacity=initial_capacity
            )
        self.filter_data: Dict[Any, Any] = {}

    def add(self, key: Any, vector: Any, filter_data: Any = None) -> None:
        self.store.add(key, _as_vector(vector))
        if filter_data is not None:
            self.filter_data[key] = filter_data

    def add_many(
        self, keys: List[Any], vectors: List[Any], filter_data: List[Any] | None = None
    ) -> None:
        """Bulk ingest: ONE staging append + one capacity jump for the whole batch
        (per-row adds through a growing device array would pay an XLA compile per
        capacity step)."""
        self.store.add_many(keys, np.stack([np.asarray(_as_vector(v)) for v in vectors]))
        if filter_data is not None:
            for k, f in zip(keys, filter_data):
                if f is not None:
                    self.filter_data[k] = f

    def remove(self, key: Any) -> None:
        self.store.remove(key)
        self.filter_data.pop(key, None)

    # -- rebuildable-descriptor contract (membership handoff) ----------------

    def rebuild_descriptor(self) -> "Dict[str, Any] | None":
        """The index content as a host-side descriptor another process can
        rebuild the SAME index from (keys + vectors + filter data) — the
        membership preflight's alternative to the blanket device-resident
        refusal. ``None`` when the backing store cannot export (a typed
        refusal is kept for those)."""
        export = getattr(self.store, "export_rows", None)
        if export is None:
            return None
        keys, vecs = export()
        desc: Dict[str, Any] = {
            "keys": keys,
            "vectors": vecs,
            "filter_data": dict(self.filter_data),
        }
        quant_state = getattr(self.store, "quant_state", None)
        if quant_state is not None:
            # quantized state joins the membership/checkpoint protocols:
            # mode + dtype + per-page sidecars ride the descriptor so the
            # receiving side can verify it serves the SAME tower geometry
            desc["quant"] = quant_state()
        return desc

    def iter_rebuild_fragments(
        self, rows_per_fragment: int
    ) -> "Tuple[Dict[str, Any], Any]":
        """Streaming form of :meth:`rebuild_descriptor` for the replica-feed
        bootstrap: a small header (filter data + quant sidecars) plus an
        iterator of bounded ``{"keys", "vectors"}`` row fragments, at most
        ``rows_per_fragment`` rows each. Stores with a native page-walking
        export (the tiered IVF store) stream without ever concatenating the
        corpus; dense stores chunk one host gather."""
        header: Dict[str, Any] = {
            "filter_data": dict(self.filter_data),
            # replica children construct their index FROM the header (they
            # have no graph to read the dim off), so geometry rides along
            "dim": int(getattr(self.store, "dim", 0)),
            "metric": str(getattr(self.store, "metric", "l2sq")),
        }
        quant_state = getattr(self.store, "quant_state", None)
        if quant_state is not None:
            header["quant"] = quant_state()
        stream = getattr(self.store, "iter_export_fragments", None)
        if stream is not None:
            def native() -> Any:
                for keys, vecs in stream(rows_per_fragment):
                    yield {"keys": keys, "vectors": vecs}

            return header, native()
        export = getattr(self.store, "export_rows", None)
        if export is None:
            raise RuntimeError(
                "index store cannot export rows; replica bootstrap is refused "
                "for device-opaque stores (same contract as rebuild_descriptor)"
            )
        keys, vecs = export()

        def chunked() -> Any:
            for lo in range(0, max(len(keys), 1), rows_per_fragment):
                yield {
                    "keys": list(keys[lo : lo + rows_per_fragment]),
                    "vectors": np.asarray(
                        vecs[lo : lo + rows_per_fragment], dtype=np.float32
                    ),
                }

        return header, chunked()

    def install_descriptor_header(self, header: Dict[str, Any]) -> None:
        """Install the non-row half of a descriptor (filter data; quant mode
        verification). A descriptor whose quantization mode differs from this
        store's is a typed refusal (``QuantConfigError``) — replicating fp32
        geometry into an int8 replica (or vice versa) must fail loudly, never
        serve silently mismatched scores."""
        quant = header.get("quant")
        if quant is not None:
            from pathway_tpu.ops.knn_quant import QuantConfigError

            want = str(quant.get("mode", "off"))
            have = str(getattr(self.store, "quant", "off"))
            if want != have:
                raise QuantConfigError(
                    f"rebuild descriptor carries quant mode {want!r} but this "
                    f"store runs {have!r}: replication across quantization "
                    "modes is refused (set PATHWAY_IVF_QUANT consistently)"
                )
        self.filter_data = dict(header.get("filter_data", {}))

    def install_descriptor_rows(self, keys: List[Any], vectors: Any) -> None:
        """Install one bounded row fragment (bulk append — quantized stores
        regenerate their codes on append, bit-identically per the
        ``quant_state`` contract)."""
        keys = list(keys)
        if keys:
            self.store.add_many(keys, np.asarray(vectors, dtype=np.float32))

    def install_rebuild_descriptor(self, desc: Dict[str, Any]) -> None:
        """Rebuild this (fresh) index from a :meth:`rebuild_descriptor`
        export: one bulk ingest, filter data restored alongside (the
        monolithic form of the header + fragment install pair above)."""
        self.install_descriptor_header(desc)
        self.install_descriptor_rows(
            list(desc.get("keys", [])), desc.get("vectors")
        )

    def search(self, query_vector: Any, limit: int, filter_expr: Any = None) -> List[tuple]:
        return self.search_many([query_vector], [limit], [filter_expr])[0]

    def search_many(
        self,
        query_vectors: List[Any],
        limits: List[int],
        filter_exprs: List[Any] | None = None,
    ) -> List[List[tuple]]:
        """Answer a whole commit's queries with ONE device matmul+top-k (the per-batch
        kernel the reference runs per worker, ``brute_force_knn_integration.rs:113``)."""
        n = len(query_vectors)
        if n == 0 or len(self.store) == 0:
            return [[] for _ in range(n)]
        limits = [int(l) for l in limits]
        if max(limits) <= 0:
            return [[] for _ in range(n)]
        has_filter = filter_exprs is not None and any(
            f is not None for f in filter_exprs
        )
        overfetch = max(limits) if not has_filter else max(max(limits) * 4, 16)
        overfetch = min(overfetch, max(len(self.store), 1))
        with tracing.trace_span("search", attrs={"queries": n}):
            with tracing.trace_span("search.prepare"):
                # host rows (the serving path's: views of one array per
                # encoder tick) are stacked in numpy; a batch that already
                # lives on the device goes to the store whole
                q: Any = (
                    query_vectors
                    if isinstance(query_vectors, jax.Array)
                    else np.stack([_as_vector(v) for v in query_vectors])
                )
            scores, idx, valid = self.store.search_batch(q, overfetch)
            with tracing.trace_span("search.assemble"):
                return self._assemble(scores, idx, valid, limits, filter_exprs)

    def _assemble(
        self,
        scores: np.ndarray,
        idx: np.ndarray,
        valid: np.ndarray,
        limits: List[int],
        filter_exprs: List[Any] | None,
    ) -> List[List[tuple]]:
        """Host side of a search: slots to keys, filters, each query's limit."""
        from pathway_tpu.stdlib.indexing.filters import matches_filter

        n = len(limits)
        results: List[List[tuple]] = []
        for qi in range(n):
            if limits[qi] <= 0:
                results.append([])
                continue
            flt = filter_exprs[qi] if filter_exprs is not None else None
            out: List[tuple] = []
            for j in range(idx.shape[1]):
                if not valid[qi, j]:
                    continue
                key = self.store.key_of.get(int(idx[qi, j]))
                if key is None:
                    continue
                if flt is not None and not matches_filter(
                    self.filter_data.get(key), flt
                ):
                    continue
                out.append((key, float(scores[qi, j])))
                if len(out) >= limits[qi]:
                    break
            results.append(out)
        return results


class LshKnnIndex:
    """Random-projection LSH (reference ``stdlib/ml/classifiers/_knn_lsh.py:64``), with the
    bucket scoring matmul on the TPU: candidates from bucket intersection, exact re-rank via
    the dense kernel over the candidate subset."""

    def __init__(
        self,
        dim: int,
        metric: str = "l2sq",
        bucket_length: float = 4.0,
        n_or: int = 8,
        n_and: int = 4,
        seed: int = 0,
    ):
        self.dim = dim
        self.metric = metric
        rng = np.random.default_rng(seed)
        self.projections = rng.normal(size=(n_or, n_and, dim)).astype(np.float32)
        self.offsets = rng.uniform(0, bucket_length, size=(n_or, n_and)).astype(np.float32)
        self.bucket_length = bucket_length
        self.n_or = n_or
        self.buckets: List[Dict[tuple, set]] = [dict() for _ in range(n_or)]
        self.vectors: Dict[Any, np.ndarray] = {}
        self.filter_data: Dict[Any, Any] = {}

    def _bucket_ids(self, vector: np.ndarray) -> List[tuple]:
        # (n_or, n_and) integer bucket coordinates
        proj = np.einsum("oad,d->oa", self.projections, vector)
        ids = np.floor((proj + self.offsets) / self.bucket_length).astype(np.int64)
        return [tuple(ids[o]) for o in range(self.n_or)]

    def add(self, key: Any, vector: Any, filter_data: Any = None) -> None:
        vector = _as_vector(vector)
        if key in self.vectors:
            self.remove(key)
        self.vectors[key] = vector
        for o, bid in enumerate(self._bucket_ids(vector)):
            self.buckets[o].setdefault(bid, set()).add(key)
        if filter_data is not None:
            self.filter_data[key] = filter_data

    def remove(self, key: Any) -> None:
        vector = self.vectors.pop(key, None)
        if vector is None:
            return
        for o, bid in enumerate(self._bucket_ids(vector)):
            bucket = self.buckets[o].get(bid)
            if bucket:
                bucket.discard(key)
        self.filter_data.pop(key, None)

    def search(self, query_vector: Any, limit: int, filter_expr: Any = None) -> List[tuple]:
        query = _as_vector(query_vector)
        candidates: set = set()
        for o, bid in enumerate(self._bucket_ids(query)):
            candidates |= self.buckets[o].get(bid, set())
        if not candidates:
            return []
        from pathway_tpu.stdlib.indexing.filters import matches_filter

        if filter_expr is not None:
            candidates = {
                c for c in candidates if matches_filter(self.filter_data.get(c), filter_expr)
            }
            if not candidates:
                return []
        cand = list(candidates)
        matrix = np.stack([self.vectors[c] for c in cand])
        scores = _score_candidates(jnp.asarray(matrix), jnp.asarray(query), self.metric)
        scores = np.asarray(scores)
        order = np.argsort(-scores)[:limit]
        return [(cand[i], float(scores[i])) for i in order]


@functools.partial(jax.jit, static_argnames=("metric",))
def _score_candidates(matrix: jax.Array, query: jax.Array, metric: str) -> jax.Array:
    scores = matrix @ query
    if metric == "l2sq":
        scores = -(jnp.sum(matrix * matrix, axis=1) + jnp.sum(query * query) - 2.0 * scores)
    elif metric == "cos":
        scores = scores / jnp.maximum(
            jnp.linalg.norm(matrix, axis=1) * jnp.linalg.norm(query), 1e-30
        )
    return scores


def _as_vector(value: Any) -> np.ndarray:
    """One vector cell as a fresh host float32 ``(dim,)`` array (a device
    array is fetched)."""
    if isinstance(value, (np.ndarray, jax.Array, tuple, list)):
        return np.array(value, dtype=np.float32).reshape(-1)
    raise TypeError(f"expected a vector, got {type(value).__name__}")


class IvfKnnIndex(BruteForceKnnIndex):
    """ExternalIndex-protocol adapter over the IVF-Flat store (the reference's
    approximate index role — USearch HNSW — served the TPU way; see
    ``ops/knn_ivf.py``)."""

    def __init__(
        self,
        dim: int,
        metric: str = "l2sq",
        initial_capacity: int = 1024,
        n_clusters: int = 64,
        n_probe: int = 8,
        mesh: Any = None,
        tiered: "bool | None" = None,
    ):
        from pathway_tpu.ops.knn_tiers import tiering_enabled

        if tiered is None:
            tiered = tiering_enabled()
        if mesh is not None:
            from pathway_tpu.parallel.knn_sharded import ShardedIvfKnnStore

            store: Any = ShardedIvfKnnStore(
                mesh,
                dim,
                metric=metric,
                initial_capacity=initial_capacity,
                n_clusters=n_clusters,
                n_probe=n_probe,
                tiered=tiered,
            )
        elif tiered:
            from pathway_tpu.ops.knn_tiers import TieredIvfKnnStore

            store = TieredIvfKnnStore(
                dim,
                metric=metric,
                initial_capacity=initial_capacity,
                n_clusters=n_clusters,
                n_probe=n_probe,
            )
        else:
            from pathway_tpu.ops.knn_ivf import IvfKnnStore

            store = IvfKnnStore(
                dim,
                metric=metric,
                initial_capacity=initial_capacity,
                n_clusters=n_clusters,
                n_probe=n_probe,
            )
        super().__init__(
            dim,
            metric=metric,
            initial_capacity=initial_capacity,
            _store=store,
        )
