"""Mesh-sharded KNN: the multi-worker sharded index (BASELINE config #5).

The reference shards index rows across workers by key and exchanges query/result streams
over TCP (``src/engine/dataflow/operators/external_index.rs`` + ``shard.rs``). Here the
vector store is ONE logical ``(capacity, dim)`` array row-sharded over the ``data`` mesh
axis; a search is a ``shard_map``: each device computes a local MXU matmul + ``top_k``
over its rows, then one ``all_gather`` of (n_shards × k) candidates and a final merge
``top_k`` — the ICI all-gather top-k merge pattern.

Rows shard contiguously (NamedSharding block layout); the host allocator hands out slots
round-robin across shards so loads stay balanced the way the reference's key-hash routing
does.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.ops.knn import (
    SlotIngestMixin,
    next_pow2,
    pad_pow2,
    pad_queries_pow2,
    pow2_target,
)


def _local_search(
    data: jax.Array,  # (cap_local, dim) this shard's rows
    valid: jax.Array,  # (cap_local,)
    norms: jax.Array,  # (cap_local,)
    queries: jax.Array,  # (q, dim) replicated
    k: int,
    metric: str,
    axis: str,
) -> Tuple[jax.Array, jax.Array]:
    scores = jnp.dot(queries, data.T, preferred_element_type=jnp.float32)
    if metric == "l2sq":
        qn = jnp.sum(queries * queries, axis=1, keepdims=True)
        scores = -(qn + norms[None, :] - 2.0 * scores)
    elif metric == "cos":
        qn = jnp.linalg.norm(queries, axis=1, keepdims=True)
        scores = scores / jnp.maximum(qn * jnp.sqrt(norms)[None, :], 1e-30)
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    local_scores, local_idx = lax.top_k(scores, k)  # (q, k) per shard
    shard = lax.axis_index(axis)
    # contiguous row sharding: shard s owns global rows [s * cap_local, (s+1) * cap_local)
    global_idx = shard * data.shape[0] + local_idx
    all_scores = lax.all_gather(local_scores, axis, axis=1)  # (q, n_shards, k)
    all_idx = lax.all_gather(global_idx, axis, axis=1)
    q = queries.shape[0]
    flat_scores = all_scores.reshape(q, -1)
    flat_idx = all_idx.reshape(q, -1)
    top_scores, pos = lax.top_k(flat_scores, k)
    return top_scores, jnp.take_along_axis(flat_idx, pos, axis=1)


class ShardedKNNStore(SlotIngestMixin):
    """Keyed dense vector store row-sharded over a mesh axis.

    Host API matches :class:`pathway_tpu.ops.knn.DenseKNNStore` (add/remove/search_batch)
    so the engine's external-index operator can swap it in when a mesh is configured;
    the staged-slot ingest comes from the shared :class:`SlotIngestMixin`.
    """

    def __init__(
        self,
        mesh: Mesh,
        dim: int,
        metric: str = "l2sq",
        axis: str = "data",
        initial_capacity: int = 1024,
    ):
        assert metric in ("l2sq", "cos", "ip")
        self.mesh = mesh
        self.axis = axis
        self.dim = dim
        self.metric = metric
        self.n_shards = mesh.shape[axis]
        # capacity divisible by n_shards so every shard holds capacity // n rows
        self.capacity = -(-initial_capacity // self.n_shards) * self.n_shards
        self._row_sharding = NamedSharding(mesh, P(axis, None))
        self._vec_sharding = NamedSharding(mesh, P(axis))
        self._data = jax.device_put(
            jnp.zeros((self.capacity, dim), dtype=jnp.float32), self._row_sharding
        )
        self._valid = jax.device_put(
            jnp.zeros((self.capacity,), dtype=bool), self._vec_sharding
        )
        self._norms = jax.device_put(
            jnp.zeros((self.capacity,), dtype=jnp.float32), self._vec_sharding
        )
        self.slot_of: Dict[Any, int] = {}
        self.key_of: Dict[int, Any] = {}
        self._free: List[int] = _interleaved_free_list(0, self.capacity, self.n_shards)
        self._staged_vecs: List[np.ndarray] = []
        self._staged_slots: List[int] = []
        self._staged_invalid: List[int] = []
        self._update = jax.jit(
            _apply_updates,
            donate_argnums=(0, 1, 2),
            out_shardings=(self._row_sharding, self._vec_sharding, self._vec_sharding),
        )
        # jitted shard_map search per k bucket, built on first use: a fresh
        # jax.jit(shard_map(...)) per call would retrace and recompile per query
        self._search: Dict[int, Any] = {}

    def __len__(self) -> int:
        return len(self.slot_of)


    def _grow(self, target: int | None = None) -> None:
        self._flush()
        old = self.capacity
        self.capacity = pow2_target(old, target)
        extra = self.capacity - old
        self._data = jax.device_put(
            jnp.concatenate([self._data, jnp.zeros((extra, self.dim), jnp.float32)]),
            self._row_sharding,
        )
        self._valid = jax.device_put(
            jnp.concatenate([self._valid, jnp.zeros((extra,), bool)]), self._vec_sharding
        )
        self._norms = jax.device_put(
            jnp.concatenate([self._norms, jnp.zeros((extra,), jnp.float32)]),
            self._vec_sharding,
        )
        self._free = _interleaved_free_list(old, self.capacity, self.n_shards) + self._free

    def _flush(self) -> None:
        if not (self._staged_slots or self._staged_invalid):
            return
        if self._staged_slots:
            set_slots = np.array(self._staged_slots, dtype=np.int32)
            set_vecs = np.stack(self._staged_vecs).astype(np.float32)
        else:
            set_slots = np.zeros((0,), dtype=np.int32)
            set_vecs = np.zeros((0, self.dim), dtype=np.float32)
        still_invalid = [s for s in set(self._staged_invalid) if s not in self.key_of]
        inv_slots = np.array(sorted(still_invalid), dtype=np.int32)
        set_slots, set_vecs, _ = pad_pow2(set_slots, set_vecs)
        inv_slots, _, _ = pad_pow2(inv_slots)
        self._data, self._valid, self._norms = self._update(
            self._data,
            self._valid,
            self._norms,
            jnp.asarray(set_slots),
            jnp.asarray(set_vecs),
            jnp.asarray(inv_slots),
        )
        self._staged_slots, self._staged_vecs, self._staged_invalid = [], [], []

    # -- search --

    def search_batch(
        self, queries: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        self._flush()
        if isinstance(queries, jax.Array):
            # device-resident queries feed the sharded kernel without a host bounce
            queries = queries.astype(jnp.float32).reshape(-1, self.dim)
        else:
            queries = np.asarray(queries, dtype=np.float32).reshape(-1, self.dim)
        cap_local = self.capacity // self.n_shards
        k_eff = max(1, min(k, cap_local))
        # the dense store's bucketing policy: pow2 query count (floor 8) and
        # pow2 k bound the compiles at O(log) however ragged the traffic is
        q_dev, nq = pad_queries_pow2(jnp.asarray(queries), self.dim)
        k_pad = min(next_pow2(k_eff), cap_local)
        fn = self._search.get(k_pad)
        if fn is None:
            fn = self._search[k_pad] = jax.jit(
                jax.shard_map(
                    functools.partial(
                        _local_search, k=k_pad, metric=self.metric, axis=self.axis
                    ),
                    mesh=self.mesh,
                    in_specs=(P(self.axis, None), P(self.axis), P(self.axis), P()),
                    out_specs=(P(), P()),
                    check_vma=False,  # all_gather output is replicated by construction
                )
            )
        top_scores, top_idx = fn(self._data, self._valid, self._norms, q_dev)
        scores, idx = jax.device_get((top_scores[:nq, :k_eff], top_idx[:nq, :k_eff]))
        return scores, idx, np.isfinite(scores)


def _axis_devices(mesh: Mesh, axis: str) -> List[Any]:
    """One representative device per position along ``axis`` (index 0 of every
    other mesh axis)."""
    arr = np.asarray(mesh.devices)
    ax = list(mesh.axis_names).index(axis)
    arr = np.moveaxis(arr, ax, 0)
    return list(arr.reshape(arr.shape[0], -1)[:, 0])


class ShardedIvfKnnStore:
    """Row-partitioned IVF-Flat over a mesh axis: one :class:`IvfKnnStore` per
    shard, each pinned to its own device (centroids, inverted lists, and the
    fused probe→gather→score kernel all run shard-local), with the per-shard
    top-k candidates merged into the global top-k — the same all-gather top-k
    merge contract as :class:`ShardedKNNStore`, performed host-side because the
    per-shard IVF state (assignments, CSR) is host-managed.

    Keys route round-robin to shards (the reference's key-hash balance), and
    global slot ids interleave as ``local_slot * n_shards + shard`` so the
    engine's ``key_of`` contract is preserved."""

    def __init__(
        self,
        mesh: Mesh,
        dim: int,
        metric: str = "l2sq",
        axis: str = "data",
        initial_capacity: int = 1024,
        n_clusters: int = 64,
        n_probe: int = 8,
        dtype: Any = None,
        tiered: bool = False,
        quant: "str | None" = None,
    ):
        from pathway_tpu.ops.knn_ivf import IvfKnnStore
        from pathway_tpu.ops.knn_quant import quant_mode

        # quantized blocks live in the tiered sub-stores only — the flat
        # per-shard IvfKnnStore path stays fp32, so the resolved mode must
        # say so (descriptor mode checks compare against this property)
        self._quant = quant_mode(quant) if tiered else "off"

        devices = _axis_devices(mesh, axis)
        self.mesh = mesh
        self.axis = axis
        self.dim = dim
        self.metric = metric
        self.n_shards = len(devices)
        self.tiered = bool(tiered)
        per_shard_cap = max(16, -(-initial_capacity // self.n_shards))
        if tiered:
            # one tiered sub-store per shard device, the per-chip HBM budget
            # split evenly (each shard manages its own hot set / prefetch /
            # background rebuild — the swap stays shard-local, riding each
            # shard's own commit boundary)
            from pathway_tpu.ops.knn_tiers import TieredIvfKnnStore, hbm_budget_bytes

            budget = hbm_budget_bytes()
            per_shard_budget = budget // self.n_shards if budget else 0
            self.stores: List[Any] = [
                TieredIvfKnnStore(
                    dim,
                    metric=metric,
                    initial_capacity=per_shard_cap,
                    n_clusters=n_clusters,
                    n_probe=n_probe,
                    device=dev,
                    hbm_budget_bytes=per_shard_budget,
                    quant=self._quant,
                )
                for dev in devices
            ]
        else:
            kwargs: dict = {} if dtype is None else {"dtype": dtype}
            self.stores = [
                IvfKnnStore(
                    dim,
                    metric=metric,
                    initial_capacity=per_shard_cap,
                    n_clusters=n_clusters,
                    n_probe=n_probe,
                    device=dev,
                    **kwargs,
                )
                for dev in devices
            ]
        self.slot_of: Dict[Any, int] = {}
        self.key_of: Dict[int, Any] = {}
        self._shard_of: Dict[Any, int] = {}
        self._rr = 0

    def __len__(self) -> int:
        return len(self.slot_of)

    def _shard_for(self, key: Any) -> int:
        shard = self._shard_of.get(key)
        if shard is None:
            shard = self._rr
            self._rr = (self._rr + 1) % self.n_shards
            self._shard_of[key] = shard
        return shard

    def _register(self, key: Any, shard: int) -> None:
        old = self.slot_of.pop(key, None)
        if old is not None:
            self.key_of.pop(old, None)
        gid = self.stores[shard].slot_of[key] * self.n_shards + shard
        self.slot_of[key] = gid
        self.key_of[gid] = key

    def add(self, key: Any, vector: np.ndarray) -> None:
        shard = self._shard_for(key)
        self.stores[shard].add(key, vector)
        self._register(key, shard)

    def add_many(self, keys: List[Any], vectors: np.ndarray) -> None:
        vectors = np.asarray(vectors, dtype=np.float32).reshape(len(keys), self.dim)
        by_shard: Dict[int, List[int]] = {}
        for i, key in enumerate(keys):
            by_shard.setdefault(self._shard_for(key), []).append(i)
        for shard, idxs in by_shard.items():
            self.stores[shard].add_many([keys[i] for i in idxs], vectors[idxs])
            for i in idxs:
                self._register(keys[i], shard)

    def remove(self, key: Any) -> None:
        shard = self._shard_of.pop(key, None)
        if shard is None:
            return
        self.stores[shard].remove(key)
        gid = self.slot_of.pop(key, None)
        if gid is not None:
            self.key_of.pop(gid, None)

    def _flush(self) -> None:
        for store in self.stores:
            store._flush()

    def search_batch(
        self, queries: Any, k: int
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        from pathway_tpu.ops.knn import topk_rows

        queries = np.asarray(queries, dtype=np.float32).reshape(-1, self.dim)
        k_eff = max(1, k)
        nq = queries.shape[0]
        parts_s: List[np.ndarray] = []
        parts_i: List[np.ndarray] = []

        def globalize(s: np.ndarray, i: np.ndarray, shard: int) -> None:
            gi = np.where(i >= 0, i * self.n_shards + shard, -1)
            if s.shape[1] < k_eff:
                pad = k_eff - s.shape[1]
                s = np.pad(s, ((0, 0), (0, pad)), constant_values=-np.inf)
                gi = np.pad(gi, ((0, 0), (0, pad)), constant_values=-1)
            parts_s.append(s[:, :k_eff])
            parts_i.append(gi[:, :k_eff])

        if jax.default_backend() == "cpu" or self.tiered:
            # host BLAS path per shard — host-bound, nothing to overlap (the
            # tiered sub-stores dispatch their own hot-block device GEMMs and
            # prefetch staging inside search_batch)
            for shard, store in enumerate(self.stores):
                s, i, _v = store.search_batch(queries, k_eff)
                globalize(s, i, shard)
        else:
            # launch EVERY shard's fused kernel before fetching any result:
            # dispatch is async, so the per-shard searches overlap across their
            # devices and batch latency is max-over-shards, not the sum
            launched = [
                store._search_device_launch(queries, k_eff)
                if store._prepare_search()
                else None
                for store in self.stores
            ]
            for shard, handle in enumerate(launched):
                if handle is None:
                    globalize(
                        np.full((nq, k_eff), -np.inf, dtype=np.float32),
                        np.full((nq, k_eff), -1, dtype=np.int64),
                        shard,
                    )
                else:
                    s, i = jax.device_get(handle)
                    globalize(s, i.astype(np.int64), shard)
        scores, idx = topk_rows(
            np.concatenate(parts_s, axis=1), np.concatenate(parts_i, axis=1), k_eff
        )
        return scores, idx, np.isfinite(scores)

    @property
    def quant(self) -> str:
        return self._quant

    def quant_state(self) -> Dict[str, Any]:
        """Aggregated quantization sidecar snapshot across shards — each
        sub-store's per-cluster scales keyed by ``"shard:cluster"`` so the
        descriptor contract stays flat while shard-local recalibration
        history survives the round-trip."""
        if self._quant == "off" or not self.tiered:
            return {"mode": "off"}
        clusters: Dict[str, Any] = {}
        for shard, store in enumerate(self.stores):
            state = store.quant_state()
            if state.get("mode") == "off":
                continue
            for cid, entry in state.get("clusters", {}).items():
                clusters[f"{shard}:{cid}"] = entry
        return {"mode": self._quant, "dtype": "int8", "clusters": clusters}

    def export_rows(self) -> Tuple[List[Any], np.ndarray]:
        """Every live (key, vector) pair across all shards — the rebuildable-
        descriptor contract shared with the single-chip stores."""
        keys: List[Any] = []
        parts: List[np.ndarray] = []
        for store in self.stores:
            shard_keys, shard_vecs = store.export_rows()
            keys.extend(shard_keys)
            if len(shard_keys):
                parts.append(np.asarray(shard_vecs, dtype=np.float32))
        if not parts:
            return keys, np.zeros((0, self.dim), dtype=np.float32)
        return keys, np.concatenate(parts)


def _interleaved_free_list(start: int, stop: int, n_shards: int) -> List[int]:
    """Free slots ordered so successive pops cycle shards (pop takes from the end)."""
    span = stop - start
    per_shard = span // n_shards
    order = [
        start + shard * per_shard + i
        for i in range(per_shard)
        for shard in range(n_shards)
    ]
    order.extend(range(start + per_shard * n_shards, stop))
    return order[::-1]


def _apply_updates(
    data: jax.Array,
    valid: jax.Array,
    norms: jax.Array,
    set_slots: jax.Array,
    set_vecs: jax.Array,
    inv_slots: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    data = data.at[set_slots].set(set_vecs, mode="drop")
    norms = norms.at[set_slots].set(jnp.sum(set_vecs * set_vecs, axis=1), mode="drop")
    valid = valid.at[set_slots].set(True, mode="drop")
    valid = valid.at[inv_slots].set(False, mode="drop")
    return data, valid, norms
