"""Segment reduction kernels — the groupby-reduce hot path.

TPU-native counterpart of the reference's incremental reduce (``src/engine/reduce.rs:22-56``
semigroup impls applied inside DD's ``reduce``). A commit's delta rows are assigned dense
segment ids (one per touched group) and reduced with vectorized kernels:

- large float32/bfloat16 batches lower to ``jax.ops.segment_sum`` under ``jit`` — XLA
  compiles the scatter-add for the VPU, and the batch stays on device when the caller's
  columns already live there;
- everything else uses exact host kernels (``np.add.at`` / ``np.bincount``) — int64 sums
  must not round-trip through float32, and tiny unit-test batches would lose to the
  host↔device transfer.

This mirrors the reference's semigroup-vs-recompute reducer classes: these kernels
serve the semigroup side (count/sum); recompute reducers keep per-group multisets.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Any

import numpy as np

from pathway_tpu.internals.shapes import next_pow2 as _next_pow2

# Below this, host↔device transfer dominates the reduction itself.
_DEVICE_THRESHOLD = 1 << 15


@lru_cache(maxsize=1)
def _jax():
    try:
        import jax

        return jax
    except Exception:  # pragma: no cover - jax is baked into this image
        return None


@lru_cache(maxsize=8)
def _jit_segment_sum(num_segments: int):
    # callers pad num_segments to a power of two so the per-commit touched-group
    # count doesn't retrace/recompile the kernel every batch
    jax = _jax()

    @jax.jit
    def kernel(values, segment_ids):
        return jax.ops.segment_sum(values, segment_ids, num_segments=num_segments)

    return kernel


# Above this row count, a configured multi-shard mesh routes the reduction through
# the key-hash exchange (mutable for tests/dryruns to force the collective path).
MESH_THRESHOLD = 1 << 15

# Opt-out for the float64 two-float-split mesh policy (set False to force f64 sums
# onto the exact host reduction even when a mesh is configured).
MESH_F64_SPLIT = True

# Magnitudes above this risk float32 partial-sum overflow on the mesh (f32 max is
# ~3.4e38; a 2^15-row batch of equal-sign values needs ~2^15 headroom) — such
# batches stay on the exact host path.
_F32_SAFE_MAX = 1e33


def segment_sum(
    values: np.ndarray,
    segment_ids: np.ndarray,
    num_segments: int,
    key_lo: np.ndarray | None = None,
) -> np.ndarray:
    """Sum ``values`` into ``num_segments`` buckets given per-row segment ids.

    Exactness contract: integer inputs reduce in int64 on host; small float batches
    reduce on host. float32 batches above the device threshold ride XLA. With a
    default mesh configured (``parallel.set_default_mesh``) and ``key_lo`` given,
    large float batches route through the mesh exchange (``groupby_sharded``) —
    float64 via a COMPENSATED TWO-FLOAT SPLIT (TPUs have no f64): each value splits
    into a float32 high part and a float32 residual, both ride the same exchange,
    and the halves recombine in float64 on host. Input-representation error is
    eliminated; accumulation error is that of two f32 segment sums (~1e-7 relative
    per summand), the documented engine policy for mesh-routed f64 reductions.
    """
    values = np.asarray(values)
    segment_ids = np.asarray(segment_ids, dtype=np.int64)
    jax = _jax()
    if jax is not None and key_lo is not None and values.dtype.kind == "f":
        from pathway_tpu.parallel.mesh import data_shards, get_default_mesh

        mesh = get_default_mesh()
        if data_shards(mesh) > 1 and len(values) >= MESH_THRESHOLD:
            from pathway_tpu.parallel.groupby_sharded import sharded_segment_sum

            key_lo = np.asarray(key_lo)
            if values.dtype == np.float32:
                return sharded_segment_sum(
                    mesh, key_lo, segment_ids, values, num_segments
                ).astype(values.dtype)
            if MESH_F64_SPLIT and np.max(np.abs(values), initial=0.0) < _F32_SAFE_MAX:
                hi = values.astype(np.float32)
                lo = (values - hi.astype(np.float64)).astype(np.float32)
                s_hi = sharded_segment_sum(mesh, key_lo, segment_ids, hi, num_segments)
                s_lo = sharded_segment_sum(mesh, key_lo, segment_ids, lo, num_segments)
                return s_hi.astype(np.float64) + s_lo.astype(np.float64)
            # overflow-risky or opted-out f64: exact host reduction
    if (
        jax is not None
        and values.dtype == np.float32
        and len(values) >= _DEVICE_THRESHOLD
    ):
        padded = _next_pow2(num_segments)
        out = _jit_segment_sum(padded)(values, segment_ids)
        return np.asarray(out)[:num_segments]
    if values.dtype == object:
        out_obj = np.zeros(num_segments, dtype=object)
        for i in range(len(values)):
            out_obj[segment_ids[i]] = out_obj[segment_ids[i]] + values[i]
        return out_obj
    out = np.zeros(num_segments, dtype=values.dtype if values.dtype.kind == "f" else np.int64)
    np.add.at(out, segment_ids, values)
    return out


def segment_count(
    segment_ids: np.ndarray, num_segments: int, weights: np.ndarray | None = None
) -> np.ndarray:
    """Count rows (or sum integer weights, e.g. +1/-1 diffs) per segment."""
    segment_ids = np.asarray(segment_ids, dtype=np.int64)
    if weights is None:
        return np.bincount(segment_ids, minlength=num_segments).astype(np.int64)
    out = np.zeros(num_segments, dtype=np.int64)
    np.add.at(out, segment_ids, np.asarray(weights, dtype=np.int64))
    return out


def segment_min(values: np.ndarray, segment_ids: np.ndarray, num_segments: int) -> np.ndarray:
    values = np.asarray(values)
    segment_ids = np.asarray(segment_ids, dtype=np.int64)
    if values.dtype.kind == "f":
        out = np.full(num_segments, np.inf, dtype=values.dtype)
    else:
        out = np.full(num_segments, np.iinfo(np.int64).max, dtype=np.int64)
    np.minimum.at(out, segment_ids, values)
    return out


def segment_max(values: np.ndarray, segment_ids: np.ndarray, num_segments: int) -> np.ndarray:
    values = np.asarray(values)
    segment_ids = np.asarray(segment_ids, dtype=np.int64)
    if values.dtype.kind == "f":
        out = np.full(num_segments, -np.inf, dtype=values.dtype)
    else:
        out = np.full(num_segments, np.iinfo(np.int64).min, dtype=np.int64)
    np.maximum.at(out, segment_ids, values)
    return out


def segment_slices(
    segment_ids: np.ndarray, num_segments: int | None = None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stable sort rows by segment: returns (order, starts, ends) such that
    ``order[starts[s]:ends[s]]`` are the row indices of segment ``s`` in input order.
    Segments with no rows get empty slices."""
    segment_ids = np.asarray(segment_ids, dtype=np.int64)
    order = np.argsort(segment_ids, kind="stable")
    sorted_ids = segment_ids[order]
    if num_segments is None:
        num_segments = int(sorted_ids[-1]) + 1 if len(sorted_ids) else 0
    starts = np.searchsorted(sorted_ids, np.arange(num_segments), side="left")
    ends = np.searchsorted(sorted_ids, np.arange(num_segments), side="right")
    return order, starts, ends
