"""Mesh-sharded groupby-reduce: the engine's grouped aggregation over the exchange pact.

The reference shards every ``reduce`` by routing rows to the worker owning the group key
(``src/engine/dataflow/shard.rs:15-20``; exchange inside DD's ``reduce``). Here the same
routing is one ``shard_map``: each device buckets its local rows by destination shard
(low bits of the group key), one ``all_to_all`` delivers the buckets over ICI, every
shard segment-sums the rows it owns, and a ``psum`` assembles the global per-group sums
(non-owned segments contribute zero, so the psum is also the ownership merge).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from pathway_tpu.parallel.exchange import bucket_rows


@partial(
    jax.jit,
    static_argnames=("mesh", "axis", "n_shards", "capacity", "num_segments"),
)
def _sharded_segment_sum_impl(
    key_lo: jax.Array,
    seg_ids: jax.Array,
    values: jax.Array,
    *,
    mesh: Mesh,
    axis: str,
    n_shards: int,
    capacity: int,
    num_segments: int,
) -> jax.Array:
    def local(k_lo: jax.Array, segs: jax.Array, vals: jax.Array) -> jax.Array:
        b_vals, valid, _ = bucket_rows(k_lo, vals, n_shards, capacity)
        b_segs, _, _ = bucket_rows(k_lo, segs, n_shards, capacity)
        rv = lax.all_to_all(b_vals, axis, 0, 0, tiled=False)
        rs = lax.all_to_all(b_segs, axis, 0, 0, tiled=False)
        rvalid = lax.all_to_all(valid, axis, 0, 0, tiled=False)
        vals_f = rv.reshape(-1)
        segs_f = rs.reshape(-1)
        ok = rvalid.reshape(-1)
        contrib = jnp.where(ok, vals_f, jnp.zeros((), dtype=vals_f.dtype))
        local_sum = jax.ops.segment_sum(
            contrib, jnp.where(ok, segs_f, 0), num_segments=num_segments
        )
        return lax.psum(local_sum, axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,  # psum output is replicated by construction
    )(key_lo, seg_ids, values)


def sharded_segment_sum(
    mesh: Mesh,
    key_lo: np.ndarray,
    seg_ids: np.ndarray,
    values: np.ndarray,
    num_segments: int,
    axis: str = "data",
) -> np.ndarray:
    """Sum ``values`` per segment with rows exchanged to their key-owning shard first.

    Host-side entry: pads the batch so rows split evenly over the axis, runs the
    exchange + local reduce + psum on the mesh, returns the (num_segments,) host array.
    """
    n_shards = mesh.shape[axis]
    n = len(values)
    # pad rows and segment count to powers of two so varying per-commit batch sizes
    # and touched-group counts reuse one compiled collective program
    padded_local = 1 << max(0, (-(-n // n_shards) - 1).bit_length())
    padded_n = padded_local * n_shards
    padded_m = 1 << max(0, (num_segments - 1).bit_length())
    pad = padded_n - n
    if pad:
        key_lo = np.concatenate([key_lo, np.zeros(pad, dtype=key_lo.dtype)])
        seg_ids = np.concatenate([seg_ids, np.zeros(pad, dtype=seg_ids.dtype)])
        values = np.concatenate([values, np.zeros(pad, dtype=values.dtype)])
    out = _sharded_segment_sum_impl(
        jnp.asarray(key_lo.astype(np.uint32)),
        jnp.asarray(seg_ids.astype(np.int32)),
        jnp.asarray(values.astype(np.float32)),
        mesh=mesh,
        axis=axis,
        n_shards=n_shards,
        capacity=padded_local,
        num_segments=padded_m,
    )
    return np.asarray(out)[:num_segments]
