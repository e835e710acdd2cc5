"""Key-hash exchange: the dataflow ``Exchange`` pact on a device mesh.

The reference routes rows to workers by the low bits of the 128-bit key
(``src/engine/dataflow/shard.rs:15-20``) over timely's TCP/shared-memory channels. Here the
same routing becomes an on-device bucketed ``all_to_all`` over ICI: rows are bucketed by
``shard = key.lo & (n_shards - 1)``, padded to a fixed per-bucket capacity (XLA static
shapes), and exchanged in one collective. Host-side connectors instead pre-route with
:func:`shard_of_keys` before device upload (cheaper when data is already on the host).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from pathway_tpu.internals.keys import KEY_DTYPE, shard_of


def shard_of_keys(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Host-side routing: worker/shard id per key (low bits, reference parity)."""
    return shard_of(keys, n_shards)


def _bucket_counts(shard_ids: jax.Array, n_shards: int) -> jax.Array:
    return jnp.sum(shard_ids[None, :] == jnp.arange(n_shards)[:, None], axis=1)


@partial(jax.jit, static_argnames=("n_shards", "capacity"))
def bucket_rows(
    key_lo: jax.Array, values: jax.Array, n_shards: int, capacity: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Group rows by destination shard into fixed-capacity buckets.

    Returns ``(bucketed_values (n_shards, capacity, ...), valid (n_shards, capacity),
    dropped_count)``. Rows beyond ``capacity`` for a bucket are counted as dropped — the
    caller sizes capacity from the host-side batch so this is a correctness assert, not a
    data-loss path.
    """
    shard_ids = (key_lo & (n_shards - 1)).astype(jnp.int32)
    order = jnp.argsort(shard_ids, stable=True)
    sorted_ids = shard_ids[order]
    sorted_vals = values[order]
    # position of each row within its bucket
    pos_in_bucket = jnp.arange(len(key_lo)) - jnp.searchsorted(
        sorted_ids, sorted_ids, side="left"
    )
    ok = pos_in_bucket < capacity
    flat_slot = sorted_ids * capacity + pos_in_bucket
    out = jnp.zeros((n_shards * capacity,) + values.shape[1:], dtype=values.dtype)
    out = out.at[jnp.where(ok, flat_slot, n_shards * capacity - 1)].set(
        jnp.where(ok.reshape((-1,) + (1,) * (values.ndim - 1)), sorted_vals, 0),
        mode="drop",
    )
    valid = jnp.zeros((n_shards * capacity,), dtype=bool)
    valid = valid.at[jnp.where(ok, flat_slot, 0)].set(ok, mode="drop")
    dropped = jnp.sum(~ok)
    return (
        out.reshape((n_shards, capacity) + values.shape[1:]),
        valid.reshape(n_shards, capacity),
        dropped,
    )


def exchange_by_key(
    mesh: Mesh,
    key_lo: jax.Array,
    values: jax.Array,
    *,
    axis: str = "data",
    capacity: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """All-to-all exchange of rows to their key-owning shard along a mesh axis.

    ``key_lo``/``values`` are sharded on their leading (row) axis over ``axis``. Each
    device buckets its local rows by destination, then one ``all_to_all`` delivers every
    bucket to its owner. Returns ``(values, valid)`` with leading row axis still sharded
    over ``axis`` — each shard now holds only rows it owns (padded; see ``valid``).
    """
    n_shards = mesh.shape[axis]
    if capacity is None:
        capacity = max(1, values.shape[0])  # conservative: all local rows → one bucket

    def local(k_lo: jax.Array, vals: jax.Array) -> tuple[jax.Array, jax.Array]:
        bucketed, valid, _ = bucket_rows(k_lo, vals, n_shards, capacity)
        recv = jax.lax.all_to_all(bucketed, axis, 0, 0, tiled=False)
        recv_valid = jax.lax.all_to_all(valid, axis, 0, 0, tiled=False)
        return (
            recv.reshape((n_shards * capacity,) + vals.shape[1:]),
            recv_valid.reshape(n_shards * capacity),
        )

    spec_in = P(axis, *([None] * (values.ndim - 1)))
    # check_vma off: the collectives below produce their outputs' replication
    # by construction
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), spec_in),
        out_specs=(spec_in, P(axis)),
        check_vma=False,
    )(key_lo, values)
