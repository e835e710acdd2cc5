"""Device-mesh construction.

Replaces the reference's worker/process topology config (``src/engine/dataflow/config.rs:88`` —
``PATHWAY_THREADS``/``PATHWAY_PROCESSES`` → timely ``CommunicationConfig``) with a named
``jax.sharding.Mesh``. Axis conventions:

- ``data``  — batch/row parallelism (the reference's hash-sharded worker axis);
- ``model`` — tensor parallelism inside kernels (no reference analog: the reference has no
  DNN compute; this axis exists because our hot path IS a DNN + matmul-KNN).

Multi-host: on a real pod, ``jax.devices()`` already spans hosts and ICI/DCN routing is
XLA's job — the same mesh code covers single-chip, one host × N chips, and N hosts.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh


def mesh_shape_for(n_devices: int, model_parallel: Optional[int] = None) -> tuple[int, int]:
    """(data, model) factorization. Prefers the largest model axis ≤4 that divides n
    (MiniLM has 12 heads → model axis must divide 12 for head-sharded TP)."""
    if model_parallel is None:
        for m in (4, 2, 1):
            if n_devices % m == 0 and 12 % m == 0:
                model_parallel = m
                break
        else:
            model_parallel = 1
    if n_devices % model_parallel:
        raise ValueError(f"{n_devices} devices not divisible by model={model_parallel}")
    return n_devices // model_parallel, model_parallel


def make_mesh(
    n_devices: Optional[int] = None,
    axis_names: Sequence[str] = ("data", "model"),
    model_parallel: Optional[int] = None,
) -> Mesh:
    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if n_devices > len(devices):
        raise ValueError(f"requested {n_devices} devices, have {len(devices)}")
    data, model = mesh_shape_for(n_devices, model_parallel)
    grid = np.asarray(devices[:n_devices]).reshape(data, model)
    return Mesh(grid, axis_names=tuple(axis_names))


_default_mesh: Optional[Mesh] = None


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    """Configure the mesh the ENGINE runs on (the reference's worker-count config,
    ``PATHWAY_THREADS``/``PATHWAY_PROCESSES`` → here a device mesh). When set with a
    ``data`` axis larger than 1, external KNN indexes build mesh-sharded stores and
    large groupby-reduce batches route through the key-hash exchange."""
    global _default_mesh
    _default_mesh = mesh


def get_default_mesh() -> Optional[Mesh]:
    return _default_mesh


def data_shards(mesh: Optional[Mesh]) -> int:
    if mesh is None or "data" not in mesh.axis_names:
        return 1
    return mesh.shape["data"]


def cpu_virtual_devices(n: int) -> None:
    """Request an n-device virtual CPU platform. Must run before jax initializes
    its backend; used by the multi-chip dry-run driver (the test conftest sets the
    same two variables itself, before importing jax)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")



def require_cpu_platform(who: str) -> None:
    """Stop a process that is one of several on this host unless JAX is on the
    CPU platform.

    An accelerator belongs to ONE process: the first rank to open it holds it,
    and every other rank fails inside libtpu with an error that names a lock
    file ("Run sudo rm /tmp/libtpu_lockfile"), not the cause. Until ranks are
    assigned chips (not built), ``spawn -n N`` ranks and replica children run
    with ``JAX_PLATFORMS=cpu``; anything else ends here, before the first
    commit, with the cause in the message. The chip path is one process."""
    why = (
        "but it is one of several processes on this host and an accelerator "
        "belongs to one process at a time. Run cluster (`spawn -n N`) and "
        "replica lanes with JAX_PLATFORMS=cpu; the chip path is one process "
        "(see chip_smoke.py). Per-rank chip assignment is not implemented."
    )
    try:
        backend = jax.default_backend()
    except RuntimeError as exc:
        raise RuntimeError(
            f"{who} could not open the accelerator JAX selected (another "
            f"process holds it), {why}"
        ) from exc
    if backend != "cpu":
        raise RuntimeError(f"{who} opened the {backend!r} platform, {why}")
