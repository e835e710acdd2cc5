"""What the slot-based decoders share on the host: the parameters, the slots
and the two jitted programs, as the generation service drives them.

A model's file (``models/lfm2.py``, ``models/mistral4.py``,
``models/falcon_h1.py``) gives the two
programs, ``lm_prefill(params, state, ids, length, slot, *, cfg)`` and
``lm_decode(params, state, active, *, cfg)``, each returning (state, tokens,
counts), with ``init_params`` and ``init_state``; a subclass names them and
the per-call counts its programs return beside their tokens (``count_names``:
one number or a vector of as many), which the service sums under those names.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std", "mean"))
def _draw(key, *, shape, dtype, std, mean):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape", "name"))
def _draw_ssm_vector(key, *, shape, name):
    """Mamba-2's own initialisation of a state-space mixer's per-head vectors:
    ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of ``exp U(log
    1e-3, log 1e-1)``, ``D = 1``."""
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return jnp.ones(shape, jnp.float32)


def random_params(shapes: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Random parameters for a tree of shapes, made on the device one array at
    a time, by each leaf's name: matrices normal at ``1/sqrt(fan in)``, the
    table (``embed``) at 0.02, norms around 1, an ``expert_bias`` at 0.05, a
    convolution's bias (``conv_b``) at 0.02, a state-space mixer's ``A_log``,
    ``dt_bias`` and ``D`` as ``_draw_ssm_vector`` has them. A leaf's key follows
    from its place in its own tree, so a name added here moves no other draw."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = path[-1].key
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        if name in ("A_log", "dt_bias", "D"):
            out.append(_draw_ssm_vector(key, shape=leaf.shape, name=name))
            continue
        if name.endswith("norm"):
            std, mean = 0.1, 1.0
        elif name == "expert_bias":
            std, mean = 0.05, 0.0
        elif name in ("embed", "conv_b"):
            std, mean = 0.02, 0.0
        else:  # a matrix: the axis before the last is the one summed over
            std, mean = float(leaf.shape[-2 if name != "conv_w" else -1]) ** -0.5, 0.0
        out.append(_draw(key, shape=leaf.shape, dtype=leaf.dtype, std=std, mean=mean))
    return jax.tree_util.tree_unflatten(treedef, out)


class SlotDecoder:
    """Not thread-safe: one thread owns it."""

    count_names: Tuple[str, ...]
    lm_prefill: Callable[..., Any]
    lm_decode: Callable[..., Any]
    init_params: Callable[..., Dict[str, Any]]
    init_state: Callable[..., Dict[str, Any]]

    def __init__(self, cfg: Any, params: Dict[str, Any] | None = None, *, slots: int,
                 max_prompt_tokens: int, max_new_tokens: int, prefill_buckets: Sequence[int], seed: int = 0):
        if max(prefill_buckets) < max_prompt_tokens:
            raise ValueError(f"the largest prefill bucket {max(prefill_buckets)} is under {max_prompt_tokens}")
        self.cfg, self.slots = cfg, int(slots)
        self.max_prompt_tokens, self.max_new_tokens = int(max_prompt_tokens), int(max_new_tokens)
        self.prefill_buckets = tuple(sorted(int(b) for b in prefill_buckets))
        self.weights_source = "given" if params is not None else "random-init"
        self.params = params if params is not None else type(self).init_params(cfg, seed)
        # room for the longest prompt and its tokens, to a multiple of 64
        self.max_len = -(-(self.prefill_buckets[-1] + self.max_new_tokens) // 64) * 64
        self.state = type(self).init_state(cfg, self.slots, self.max_len, self.params["embed"].dtype)

    def bucket_of(self, n_tokens: int) -> int:
        return next(b for b in self.prefill_buckets if b >= n_tokens)

    def prefill(self, slot: int, ids: Sequence[int]) -> Tuple[jax.Array, jax.Array]:
        """Enqueue one prompt's prefill into ``slot``: (first token, counts), on the device."""
        padded = np.zeros((self.bucket_of(len(ids)),), np.int32)
        padded[: len(ids)] = ids
        self.state, token, counts = type(self).lm_prefill(self.params, self.state, padded, np.int32(len(ids)),
                                                          np.int32(slot), cfg=self.cfg)
        return token, counts

    def decode(self, active: Any) -> Tuple[jax.Array, jax.Array]:
        """Enqueue one step over all slots: (a token a slot, counts), on the device."""
        self.state, tokens, counts = type(self).lm_decode(self.params, self.state, active, cfg=self.cfg)
        return tokens, counts

    def warm(self) -> None:
        """Compile every program the service can call: each prefill bucket, the step."""
        for bucket in self.prefill_buckets:
            self.prefill(0, [0] * min(bucket, self.max_prompt_tokens))
        tokens, _ = self.decode(np.zeros((self.slots,), bool))
        tokens.block_until_ready()

    @classmethod
    def compiled_programs(cls) -> int:
        """Programs compiled so far, over every decoder of this kind in the process."""
        return int(cls.lm_prefill._cache_size() + cls.lm_decode._cache_size())
