"""The grouped expert product the decoders share: sort by expert, XLA's own
grouped product over the stack of experts this process holds, unsort.

A router stays with its model (its scores, its bias, its normaliser differ
from family to family). What a model hands over is, for every (token, chosen
expert) pair, the expert's index *in the held stack* or ``held`` (the stack's
length) for "none": a token that is padding, a slot that holds no request, or
an expert that lives on another chip. Pairs of no expert sort last, enter no
group and add nothing; no token is dropped by a capacity limit and no expert
without a token is read. The pair's weight is the model's own, normalised over
whatever the model normalises over (all the chosen experts, held or not).

The exchange of routed tokens between the chips that share a layer is not
here: one chip computes what its own experts give and nothing stands in for
the others.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def precision(dtype: Any) -> Any:
    # float32 parameters (the CPU tests) are multiplied exactly; bfloat16 operands are one pass anyway
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def grouped_experts(p: Dict[str, jax.Array], h: jax.Array, local: jax.Array,
                    weights: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Every pair's SwiGLU through its expert, weighted and summed per token.

    ``p["w1"]``, ``p["w3"]`` (held, hidden, width) and ``p["w2"]`` (held, width,
    hidden): the held experts' stack. ``h`` (tokens, hidden, float32, already
    normed). ``local`` (tokens, k): each pair's index into the stack, ``held``
    for none. ``weights`` (tokens, k). Returns the float32 output (tokens,
    hidden) and the pairs each held expert got (held,)."""
    n, k = local.shape
    e, dtype = p["w1"].shape[0], p["w1"].dtype
    with jax.named_scope("moe_route"):
        # one row per (token, chosen expert), sorted by expert; expert ``e`` is "none" and sorts last
        flat = local.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        group_sizes = jnp.sum(flat[:, None] == jnp.arange(e)[None, :], axis=0, dtype=jnp.int32)
        rows = h.astype(dtype)[order // k]
    with jax.named_scope("moe_experts"):
        grouped = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes, precision=precision(dtype),
                                    preferred_element_type=jnp.float32)
        mid = jax.nn.silu(grouped(rows, p["w1"])) * grouped(rows, p["w3"])
        out = grouped(mid.astype(dtype), p["w2"])
        # rows of no group hold whatever the product left there
        out = jnp.where((flat[order] < e)[:, None], out * weights.reshape(-1)[order][:, None], 0.0)
        out = out[jnp.argsort(order)].reshape(n, k, -1).sum(axis=1)
    return out, group_sizes
