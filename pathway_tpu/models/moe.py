"""The expert product the decoders share: sort by expert, a product per expert
over the stack of experts this process holds, unsort.

A router stays with its model (its scores, its bias, its normaliser differ
from family to family). What a model hands over is, for every (token, chosen
expert) pair, the expert's index *in the held stack* or ``held`` (the stack's
length) for "none": a token that is padding, a slot that holds no request, or
an expert that lives on another chip. Pairs of no expert sort last, enter no
group and add nothing. The pair's weight is the model's own, normalised over
whatever the model normalises over (all the chosen experts, held or not).

**One algorithm, two forms of its product, chosen from the call's static
shape** (``batched_capacity``: tokens x k over the experts the router chooses
among is an expert's even share of rows):

* under ``FLOOR_ROWS`` rows an expert (a decode step: one or two) the product
  is XLA's grouped one (``jax.lax.ragged_dot``) over the sorted rows, and
  nothing else is lowered: no expert without a row is read;
* from there on (a prefill: 30-130) each held expert's rows are gathered into
  ``capacity`` places of a padded buffer (``CAPACITY_FACTOR`` times the even
  share, to a multiple of ``CAPACITY_TILE``) and the product is one batched
  ``einsum`` over (held, capacity, hidden): it reads **every held expert's
  weights once**, rows or none, which is why it is for prefills only, and it
  runs at the matrix unit's pace where the grouped product tiles 512 rows for
  a group of 50. A padded place is a zero row that nothing gathers back.

**No pair is dropped by the capacity, ever:** a call in which any held expert
got more rows than ``capacity`` runs the grouped product whole
(``jax.lax.cond``), and the function says which form ran.

The exchange of routed tokens between the chips that share a layer is not
here: one chip computes what its own experts give and nothing stands in for
the others.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

FLOOR_ROWS = 16  # an expert's even share of rows under which the grouped product is all that is lowered
CAPACITY_FACTOR = 4  # places an expert over its even share (PERF.md section 5 has the fallback share by bucket)
CAPACITY_TILE = 64  # the capacity's step: a few sizes of padded buffer, each a whole number of the matrix unit's row tiles


def precision(dtype: Any) -> Any:
    # float32 parameters (the CPU tests) are multiplied exactly; bfloat16 operands are one pass anyway
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def batched_capacity(tokens: int, k: int, router_experts: int) -> int:
    """Places a held expert gets in the padded buffer of a call over ``tokens``
    rows of ``k`` chosen experts each, out of ``router_experts``; 0 where the
    even share is under ``FLOOR_ROWS`` and the call takes the grouped product."""
    pairs = tokens * k
    if pairs < FLOOR_ROWS * router_experts:
        return 0
    return -(-CAPACITY_FACTOR * pairs // (router_experts * CAPACITY_TILE)) * CAPACITY_TILE


def grouped_experts(p: Dict[str, jax.Array], h: jax.Array, local: jax.Array, weights: jax.Array,
                    router_experts: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Every pair's SwiGLU through its expert, weighted and summed per token.

    ``p["w1"]``, ``p["w3"]`` (held, hidden, width) and ``p["w2"]`` (held, width,
    hidden): the held experts' stack. ``h`` (tokens, hidden, float32, already
    normed). ``local`` (tokens, k): each pair's index into the stack, ``held``
    for none. ``weights`` (tokens, k). ``router_experts``: how many experts the
    router chose among (the stack's length, or more where other chips hold the
    rest), from which the form of the product follows (the module's text).
    Returns the float32 output (tokens, hidden), the pairs each held expert got
    (held,) and whether the batched product ran (int32: always 0 for a shape
    under the floor, 0 in a call in which an expert overflowed its capacity)."""
    n, k = local.shape
    e, dtype = p["w1"].shape[0], p["w1"].dtype
    capacity = batched_capacity(n, k, router_experts)
    with jax.named_scope("moe_route"):
        # one row per (token, chosen expert), sorted by expert; expert ``e`` is "none" and sorts last
        flat = local.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        group_sizes = jnp.sum(flat[:, None] == jnp.arange(e)[None, :], axis=0, dtype=jnp.int32)
    with jax.named_scope("moe_experts"):

        def grouped():
            dot = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes, precision=precision(dtype),
                                    preferred_element_type=jnp.float32)
            rows = h.astype(dtype)[order // k]
            mid = jax.nn.silu(dot(rows, p["w1"])) * dot(rows, p["w3"])
            out = dot(mid.astype(dtype), p["w2"])
            # rows of no group hold whatever the product left there
            out = jnp.where((flat[order] < e)[:, None], out * weights.reshape(-1)[order][:, None], 0.0)
            return out[jnp.argsort(order)].reshape(n, k, -1).sum(axis=1)

        def batched():
            dot = functools.partial(jnp.einsum, precision=precision(dtype), preferred_element_type=jnp.float32)
            starts = jnp.cumsum(group_sizes) - group_sizes
            place = jnp.arange(capacity)
            # expert x place -> the token whose row lies there: one gather from ``h``, a zero row past the group's size
            token = (order // k)[jnp.minimum(starts[:, None] + place[None, :], n * k - 1)]
            padded = jnp.where((place[None, :] < group_sizes[:, None])[:, :, None], h.astype(dtype)[token], 0)
            mid = jax.nn.silu(dot("ech,ehw->ecw", padded, p["w1"])) * dot("ech,ehw->ecw", padded, p["w3"])
            out = dot("ecw,ewh->ech", mid.astype(dtype), p["w2"]).reshape(e * capacity, -1)
            # pair -> its place: its expert's block, its rank among the expert's sorted rows; one gather back, in
            # the tokens' own order. A pair of no expert reads some place and is masked.
            expert = jnp.minimum(flat, e - 1)
            rank = jnp.clip(jnp.argsort(order) - starts[expert], 0, capacity - 1)
            out = jnp.where((flat < e)[:, None], out[expert * capacity + rank] * weights.reshape(-1)[:, None], 0.0)
            return out.reshape(n, k, -1).sum(axis=1)

        if not capacity:
            return grouped(), group_sizes, jnp.int32(0)
        fits = jnp.max(group_sizes) <= capacity
        return jax.lax.cond(fits, batched, grouped), group_sizes, fits.astype(jnp.int32)
