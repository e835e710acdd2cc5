"""The expert product the decoders share: sort by expert, a product per expert
over the stack of experts this process holds, unsort.

A router stays with its model (its scores, its bias, its normaliser differ
from family to family). What a model hands over is, for every (token, chosen
expert) pair, the expert's index *in the held stack* or ``held`` (the stack's
length) for "none": a token that is padding, a slot that holds no request, or
an expert that lives on another chip. Pairs of no expert sort last, enter no
group and add nothing. The pair's weight is the model's own, normalised over
whatever the model normalises over (all the chosen experts, held or not).

**One algorithm, three forms of its product, chosen from the call's static
shape** (``batched_capacity``: tokens x k over the experts the router chooses
among is an expert's even share of rows):

* under ``FLOOR_ROWS`` rows an expert (a decode step: one or two) the product
  is one Pallas kernel, ``streamed_product``, that reads **each touched held
  expert's weights once** and no other expert's, with every row of the call
  kept in fast memory;
* from there on (a prefill: 30-130) each held expert's rows are gathered into
  ``capacity`` places of a padded buffer (``CAPACITY_FACTOR`` times the even
  share, to a multiple of ``CAPACITY_TILE``) and the product is one batched
  ``einsum`` over (held, capacity, hidden): it reads **every held expert's
  weights once**, rows or none, which is why it is for prefills only, and it
  runs at the matrix unit's pace where the grouped product tiles 512 rows for
  a group of 50. A padded place is a zero row that nothing gathers back;
* a call of that shape in which any held expert got more rows than
  ``capacity`` runs XLA's grouped product (``jax.lax.ragged_dot``) over the
  sorted rows whole (``jax.lax.cond``): **no pair is dropped by the capacity,
  ever**.

The function says which form ran. The exchange of routed tokens between the
chips that share a layer is not here: one chip computes what its own experts
give and nothing stands in for the others.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FLOOR_ROWS = 16  # an expert's even share of rows under which the streamed kernel is all that is lowered
CAPACITY_FACTOR = 4  # places an expert over its even share (PERF.md section 5 has the fallback share by bucket)
CAPACITY_TILE = 64  # the capacity's step: a few sizes of padded buffer, each a whole number of the matrix unit's row tiles
STREAM_TILE = 256  # columns of an expert's width the streamed kernel fetches a visit (PERF.md section 6 has the sweep)


def precision(dtype: Any) -> Any:
    # float32 parameters (the CPU tests) are multiplied exactly; bfloat16 operands are one pass anyway
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def batched_capacity(tokens: int, k: int, router_experts: int) -> int:
    """Places a held expert gets in the padded buffer of a call over ``tokens``
    rows of ``k`` chosen experts each, out of ``router_experts``; 0 where the
    even share is under ``FLOOR_ROWS`` and the call takes the streamed kernel."""
    pairs = tokens * k
    if pairs < FLOOR_ROWS * router_experts:
        return 0
    return -(-CAPACITY_FACTOR * pairs // (router_experts * CAPACITY_TILE)) * CAPACITY_TILE


def stream_tile(width: int) -> int:
    """The columns of an expert's width a visit of the streamed kernel fetches:
    the largest multiple of 128 that divides ``width`` and is at most
    ``STREAM_TILE``; the whole width where there is none (a width under 128)."""
    return max((t for t in range(128, min(width, STREAM_TILE) + 1, 128) if width % t == 0), default=width)


def grouped_product(p: Dict[str, jax.Array], rows: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """XLA's grouped product: ``rows`` (pairs, hidden) sorted by expert, in the
    weights' dtype, ``group_sizes`` (held,) the rows of each expert in that
    order. Returns every row's SwiGLU through its expert (pairs, hidden), float32;
    rows of no group hold whatever the product left there."""
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes, precision=precision(rows.dtype),
                            preferred_element_type=jnp.float32)
    mid = jax.nn.silu(dot(rows, p["w1"])) * dot(rows, p["w3"])
    return dot(mid.astype(rows.dtype), p["w2"])


def streamed_product(p: Dict[str, jax.Array], rows: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``grouped_product``'s result as one Pallas kernel that reads each touched
    expert's weights from HBM once; rows of no group are zeros.

    The touched experts (those with a row), in ascending order, and every
    expert's first sorted row and row count are scalar-prefetched. One loop
    walks the touched experts times tiles of ``stream_tile(width)`` columns of
    their width, as many visits as there are and no more: an expert without a
    row is neither fetched nor visited, and with no touched expert nothing is
    fetched and the result is zeros. A visit's ``w1`` and ``w3`` columns and
    ``w2`` rows are copied into one of two buffers while the visit before is
    multiplied. Every row of the call stays in VMEM with the float32 result it
    accumulates into (written back once): a visit multiplies them all against
    its tile, masks the rows of other experts out of the gate, and adds the
    product with ``w2``. Operands in the weights' dtype, sums in float32, as
    ``grouped_product``. Off the TPU it runs in the Pallas interpreter."""
    m, hidden = rows.shape
    width, dtype = p["w1"].shape[2], p["w1"].dtype
    tile = stream_tile(width)
    tiles = width // tile
    starts = (jnp.cumsum(group_sizes) - group_sizes).astype(jnp.int32)
    touched = jnp.argsort(group_sizes == 0, stable=True).astype(jnp.int32)  # the touched first, in ascending order
    visits = (tiles * jnp.sum(group_sizes > 0, dtype=jnp.int32)).reshape(1)
    dot = functools.partial(jnp.dot, precision=precision(dtype), preferred_element_type=jnp.float32)

    def kernel(touched_ref, starts_ref, sizes_ref, visits_ref, rows_ref, w1_hbm, w3_hbm, w2_hbm, out_ref,
               w1_buf, w3_buf, w2_buf, sems):
        def copies(visit, slot):  # the visit's w1 and w3 columns and w2 rows, into buffer ``slot``
            expert = touched_ref[visit // tiles]
            cols = pl.ds(pl.multiple_of(visit % tiles * tile, tile), tile)
            return (pltpu.make_async_copy(w1_hbm.at[expert, :, cols], w1_buf.at[slot], sems.at[0, slot]),
                    pltpu.make_async_copy(w3_hbm.at[expert, :, cols], w3_buf.at[slot], sems.at[1, slot]),
                    pltpu.make_async_copy(w2_hbm.at[expert, cols, :], w2_buf.at[slot], sems.at[2, slot]))

        out_ref[...] = jnp.zeros_like(out_ref)
        n = visits_ref[0]

        @pl.when(n > 0)
        def _first():
            for copy in copies(0, 0):
                copy.start()

        def body(visit, carry):
            slot = visit % 2

            @pl.when(visit + 1 < n)
            def _next():
                for copy in copies(visit + 1, 1 - slot):
                    copy.start()

            for copy in copies(visit, slot):
                copy.wait()
            expert = touched_ref[visit // tiles]
            row = jax.lax.broadcasted_iota(jnp.int32, (m, tile), 0)
            mine = (row >= starts_ref[expert]) & (row < starts_ref[expert] + sizes_ref[expert])
            x = rows_ref[...]
            mid = jnp.where(mine, jax.nn.silu(dot(x, w1_buf[slot])) * dot(x, w3_buf[slot]), 0.0)
            out_ref[...] += dot(mid.astype(dtype), w2_buf[slot])
            return carry

        jax.lax.fori_loop(0, n, body, 0)

    # both buffers of the three tiles, the rows and the result each double-buffered, and room for the temporaries
    item = jnp.dtype(dtype).itemsize
    vmem = 6 * hidden * tile * item + 2 * m * hidden * (item + 4) + m * (hidden + 4 * tile) * 4 + (4 << 20)
    whole = pl.BlockSpec((m, hidden), lambda i, *_: (0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[whole, hbm, hbm, hbm],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((2, hidden, tile), dtype), pltpu.VMEM((2, hidden, tile), dtype),
                            pltpu.VMEM((2, tile, hidden), dtype), pltpu.SemaphoreType.DMA((3, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((m, hidden), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=jax.default_backend() != "tpu",
        name="moe_streamed",
    )(touched, starts, group_sizes.astype(jnp.int32), visits, rows, p["w1"], p["w3"], p["w2"])


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
    (held,) and which form ran, int32 (2,): (1, 0) the batched product, (0, 1)
    the streamed kernel (a shape under the floor, always), (0, 0) the grouped
    product (a call in which an expert overflowed its capacity)."""
    n, k = local.shape
    e, dtype = p["w1"].shape[0], p["w1"].dtype
    capacity = batched_capacity(n, k, router_experts)
    with jax.named_scope("moe_route"):
        # one row per (token, chosen expert), sorted by expert; expert ``e`` is "none" and sorts last
        flat = local.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        group_sizes = jnp.sum(flat[:, None] == jnp.arange(e)[None, :], axis=0, dtype=jnp.int32)
    with jax.named_scope("moe_experts"):

        def unsorted(product):
            out = product(p, h.astype(dtype)[order // k], group_sizes)
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
            return unsorted(streamed_product), group_sizes, jnp.array([0, 1], jnp.int32)
        fits = jnp.max(group_sizes) <= capacity
        out = jax.lax.cond(fits, batched, functools.partial(unsorted, grouped_product))
        return out, group_sizes, jnp.stack([fits.astype(jnp.int32), jnp.int32(0)])
