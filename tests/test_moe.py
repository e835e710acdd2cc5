"""The expert product both decoders share (``models/moe.py``), in float32 on the
CPU: the batched form over capacity-padded groups against the grouped form and
against every pair computed one at a time, at the edges of the capacity, and
which form a call's static shape lowers to.

The shape of most cases: 128 tokens of 2 chosen experts under a router of 16,
so an expert's even share is 16 rows (the floor) and its capacity 64 places,
while one expert can be handed up to 128 rows.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.models import moe

HIDDEN, WIDTH, TOKENS, K, ROUTER = 32, 16, 128, 2, 16


def stack(held, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w1": jax.random.normal(keys[0], (held, HIDDEN, WIDTH), jnp.float32) / HIDDEN ** 0.5,
            "w3": jax.random.normal(keys[1], (held, HIDDEN, WIDTH), jnp.float32) / HIDDEN ** 0.5,
            "w2": jax.random.normal(keys[2], (held, WIDTH, HIDDEN), jnp.float32) / WIDTH ** 0.5}


def pairs(first_to_0, held, seed=0, padding=0, never=()):
    """(tokens, 2) chosen experts under the router of 16, as a model hands them
    over: the first ``first_to_0`` tokens choose expert 0 first, nobody else
    does; ``never`` are chosen by nobody; the last ``padding`` tokens and every
    expert from ``held`` on are "none" (``held``)."""
    rng = np.random.default_rng(seed)
    others = [e for e in range(1, ROUTER) if e not in never]
    chosen = np.stack([rng.choice(others, size=2, replace=False) for _ in range(TOKENS)])
    chosen[:first_to_0, 0] = 0
    chosen[TOKENS - padding:] = ROUTER
    return jnp.asarray(np.where(chosen < held, chosen, held), jnp.int32)


def one_at_a_time(p, h, local, weights):
    """Every held pair through its expert, one product a pair."""
    held = p["w1"].shape[0]
    out = np.zeros((h.shape[0], HIDDEN), np.float64)
    w1, w3, w2 = (np.asarray(p[name], np.float64) for name in ("w1", "w3", "w2"))
    for t, row in enumerate(np.asarray(h, np.float64)):
        for j, e in enumerate(np.asarray(local[t])):
            if e < held:
                a = row @ w1[e]
                out[t] += float(weights[t, j]) * ((a / (1.0 + np.exp(-a)) * (row @ w3[e])) @ w2[e])
    return out


def grouped_form(monkeypatch, *args):
    """The same call with the floor out of reach: the grouped product, as a step takes it."""
    with monkeypatch.context() as m:
        m.setattr(moe, "FLOOR_ROWS", 10 ** 9)
        return moe.grouped_experts(*args)


CASES = {
    # name: (held, tokens that choose expert 0 first, padding tokens, experts nobody chooses, batched?)
    "uneven_groups": (16, 40, 0, (), 1),
    "a_group_exactly_at_the_capacity": (16, 64, 0, (), 1),
    "a_group_one_over_the_capacity": (16, 65, 0, (), 0),
    "every_token_to_one_expert": (16, 128, 0, (), 0),
    "padding_tokens_choose_none": (16, 50, 30, (), 1),
    "experts_with_no_row": (16, 30, 0, (3, 4, 9, 15), 1),
    "held_fewer_than_the_router_chooses_among": (4, 64, 10, (), 1),
    "held_fewer_and_one_over": (4, 65, 0, (2,), 0),
    "no_pair_held_at_all": (4, 0, 128, (), 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_batched_product_equals_the_grouped_one_and_every_pair_alone(case, monkeypatch):
    held, first_to_0, padding, never, want_batched = CASES[case]
    assert moe.batched_capacity(TOKENS, K, ROUTER) == 64
    p, local = stack(held), pairs(first_to_0, held, padding=padding, never=never)
    h = jax.random.normal(jax.random.PRNGKey(1), (TOKENS, HIDDEN), jnp.float32)
    weights = jax.random.uniform(jax.random.PRNGKey(2), (TOKENS, K), jnp.float32, 0.1, 1.0)
    out, sizes, batched = jax.jit(moe.grouped_experts, static_argnums=4)(p, h, local, weights, ROUTER)
    want_sizes = [int(np.sum(np.asarray(local) == e)) for e in range(held)]
    assert sizes.tolist() == want_sizes and want_sizes[0] == first_to_0 - max(0, first_to_0 + padding - TOKENS)
    assert int(batched) == want_batched == int(max(want_sizes) <= 64)
    g_out, g_sizes, g_batched = grouped_form(monkeypatch, p, h, local, weights, ROUTER)
    assert g_sizes.tolist() == want_sizes and int(g_batched) == 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(g_out), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), one_at_a_time(p, h, local, weights), atol=1e-5)
    # a token whose pairs are all of no expert gets nothing, from either form
    nothing = np.all(np.asarray(local) == held, axis=1)
    assert nothing.sum() >= padding and not np.asarray(out)[nothing].any()


def lowered(tokens, k, held, router):
    p = {"w1": jax.ShapeDtypeStruct((held, HIDDEN, WIDTH), jnp.float32),
         "w3": jax.ShapeDtypeStruct((held, HIDDEN, WIDTH), jnp.float32),
         "w2": jax.ShapeDtypeStruct((held, WIDTH, HIDDEN), jnp.float32)}
    fn = jax.jit(lambda p, h, local, weights: moe.grouped_experts(p, h, local, weights, router))
    # for a TPU, where XLA's grouped product stays one operation (the CPU's lowering spells it out)
    return jax.export.export(fn, platforms=["tpu"])(
        p, jax.ShapeDtypeStruct((tokens, HIDDEN), jnp.float32), jax.ShapeDtypeStruct((tokens, k), jnp.int32),
        jax.ShapeDtypeStruct((tokens, k), jnp.float32)).mlir_module()


@pytest.mark.parametrize("tokens,k,held,router", [(16, 4, 32, 32), (16, 4, 32, 128), (127, 2, 4, 16)])
def test_a_shape_under_the_floor_lowers_to_the_grouped_product_and_no_conditional(tokens, k, held, router):
    text = lowered(tokens, k, held, router)
    assert text.count("@chlo.ragged_dot(") == 3
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert f"{held}x64x" not in text  # no padded buffer


@pytest.mark.parametrize("tokens,k,held,router", [(128, 2, 16, 16), (128, 2, 4, 16), (256, 4, 32, 32)])
def test_a_shape_over_the_floor_lowers_to_a_conditional_over_both_products(tokens, k, held, router):
    text = lowered(tokens, k, held, router)
    capacity = moe.batched_capacity(tokens, k, router)
    assert capacity > 0 and ("stablehlo.case" in text or "stablehlo.if" in text)
    assert text.count("@chlo.ragged_dot(") == 3
    assert text.count(f"tensor<{held}x{capacity}x{HIDDEN}xf32>, tensor<{held}x{HIDDEN}x{WIDTH}xf32>") == 2  # w1, w3
    assert text.count(f"tensor<{held}x{capacity}x{WIDTH}xf32>, tensor<{held}x{WIDTH}x{HIDDEN}xf32>") == 1  # w2


@pytest.mark.parametrize("tokens,k,router,capacity", [
    (16, 4, 32, 0), (16, 4, 128, 0),  # a step of 16 slots: 2 rows an expert, and half a row
    (256, 4, 32, 128), (512, 4, 32, 256), (1024, 4, 32, 512),  # the lfm2_moe cell's prefill buckets: 32, 64, 128 rows
    (1024, 4, 128, 128), (1536, 4, 128, 192), (2048, 4, 128, 256),  # the mistral4 cell's: 32, 48, 64 rows
    (127, 2, 16, 0), (128, 2, 16, 64), (136, 2, 16, 128),  # the floor; a tile of 64 places
])
def test_the_capacity_is_four_even_shares_in_tiles_of_64_from_sixteen_rows_an_expert_on(tokens, k, router, capacity):
    assert moe.batched_capacity(tokens, k, router) == capacity
