"""The expert product both decoders share (``models/moe.py``) on the CPU: the
batched form over capacity-padded groups against the grouped form and against
every pair computed one at a time, at the edges of the capacity; the streamed
kernel (in the Pallas interpreter) against the grouped form and the same
reference at the decoders' step shape; and which form a call's static shape
lowers to.

The shape of most batched cases: 128 tokens of 2 chosen experts under a router
of 16, so an expert's even share is 16 rows (the floor) and its capacity 64
places, while one expert can be handed up to 128 rows. The streamed cases are a
step's: 16 slots of 4 chosen experts each.
"""

from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.models import moe

HIDDEN, WIDTH, TOKENS, K, ROUTER = 32, 16, 128, 2, 16


def stack(held, seed=0, hidden=HIDDEN, width=WIDTH, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w1": (jax.random.normal(keys[0], (held, hidden, width), jnp.float32) / hidden ** 0.5).astype(dtype),
            "w3": (jax.random.normal(keys[1], (held, hidden, width), jnp.float32) / hidden ** 0.5).astype(dtype),
            "w2": (jax.random.normal(keys[2], (held, width, hidden), jnp.float32) / width ** 0.5).astype(dtype)}


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
    """Every held pair through its expert, one product a pair, in float64."""
    held = p["w1"].shape[0]
    out = np.zeros(h.shape, np.float64)
    w1, w3, w2 = (np.asarray(p[name].astype(jnp.float32), np.float64) for name in ("w1", "w3", "w2"))
    for t, row in enumerate(np.asarray(h, np.float64)):
        for j, e in enumerate(np.asarray(local[t])):
            if e < held:
                a = row @ w1[e]
                out[t] += float(weights[t, j]) * ((a / (1.0 + np.exp(-a)) * (row @ w3[e])) @ w2[e])
    return out


def grouped_form(monkeypatch, *args):
    """The same call with the floor out of reach and XLA's grouped product where
    the streamed kernel would run: the parent's step."""
    with monkeypatch.context() as m:
        m.setattr(moe, "FLOOR_ROWS", 10 ** 9)
        m.setattr(moe, "streamed_product", moe.grouped_product)
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
    out, sizes, form = jax.jit(moe.grouped_experts, static_argnums=4)(p, h, local, weights, ROUTER)
    want_sizes = [int(np.sum(np.asarray(local) == e)) for e in range(held)]
    assert sizes.tolist() == want_sizes and want_sizes[0] == first_to_0 - max(0, first_to_0 + padding - TOKENS)
    # batched where every group fits, else grouped; never streamed at this shape
    assert form.tolist() == [want_batched, 0] and want_batched == int(max(want_sizes) <= 64)
    g_out, g_sizes, _ = grouped_form(monkeypatch, p, h, local, weights, ROUTER)
    assert g_sizes.tolist() == want_sizes
    np.testing.assert_allclose(np.asarray(out), np.asarray(g_out), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), one_at_a_time(p, h, local, weights), atol=1e-5)
    # a token whose pairs are all of no expert gets nothing, from either form
    nothing = np.all(np.asarray(local) == held, axis=1)
    assert nothing.sum() >= padding and not np.asarray(out)[nothing].any()


# a step of 16 slots of 4 chosen experts; the stack 128 wide in, 384 out (three of the kernel's tiles of 128)
SLOTS, STEP_K, STEP_HIDDEN, STEP_WIDTH = 16, 4, 128, 384
DECODERS = {"lfm2": (32, 32), "mistral4": (8, 32)}  # (held, the router's width): all held, or a quarter


def step_pairs(live, held, router, seed=0, to_expert_0=False):
    """(slots, 4) chosen experts of a step with ``live`` slots holding a request,
    as a model hands them over: 4 distinct of the router's, "none" (``held``)
    for a slot that holds none and for an expert held elsewhere; with
    ``to_expert_0`` every live slot chooses expert 0 first."""
    rng = np.random.default_rng(seed)
    chosen = np.full((SLOTS, STEP_K), router)
    for slot in range(live):
        chosen[slot] = rng.choice(router, size=STEP_K, replace=False)
        if to_expert_0 and 0 not in chosen[slot]:
            chosen[slot, 0] = 0
    return jnp.asarray(np.where(chosen < held, chosen, held), jnp.int32)


def streamed_case(local, held, router, dtype, monkeypatch):
    """The step through ``grouped_experts`` (the streamed kernel) and through the
    grouped form, and the reference, for a stack whose experts without a row
    hold NaN: a visit to any of them would spread NaN through ``w2``."""
    p = stack(held, hidden=STEP_HIDDEN, width=STEP_WIDTH, dtype=dtype)
    touched = np.isin(np.arange(held), np.asarray(local))
    p_nan = {name: jnp.where(touched[:, None, None], w, jnp.nan).astype(dtype) for name, w in p.items()}
    h = jax.random.normal(jax.random.PRNGKey(1), (SLOTS, STEP_HIDDEN), jnp.float32)
    weights = jax.random.uniform(jax.random.PRNGKey(2), (SLOTS, STEP_K), jnp.float32, 0.1, 1.0)
    assert moe.batched_capacity(SLOTS, STEP_K, router) == 0 and moe.stream_tile(STEP_WIDTH) == 128
    out, sizes, form = jax.jit(moe.grouped_experts, static_argnums=4)(p_nan, h, local, weights, router)
    assert form.tolist() == [0, 1] and sizes.tolist() == [int(np.sum(np.asarray(local) == e)) for e in range(held)]
    g_out, _, _ = grouped_form(monkeypatch, p, h, local, weights, router)
    return np.asarray(out), np.asarray(g_out), one_at_a_time(p, h, local, weights)


# float32 multiplies exactly (Precision.HIGHEST) and differs from the grouped form in the order of its sums;
# bfloat16 operands round the gate before w2 as the grouped form does, and a sum's order moves one rounding
TOLERANCE = {jnp.float32: (1e-5, 1e-5), jnp.bfloat16: (2e-3, 5e-2)}  # (against the grouped form, the float64 pairs)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("live", [0, 1, 5, 16])
@pytest.mark.parametrize("decoder", sorted(DECODERS))
def test_the_streamed_kernel_equals_the_grouped_product_and_every_pair_alone_at_a_steps_shape(
        decoder, live, dtype, monkeypatch):
    held, router = DECODERS[decoder]
    local = step_pairs(live, held, router, seed=live + 1)
    out, grouped, want = streamed_case(local, held, router, dtype, monkeypatch)
    to_grouped, to_pairs = TOLERANCE[dtype]
    np.testing.assert_allclose(out, grouped, atol=to_grouped)
    np.testing.assert_allclose(out, want, atol=to_pairs)
    # a slot that holds no request, and a token whose experts are all held elsewhere, gets exactly nothing
    nothing = np.all(np.asarray(local) == held, axis=1)
    assert nothing.sum() >= SLOTS - live and not out[nothing].any()
    if live:
        assert np.abs(want).max() > 0.1  # something was computed


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["every_live_slot_to_expert_0", "no_pair_held_at_all"])
def test_the_streamed_kernel_at_the_edges_of_a_step(case, dtype, monkeypatch):
    """Sixteen rows in one expert's group beside groups of one; and a share of a
    router of 32 that holds 8 and gets no pair in a step where 16 slots choose
    among the other 24: nothing is fetched (every weight is NaN) and the
    output is zeros."""
    if case == "every_live_slot_to_expert_0":
        held, router = DECODERS["lfm2"]
        local = step_pairs(16, held, router, seed=3, to_expert_0=True)
        assert int(np.sum(np.asarray(local) == 0)) == 16
    else:
        held, router = DECODERS["mistral4"]
        local = jnp.full((SLOTS, STEP_K), held, jnp.int32)  # every chosen expert is held on another chip
    out, grouped, want = streamed_case(local, held, router, dtype, monkeypatch)
    to_grouped, to_pairs = TOLERANCE[dtype]
    np.testing.assert_allclose(out, grouped, atol=to_grouped)
    np.testing.assert_allclose(out, want, atol=to_pairs)
    assert out.any() == (case != "no_pair_held_at_all")


def lowered(tokens, k, held, router, hidden=HIDDEN, width=WIDTH):
    p = {"w1": jax.ShapeDtypeStruct((held, hidden, width), jnp.float32),
         "w3": jax.ShapeDtypeStruct((held, hidden, width), jnp.float32),
         "w2": jax.ShapeDtypeStruct((held, width, hidden), jnp.float32)}
    fn = jax.jit(lambda p, h, local, weights: moe.grouped_experts(p, h, local, weights, router))
    # for a TPU, where XLA's grouped product stays one operation (the CPU's lowering spells it out), and with the
    # TPU as the backend the program asks for, so that the kernel lowers as the chip compiles it
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return jax.export.export(fn, platforms=["tpu"])(
            p, jax.ShapeDtypeStruct((tokens, hidden), jnp.float32), jax.ShapeDtypeStruct((tokens, k), jnp.int32),
            jax.ShapeDtypeStruct((tokens, k), jnp.float32)).mlir_module()


@pytest.mark.parametrize("tokens,k,held,router", [(16, 4, 32, 32), (16, 4, 32, 128), (127, 2, 4, 16)])
def test_a_shape_under_the_floor_lowers_to_the_grouped_product_and_no_conditional(tokens, k, held, router):
    """Under the floor the product is the streamed kernel, one custom call, and
    nothing else: no ``ragged_dot``, no conditional, no padded buffer."""
    text = lowered(tokens, k, held, router)
    assert text.count("tpu_custom_call") == 1 and 'kernel_name = "moe_streamed"' in text
    assert "@chlo.ragged_dot(" not in text
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert f"{held}x64x" not in text  # no padded buffer


@pytest.mark.parametrize("tokens,k,held,router", [(128, 2, 16, 16), (128, 2, 4, 16), (256, 4, 32, 32)])
def test_a_shape_over_the_floor_lowers_to_a_conditional_over_both_products(tokens, k, held, router):
    text = lowered(tokens, k, held, router)
    capacity = moe.batched_capacity(tokens, k, router)
    assert capacity > 0 and ("stablehlo.case" in text or "stablehlo.if" in text)
    assert text.count("@chlo.ragged_dot(") == 3 and "tpu_custom_call" not in text
    assert text.count(f"tensor<{held}x{capacity}x{HIDDEN}xf32>, tensor<{held}x{HIDDEN}x{WIDTH}xf32>") == 2  # w1, w3
    assert text.count(f"tensor<{held}x{capacity}x{WIDTH}xf32>, tensor<{held}x{WIDTH}x{HIDDEN}xf32>") == 1  # w2


@pytest.mark.parametrize("tokens,router,streamed", [
    (16, 32, True), (16, 128, True),  # both cells' steps
    (256, 32, False), (512, 32, False), (1024, 32, False),  # the lfm2_moe cell's prefill buckets
    (1024, 128, False), (1536, 128, False), (2048, 128, False),  # the mistral4 cell's
])
def test_a_prefill_bucket_never_takes_the_streamed_kernel_and_a_step_always_does(tokens, router, streamed):
    """At the cells' own shapes (four chosen experts, 32 held): the form follows
    from the static shape, and the call reports the form that ran."""
    assert (moe.batched_capacity(tokens, 4, router) == 0) == streamed
    text = lowered(tokens, 4, 32, router, hidden=128, width=256)
    assert text.count('kernel_name = "moe_streamed"') == streamed and text.count("@chlo.ragged_dot(") == 3 * (not streamed)
    chosen = np.random.default_rng(tokens).integers(0, router, (tokens, 4))  # an even router: no expert overflows
    local = jnp.asarray(np.where(chosen < 32, chosen, 32), jnp.int32)
    h = jax.random.normal(jax.random.PRNGKey(0), (tokens, 128), jnp.float32)
    _, _, form = jax.jit(moe.grouped_experts, static_argnums=4)(
        stack(32, hidden=128, width=256), h, local, jnp.ones((tokens, 4)), router)
    assert form.tolist() == ([0, 1] if streamed else [1, 0])


@pytest.mark.parametrize("tokens,k,router,capacity", [
    (16, 4, 32, 0), (16, 4, 128, 0),  # a step of 16 slots: 2 rows an expert, and half a row
    (256, 4, 32, 128), (512, 4, 32, 256), (1024, 4, 32, 512),  # the lfm2_moe cell's prefill buckets: 32, 64, 128 rows
    (1024, 4, 128, 128), (1536, 4, 128, 192), (2048, 4, 128, 256),  # the mistral4 cell's: 32, 48, 64 rows
    (127, 2, 16, 0), (128, 2, 16, 64), (136, 2, 16, 128),  # the floor; a tile of 64 places
])
def test_the_capacity_is_four_even_shares_in_tiles_of_64_from_sixteen_rows_an_expert_on(tokens, k, router, capacity):
    assert moe.batched_capacity(tokens, k, router) == capacity


@pytest.mark.parametrize("width,tile", [(1792, 256), (2048, 256), (384, 128), (640, 128), (32, 32), (100, 100)])
def test_the_kernels_tile_is_the_largest_multiple_of_128_that_divides_the_width_up_to_256(width, tile):
    assert moe.stream_tile(width) == tile
