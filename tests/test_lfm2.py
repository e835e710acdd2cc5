"""The ``lfm2_moe`` decoder's two device programs against the plain reference
(``models/lfm2_reference.py``), at a tiny size on the CPU: hidden 64, 2 dense +
4 expert layers (one period of the published pattern), 8 experts, top 2,
vocabulary 4,096, seeded weights.

Tolerances. With float32 parameters the program multiplies exactly
(``Precision.HIGHEST``) and differs from the reference only in the order of its
sums (a cache read back, a grouped product, rows in another order): the logits
agree to a thousandth of their spread (0.16 at hidden 64: the table's 0.02 times
its root), and the greedy tokens are equal. With bfloat16
parameters, as served, every product's operands carry 8 bits: the logits agree
to 0.15 of their spread at this size (measured up to 0.07 over the seeds here),
and no token is compared.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.models import lfm2, moe
from pathway_tpu.models import lfm2_reference as ref

TINY = dict(
    vocab_size=4096, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=6,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"], num_dense_layers=2,
    num_attention_heads=4, num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
)
CFG = lfm2.Lfm2Config.from_dict(TINY)


def assert_close(served, want, share=1e-3):
    """Within ``share`` of the reference logits' spread."""
    assert np.max(np.abs(np.asarray(served) - np.asarray(want))) < share * np.std(np.asarray(want))


@pytest.fixture(scope="module")
def params():
    return lfm2.init_params(CFG, seed=3, dtype=jnp.float32)


def decoder(params, cfg=CFG, slots=4, buckets=(16, 32), new=9):
    return lfm2.Lfm2Decoder(cfg, params, slots=slots, max_prompt_tokens=max(buckets), max_new_tokens=new,
                            prefill_buckets=buckets)


# the un-jitted cores, jitted here so that a test does not dispatch them op by op
PREFILL = jax.jit(lfm2.prefill_logits, static_argnames=("cfg",))
DECODE = jax.jit(lfm2.decode_logits, static_argnames=("cfg",))
FORWARD = jax.jit(ref.forward, static_argnames=("cfg",))


def reference(params, seq, cfg=CFG, pad_to=48):
    """The reference's (logits, chosen experts per layer) at every position of
    ``seq``. The model is causal, so the sequence is padded on the right to one
    length (one compiled program a configuration) and the padding cut off."""
    logits, chosen = FORWARD(params, jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32), cfg=cfg)
    return np.asarray(logits)[: len(seq)], [np.asarray(c)[: len(seq)] for c in chosen]


def assert_greedy(params, prompt, tokens, cfg=CFG):
    """``tokens`` are the reference's greedy continuation of ``prompt``: each
    is the reference's largest logit given the prompt and the tokens before it."""
    logits, _ = reference(params, prompt + tokens, cfg)
    assert tokens == np.argmax(logits[len(prompt) - 1 : -1], axis=-1).tolist()


def prompt_of(n, seed=0, vocab=CFG.vocab_size):
    return np.random.default_rng(seed).integers(0, vocab, size=n).tolist()


def run_through_cache(dec, slot, prompt, steps):
    """Prefill then ``steps`` decode steps of one slot, with the un-jitted
    cores so that the logits can be read: (logits per position, tokens,
    each call's counts: experts touched, layers batched)."""
    state, logits, touched = PREFILL(
        dec.params, dec.state, jnp.asarray(prompt + [0] * (dec.bucket_of(len(prompt)) - len(prompt)), jnp.int32),
        jnp.int32(len(prompt)), jnp.int32(slot), cfg=dec.cfg)
    token = int(jnp.argmax(logits))
    state["pos"] = state["pos"].at[slot].set(len(prompt))
    state["last"] = state["last"].at[slot].set(token)
    rows, tokens, counts = [np.asarray(logits)], [token], [touched.tolist()]
    active = np.zeros((dec.slots,), bool)
    active[slot] = True
    for _ in range(steps):
        state, logits, touched = DECODE(dec.params, state, jnp.asarray(active), cfg=dec.cfg)
        token = int(jnp.argmax(logits[slot]))
        state["pos"] = state["pos"].at[slot].add(1)
        state["last"] = state["last"].at[slot].set(token)
        rows.append(np.asarray(logits[slot]))
        tokens.append(token)
        counts.append(touched.tolist())
    dec.state = state
    return np.stack(rows), tokens, counts


def test_prefill_then_decode_gives_the_references_logits_at_every_position(params):
    prompt = prompt_of(11)
    served, tokens, _ = run_through_cache(decoder(params), 1, prompt, 8)
    full, _ = reference(params, prompt + tokens)
    want = full[len(prompt) - 1 : len(prompt) + 8]
    assert np.std(want) > 0.1  # the logits spread: a wrong program would pick other tokens
    assert_close(served, want)
    assert_greedy(params, prompt, tokens)


@pytest.mark.parametrize("length", [64, 41])
def test_a_prefill_bucket_with_sixteen_rows_an_expert_takes_the_batched_product_and_holds_the_reference(params, length):
    """Buckets of 16 and 32 tokens (2 of 8 experts each: 4 and 8 rows an expert)
    lower to the streamed kernel alone; 64 is at the floor, and each of the four
    expert layers' products runs batched (64 places an expert for at most 64
    rows: nothing can overflow). The steps after it stream, every layer."""
    prompt = prompt_of(length, seed=50)
    served, tokens, counts = run_through_cache(decoder(params, buckets=(16, 64)), 2, prompt, 3)
    full, chosen = reference(params, prompt + tokens, pad_to=80)
    assert_close(served, full[length - 1 : length + 3])
    assert_greedy(params, prompt, tokens)
    assert counts[0] == [sum(len(set(c[:length].ravel().tolist())) for c in chosen), 4, 0]
    assert [c[1:] for c in counts[1:]] == [[0, 4]] * 3


def one_operator(kind, ffn):
    """A one-layer model around one operator: what is compared is that operator's lines."""
    return lfm2.Lfm2Config.from_dict(dict(TINY, num_hidden_layers=1, layer_types=[kind],
                                          num_dense_layers=1 if ffn == "dense" else 0))


@pytest.mark.parametrize("kind,ffn", [("conv", "dense"), ("full_attention", "dense"), ("conv", "moe")])
def test_each_operator_alone_across_the_prefill_decode_boundary(kind, ffn):
    cfg = one_operator(kind, ffn)
    p = lfm2.init_params(cfg, seed=7, dtype=jnp.float32)
    prompt = prompt_of(5, seed=1)
    served, tokens, _ = run_through_cache(decoder(p, cfg), 0, prompt, 4)
    full, _ = reference(p, prompt + tokens, cfg)
    assert_close(served, full[4:9])


def test_the_convolutions_tail_is_the_last_two_inputs_also_of_a_one_token_prompt():
    cfg = one_operator("conv", "dense")
    p = lfm2.init_params(cfg, seed=7, dtype=jnp.float32)
    layer = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p["layers"][0])
    for n in (1, 2, 6):
        prompt = prompt_of(n, seed=n)
        dec = decoder(p, cfg)
        dec.prefill(2, prompt)
        h = ref.rmsnorm(p["embed"][jnp.asarray(prompt)], layer["operator_norm"], cfg.norm_eps)
        with jax.default_matmul_precision("highest"):
            b, _, u = jnp.split(h @ layer["in_proj"], 3, axis=-1)
        want = np.concatenate([np.zeros((2, cfg.hidden_size), np.float32), np.asarray(b * u)])[-2:]
        np.testing.assert_allclose(np.asarray(dec.state["tail"][0][2]), want, atol=1e-5)


def test_keys_are_cached_after_the_norm_and_rope():
    cfg = one_operator("full_attention", "dense")
    p = lfm2.init_params(cfg, seed=9, dtype=jnp.float32)
    layer = p["layers"][0]
    prompt = prompt_of(7, seed=2)
    dec = decoder(p, cfg)
    dec.prefill(3, prompt)
    h = ref.rmsnorm(p["embed"][jnp.asarray(prompt)], layer["operator_norm"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        k = (h @ layer["wk"]).reshape(7, cfg.num_key_value_heads, cfg.head_dim)
        v = (h @ layer["wv"]).reshape(7, cfg.num_key_value_heads, cfg.head_dim)
    cos, sin = ref.rope_tables(jnp.arange(7), cfg.head_dim, cfg.rope_theta)
    normed = ref.rmsnorm(k, layer["k_norm"], cfg.norm_eps)
    rotated = normed * cos[:, None] + ref.rotate_half(normed) * sin[:, None]
    np.testing.assert_allclose(np.asarray(dec.state["k"][0][3, :7]), np.asarray(rotated), atol=1e-5)
    np.testing.assert_allclose(np.asarray(dec.state["v"][0][3, :7]), np.asarray(v), atol=1e-5)
    assert float(jnp.max(jnp.abs(rotated - k))) > 0.1  # neither the norm nor the rotation is the identity here


def test_experts_are_chosen_by_biased_scores_and_weighted_by_unbiased_ones():
    cfg = one_operator("conv", "moe")
    p = lfm2.init_params(cfg, seed=11, dtype=jnp.float32)
    layer = dict(p["layers"][0])
    # a bias that puts experts 6 and 7 first whatever their scores
    layer["expert_bias"] = jnp.asarray([0, 0, 0, 0, 0, 0, 5.0, 5.0], jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(0), (10, cfg.hidden_size), jnp.float32)
    out, touched = lfm2._moe(layer, h, jnp.ones((10,), bool), cfg)
    want, chosen = ref.moe_ffn(jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), layer), h, cfg)
    assert sorted(set(np.asarray(chosen).ravel().tolist())) == [6, 7] and touched.tolist() == [2, 0, 1]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    # weights taken from the biased scores would be 5.x / (5.x + 5.y), near a half each: not what is served
    scores = jax.nn.sigmoid(h @ layer["gate"])[:, 6:]
    unbiased = scores / (scores.sum(-1, keepdims=True) + 1e-6)
    assert float(jnp.max(jnp.abs(unbiased - 0.5))) > 0.1
    # and a token that is not valid chooses nothing and gets nothing
    out, touched = lfm2._moe(layer, h, jnp.arange(10) < 0, cfg)
    assert touched.tolist() == [0, 0, 1] and float(jnp.max(jnp.abs(out))) == 0.0


def test_prompts_of_unequal_length_in_the_slots_get_the_tokens_they_get_alone(params):
    prompts = {0: prompt_of(5, 10), 2: prompt_of(16, 11), 3: prompt_of(27, 12)}
    together = decoder(params)
    tokens = {slot: [int(together.prefill(slot, ids)[0])] for slot, ids in prompts.items()}
    active = np.array([s in prompts for s in range(4)])
    for _ in range(8):
        step, _ = together.decode(active)
        for slot in prompts:
            tokens[slot].append(int(step[slot]))
    for slot, ids in prompts.items():
        assert_greedy(params, ids, tokens[slot])
    # the slot that held no request wrote nothing
    assert int(together.state["pos"][1]) == 0 and not np.asarray(together.state["tail"][0][1]).any()
    assert not np.asarray(together.state["k"][0][1]).any()


def test_a_freed_slot_leaks_nothing_into_its_next_request(params):
    first, second = prompt_of(30, 20), prompt_of(6, 21)
    used = decoder(params)
    run_through_cache(used, 1, first, 8)  # the slot now holds 38 positions of another request
    again, tokens, _ = run_through_cache(used, 1, second, 8)
    fresh, fresh_tokens, _ = run_through_cache(decoder(params), 1, second, 8)
    np.testing.assert_array_equal(again, fresh)
    assert tokens == fresh_tokens
    assert_greedy(params, second, tokens)


def test_the_padding_bucket_changes_nothing(params):
    prompt = prompt_of(13, 30)
    small, t_small, n_small = run_through_cache(decoder(params, buckets=(16, 32)), 0, prompt, 3)
    large, t_large, n_large = run_through_cache(decoder(params, buckets=(32,)), 0, prompt, 3)
    assert_close(small, large)
    assert t_small == t_large and n_small == n_large


def test_the_distinct_expert_count_equals_the_references(params):
    prompts = {1: prompt_of(9, 40), 2: prompt_of(14, 41)}
    dec = decoder(params)
    sequences = {}
    for slot, ids in prompts.items():
        token, touched = dec.prefill(slot, ids)
        _, chosen = reference(params, ids)
        # a 16 bucket gives an expert 4 rows: the four layers stream in the prefill too
        assert np.asarray(touched).tolist() == [sum(len(set(c.ravel().tolist())) for c in chosen), 0, 4]
        sequences[slot] = ids + [int(token)]
    active = np.array([False, True, True, False])
    for _ in range(4):
        tokens, touched = dec.decode(active)
        # what the step's rows chose: the reference's choice at the last position of each sequence so far
        per_layer = [set() for _ in range(4)]
        for slot, seq in sequences.items():
            _, chosen = reference(params, seq)
            for layer, c in enumerate(chosen):
                per_layer[layer] |= set(c[-1].tolist())
            seq.append(int(tokens[slot]))
        assert np.asarray(touched).tolist() == [sum(len(s) for s in per_layer), 0, 4]


def test_bfloat16_as_served_stays_near_the_float32_reference():
    p = lfm2.init_params(CFG, seed=3)  # bfloat16 matrices, float32 norms and router
    assert p["embed"].dtype == jnp.bfloat16 and p["layers"][2]["gate"].dtype == jnp.float32
    prompt = prompt_of(11)
    served, tokens, _ = run_through_cache(decoder(p), 1, prompt, 8)
    full, _ = reference(p, prompt + tokens)
    want = full[len(prompt) - 1 : len(prompt) + 8]
    assert np.max(np.abs(served - want)) < 0.15 * np.std(want)


def test_config_from_the_published_json_and_its_cut():
    published = lfm2.Lfm2Config()
    assert published.layer_types.count("full_attention") == 6 and published.head_dim == 64
    cut = lfm2.Lfm2Config.from_dict({"num_hidden_layers": 14, "layer_types": list(lfm2.PUBLISHED_LAYER_TYPES[:14]),
                                     "model_type": "lfm2_moe", "max_position_embeddings": 128000})
    shapes = lfm2.param_shapes(cut)
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n_params == 4_667_077_376  # issue 28's arithmetic: 4,667M in layers 0-13 and the tied table
    with pytest.raises(ValueError):
        lfm2.Lfm2Config.from_dict({"num_hidden_layers": 3})


def parents_random_params(shapes, seed):
    """``slot_decoder.random_params`` as it stood before it learnt the state-space
    mixer's four vector names (PR 35), spelled out: the rule by a leaf's name,
    the key by its place in its own tree."""
    from pathway_tpu.models.slot_decoder import _draw

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = path[-1].key
        if name.endswith("norm"):
            std, mean = 0.1, 1.0
        elif name == "expert_bias":
            std, mean = 0.05, 0.0
        elif name == "embed":
            std, mean = 0.02, 0.0
        else:
            std, mean = float(leaf.shape[-2 if name != "conv_w" else -1]) ** -0.5, 0.0
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        out.append(_draw(key, shape=leaf.shape, dtype=leaf.dtype, std=std, mean=mean))
    return jax.tree_util.tree_unflatten(treedef, out)


def assert_bit_equal(got, want):
    same = jax.tree.map(lambda a, b: a.dtype == b.dtype and np.array_equal(np.asarray(a).view(np.uint8),
                                                                           np.asarray(b).view(np.uint8)), got, want)
    assert all(jax.tree.leaves(same))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_init_params_at_a_seed_is_bit_equal_to_the_parents_draw(dtype):
    """``random_params`` gained four names for another decoder; no leaf of this one moved."""
    assert_bit_equal(lfm2.init_params(CFG, seed=3, dtype=dtype), parents_random_params(lfm2.param_shapes(CFG, dtype), 3))


def slots_through_both_forms(module, dec, prompts, steps, monkeypatch):
    """``prompts`` (slot -> ids) prefilled and then ``steps`` steps over those
    slots together, once as the program runs and once with XLA's grouped
    product where the streamed kernel runs (the parent's step), each with its
    own jitted cores: per form the tokens of each slot, the step's logits of the
    live slots and each call's counts."""
    def run():
        prefill = jax.jit(lambda *a, cfg: module.prefill_logits(*a, cfg), static_argnames=("cfg",))
        decode = jax.jit(lambda *a, cfg: module.decode_logits(*a, cfg), static_argnames=("cfg",))
        state, tokens, logits, counts = dec.state, {}, [], []
        active = np.isin(np.arange(dec.slots), list(prompts))
        for slot, ids in prompts.items():
            padded = jnp.asarray(ids + [0] * (dec.bucket_of(len(ids)) - len(ids)), jnp.int32)
            state, out, n = prefill(dec.params, state, padded, jnp.int32(len(ids)), jnp.int32(slot), cfg=dec.cfg)
            tokens[slot] = [int(jnp.argmax(out))]
            state["pos"] = state["pos"].at[slot].set(len(ids))
            state["last"] = state["last"].at[slot].set(tokens[slot][0])
            counts.append(np.asarray(n).tolist())
        for _ in range(steps):
            state, out, n = decode(dec.params, state, jnp.asarray(active), cfg=dec.cfg)
            step = np.asarray(jnp.argmax(out, axis=-1))
            for slot in prompts:
                tokens[slot].append(int(step[slot]))
            state["pos"] = jnp.where(active, state["pos"] + 1, state["pos"])
            state["last"] = jnp.where(active, jnp.asarray(step, jnp.int32), state["last"])
            logits.append(np.asarray(out)[active])
            counts.append(np.asarray(n).tolist())
        return tokens, np.stack(logits), counts

    streamed = run()
    with monkeypatch.context() as m:
        m.setattr(moe, "streamed_product", moe.grouped_product)
        grouped = run()
    return streamed, grouped


def test_a_steps_expert_layers_all_stream_and_give_the_grouped_products_tokens(params, monkeypatch):
    """Every step's four expert layers run the streamed kernel (here in the
    Pallas interpreter) and a 64 bucket's prefill runs none (it is batched);
    three slots, one of them choosing what the others choose in some layers,
    get the tokens the grouped product gives, and the reference's."""
    prompts = {0: prompt_of(41, seed=53), 2: prompt_of(50, seed=54), 3: prompt_of(33, seed=55)}
    (tokens, logits, counts), (g_tokens, g_logits, g_counts) = slots_through_both_forms(
        lfm2, decoder(params, buckets=(16, 64)), prompts, 6, monkeypatch)
    assert tokens == g_tokens and counts == g_counts
    assert_close(logits, g_logits, share=1e-5)
    assert [c[1:] for c in counts] == [[4, 0]] * 3 + [[0, 4]] * 6  # (batched, streamed) layers of each call
    for slot, ids in prompts.items():
        assert_greedy(params, ids, tokens[slot])
