"""A.X-K1's keys in the latent-attention decoder (``models/mistral4.py``)
against its plain reference (``models/axk1_reference.py``), at a tiny size on
the CPU: hidden 64, 3 layers of which the first is dense (a SwiGLU of 96), 4
heads of 16 + 8 for queries and keys and 16 for values (A.X-K1's 192 / 128 in
small), ranks 32 and 16, a sigmoid router over 32 experts in 8 groups of 4 that
keeps 4 groups and chooses 8 experts, weights scaled by 2.5, a shared expert;
one of sixteen shares held here (experts 6-7, the second half of group 1),
vocabulary 4,096 of which 1,024 rows are held, seeded weights.

Tolerances, as ``tests/test_mistral4.py``'s. With float32 parameters the
program multiplies exactly and differs from the reference only in the order of
its sums and the form of its attention: the logits agree to a thousandth of
their spread (they read 1e-5; the program in bfloat16 reads 0.048, about fifty
times the limit). With bfloat16 parameters, as served, they agree to 0.15 of
their spread at this size (0.048; the same weights rounded to float8 read
1.35), and no token is compared.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.models import axk1_reference as ref
from pathway_tpu.models import mistral4
from pathway_tpu.models.generation_service import GenerationService

from .test_mistral4 import assert_close, prompt_of, run_through_cache

ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
UNCUT = dict(
    vocab_size=4096, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=32, num_experts_per_tok=8,
    n_shared_experts=1, moe_intermediate_size=16, intermediate_size=96, first_k_dense_replace=1,
    scoring_func="sigmoid", n_group=8, topk_group=4, topk_method="none", norm_topk_prob=True,
    routed_scaling_factor=2.5, rope_theta=10000, rope_scaling=ROPE, seq_aux=True,
)
TINY = dict(UNCUT, vocab_size=1024, n_routed_experts=2, n_router_experts=32, first_expert=6)
CFG = mistral4.Mistral4Config.from_dict(TINY)
WHOLE = mistral4.Mistral4Config.from_dict(UNCUT)
EXPERT_LAYERS = 2

FORWARD = jax.jit(ref.forward, static_argnames=("cfg",))


def share_of(whole, cfg):
    """One chip's share of an uncut parameter tree: its experts of every expert
    layer, its rows of the table and of the head; everything else whole."""
    lo, hi = cfg.first_expert, cfg.first_expert + cfg.n_routed_experts
    layers = [dict(p, w1=p["w1"][lo:hi], w3=p["w3"][lo:hi], w2=p["w2"][lo:hi]) if "gate" in p else p
              for p in whole["layers"]]
    return dict(whole, embed=whole["embed"][: cfg.vocab_size], lm_head=whole["lm_head"][:, : cfg.vocab_size],
                layers=layers)


@pytest.fixture(scope="module")
def whole():
    return mistral4.init_params(WHOLE, seed=7, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(whole):
    return share_of(whole, CFG)


def decoder(params, cfg=CFG, slots=4, buckets=(16, 64), new=9):
    return mistral4.Mistral4Decoder(cfg, params, slots=slots, max_prompt_tokens=max(buckets), max_new_tokens=new,
                                    prefill_buckets=buckets)


def reference(params, seq, cfg=CFG, pad_to=80):
    """The reference's (logits, chosen experts, kept groups) at every position
    of ``seq`` (the last two per expert layer), padded on the right (the model
    is causal)."""
    logits, chosen, kept = FORWARD(params, jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32), cfg=cfg)
    n = len(seq)
    return np.asarray(logits)[:n], [np.asarray(c)[:n] for c in chosen], [np.asarray(k)[:n] for k in kept]


def assert_greedy(params, prompt, tokens, cfg=CFG):
    logits, _, _ = reference(params, prompt + tokens, cfg)
    assert tokens == np.argmax(logits[len(prompt) - 1 : -1], axis=-1).tolist()


def held(c, cfg=CFG):
    return c[(c >= cfg.first_expert) & (c < cfg.first_expert + cfg.n_routed_experts)]


def reaches(kept, cfg=CFG):
    """Rows whose kept groups include this share's group."""
    return int(kept[:, cfg.first_expert // (cfg.router_width // cfg.n_group)].sum())


@pytest.mark.parametrize("length", [11, 50])
def test_prefill_then_decode_through_the_latent_cache_gives_the_references_logits_at_every_position(params, length):
    """A 16 bucket (its expert layers stream) and a 64 bucket (8 pairs over a
    router of 32 give an expert 16 rows: batched), then eight steps."""
    prompt = prompt_of(length, seed=length)
    served, tokens, counts = run_through_cache(decoder(params), 1, prompt, 8)
    full, _, _ = reference(params, prompt + tokens)  # the expanded form, no cache
    want = full[length - 1 : length + 8]
    assert np.std(want) > 0.1  # the logits spread: a wrong program would pick other tokens
    assert_close(served, want)
    assert_greedy(params, prompt, tokens)
    assert counts[0][4:] == ([0, EXPERT_LAYERS] if length < 16 else [EXPERT_LAYERS, 0])


def test_the_router_keeps_four_groups_by_their_two_best_and_weighs_the_chosen_eight_to_the_scale(whole):
    """The program's router and the reference's against the equations spelled
    out in float64: sigmoid scores; a group's score is the sum of its two best;
    the 4 best groups are kept and the 8 best experts chosen inside them; the
    weights are the chosen scores over their sum, times 2.5."""
    p = whole["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(2), (40, WHOLE.hidden_size), jnp.float32)
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(h, np.float64) @ np.asarray(p["gate"], np.float64))))
    groups = scores.reshape(40, 8, 4)
    keep = np.argsort(-np.sort(groups, axis=-1)[..., -2:].sum(-1), axis=-1)[:, :4]
    kept = np.zeros((40, 8), bool)
    np.put_along_axis(kept, keep, True, axis=1)
    chosen = np.argsort(-np.where(kept[:, :, None], groups, 0.0).reshape(40, 32), axis=-1)[:, :8]
    weights = np.take_along_axis(scores, chosen, -1)
    weights = weights / weights.sum(-1, keepdims=True) * 2.5
    with jax.default_matmul_precision("highest"):
        ref_chosen, ref_weights, ref_kept = ref.route(p, h, WHOLE)
    got_weights, got_chosen, got_kept = mistral4.route(p, h, WHOLE)
    for c, w, k in ((got_chosen, got_weights, got_kept), (ref_chosen, ref_weights, ref_kept)):
        np.testing.assert_array_equal(np.asarray(c), chosen)  # best first, as both order them
        np.testing.assert_allclose(np.asarray(w), weights, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(k), kept)
    np.testing.assert_allclose(np.asarray(got_weights).sum(-1), 2.5, rtol=1e-6)
    # the limit chooses: over all 32 experts some tokens' best 8 lie outside their kept groups
    unlimited = np.sort(np.argsort(-scores, axis=-1)[:, :8], axis=-1)
    assert (unlimited != np.sort(chosen, axis=-1)).any(axis=1).sum() >= 10
    # and each chosen expert lies in a kept group
    assert np.take_along_axis(kept, chosen // 4, axis=1).all()


def test_the_sixteen_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer(whole):
    """Expert parallelism over sixteen chips, the router's groups intact: what
    each chip computes from its 2 of the 32 experts, summed, with the shared
    expert counted once, is the uncut reference's whole expert layer; every
    routed pair is held by exactly one share; a share's ``group_rows`` counts
    the tokens whose kept groups include its group, so the sixteen count each
    token's four kept groups twice over."""
    p = whole["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(0), (12, WHOLE.hidden_size), jnp.float32)
    valid = jnp.ones((12,), bool)
    with jax.default_matmul_precision("highest"):
        routed, chosen, kept = ref.routed_ffn(p, h, WHOLE)
        want = ref.shared_ffn(p, h) + routed
    total, pairs_held, group_rows = mistral4.shared_expert(p, h), 0, 0
    for rank in range(16):
        cfg = mistral4.Mistral4Config.from_dict(dict(TINY, first_expert=2 * rank))
        part, counts = mistral4.routed_experts(share_of(whole, cfg)["layers"][2], h, valid, cfg)
        total = total + part
        mine = held(np.asarray(chosen), cfg)
        assert counts.tolist()[:4] == [len(set(mine.tolist())), mine.size, 12 * 8, reaches(np.asarray(kept), cfg)]
        pairs_held += int(counts[1])
        group_rows += int(counts[3])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    assert pairs_held == 12 * 8 and group_rows == 12 * 4 * 2
    # one share alone is not the layer: the absent experts' part is left out, not made up
    alone, _ = mistral4._ffn(share_of(whole, CFG)["layers"][2], h, valid, 2, CFG)
    assert float(jnp.max(jnp.abs(alone - want))) > 0.05


def test_the_dense_first_layer(whole, params):
    """Layer 0 holds a SwiGLU of ``intermediate_size`` and no router, shared
    expert or experts; its feed-forward is that SwiGLU alone, under its own
    scope, and counts nothing. Layers 1-2 are expert layers."""
    tree = mistral4.param_shapes(CFG, jnp.float32)
    assert set(tree["layers"][0]) - set(tree["layers"][1]) == set()
    assert {k for k in tree["layers"][1] if k not in tree["layers"][0]} == {"gate", "shared_w1", "shared_w3",
                                                                             "shared_w2"}
    assert tree["layers"][0]["w1"].shape == (64, 96) and tree["layers"][0]["w2"].shape == (96, 64)
    assert tree["layers"][1]["w1"].shape == (2, 64, 16) and tree["layers"][2]["gate"].shape == (64, 32)
    p = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(1), (5, 64), jnp.float32)
    out, counts = mistral4._ffn(p, h, jnp.ones((5,), bool), 0, CFG)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref.dense_ffn(p, h)), atol=1e-5)
    assert counts.tolist() == [0] * 6
    dense = jax.jit(lambda p, h: mistral4._ffn(p, h, jnp.ones((5,), bool), 0, CFG))
    lowered = dense.lower(p, h).as_text(debug_info=True)
    assert "dense_mlp" in lowered and "moe_" not in lowered
    # the dense layer is part of the model: without it the logits move
    prompt = prompt_of(9, seed=3)
    bare = dict(params, layers=[dict(p, w2=jnp.zeros_like(p["w2"]))] + params["layers"][1:])
    served, _, _ = run_through_cache(decoder(params), 0, prompt, 0)
    without, _, _ = run_through_cache(decoder(bare), 0, prompt, 0)
    assert np.max(np.abs(served - without)) > 0.1 * np.std(served)


def test_the_counts_equal_the_references(params):
    """Each call's six counts over the two expert layers: distinct held experts
    chosen, routed pairs held, routed pairs, ``group_rows`` (tokens whose kept
    groups include group 1, this share's), then the form of the product."""
    prompts = {1: prompt_of(9, 40), 2: prompt_of(14, 41)}
    dec = decoder(params)
    sequences = {}
    for slot, ids in prompts.items():
        token, counts = dec.prefill(slot, ids)
        _, chosen, kept = reference(params, ids)
        assert np.asarray(counts).tolist() == [sum(len(set(held(c).tolist())) for c in chosen),
                                               sum(held(c).size for c in chosen), EXPERT_LAYERS * 8 * len(ids),
                                               sum(reaches(k) for k in kept), 0, EXPERT_LAYERS]
        sequences[slot] = ids + [int(token)]
    active = np.array([False, True, True, False])
    grew = 0
    for _ in range(5):
        tokens, counts = dec.decode(active)
        chosen_now, kept_now = [[] for _ in range(EXPERT_LAYERS)], 0
        for slot, seq in sequences.items():
            _, chosen, kept = reference(params, seq)
            for layer, c in enumerate(chosen):
                chosen_now[layer] += held(c[-1:]).tolist()
            kept_now += sum(reaches(k[-1:]) for k in kept)
            seq.append(int(tokens[slot]))
        assert np.asarray(counts).tolist() == [sum(len(set(c)) for c in chosen_now), sum(map(len, chosen_now)),
                                               2 * EXPERT_LAYERS * 8, kept_now, 0, EXPERT_LAYERS]
        grew += kept_now
    assert 0 < grew < 5 * 2 * EXPERT_LAYERS  # some rows' kept groups leave group 1 out


def test_a_burst_through_the_service_equals_one_at_a_time_and_sums_group_rows(params):
    """Eight prompts at once into four slots (each freed and filled again)
    give the reference's greedy tokens; the service sums the decoder's new
    count as it sums any, a step's as ``lm_group_rows``."""
    prompts = [prompt_of(n, seed=80 + n) for n in (4, 31, 9, 16, 2, 23, 12, 27)]
    svc = GenerationService(decoder(params, new=6))
    tokens = [f.result(timeout=120) for f in [svc.submit(p) for p in prompts]]
    stats = svc.stats()
    svc.close()
    for prompt, got in zip(prompts, tokens):
        assert_greedy(params, prompt, got)
    rows = stats["lm_decode_rows"]
    assert rows == 8 * 5 and stats["lm_routed_pairs"] == rows * EXPERT_LAYERS * 8
    assert 0 < stats["lm_group_rows"] < rows * EXPERT_LAYERS and 0 < stats["lm_prefill_group_rows"]
    assert stats["lm_routed_pairs_held"] < stats["lm_routed_pairs"] / 8


def test_bfloat16_as_served_stays_near_the_float32_reference(whole):
    p = share_of(mistral4.init_params(WHOLE, seed=7), CFG)  # bfloat16 matrices and cache, float32 norms and router
    assert p["embed"].dtype == jnp.bfloat16 and p["layers"][2]["gate"].dtype == jnp.float32
    assert p["layers"][0]["w1"].dtype == jnp.bfloat16
    prompt = prompt_of(11)
    served, tokens, _ = run_through_cache(decoder(p), 1, prompt, 8)
    full, _, _ = reference(p, prompt + tokens)
    want = full[len(prompt) - 1 : len(prompt) + 8]
    gap = np.max(np.abs(served - want)) / np.std(want)
    assert 1e-3 < gap < 0.15  # bfloat16's rounding shows, and stays well inside the spread


PUBLISHED = {  # skt/A.X-K1's config.json
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 7168,
    "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 131072, "model_type": "axk1",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 64, "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840,
}


def test_config_from_the_published_json_and_its_cut():
    whole = mistral4.Mistral4Config.from_dict(PUBLISHED)
    assert (whole.first_k_dense_replace, whole.intermediate_size, whole.scoring_func, whole.n_group,
            whole.topk_group, whole.routed_scaling_factor) == (1, 18432, "sigmoid", 8, 4, 2.5)
    assert (whole.rope_factor, whole.rope_original_max_position_embeddings, whole.qk_head_dim) == (32, 4096, 192)
    m = 0.1 * math.log(32) + 1
    assert m == pytest.approx(1.3466, abs=1e-4) and whole.softmax_scale == pytest.approx(0.13086, abs=1e-5)
    shapes = lambda cfg: jax.tree.leaves(mistral4.param_shapes(cfg))
    count = lambda cfg: sum(int(np.prod(s.shape)) for s in shapes(cfg))
    # 61 layers: a dense one of 497,500,160 and 60 of 146,554,880 + 192 experts of 44,040,192, the table and the head
    assert count(whole) == 497_500_160 + 60 * (146_554_880 + 192 * 44_040_192) + 2 * 163840 * 7168 + 7168
    assert round(count(whole) / 1e9, 1) == 519.0
    cut = mistral4.Mistral4Config.from_dict(dict(PUBLISHED, num_hidden_layers=7, n_routed_experts=12,
                                                 n_router_experts=192, first_expert=0, vocab_size=20480))
    assert count(cut) == 4_841_331_712  # layers 0-6, experts 0-11 of each expert layer, rows 0-20,479
    gb = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in shapes(cut)) / 1e9
    assert round(gb, 2) == 9.70
    assert cut.router_width == 192 and mistral4.param_shapes(cut)["layers"][3]["gate"].shape == (7168, 192)
    with pytest.raises(ValueError):
        mistral4.Mistral4Config.from_dict(dict(PUBLISHED, topk_method="noaux_tc"))  # its correction bias is not here
    with pytest.raises(ValueError):
        mistral4.Mistral4Config.from_dict(dict(PUBLISHED, n_group=7))


def test_the_chat_serves_a_x_k1_from_its_config(whole):
    """``Mistral4Chat`` takes A.X-K1's config as it stands: the decoder reads
    the dense layer, the sigmoid and the groups from it. (The hash tokenizer
    wants over 3,000 ids, so all 4,096 rows are held here.)"""
    from pathway_tpu.xpacks.llm.llms import Mistral4Chat

    config = dict(TINY, vocab_size=4096)
    cfg = mistral4.Mistral4Config.from_dict(config)
    p = share_of(whole, cfg)
    chat = Mistral4Chat(config, p, slots=2, max_prompt_tokens=32, max_new_tokens=5, prefill_buckets=(16, 32))
    assert chat.config == cfg and cfg.first_k_dense_replace == 1 and cfg.scoring_func == "sigmoid"
    ids = chat.tokenize("what does the w17 of w9 say")
    tokens = chat.service.submit(ids).result(timeout=120)
    chat.service.close()
    assert len(tokens) == 5
    assert_greedy(p, ids, tokens, cfg)
