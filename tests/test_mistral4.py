"""The ``mistral4`` decoder's two device programs against the plain reference
(``models/mistral4_reference.py``), at a tiny size on the CPU: hidden 64, 3
layers, 4 heads, ranks 32 (queries) and 16 (the latent), head sizes 8 / 8 / 16
(unrotated, rotated, value), a router over 16 experts with top 2 of which 4 are
held here (experts 4-7: the second of four shares), vocabulary 4,096 of which
1,024 rows are held, seeded weights.

Tolerances. With float32 parameters the program multiplies exactly
(``Precision.HIGHEST``) and differs from the reference in the order of its sums
and in the form of its attention (the step folds ``Wuk`` into the query and
applies ``Wuv`` after the mix; the reference expands keys and values): the
logits agree to a thousandth of their spread. With bfloat16 parameters, as
served, the logits agree to 0.15 of their spread at this size and no token is
compared.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pathway_tpu as pw
from pathway_tpu.models import mistral4
from pathway_tpu.models import mistral4_reference as ref
from pathway_tpu.models.generation_service import GenerationService

UNCUT = dict(
    vocab_size=4096, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16, num_experts_per_tok=2,
    n_shared_experts=1, moe_intermediate_size=32,
    rope_parameters={"rope_theta": 10000, "factor": 128, "original_max_position_embeddings": 8192, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1, "rope_type": "yarn", "llama_4_scaling_beta": 0.1},
)
TINY = dict(UNCUT, vocab_size=1024, n_routed_experts=4, n_router_experts=16, first_expert=4)
CFG = mistral4.Mistral4Config.from_dict(TINY)
WHOLE = mistral4.Mistral4Config.from_dict(UNCUT)


def assert_close(served, want, share=1e-3):
    """Within ``share`` of the reference logits' spread."""
    assert np.max(np.abs(np.asarray(served) - np.asarray(want))) < share * np.std(np.asarray(want))


def share_of(whole, cfg):
    """One chip's share of an uncut parameter tree: its experts of every layer,
    its rows of the table and of the head; everything else whole."""
    lo, hi = cfg.first_expert, cfg.first_expert + cfg.n_routed_experts
    layers = [dict(p, w1=p["w1"][lo:hi], w3=p["w3"][lo:hi], w2=p["w2"][lo:hi]) for p in whole["layers"]]
    return dict(whole, embed=whole["embed"][: cfg.vocab_size], lm_head=whole["lm_head"][:, : cfg.vocab_size],
                layers=layers)


@pytest.fixture(scope="module")
def whole():
    return mistral4.init_params(WHOLE, seed=3, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(whole):
    return share_of(whole, CFG)


def decoder(params, cfg=CFG, slots=4, buckets=(16, 32), new=9):
    return mistral4.Mistral4Decoder(cfg, params, slots=slots, max_prompt_tokens=max(buckets), max_new_tokens=new,
                                    prefill_buckets=buckets)


# the un-jitted cores, jitted here so that a test does not dispatch them op by op
PREFILL = jax.jit(mistral4.prefill_logits, static_argnames=("cfg",))
DECODE = jax.jit(mistral4.decode_logits, static_argnames=("cfg",))
FORWARD = jax.jit(ref.forward, static_argnames=("cfg",))


def reference(params, seq, cfg=CFG, pad_to=48):
    """The reference's (logits, chosen experts per layer) at every position of
    ``seq``, padded on the right to one length (the model is causal)."""
    logits, chosen = FORWARD(params, jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32), cfg=cfg)
    return np.asarray(logits)[: len(seq)], [np.asarray(c)[: len(seq)] for c in chosen]


def assert_greedy(params, prompt, tokens, cfg=CFG):
    """``tokens`` are the reference's greedy continuation of ``prompt``."""
    logits, _ = reference(params, prompt + tokens, cfg)
    assert tokens == np.argmax(logits[len(prompt) - 1 : -1], axis=-1).tolist()


def prompt_of(n, seed=0, vocab=CFG.vocab_size):
    return np.random.default_rng(seed).integers(0, vocab, size=n).tolist()


def run_through_cache(dec, slot, prompt, steps):
    """Prefill (expanded) then ``steps`` decode steps (absorbed) of one slot:
    (logits per position, tokens, the counts of each call)."""
    state, logits, counts = PREFILL(
        dec.params, dec.state, jnp.asarray(prompt + [0] * (dec.bucket_of(len(prompt)) - len(prompt)), jnp.int32),
        jnp.int32(len(prompt)), jnp.int32(slot), cfg=dec.cfg)
    token = int(jnp.argmax(logits))
    state["pos"] = state["pos"].at[slot].set(len(prompt))
    state["last"] = state["last"].at[slot].set(token)
    rows, tokens, counted = [np.asarray(logits)], [token], [np.asarray(counts).tolist()]
    active = np.zeros((dec.slots,), bool)
    active[slot] = True
    for _ in range(steps):
        state, logits, counts = DECODE(dec.params, state, jnp.asarray(active), cfg=dec.cfg)
        token = int(jnp.argmax(logits[slot]))
        state["pos"] = state["pos"].at[slot].add(1)
        state["last"] = state["last"].at[slot].set(token)
        rows.append(np.asarray(logits[slot]))
        tokens.append(token)
        counted.append(np.asarray(counts).tolist())
    dec.state = state
    return np.stack(rows), tokens, counted


def test_prefill_then_decode_through_the_latent_cache_gives_the_references_logits_at_every_position(params):
    prompt = prompt_of(11)
    served, tokens, _ = run_through_cache(decoder(params), 1, prompt, 8)
    full, _ = reference(params, prompt + tokens)  # the expanded form, no cache
    want = full[len(prompt) - 1 : len(prompt) + 8]
    assert np.std(want) > 0.1  # the logits spread: a wrong program would pick other tokens
    assert_close(served, want)
    assert_greedy(params, prompt, tokens)


@pytest.mark.parametrize("length", [128, 90])
def test_a_prefill_bucket_with_sixteen_rows_an_expert_takes_the_batched_product_and_holds_the_reference(params, length):
    """Buckets of 16 and 32 tokens (2 of the router's 16 each: 2 and 4 rows an
    expert) lower to the streamed kernel alone; 128 is at the floor, and the
    product of each of the three layers runs batched over the 4 held experts'
    64 places, the pairs of the 12 absent ones "none". The steps stream."""
    prompt = prompt_of(length, seed=50)
    served, tokens, counts = run_through_cache(decoder(params, buckets=(16, 128)), 2, prompt, 3)
    full, chosen = reference(params, prompt + tokens, pad_to=144)
    assert_close(served, full[length - 1 : length + 3])
    assert_greedy(params, prompt, tokens)
    held = [c[:length][(c[:length] >= CFG.first_expert) & (c[:length] < CFG.first_expert + CFG.n_routed_experts)]
            for c in chosen]
    assert max(np.bincount(c).max() for c in held) <= 64  # no held expert over its capacity: nothing falls back
    assert counts[0] == [sum(len(set(c.tolist())) for c in held), sum(c.size for c in held), 3 * 2 * length, 3, 0]
    assert [c[3:] for c in counts[1:]] == [[0, 3]] * 3


def test_the_four_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer(whole):
    """Expert parallelism's bookkeeping: what each of four chips computes from
    its 4 of the 16 experts, summed, with the shared expert (which every chip
    computes alike) counted once, is the uncut reference's whole expert layer."""
    p = whole["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(0), (10, WHOLE.hidden_size), jnp.float32)
    valid = jnp.ones((10,), bool)
    with jax.default_matmul_precision("highest"):
        routed, chosen = ref.routed_ffn(p, h, WHOLE)
        want = ref.shared_ffn(p, h) + routed
    total, held, pairs = mistral4.shared_expert(p, h), 0, set()
    for rank in range(4):
        cfg = mistral4.Mistral4Config.from_dict(dict(TINY, first_expert=4 * rank))
        part, counts = mistral4.routed_experts(share_of(whole, cfg)["layers"][1], h, valid, cfg)
        with jax.default_matmul_precision("highest"):
            ref_part, _ = ref.routed_ffn(share_of(whole, cfg)["layers"][1], h, cfg)
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref_part), atol=1e-5)
        total = total + part
        held += int(counts[1])
        pairs.add(int(counts[2]))
        mine = np.asarray(chosen)[(np.asarray(chosen) >= 4 * rank) & (np.asarray(chosen) < 4 * rank + 4)]
        assert int(counts[0]) == len(set(mine.tolist())) and int(counts[1]) == mine.size
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    assert held == 20 and pairs == {20}  # every routed pair is held by exactly one share
    # one share alone is not the layer: the absent experts' part is left out, not made up
    assert float(jnp.max(jnp.abs(mistral4._moe(share_of(whole, CFG)["layers"][1], h, valid, CFG)[0] - want))) > 0.05
    # and a token that is not valid chooses nothing and gets the shared expert alone
    out, counts = mistral4._moe(share_of(whole, CFG)["layers"][1], h, jnp.arange(10) < 0, CFG)
    assert counts.tolist() == [0, 0, 0, 0, 1]
    np.testing.assert_allclose(np.asarray(out), np.asarray(mistral4.shared_expert(p, h)), atol=1e-6)


def test_logits_over_the_slice_equal_the_uncut_heads_rows(whole):
    """A sliced vocabulary is a smaller vocabulary: ids from the slice, and the
    slice's logits are the uncut head's logits of those rows."""
    cfg = mistral4.Mistral4Config.from_dict(dict(UNCUT, vocab_size=1024))
    prompt = prompt_of(9, seed=4, vocab=1024)
    served, tokens, _ = run_through_cache(decoder(share_of(whole, cfg), cfg), 2, prompt, 5)
    assert served.shape == (6, 1024) and max(tokens) < 1024
    full, _ = reference(whole, prompt + tokens, WHOLE)
    assert full.shape[1] == 4096
    want = full[len(prompt) - 1 : len(prompt) + 5, :1024]
    assert np.max(np.abs(served - want)) < 1e-3 * np.std(want)


@pytest.mark.parametrize("cfg", [CFG, mistral4.Mistral4Config()], ids=["tiny", "published"])
def test_yarn_frequencies_and_interleaved_rope_against_the_references_own_spelling(cfg):
    got, want = np.asarray(mistral4.yarn_inv_freq(cfg)), np.asarray(ref.yarn_inv_freq(cfg))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    half = cfg.qk_rope_head_dim // 2
    plain = 10000.0 ** (-2.0 * np.arange(half) / cfg.qk_rope_head_dim)
    if cfg.qk_rope_head_dim == 64:  # issue 32's numbers: untouched up to pair 12, divided by 128 from pair 25 on
        np.testing.assert_allclose(got[:13], plain[:13], rtol=1e-6)
        np.testing.assert_allclose(got[25:], plain[25:] / 128, rtol=1e-6)
        assert plain[18] / 128 < got[18] < plain[18]
        assert cfg.softmax_scale == pytest.approx(128 ** -0.5 * (0.1 * math.log(128) + 1) ** 2)
        assert 0.1 * math.log(128) + 1 == pytest.approx(1.4852, abs=1e-4) and ref.attention_factor(cfg) == 1.0
    assert cfg.softmax_scale == pytest.approx(ref.softmax_scale(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (7, 3, cfg.qk_rope_head_dim), jnp.float32)
    positions = jnp.asarray([0, 1, 2, 5, 100, 1500, 2111])
    turned = np.asarray(mistral4._rope(x, positions, cfg))
    np.testing.assert_allclose(turned, np.asarray(ref.rope_interleaved(x, positions, cfg)), atol=3e-4)  # float32 angles of up to 2,111 radians
    np.testing.assert_allclose(np.asarray(mistral4._rope(x[:, 0], positions, cfg)), turned[:, 0], atol=1e-6)
    # pairs are neighbours (2i, 2i+1), not halves: the rotation keeps each neighbour pair's length
    pairs = lambda a: np.asarray(a).reshape(7, 3, half, 2)
    np.testing.assert_allclose(np.linalg.norm(pairs(turned), axis=-1), np.linalg.norm(pairs(x), axis=-1), rtol=1e-4)
    np.testing.assert_array_equal(turned[0], np.asarray(x)[0])  # position 0 turns nothing
    split = np.asarray(x).reshape(7, 3, 2, half)  # rotate-half's pairs (i, i + half) are not kept
    assert np.abs(np.linalg.norm(turned.reshape(7, 3, 2, half), axis=2) - np.linalg.norm(split, axis=2)).max() > 0.1


def test_the_cache_holds_the_normed_latent_and_the_one_rotated_key(params):
    prompt = prompt_of(7, seed=2)
    dec = decoder(params)
    dec.prefill(3, prompt)
    layer = params["layers"][0]
    h = ref.rmsnorm(params["embed"][jnp.asarray(prompt)], layer["attn_norm"], CFG.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        both = h @ layer["wdkv"]
    ckv = ref.rmsnorm(both[:, :16], layer["kv_norm"], CFG.rms_norm_eps)
    kr = ref.rope_interleaved(both[:, 16:], jnp.arange(7), CFG)
    assert dec.state["ckv"][0].shape == (4, dec.max_len, 16) and dec.state["kr"][0].shape == (4, dec.max_len, 8)
    np.testing.assert_allclose(np.asarray(dec.state["ckv"][0][3, :7]), np.asarray(ckv), atol=1e-5)
    np.testing.assert_allclose(np.asarray(dec.state["kr"][0][3, :7]), np.asarray(kr), atol=1e-5)
    assert float(jnp.max(jnp.abs(kr - both[:, 16:]))) > 0.1  # the rotation is not the identity here
    # 24 numbers a token and layer, against 4 heads x (16 + 16) for full keys and values
    assert not np.asarray(dec.state["ckv"][0][2]).any()  # the neighbour slot wrote nothing


def service_tokens(params, prompts, slots, new=6):
    svc = GenerationService(decoder(params, slots=slots, new=new))
    futures = [svc.submit(p) for p in prompts]
    tokens = [f.result(timeout=120) for f in futures]
    stats = svc.stats()
    svc.close()
    return tokens, stats


def test_a_slot_freed_and_refilled_and_a_burst_of_twice_the_slots_equal_one_at_a_time_generation(params):
    first, second = prompt_of(30, 20), prompt_of(6, 21)
    used = decoder(params)
    run_through_cache(used, 1, first, 8)  # the slot now holds 38 positions of another request
    again, tokens, _ = run_through_cache(used, 1, second, 8)
    fresh, fresh_tokens, _ = run_through_cache(decoder(params), 1, second, 8)
    np.testing.assert_array_equal(again, fresh)
    assert tokens == fresh_tokens
    assert_greedy(params, second, tokens)
    # eight prompts at once into four slots: every slot live, each freed and filled again
    prompts = [prompt_of(n, seed=60 + n) for n in (4, 31, 9, 16, 2, 23, 12, 27)]
    burst, stats = service_tokens(params, prompts, slots=4)
    alone = [service_tokens(params, [p], slots=4)[0][0] for p in prompts]
    assert burst == alone
    for prompt, got in zip(prompts, burst):
        assert_greedy(params, prompt, got)
    assert stats["lm_prefill_calls"] == 8 and stats["lm_decode_rows"] == 8 * 5
    assert stats["lm_routed_pairs"] == 8 * 5 * 3 * 2 and stats["lm_prefill_routed_pairs"] == 3 * 2 * sum(map(len, prompts))
    assert 0 < stats["lm_routed_pairs_held"] < stats["lm_routed_pairs"]
    assert stats["lm_experts_touched"] <= stats["lm_routed_pairs_held"]


def test_the_counts_equal_the_references(params):
    prompts = {1: prompt_of(9, 40), 2: prompt_of(14, 41)}
    dec = decoder(params)
    held = lambda c: c[(c >= CFG.first_expert) & (c < CFG.first_expert + CFG.n_routed_experts)]
    sequences = {}
    for slot, ids in prompts.items():
        token, counts = dec.prefill(slot, ids)
        _, chosen = reference(params, ids)
        # a 16 bucket gives a held expert 2 rows: the three layers stream in the prefill too
        assert np.asarray(counts).tolist() == [sum(len(set(held(c).tolist())) for c in chosen),
                                               sum(held(c).size for c in chosen), 3 * 2 * len(ids), 0, 3]
        sequences[slot] = ids + [int(token)]
    active = np.array([False, True, True, False])
    for _ in range(4):
        tokens, counts = dec.decode(active)
        per_layer = [[] for _ in range(3)]
        for slot, seq in sequences.items():
            _, chosen = reference(params, seq)
            for layer, c in enumerate(chosen):
                per_layer[layer] += held(c[-1]).tolist()
            seq.append(int(tokens[slot]))
        assert np.asarray(counts).tolist() == [sum(len(set(c)) for c in per_layer), sum(len(c) for c in per_layer), 12, 0,
                                               3]


def test_the_padding_bucket_changes_nothing(params):
    prompt = prompt_of(13, 30)
    small, t_small, n_small = run_through_cache(decoder(params, buckets=(16, 32)), 0, prompt, 3)
    large, t_large, n_large = run_through_cache(decoder(params, buckets=(32,)), 0, prompt, 3)
    assert_close(small, large)
    assert t_small == t_large and n_small == n_large


def test_bfloat16_as_served_stays_near_the_float32_reference():
    p = share_of(mistral4.init_params(WHOLE, seed=3), CFG)  # bfloat16 matrices and cache, float32 norms and router
    assert p["embed"].dtype == jnp.bfloat16 and p["layers"][2]["gate"].dtype == jnp.float32
    prompt = prompt_of(11)
    dec = decoder(p)
    assert dec.state["ckv"][0].dtype == jnp.bfloat16
    served, tokens, _ = run_through_cache(dec, 1, prompt, 8)
    full, _ = reference(p, prompt + tokens)
    want = full[len(prompt) - 1 : len(prompt) + 8]
    assert np.max(np.abs(served - want)) < 0.15 * np.std(want)


def test_config_from_the_published_json_and_its_cut():
    published = mistral4.Mistral4Config.from_dict({
        "model_type": "mistral4", "first_k_dense_replace": 0, "intermediate_size": 12288, "head_dim": 128,
        "rope_interleave": True, "rope_parameters": UNCUT["rope_parameters"]})
    assert published == mistral4.Mistral4Config() and published.router_width == 128 and published.qk_head_dim == 128
    count = lambda cfg: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(mistral4.param_shapes(cfg)))
    # issue 32's arithmetic: 36 layers of 3,274,974,464, the table and the head, the last norm
    assert count(published) == 36 * 3_274_974_464 + 1_073_741_824 + 4_096
    cut = mistral4.Mistral4Config.from_dict({"num_hidden_layers": 6, "n_routed_experts": 32, "n_router_experts": 128,
                                             "first_expert": 0, "vocab_size": 32768})
    assert count(cut) == 5_422_771_712  # one chip's share of six layers: 10.85 GB in bfloat16
    assert cut.router_width == 128 and mistral4.param_shapes(cut)["layers"][0]["gate"].shape == (4096, 128)
    with pytest.raises(ValueError):
        mistral4.Mistral4Config.from_dict({"n_routed_experts": 32, "n_router_experts": 128, "first_expert": 100})


def test_mistral4_chat_through_fully_async_in_a_select(whole):
    """The chat is a ``fully_async`` UDF: a ``select`` that calls it has a row
    once the reply is there, and the reply is the reference's greedy tokens.
    (The hash tokenizer wants over 3,000 ids, so all 4,096 rows are held here.)"""
    from pathway_tpu.internals.json import Json
    from pathway_tpu.xpacks.llm.llms import DeviceChat, Lfm2Chat, Mistral4Chat
    from tests.utils import capture_update_stream

    config = dict(TINY, vocab_size=4096)
    cfg = mistral4.Mistral4Config.from_dict(config)
    params = share_of(whole, cfg)
    chat = Mistral4Chat(config, params, slots=2, max_prompt_tokens=32, max_new_tokens=5, prefill_buckets=(16, 32))
    assert isinstance(chat, DeviceChat) and issubclass(Lfm2Chat, DeviceChat) and chat.config == cfg
    assert chat.service.decoder is chat.decoder and chat.decoder.count_names == mistral4.COUNT_NAMES
    questions = ["w001 w002 what", "a much longer question " + " ".join(f"w{i:03d}" for i in range(12)), "w7"]
    queries = pw.debug.table_from_rows(pw.schema_builder({"messages": pw.Json}),
                                       [(Json([{"role": "user", "content": q}]),) for q in questions])
    stream = capture_update_stream(queries.select(reply=chat(pw.this.messages)))
    replies = sorted(r["reply"] for r in stream if r["__diff__"] == 1)
    # read before the graph runs again below: a second run may call the chat again
    assert len(replies) == 3 and chat.service.stats()["lm_prefill_calls"] == 3
    for q in questions:
        ids = chat.tokenize(q)
        assert len(ids) == len(q.split()) and all(2000 <= t < 4096 - 1000 for t in ids)  # hash ids, in the slice
        [tokens] = [t for t in map(Mistral4Chat.reply_ids, replies) if continues(params, ids, t, cfg)]
        assert len(tokens) == 5
    # the answers' rows came in commits later than the one that took the questions
    took = {r["__time__"] for r in capture_update_stream(queries)}
    assert not {r["__time__"] for r in stream} & took
    chat.service.close()


def continues(params, ids, tokens, cfg):
    logits, _ = reference(params, ids + tokens, cfg)
    return tokens == np.argmax(logits[len(ids) - 1 : -1], axis=-1).tolist()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_init_params_at_a_seed_is_bit_equal_to_the_parents_draw(dtype):
    """``random_params`` gained four names for another decoder; no leaf of this one moved."""
    from .test_lfm2 import assert_bit_equal, parents_random_params

    assert_bit_equal(mistral4.init_params(WHOLE, seed=3, dtype=dtype),
                     parents_random_params(mistral4.param_shapes(WHOLE, dtype), 3))


def test_a_steps_expert_layers_all_stream_and_give_the_grouped_products_tokens(params, monkeypatch):
    """Every step's three expert layers run the streamed kernel over the 4 held
    experts (here in the Pallas interpreter; a layer in which no held expert has
    a row fetches nothing) and a 128 bucket's prefill runs none (it is
    batched); three slots get the tokens the grouped product gives, and the
    reference's."""
    from .test_lfm2 import slots_through_both_forms

    prompts = {0: prompt_of(100, seed=53), 1: prompt_of(90, seed=54), 3: prompt_of(70, seed=55)}
    (tokens, logits, counts), (g_tokens, g_logits, g_counts) = slots_through_both_forms(
        mistral4, decoder(params, buckets=(16, 128)), prompts, 6, monkeypatch)
    assert tokens == g_tokens and counts == g_counts
    assert_close(logits, g_logits, share=1e-5)
    assert [c[3:] for c in counts] == [[3, 0]] * 3 + [[0, 3]] * 6  # (batched, streamed) layers of each call
    for slot, ids in prompts.items():
        assert_greedy(params, ids, tokens[slot])
