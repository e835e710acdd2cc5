"""The ``falcon_h1`` decoder's two device programs against the plain reference
(``models/falcon_h1_reference.py``), at the rehearsal's size on the CPU: hidden
64, 3 blocks, 4 state-space heads of 16 in 2 groups with a state of 16, a
convolution of 4 taps, chunks of 8 tokens, 4 query and 2 key/value heads of 16,
a SwiGLU of 128, vocabulary 4,096, seeded weights (``init_params``, each matrix
that a multiplier follows divided by it: ``mup_scaled``). The multipliers are
the published ones but ``attention_in_multiplier`` (1 as published, 0.5 here,
so that leaving it out shows).

Tolerances. With float32 parameters the program multiplies exactly
(``Precision.HIGHEST``) and differs from the reference in the order of its sums
and in the form of its scan (chunked products against the token recurrence):
the logits agree to a thousandth of their spread. With bfloat16 parameters, as
served, they agree to 0.15 of their spread at this size and no token is compared.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pathway_tpu as pw
from pathway_tpu.models import falcon_h1
from pathway_tpu.models import falcon_h1_reference as ref
from pathway_tpu.models.generation_service import GenerationService

# https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json, the keys that say something of its shape
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None, "embedding_multiplier": 5.656854249492381, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 21504, "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
    "mamba_n_heads": 32, "mamba_norm_before_gate": False, "mamba_proj_bias": False, "mamba_rms_norm": True,
    "mamba_use_mlp": True, "max_position_embeddings": 262144, "mlp_bias": False, "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284], "model_type": "falcon_h1",
    "num_attention_heads": 20, "num_hidden_layers": 72, "num_key_value_heads": 4, "num_logits_to_keep": 1,
    "projectors_bias": False, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25, "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                                                   0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False, "vocab_size": 261120,
}
TINY = dict(PUBLISHED, hidden_size=64, intermediate_size=128, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=8, vocab_size=4096, attention_in_multiplier=0.5)
CFG = falcon_h1.FalconH1Config.from_dict(TINY)
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")


def assert_close(served, want, share=1e-3):
    """Within ``share`` of the reference logits' spread."""
    assert np.max(np.abs(np.asarray(served) - np.asarray(want))) < share * np.std(np.asarray(want))


def mup_scaled(params, cfg=CFG):
    """muP's own premise: a matrix that a multiplier follows is that much larger
    (``benchmarks/falcon_h1_weights.py`` draws the cell's weights so). With every
    matrix at ``1/sqrt(fan in)`` the published multipliers shrink each branch to
    a few hundredths of the stream, the state-space state adds next to nothing
    beside ``D x`` and a wrong ``mup`` would not show."""
    gate_multiplier, down_multiplier = cfg.mlp_multipliers
    layers = [dict(p, in_proj=p["in_proj"] / (cfg.ssm_in_multiplier * ref.mup_vector(cfg)),
                   out_proj=p["out_proj"] / cfg.ssm_out_multiplier, wq=p["wq"] / cfg.attention_in_multiplier,
                   wk=p["wk"] / (cfg.attention_in_multiplier * cfg.key_multiplier),
                   wv=p["wv"] / cfg.attention_in_multiplier, wo=p["wo"] / cfg.attention_out_multiplier,
                   w1=p["w1"] / gate_multiplier, w2=p["w2"] / down_multiplier) for p in params["layers"]]
    scaled = dict(params, embed=params["embed"] / (0.02 * cfg.embedding_multiplier),
                  lm_head=params["lm_head"] / cfg.lm_head_multiplier, layers=layers)
    return jax.tree.map(lambda a, b: a.astype(b.dtype), scaled, params)


@pytest.fixture(scope="module")
def params():
    return mup_scaled(falcon_h1.init_params(CFG, seed=3, dtype=jnp.float32))


def decoder(params, cfg=CFG, slots=4, buckets=(16, 32), new=9):
    return falcon_h1.FalconH1Decoder(cfg, params, slots=slots, max_prompt_tokens=max(buckets), max_new_tokens=new,
                                     prefill_buckets=buckets)


# the un-jitted cores, jitted here so that a test does not dispatch them op by op
PREFILL = jax.jit(falcon_h1.prefill_logits, static_argnames=("cfg",))
DECODE = jax.jit(falcon_h1.decode_logits, static_argnames=("cfg",))
FORWARD = jax.jit(ref.forward, static_argnames=("cfg",))


def reference(params, seq, cfg=CFG):
    """The reference's (logits at every position of ``seq``, each block's state
    after its last token). No padding: the state is the sequence's own."""
    logits, states = FORWARD(params, jnp.asarray(seq, jnp.int32), cfg=cfg)
    return np.asarray(logits), [np.asarray(s) for s in states]


def assert_greedy(params, prompt, tokens, cfg=CFG):
    """``tokens`` are the reference's greedy continuation of ``prompt``."""
    logits, _ = reference(params, prompt + tokens, cfg)
    assert tokens == np.argmax(logits[len(prompt) - 1 : -1], axis=-1).tolist()


def prompt_of(n, seed=0, vocab=CFG.vocab_size):
    return np.random.default_rng(seed).integers(0, vocab, size=n).tolist()


def prefill(dec, slot, prompt):
    bucket = dec.bucket_of(len(prompt))
    return PREFILL(dec.params, dec.state, jnp.asarray(prompt + [0] * (bucket - len(prompt)), jnp.int32),
                   jnp.int32(len(prompt)), jnp.int32(slot), cfg=dec.cfg)


def run_through_state(dec, slot, prompt, steps):
    """Prefill (the chunked scan) then ``steps`` decode steps (the recurrence)
    of one slot: (logits per position, tokens, the counts of each call)."""
    state, logits, counts = prefill(dec, slot, prompt)
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


def test_prefill_then_decode_through_the_state_gives_the_references_logits_at_every_position(params):
    prompt = prompt_of(11)  # ends inside the second chunk of 8
    dec = decoder(params)
    served, tokens, counts = run_through_state(dec, 1, prompt, 8)
    full, states = reference(params, prompt + tokens)
    want = full[len(prompt) - 1 : len(prompt) + 8]
    assert np.std(want) > 0.1  # the logits spread: a wrong program would pick other tokens
    assert_close(served, want)
    assert_greedy(params, prompt, tokens)
    # a prefill rewrites its slot's state in every block, and so does a step with its one live slot
    assert counts == [[3]] * 9
    # the slot's state after the eighth step is the reference's after the token that step fed
    _, before_last = reference(params, (prompt + tokens)[:-1])
    for got, want in zip(dec.state["ssm"], before_last):
        np.testing.assert_allclose(np.asarray(got[1]), want, atol=1e-5)


STEP = jax.jit(falcon_h1.ssm_step)
MASKS = {"none": [0, 0, 0, 0, 0], "all": [1, 1, 1, 1, 1], "scattered": [0, 1, 0, 1, 1], "first_only": [1, 0, 0, 0, 0],
         "last_only": [0, 0, 0, 0, 1]}


def parent_recurrence(state, dt, a, xs, b, c, active):
    """The step's state-space update as the parent wrote it in XLA: every slot
    advanced, ``y`` of every slot, the state kept by ``where(active)``."""
    slots, n = state.shape[0], state.shape[-1]
    s = state.reshape((slots,) + xs.shape[1:] + (n,))  # (slots, groups, heads a group, d_head, state)
    s = jnp.exp(dt * a)[..., None, None] * s + (dt[..., None] * xs)[..., None] * b[:, :, None, None, :]
    y = jnp.sum(s * c[:, :, None, None, :], axis=-1)
    return jnp.where(active[:, None, None, None], s.reshape(state.shape), state), y


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("groups,heads,d_head,n", [(2, 4, 16, 16), (2, 16, 16, 128)],
                         ids=["tiny", "sixteen_heads_of_a_lane_wide_state"])
def test_the_step_kernel_equals_the_parents_recurrence_and_leaves_other_slots_bit_identical(groups, heads, d_head, n,
                                                                                           mask):
    rng = np.random.default_rng(heads + len(mask))
    slots, hpg = 5, heads // groups
    state = rng.normal(size=(slots, heads, d_head, n)).astype(np.float32)
    xs = rng.normal(size=(slots, groups, hpg, d_head)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(slots, groups, hpg)))).astype(np.float32)
    a = -np.exp(rng.uniform(0.0, 2.5, size=(groups, hpg))).astype(np.float32)
    b, c = (rng.normal(size=(slots, groups, n)).astype(np.float32) for _ in range(2))
    active = np.array(MASKS[mask], bool)
    want_state, want_y = map(np.asarray, parent_recurrence(state, dt, a, xs, b, c, active))
    # laid out as decode_logits lays them out: per head, B and C repeated over a group's heads
    got_state, got_y = map(np.asarray, STEP(
        jnp.asarray(state), jnp.exp(dt * a).reshape(slots, heads, 1), (dt[..., None] * xs).reshape(slots, heads, d_head),
        jnp.repeat(b, hpg, axis=1), jnp.repeat(c, hpg, axis=1), jnp.asarray(active)))
    np.testing.assert_allclose(got_state[active], want_state[active], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_y[active], want_y.reshape(slots, heads, d_head)[active], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got_state[~active].view(np.int32), state[~active].view(np.int32))
    assert not got_y[~active].any()


def test_a_step_counts_the_live_slots_states_and_a_prefill_its_own(params):
    dec = decoder(params)
    state = dec.state
    for slot, prompt in ((0, prompt_of(5, 70)), (2, prompt_of(9, 71)), (3, prompt_of(3, 72))):
        ids = jnp.asarray(prompt + [0] * (16 - len(prompt)), jnp.int32)
        state, _, counts = falcon_h1.lm_prefill(dec.params, state, ids, jnp.int32(len(prompt)), jnp.int32(slot), cfg=CFG)
        assert np.asarray(counts).tolist() == [3]
    for mask in ([0, 0, 0, 0], [0, 0, 1, 0], [1, 0, 1, 1]):
        state, _, counts = falcon_h1.lm_decode(dec.params, state, jnp.asarray(mask, bool), cfg=CFG)
        assert np.asarray(counts).tolist() == [sum(mask) * 3]


def token_recurrence(x, dt, a, b, c):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (outer) B_t``, ``y_t = S_t C_t``, a token at a time."""
    state = np.zeros(x.shape[1:] + (b.shape[-1],), np.float64)
    ys = []
    for t in range(x.shape[0]):
        state = np.exp(dt[t] * a)[..., None, None] * state + (dt[t][..., None] * x[t])[..., None] * b[t][:, None, None, :]
        ys.append(np.sum(state * c[t][:, None, None, :], axis=-1))
    return np.stack(ys), state


@pytest.mark.parametrize("length", [5, 8, 9, 27], ids=["inside_a_chunk", "on_the_boundary", "one_past_it",
                                                       "across_several_chunks"])
def test_the_chunked_scan_equals_the_token_recurrence(length):
    rng = np.random.default_rng(length)
    x = rng.normal(size=(length, 2, 2, 16)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(length, 2, 2)))).astype(np.float32)
    a = -np.exp(rng.uniform(0.0, 2.5, size=(2, 2))).astype(np.float32)
    b, c = (rng.normal(size=(length, 2, 16)).astype(np.float32) for _ in range(2))
    want_y, want_state = token_recurrence(x.astype(np.float64), dt, a, b, c)
    y, state = jax.jit(falcon_h1.ssd_scan, static_argnames=("chunk", "dtype"))(x, dt, a, b, c, chunk=8, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state), want_state, atol=2e-4)
    # places with dt = 0 after the last token leave the state as it was, whole chunks of them too
    pad = lambda v: np.concatenate([v, rng.normal(size=(13,) + v.shape[1:]).astype(np.float32)])
    y_pad, state_pad = falcon_h1.ssd_scan(pad(x), np.concatenate([dt, np.zeros((13, 2, 2), np.float32)]), a,
                                          pad(b), pad(c), 8, jnp.float32)
    np.testing.assert_allclose(np.asarray(state_pad), np.asarray(state), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y_pad[:length]), np.asarray(y), atol=1e-6)


@pytest.mark.parametrize("length", [2, 8, 9, 27])
def test_the_slot_holds_the_references_state_after_the_last_real_token_and_its_last_three_inputs(params, length):
    prompt = prompt_of(length, seed=length)
    dec = decoder(params)
    state, logits, _ = prefill(dec, 2, prompt)
    full, states = reference(params, prompt)
    assert_close(logits, full[-1])
    for got, want in zip(state["ssm"], states):
        assert got.shape == (4, 4, 16, 16) and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got[2]), want, atol=1e-5)
        assert not np.asarray(got[1]).any() and not np.asarray(got[3]).any()  # the neighbours wrote nothing
    # the first block's tail: the convolution's inputs of the last three tokens, zeros where the prompt is shorter
    layer = params["layers"][0]
    h = ref.rmsnorm(params["embed"][jnp.asarray(prompt)] * CFG.embedding_multiplier, layer["input_norm"], CFG.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        zxbcdt = ((CFG.ssm_in_multiplier * h) @ layer["in_proj"]) * ref.mup_vector(CFG)
    inputs = np.concatenate([np.zeros((3, CFG.conv_dim), np.float32), np.asarray(zxbcdt[:, 64 : 64 + CFG.conv_dim])])
    assert state["tail"][0].shape == (4, 3, CFG.conv_dim)
    np.testing.assert_allclose(np.asarray(state["tail"][0][2]), inputs[-3:], atol=1e-5)


def test_the_padding_bucket_changes_nothing(params):
    prompt = prompt_of(13, 30)
    small, large = decoder(params, buckets=(16, 32)), decoder(params, buckets=(32,))
    a, t_a, n_a = run_through_state(small, 0, prompt, 3)
    b, t_b, n_b = run_through_state(large, 0, prompt, 3)
    assert_close(a, b, share=1e-5)
    assert t_a == t_b and n_a == n_b
    for name in ("ssm", "tail"):
        for got, want in zip(small.state[name], large.state[name]):
            np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=1e-5)


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
    run_through_state(used, 1, first, 8)  # the slot now holds another request's state, tail, keys and values
    others = np.array([0, 2, 3])
    neighbours = [np.asarray(s[others]) for s in used.state["ssm"]]
    again, tokens, _ = run_through_state(used, 1, second, 8)
    fresh, fresh_tokens, _ = run_through_state(decoder(params), 1, second, 8)
    np.testing.assert_array_equal(again, fresh)  # the prefill started from a zero state, not from the slot's old one
    assert tokens == fresh_tokens
    assert_greedy(params, second, tokens)
    for before, now in zip(neighbours, used.state["ssm"]):  # an inactive row's state is untouched
        np.testing.assert_array_equal(before, np.asarray(now[others]))
    # eight prompts at once into four slots: every slot live, each freed and filled again
    prompts = [prompt_of(n, seed=60 + n) for n in (4, 31, 9, 16, 2, 23, 12, 27)]
    burst, stats = service_tokens(params, prompts, slots=4)
    alone = [service_tokens(params, [p], slots=4)[0][0] for p in prompts]
    assert burst == alone
    for prompt, got in zip(prompts, burst):
        assert_greedy(params, prompt, got)
    assert stats["lm_prefill_calls"] == 8 and stats["lm_decode_rows"] == 8 * 5
    assert stats["lm_prefill_state_rows"] == 8 * 3 and stats["lm_state_rows"] == stats["lm_decode_rows"] * 3


def last_logits(params, prompt, cfg):
    """The program's logits of ``prompt``'s last token under ``cfg``, un-jitted (one new ``cfg`` a case)."""
    dec = decoder(params, cfg)
    ids = jnp.asarray(prompt + [0] * (16 - len(prompt)), jnp.int32)
    return np.asarray(falcon_h1.prefill_logits(dec.params, dec.state, ids, jnp.int32(len(prompt)), jnp.int32(0), cfg)[1])


WRONG = {name: dataclasses.replace(CFG, **{name: 1.0}) for name in MULTIPLIERS}
WRONG.update({f"mlp_multipliers_{i}": dataclasses.replace(
    CFG, mlp_multipliers=tuple(1.0 if j == i else m for j, m in enumerate(CFG.mlp_multipliers))) for i in range(2)})
WRONG.update({f"ssm_multipliers_{i}": dataclasses.replace(
    CFG, ssm_multipliers=tuple(1.0 if j == i else m for j, m in enumerate(CFG.ssm_multipliers))) for i in range(5)})
WRONG["mup_segments_permuted"] = dataclasses.replace(CFG, ssm_multipliers=CFG.ssm_multipliers[1:] + CFG.ssm_multipliers[:1])


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_multiplier_left_out_moves_the_logits_by_more_than_the_tolerance(params, name):
    """So the comparison above would catch it: the program under a config with
    one multiplier at 1 (or ``mup``'s five segments one place on) against the
    reference under the true one."""
    prompt = prompt_of(11)
    want = reference(params, prompt)[0][-1]
    assert_close(last_logits(params, prompt, CFG), want)
    assert np.max(np.abs(last_logits(params, prompt, WRONG[name]) - want)) > 10 * 1e-3 * np.std(want)


def test_bfloat16_as_served_stays_near_the_float32_reference():
    p = mup_scaled(falcon_h1.init_params(CFG, seed=3))  # bfloat16 matrices; float32 norms, convolution and the mixer's vectors
    assert p["embed"].dtype == jnp.bfloat16 and p["layers"][2]["A_log"].dtype == jnp.float32
    prompt = prompt_of(11)
    dec = decoder(p)
    assert dec.state["k"][0].dtype == dec.state["tail"][0].dtype == jnp.bfloat16
    assert dec.state["ssm"][0].dtype == jnp.float32  # the recurrent state stays float32 beside bfloat16 weights
    served, tokens, _ = run_through_state(dec, 1, prompt, 8)
    full, _ = reference(p, prompt + tokens)
    want = full[len(prompt) - 1 : len(prompt) + 8]
    assert np.max(np.abs(served - want)) < 0.15 * np.std(want)


def test_the_state_space_vectors_are_drawn_as_mamba2_draws_them(params):
    layer = params["layers"][1]
    assert np.all((np.exp(np.asarray(layer["A_log"])) >= 1.0) & (np.exp(np.asarray(layer["A_log"])) <= 16.0))
    dt = np.log1p(np.exp(np.asarray(layer["dt_bias"])))  # softplus of the bias: the step at a zero input
    assert np.all((dt > 1e-3 * 0.999) & (dt < 1e-1 * 1.001)) and np.ptp(dt) > 0
    np.testing.assert_array_equal(np.asarray(layer["D"]), np.ones((4,), np.float32))
    assert 0.005 < float(jnp.std(layer["conv_b"])) < 0.04 and layer["conv_w"].shape == (CFG.conv_dim, 4)


def test_config_from_the_published_json_and_its_cut():
    published = falcon_h1.FalconH1Config.from_dict(PUBLISHED)
    assert published == falcon_h1.FalconH1Config() and published.conv_dim == 5120
    count = lambda cfg: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(falcon_h1.param_shapes(cfg)))
    # issue 35's arithmetic: 72 blocks of 430,120,032, the table, the untied head, the last norm
    assert count(published) == 72 * 430_120_032 + 2_673_873_920
    cut = falcon_h1.FalconH1Config.from_dict(dict(PUBLISHED, num_hidden_layers=6))
    assert count(cut) == 5_254_594_112  # blocks 0-5 with the whole table and head: 10.51 GB in bfloat16
    shapes = falcon_h1.param_shapes(cut)
    assert shapes["layers"][0]["in_proj"].shape == (5120, 9248) and shapes["lm_head"].shape == (5120, 261120)
    state = jax.eval_shape(lambda: falcon_h1.init_state(cut, 32, 1152))
    assert state["ssm"][5].shape == (32, 32, 128, 256) and state["ssm"][5].dtype == jnp.float32
    assert state["tail"][0].shape == (32, 3, 5120) and state["k"][0].shape == (32, 1152, 4, 128)
    for key, value in (("mamba_norm_before_gate", True), ("tie_word_embeddings", True), ("attn_layer_indices", [0, 2]),
                       ("mamba_conv_bias", False)):
        with pytest.raises(ValueError):
            falcon_h1.FalconH1Config.from_dict(dict(PUBLISHED, **{key: value}))
    with pytest.raises(ValueError):
        falcon_h1.FalconH1Config.from_dict(dict(PUBLISHED, mamba_d_ssm=2048))  # not 32 heads of 128


def test_falcon_h1_chat_through_fully_async_in_a_select(params):
    """The chat is a ``fully_async`` UDF: a ``select`` that calls it has a row
    once the reply is there, and the reply is the reference's greedy tokens."""
    from pathway_tpu.internals.json import Json
    from pathway_tpu.xpacks.llm.llms import DeviceChat, FalconH1Chat
    from tests.utils import capture_update_stream

    chat = FalconH1Chat(TINY, params, slots=2, max_prompt_tokens=32, max_new_tokens=5, prefill_buckets=(16, 32))
    assert isinstance(chat, DeviceChat) and chat.config == CFG
    assert chat.service.decoder is chat.decoder and chat.decoder.count_names == ("state_rows",)
    questions = ["w001 w002 what", "a much longer question " + " ".join(f"w{i:03d}" for i in range(12)), "w7"]
    queries = pw.debug.table_from_rows(pw.schema_builder({"messages": pw.Json}),
                                       [(Json([{"role": "user", "content": q}]),) for q in questions])
    stream = capture_update_stream(queries.select(reply=chat(pw.this.messages)))
    replies = sorted(r["reply"] for r in stream if r["__diff__"] == 1)
    # read before the graph runs again below: a second run may call the chat again
    assert len(replies) == 3 and chat.service.stats()["lm_prefill_calls"] == 3
    for q in questions:
        ids = chat.tokenize(q)
        assert len(ids) == len(q.split())
        [tokens] = [t for t in map(FalconH1Chat.reply_ids, replies) if continues(params, ids, t)]
        assert len(tokens) == 5
    # the answers' rows came in commits later than the one that took the questions
    took = {r["__time__"] for r in capture_update_stream(queries)}
    assert not {r["__time__"] for r in stream} & took
    chat.service.close()


def continues(params, ids, tokens):
    logits, _ = reference(params, ids + tokens)
    return tokens == np.argmax(logits[len(ids) - 1 : -1], axis=-1).tolist()
