"""The cell ``answer-falconh1-steady`` and what it brought, at sizes a test run can
hold: the rehearsal (``run.py --rehearse``: the configuration's ``rehearse``
group, hidden 64, 3 blocks, 4 state-space heads of 16 with a state of 16 in 2
groups, chunks of 8, 4 / 2 attention heads, vocabulary 4,096) reads ``correct:
true``, and ``false`` with each planted fault (``faulty_falconh1_run.py``) and for
each control (``run.py --calibrate``); the benchmark's own copy of the plain
reference gives what the repository's gives; the work file counts the six
blocks of the published model; the new readers read a hand-made context and
return nothing where there is nothing to read; the configuration's file holds
the published keys.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)
import run  # noqa: E402
from faulty_falconh1_run import FAULTS, SEEN_AT_THE_CELLS_SIZE_ONLY  # noqa: E402

CELL, CONFIG = "answer-falconh1-steady", "falcon-h1-34b-rag.json"
GAPS = {"logit_gap_max", "logit_gap_mean", "burst_logit_gap_max", "burst_logit_gap_mean"}
TWINS = ("lm_decode_ms_per_step", "lm_prefill_ms_per_call", "answer_mfu", "lm_slot_fill", "lm_generate_wait_p50_ms",
         "lm_step_host_p50_ms", "lm_decode_roofline", "lm_prefill_roofline")
NEW_METRICS = tuple(name + ".falconh1" for name in TWINS) + ("lm_decode_state_rows_per_row.falconh1",)
# https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json, every key that says something of its shape
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


def lines_of(command, *more):
    out = subprocess.run([sys.executable] + command + ["--workload", CELL, "--seed", "2147483777", "--seconds", "2",
                                                        "--trace", "0", *more],
                         capture_output=True, text=True, timeout=1200, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line) for line in out.stdout.strip().splitlines()]


def over_limit(compared):
    return [n for n, row in compared.items() if row["limit"] is not None and row["value"] > row["limit"]]


def test_the_rehearsal_reads_correct():
    [result] = lines_of([os.path.join(HERE, "faulty_falconh1_run.py"), "--fault", "none"])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert {"bad_replies", "text_mismatch", "rank_gap"} | GAPS <= set(result["compared"])


@pytest.mark.parametrize("fault", [f for f in FAULTS if f != "none" and f not in SEEN_AT_THE_CELLS_SIZE_ONLY])
def test_a_planted_fault_reads_not_correct(fault):
    [result] = lines_of([os.path.join(HERE, "faulty_falconh1_run.py"), "--fault", fault])
    assert result["correct"] is False and result["failed"] == 0
    # whole replies of 6 passages and 128 readable ids: only the reference's logits say that the program was wrong
    assert result["compared"]["bad_replies"]["value"] == 0
    assert set(over_limit(result["compared"])) <= GAPS and over_limit(result["compared"])
    # the warm-up's last burst fills every slot, so a fault that needs rows live together shows there
    assert {"burst_logit_gap_max", "burst_logit_gap_mean"} & set(over_limit(result["compared"]))


def test_every_control_reads_not_correct_where_the_program_reads_correct():
    [line] = lines_of([os.path.join(BENCH, "run.py"), "--rehearse"], "--calibrate", "1")
    assert line["correct"] is True and line["failed"] == 0, line["program"]
    system = run.load_module("systems", "rag_answer_falconh1")
    assert set(line["controls"]) == set(system.CONTROLS) == {"fp8_matmul", "no_attention_branch",
                                                             "no_ssm_multipliers", "state_one_token_behind"}
    limits = run.load_json("workloads", CELL + ".json")["limits"]
    for name, read in line["controls"].items():
        assert read["correct"] is False, (name, read)
        assert read["logit_gap_max"] > limits["logit_gap_max"] or read["logit_gap_mean"] > limits["logit_gap_mean"]
        assert (read["burst_logit_gap_max"] > limits["burst_logit_gap_max"]
                or read["burst_logit_gap_mean"] > limits["burst_logit_gap_mean"])


def tiny_config():
    cfg = run.load_json("configs", CONFIG)
    cfg = run.merged(cfg, cfg["rehearse"])
    return cfg, run.load_module("systems", "rag_answer_falconh1").lm_config(cfg)


def test_the_benchmarks_reference_gives_what_the_repositorys_gives():
    """Two copies of one mathematics, written apart: the benchmark's (block by
    block over a padded batch, the head in column blocks) and
    ``pathway_tpu/models/falcon_h1_reference.py`` (one sequence, whole) agree to
    float32 rounding on the benchmark's own draw of the weights."""
    import jax.numpy as jnp

    import falcon_h1_reference
    import falcon_h1_weights
    from pathway_tpu.models import falcon_h1
    from pathway_tpu.models import falcon_h1_reference as repo_reference

    cfg, lm_cfg = tiny_config()
    params = falcon_h1_weights.make_params(2**31 + 5, lm_cfg, cfg["assumed"]["weights_init"], "bfloat16")
    model = falcon_h1.FalconH1Config.from_dict(lm_cfg)
    assert model.mamba_d_state == 16 and model.mamba_chunk_size == 8 and model.conv_dim == 128
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2000, 3096, size=n).tolist() for n in (9, 30, 17)]
    served = [rng.integers(2000, 3096, size=5).tolist() for _ in prompts]
    rows = falcon_h1_reference.hidden_rows(params, lm_cfg, prompts, served, chunk=2, pad_to=16)
    read = falcon_h1_reference.read_head(params, lm_cfg, rows, served, block=1024)  # four column blocks
    whole = falcon_h1_reference.read_head(params, lm_cfg, rows, served, block=4096)
    for i, (prompt, tokens) in enumerate(zip(prompts, served)):
        logits, _ = repo_reference.forward(params, jnp.asarray(prompt + tokens, jnp.int32), model)
        at = np.asarray(logits)[len(prompt) - 1 : len(prompt) + 4]
        for got in (read, whole):
            np.testing.assert_allclose(got["top"][i], at.max(-1), atol=1e-5)
            np.testing.assert_allclose(got["at"][i], at[np.arange(5), tokens], atol=1e-5)
            np.testing.assert_allclose(got["spread"][i], at.std(-1), rtol=1e-4)
            assert got["argmax"][i].tolist() == at.argmax(-1).tolist()
    assert falcon_h1_reference.logit_gaps(read).min() >= 0.0
    # and each control is another computation: its rows differ somewhere
    for variant in run.load_module("systems", "rag_answer_falconh1").CONTROLS:
        low = falcon_h1_reference.hidden_rows(params, lm_cfg, prompts, served, variant=variant, chunk=2, pad_to=16)
        assert np.abs(low - rows).max() > 1e-3, variant


def test_the_draw_gives_a_matrix_its_multipliers_inverse_and_the_vectors_mamba2s_ranges():
    import falcon_h1_weights

    cfg, lm_cfg = tiny_config()
    init = dict(cfg["assumed"]["weights_init"], dt_min=0.001, dt_max=0.1)  # the cell's own steps
    p = falcon_h1_weights.make_params(11, lm_cfg, init, "float32")
    layer, h = p["layers"][1], lm_cfg["hidden_size"]
    std = lambda a: float(np.std(np.asarray(a)))
    assert std(p["lm_head"]) == pytest.approx(1 / (lm_cfg["lm_head_multiplier"] * h ** 0.5), rel=0.05)
    assert std(layer["wk"]) == pytest.approx(init["qk_gain"] / (lm_cfg["key_multiplier"] * h ** 0.5), rel=0.1)
    assert std(layer["out_proj"]) == pytest.approx(1 / (lm_cfg["ssm_out_multiplier"] * 64 ** 0.5), rel=0.05)
    assert std(layer["w3"]) == pytest.approx(h ** -0.5, rel=0.05) and std(p["embed"]) == pytest.approx(0.02, rel=0.05)
    # in_proj's five segments, each over ssm_in_multiplier x its own multiplier: its output times mup has unit scale
    for lo, hi, m in zip((0, 64, 128, 160, 192), (64, 128, 160, 192, 196), lm_cfg["ssm_multipliers"]):
        assert std(layer["in_proj"][:, lo:hi]) == pytest.approx(1 / (lm_cfg["ssm_in_multiplier"] * m * h ** 0.5), rel=0.15)
    a, dt = np.exp(np.asarray(layer["A_log"])), np.log1p(np.exp(np.asarray(layer["dt_bias"])))
    assert np.all((a >= 1) & (a <= 16)) and np.all((dt > 0.000999) & (dt < 0.1001))
    assert np.asarray(layer["D"]).tolist() == [1.0] * 4 and 0.005 < std(layer["conv_b"]) < 0.04


def test_the_tokenizer_and_the_template_are_the_programs():
    import falcon_h1_reference
    from pathway_tpu.models.encoder import HashTokenizer
    from pathway_tpu.xpacks.llm import prompts

    vocab = run.load_json("configs", CONFIG)["vocab_size"]
    docs = [{"text": "doc3 w001 W002"}, {"text": "doc9 w077"}]
    text = prompts.prompt_qa("w001 w002 q7", tuple(docs))
    assert falcon_h1_reference.prompt_qa("w001 w002 q7", [d["text"] for d in docs]) == text
    ids, _ = HashTokenizer(vocab_size=vocab, max_length=1 << 30)([text])
    assert falcon_h1_reference.tokenize(text, vocab) == ids[0, 1:-1].tolist()
    assert vocab == 261120 and max(falcon_h1_reference.tokenize(text, vocab)) < vocab - 1000


def test_the_configuration_holds_every_published_key_and_states_its_cut():
    cfg = run.load_json("configs", CONFIG)
    assert {k: cfg[k] for k in PUBLISHED if k != "num_hidden_layers"} == {
        k: v for k, v in PUBLISHED.items() if k != "num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 6 and cfg["published"]["num_hidden_layers"] == 72
    assert (cfg["published"]["pipeline_stages"], cfg["published"]["chips_per_layer"], cfg["published"]["stage"]) == (12, 1, 0)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "corpus"}
    system = run.load_module("systems", "rag_answer_falconh1")
    assert set(system.PUBLISHED_KEYS) <= set(PUBLISHED)
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    [entry] = [c for c in manifest["configs"] if c["name"] == "falcon-h1-34b-rag"]
    assert entry["reduced"] == ["num_hidden_layers", "corpus"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200 and len(entry["why"]) <= 200
    [cell] = [w for w in manifest["workloads"] if w["config"] == "falcon-h1-34b-rag"]
    assert cell["name"] == CELL and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cfg["num_hidden_layers"] >= 4  # the floor of a cut in depth; no head, width, state or vocabulary row is cut
    assert {"mup_order", "dt_clamp", "gated_norm", "head_to_group", "weights_init", "tokens"} <= set(cfg["assumed"])
    serving = cfg["serving"]
    assert (serving["slots"], serving["max_new_tokens"], serving["search_topk"]) == (32, 128, 6)
    assert serving["prefill_buckets"] == [256, 512, 1024] and cfg["hooks"] == ["no_checkpoint_imports"]


def test_the_work_file_counts_the_six_blocks_of_the_published_model():
    work = run.load_module("work", "falcon_h1")
    lm_cfg = run.load_module("systems", "rag_answer_falconh1").lm_config(run.load_json("configs", CONFIG))
    p = work.parameters(lm_cfg)
    blocks = p["ssm"] + p["attention"] + p["mlp"] + p["norms"]
    assert blocks == 6 * 430_120_032 + 5_120  # issue 35's arithmetic, and the last norm
    assert blocks + p["table"] + p["head"] == 5_254_594_112
    assert p["state"] == 32 * 128 * 256 and p["tail"] == 3 * 5120
    step = work.decode_step(lm_cfg, rows=9.0, context_tokens=450.0)
    state = 9.0 * 6 * (2 * 4 * p["state"] + 2 * p["tail"])
    kv = 9.0 * 2 * (2 * 4 * 128 * 450.0 * 6)
    assert step["bytes"] == pytest.approx(2 * (blocks + p["head"] + 9 * 5120) + kv + state)
    assert 8.2e9 < step["bytes"] < 8.5e9 and step["bytes"] / 819e9 > step["flops"] / 197e12  # memory bound
    # each live row adds its state read and written, 6 x 8.39 MB, and nothing for an empty slot
    assert work.decode_step(lm_cfg, 10.0, 0.0)["bytes"] - work.decode_step(lm_cfg, 9.0, 0.0)["bytes"] == pytest.approx(
        6 * (8 * p["state"] + 2 * p["tail"]) + 2 * 5120)
    # a row's recurrence: five operations a number of the state, and the convolution's taps
    row = work.decode_step(lm_cfg, 1.0, 0.0)["flops"] - 2.0 * (blocks + p["head"])
    assert row == pytest.approx(6 * (5 * p["state"] + 2 * 5120 * 4))
    call = work.prefill_call(lm_cfg, tokens=400.0)
    assert call["bytes"] == pytest.approx(2 * (blocks + p["head"] + 400 * 5120) + 6 * 4 * p["state"])
    scan = 2 * 128 * (512 + 4096) + 4 * p["state"] + 2 * 5120 * 4  # 5.4 MFLOP a token and block
    assert 5.3e6 < scan < 5.5e6
    assert call["flops"] == pytest.approx(2.0 * (400 * blocks + p["head"]) + 4 * (400 * 401 / 2) * 20 * 128 * 6
                                          + 400 * 6 * scan)
    assert call["flops"] / 197e12 > call["bytes"] / 819e9  # compute bound
    assert work.reply_flops(lm_cfg, 400.0, 128) == pytest.approx(
        call["flops"] + sum(work.decode_step(lm_cfg, 1.0, 400.0 + j)["flops"] for j in range(1, 128)))


def hand_made_context():
    system = run.load_module("systems", "rag_answer_falconh1")
    cfg = run.load_json("configs", CONFIG)
    span = lambda kind, sid, parent, start, dur: {"kind": kind, "span_id": sid, "parent_id": parent, "ts_mono": start,
                                                  "duration_s": dur, "trace_id": "t", "attrs": {}, "links": []}
    spans = [span("generate", "g1", "c1", 0.1, 1.900), span("generate", "g2", "c1", 0.2, 2.100),
             span("generate", "g3", "c2", 0.5, 2.000)]
    for i in range(5):
        spans += [span("lm.decode_step", f"s{i}", None, 1.0 + i * 0.02, 0.016),
                  span("lm.decode_step.device_wait", f"w{i}", f"s{i}", 1.0005 + i * 0.02, 0.015)]
    context = [(7, "doc7 " + "w " * 55, 0.9)] * 6
    records = [{"done": 1.5, "status": 200, "query": "w001 w002 q1", "answer": {"ids": [2000] * 128, "context": context}},
               {"done": 9.0, "status": 200, "query": "w003 q2", "answer": {"ids": [2000] * 128, "context": context}}]
    return {
        "spec": {"config": cfg, "cell": {"name": CELL}}, "gen": {"records": records, "start_at": 0.0},
        "trace_span": {"t0": 0.0, "t1": 4.0}, "spans": spans, "percentile": run.percentile,
        "peaks": run.load_json("peaks.json")["TPU v5 lite"], "work": run.load_module("work", "falcon_h1"),
        "trace": {"busy_s": 3.4, "window_s": 4.0,
                  "programs": {"jit_lm_decode": {"seconds": 3.0, "calls": 200.0},
                               "jit_lm_prefill": {"seconds": 0.4, "calls": 16.0}}},
        "counters_before": {"lm_decode_steps": 100.0, "lm_decode_rows": 800.0, "lm_state_rows": 19200.0,
                            "lm_prefill_calls": 10.0, "lm_prefill_tokens": 4000.0, "lm_prefill_state_rows": 60.0,
                            "lm_slots": 32.0},
        "counters_after": {"lm_decode_steps": 2100.0, "lm_decode_rows": 18800.0, "lm_state_rows": 403200.0,
                           "lm_prefill_calls": 170.0, "lm_prefill_tokens": 68000.0, "lm_prefill_state_rows": 1020.0,
                           "lm_slots": 32.0},
        **system.metric_context(cfg),
    }


def new_entries():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in entries] == list(NEW_METRICS)  # appended, in the issue's order
    assert all(m["workloads"] == [CELL] and m["moves"] == "retrieve_p50_ms" for m in entries)
    twins = {m["name"]: m for m in manifest["per_layer"]}
    for name in TWINS:  # units, better, source and layer as their .mistral4 twins
        ours, theirs = twins[name + ".falconh1"], twins[name + ".mistral4"]
        assert all(ours[k] == theirs[k] for k in ("unit", "better", "source", "layer"))
    last = twins["lm_decode_state_rows_per_row.falconh1"]
    assert (last["unit"], last["better"], last["source"], last["layer"]) == (
        "rows", "lower", "program_counter", "generator state-space mixer")
    # and nothing that was there names the new cell: its own entries and those without a list hold for it
    assert all(CELL not in m.get("workloads", []) for m in manifest["per_layer"] if m["name"] not in NEW_METRICS)
    return entries


def test_the_new_readers_on_a_hand_made_context():
    ctx = hand_made_context()
    got = {name: m["value"] for name, m in run.read_metrics(new_entries(), ctx).items()}
    assert set(got) == set(NEW_METRICS)
    assert got["lm_decode_ms_per_step.falconh1"] == pytest.approx(15.0)
    assert got["lm_prefill_ms_per_call.falconh1"] == pytest.approx(25.0)
    assert got["lm_slot_fill.falconh1"] == pytest.approx(100.0 * 18000 / (2000 * 32))
    assert got["lm_generate_wait_p50_ms.falconh1"] == pytest.approx(2000.0)
    assert got["lm_step_host_p50_ms.falconh1"] == pytest.approx(1.0)
    # every step moved all 32 slots' states in all six blocks for the 9 rows that held a request
    assert got["lm_decode_state_rows_per_row.falconh1"] == pytest.approx(32 / 9)
    work, lm_cfg = ctx["work"], ctx["lm_config"]
    step = work.decode_step(lm_cfg, 9.0, 400.0 + 64)
    assert got["lm_decode_roofline.falconh1"] == pytest.approx(100.0 * step["bytes"] / 819e9 / 15e-3)
    call = work.prefill_call(lm_cfg, 400.0)
    assert got["lm_prefill_roofline.falconh1"] == pytest.approx(100.0 * call["flops"] / 197e12 / 25e-3)
    assert 0 < got["lm_decode_roofline.falconh1"] < 100 and 0 < got["lm_prefill_roofline.falconh1"] < 100
    # one reply completed in the span: its question's encoding, the scan of the passages, its prefill and its 127 rows
    prompt_tokens = ctx["lm_reply_tokens"](ctx["gen"]["records"][0])
    assert prompt_tokens == 6 * 56 + 3 + 31  # the passages, the question, the template's own words
    assert got["answer_mfu.falconh1"] == pytest.approx(
        100.0 * (work.reply_flops(lm_cfg, prompt_tokens, 128) + 2.0 * 4096 * 384
                 + 5 * 6 * (8 * 384 * 384 + 4 * 384 * 1536 + 4 * 5 * 384)) / (4.0 * 197e12))
    assert 0 < got["answer_mfu.falconh1"] < 100


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    entries, ctx = new_entries(), hand_made_context()
    # an untraced run: only the counters are there
    untraced = dict(ctx, trace=None, spans=None)
    del untraced["trace_span"]
    assert set(run.read_metrics(entries, untraced)) == {"lm_slot_fill.falconh1", "lm_decode_state_rows_per_row.falconh1"}
    # a program without the state-space mixer's count: the ninth reader is silent, and does not raise
    parent = {k: v for k, v in ctx["counters_after"].items() if "state_rows" not in k}
    got = run.read_metrics(entries, dict(ctx, counters_after=parent))
    assert "lm_decode_state_rows_per_row.falconh1" not in got and "lm_slot_fill.falconh1" in got
    # a program without the generator: no such program, span or counter
    bare = dict(ctx, counters_before={}, counters_after={}, spans=[],
                trace={"busy_s": 1.0, "window_s": 4.0, "programs": {"jit__search_kernel": {"seconds": 1.0, "calls": 9.0}}})
    bare["gen"] = {"records": [dict(r, answer=None) for r in ctx["gen"]["records"]], "start_at": 0.0}
    assert run.read_metrics(entries, bare) == {}
    # another system's context: none of this system's keys
    other = {k: v for k, v in bare.items() if not k.startswith(("lm_", "live_rows"))}
    assert run.read_metrics(entries, dict(other, work=None)) == {}
    assert run.read_metrics(entries, dict(other, work=run.load_module("work", "dense_scan"))) == {}


def test_a_reply_is_read_and_judged_whole():
    system = run.load_module("systems", "rag_answer_falconh1")
    traffic = run.load_json("workloads", CELL + ".json")
    docs = [{"text": f"doc{i} w{i:03d}", "metadata": {}, "dist": -0.9 + 0.01 * i} for i in range(6)]
    body = lambda response, context=docs: json.dumps({"response": response, "context_docs": context})
    answer = system.parse_reply(body(" ".join(f"t{i}" for i in range(128))))
    assert answer["ids"] == list(range(128)) and len(answer["context"]) == 6
    assert system.good(answer, traffic)
    assert not system.good(system.parse_reply(body(" ".join(f"t{i}" for i in range(127)))), traffic)
    assert not system.good(system.parse_reply(body(" ".join(f"t{i}" for i in range(128)), docs[:5])), traffic)
    assert traffic["request"] == {"route": "/v2/answer", "text_key": "prompt", "fixed": {"return_context_docs": True}}
    assert set(traffic["limits"]) == {"bad_replies", "text_mismatch", "compiles_in_window", "kth_score_err",
                                      "score_err", "score_err_mean", "rank_gap"} | GAPS
    assert traffic["limits"]["compiles_in_window"] == 0 and traffic["rate_rps"] == int(traffic["rate_rps"])
    assert (traffic["arrival"], traffic["lead_in_s"], traffic["sample"], traffic["control_sample"]) == (
        "exponential", 3.0, 32, 8) and traffic["stall_late_p95_ms"] == 10.0
