"""The cell ``answer-mistral4-steady`` and what it brought, at sizes a test run can
hold: the rehearsal (``run.py --rehearse``: the configuration's ``rehearse``
group, hidden 64, 3 layers, ranks 32 / 16, 4 of a router's 16 experts held, top
2, vocabulary 4,096) reads ``correct: true``, and ``false`` with each planted
fault (``faulty_mistral4_run.py``) and for each control (``run.py --calibrate``);
the benchmark's own copy of the plain reference gives what the repository's
gives; the work file counts one chip's share of the published model; the new
readers read a hand-made context and return nothing where there is nothing to
read; the configuration's file holds the published widths.
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
from faulty_mistral4_run import FAULTS  # noqa: E402

CELL, CONFIG = "answer-mistral4-steady", "mistral-small-4-119b-rag.json"
GAPS = {"logit_gap_max", "logit_gap_mean", "burst_logit_gap_max", "burst_logit_gap_mean"}
INHERITED = ("lm_decode_ms_per_step", "lm_prefill_ms_per_call", "lm_decode_roofline", "lm_prefill_roofline",
             "answer_mfu", "lm_slot_fill", "lm_generate_wait_p50_ms", "lm_step_host_p50_ms")
NEW_METRICS = tuple(name + ".mistral4" for name in INHERITED) + ("lm_decode_rows_per_expert.mistral4",)


def lines_of(command, *more):
    out = subprocess.run([sys.executable] + command + ["--workload", CELL, "--seed", "2147483777", "--seconds", "2",
                                                        "--trace", "0", *more],
                         capture_output=True, text=True, timeout=1200, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line) for line in out.stdout.strip().splitlines()]


def over_limit(compared):
    return [n for n, row in compared.items() if row["limit"] is not None and row["value"] > row["limit"]]


def test_the_rehearsal_reads_correct():
    [result] = lines_of([os.path.join(HERE, "faulty_mistral4_run.py"), "--fault", "none"])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert {"bad_replies", "text_mismatch", "rank_gap"} | GAPS <= set(result["compared"])


@pytest.mark.parametrize("fault", [f for f in FAULTS if f != "none"])
def test_a_planted_fault_reads_not_correct(fault):
    [result] = lines_of([os.path.join(HERE, "faulty_mistral4_run.py"), "--fault", fault])
    assert result["correct"] is False and result["failed"] == 0
    # whole replies of 24 passages and 64 readable ids: only the reference's logits say that the program was wrong
    assert result["compared"]["bad_replies"]["value"] == 0
    assert set(over_limit(result["compared"])) <= GAPS and over_limit(result["compared"])
    # the warm-up's last burst fills every slot, so a fault that needs rows live together shows there
    assert {"burst_logit_gap_max", "burst_logit_gap_mean"} & set(over_limit(result["compared"]))


def test_every_control_reads_not_correct_where_the_program_reads_correct():
    [line] = lines_of([os.path.join(BENCH, "run.py"), "--rehearse"], "--calibrate", "1")
    assert line["correct"] is True and line["failed"] == 0, line["program"]
    system = run.load_module("systems", "rag_answer_mistral4")
    assert set(line["controls"]) == set(system.CONTROLS)
    limits = run.load_json("workloads", CELL + ".json")["limits"]
    for name, read in line["controls"].items():
        assert read["correct"] is False, (name, read)
        assert read["logit_gap_max"] > limits["logit_gap_max"] or read["logit_gap_mean"] > limits["logit_gap_mean"]
        assert (read["burst_logit_gap_max"] > limits["burst_logit_gap_max"]
                or read["burst_logit_gap_mean"] > limits["burst_logit_gap_mean"])


def tiny_config():
    cfg = run.load_json("configs", CONFIG)
    cfg = run.merged(cfg, cfg["rehearse"])
    system = run.load_module("systems", "rag_answer_mistral4")
    return cfg, system.lm_config(cfg), system.reference_config(cfg)


def test_the_benchmarks_reference_gives_what_the_repositorys_gives():
    """Two copies of one mathematics, written apart: the benchmark's (layer by
    layer over a padded batch, one expert in float32 at a time) and
    ``pathway_tpu/models/mistral4_reference.py`` (one sequence, whole) agree to
    float32 rounding on seeded weights, over a share that is not the first
    (experts 8-11 of 16)."""
    import jax.numpy as jnp

    import mistral4_reference
    import mistral4_weights
    from pathway_tpu.models import mistral4
    from pathway_tpu.models import mistral4_reference as repo_reference

    cfg, lm_cfg, ref_cfg = tiny_config()
    lm_cfg, ref_cfg = dict(lm_cfg, first_expert=8), dict(ref_cfg, first_expert=8)
    params = mistral4_weights.make_params(2**31 + 5, lm_cfg, cfg["assumed"]["weights_init"], "bfloat16")
    model = mistral4.Mistral4Config.from_dict(lm_cfg)
    assert model.first_expert == 8 and model.router_width == 16 and model.n_routed_experts == 4
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2000, 3096, size=n).tolist() for n in (9, 30, 17)]
    served = [rng.integers(2000, 3096, size=5).tolist() for _ in prompts]
    rows, chosen = mistral4_reference.hidden_rows(params, ref_cfg, prompts, served, chunk=2, pad_to=16)
    read = mistral4_reference.read_head(params, ref_cfg, rows, served)
    for i, (prompt, tokens) in enumerate(zip(prompts, served)):
        logits, picked = repo_reference.forward(params, jnp.asarray(prompt + tokens, jnp.int32), model)
        at = np.asarray(logits)[len(prompt) - 1 : len(prompt) + 4]
        np.testing.assert_allclose(read["top"][i], at.max(-1), atol=1e-5)
        np.testing.assert_allclose(read["at"][i], at[np.arange(5), tokens], atol=1e-5)
        np.testing.assert_allclose(read["spread"][i], at.std(-1), rtol=1e-4)
        assert read["argmax"][i].tolist() == at.argmax(-1).tolist()
        for layer, c in enumerate(picked):
            assert np.array_equal(np.sort(chosen[layer][i]), np.sort(np.asarray(c)[len(prompt) - 1 : len(prompt) + 4]))
    assert mistral4_reference.logit_gaps(read).min() >= 0.0
    assert max(c.max() for c in chosen) > 11  # experts are numbered over the router's whole width
    np.testing.assert_allclose(mistral4_reference.yarn_inv_freq(ref_cfg), np.asarray(mistral4.yarn_inv_freq(model)),
                               rtol=1e-6)
    assert mistral4_reference.softmax_scale(ref_cfg) == pytest.approx(model.softmax_scale)
    # and each control is another computation: its own greedy choice differs somewhere
    for variant in run.load_module("systems", "rag_answer_mistral4").CONTROLS:
        low, _ = mistral4_reference.hidden_rows(params, ref_cfg, prompts, served, variant=variant, chunk=2, pad_to=16)
        assert np.abs(low - rows).max() > 1e-3, variant


def test_the_tokenizer_over_the_slice_and_the_template_are_the_programs():
    import mistral4_reference
    from pathway_tpu.models.encoder import HashTokenizer
    from pathway_tpu.xpacks.llm import prompts

    held = run.load_json("configs", CONFIG)["vocab_size"]
    docs = [{"text": "doc3 w001 W002"}, {"text": "doc9 w077"}]
    text = prompts.prompt_qa("w001 w002 q7", tuple(docs))
    assert mistral4_reference.prompt_qa("w001 w002 q7", [d["text"] for d in docs]) == text
    ids, _ = HashTokenizer(vocab_size=held, max_length=1 << 30)([text])
    assert mistral4_reference.tokenize(text, held) == ids[0, 1:-1].tolist()
    assert held == 32768 and max(mistral4_reference.tokenize(text, held)) < held - 1000


def test_the_configuration_holds_the_published_widths_and_states_its_cut():
    cfg = run.load_json("configs", CONFIG)
    widths = {"hidden_size": 4096, "intermediate_size": 12288, "moe_intermediate_size": 2048, "q_lora_rank": 1024,
              "kv_lora_rank": 256, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "qk_head_dim": 128, "v_head_dim": 128,
              "head_dim": 128, "num_attention_heads": 32, "num_key_value_heads": 32, "num_experts_per_tok": 4,
              "n_shared_experts": 1, "n_group": 1, "topk_group": 1, "first_k_dense_replace": 0, "rms_norm_eps": 1e-6,
              "routed_scaling_factor": 1, "max_position_embeddings": 1048576}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["rope_parameters"] == {"beta_fast": 32, "beta_slow": 1, "factor": 128, "llama_4_scaling_beta": 0.1,
                                      "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 8192,
                                      "rope_theta": 10000, "rope_type": "yarn", "type": "yarn"}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (6, 32, 32768)
    assert (cfg["n_router_experts"], cfg["first_expert"]) == (128, 0)
    assert {k: cfg["published"][k] for k in ("num_hidden_layers", "n_routed_experts", "vocab_size")} == {
        "num_hidden_layers": 36, "n_routed_experts": 128, "vocab_size": 131072}
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size", "corpus"}
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    [entry] = [c for c in manifest["configs"] if c["name"] == "mistral-small-4-119b-rag"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size", "corpus"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    [cell] = [w for w in manifest["workloads"] if w["config"] == "mistral-small-4-119b-rag"]
    assert cell["name"] == CELL and cell["chips"] == 1 and len(cell["why"]) <= 200
    # the floors of a cut: at least four layers, eight experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 >= 131072
    assert {"scoring", "softmax_scale", "shared_expert_width", "llama_4_scaling_beta"} <= set(cfg["assumed"])


def test_the_work_file_counts_one_chips_share_of_the_published_model():
    work = run.load_module("work", "mistral4_moe")
    lm_cfg = run.load_module("systems", "rag_answer_mistral4").lm_config(run.load_json("configs", CONFIG))
    p = work.parameters(lm_cfg)
    outside = p["attention"] + p["routers"] + p["shared_experts"] + p["norms"]
    assert outside == 6 * 53_748_992 + 4_096 and p["one_expert"] == 25_165_824  # issue 32's arithmetic
    assert outside + 6 * 32 * p["one_expert"] + p["table"] + p["head"] == 5_422_771_712
    assert p["held_pairs_per_token"] == 1.0  # 4 of 128 chosen, 32 held
    step = work.decode_step(lm_cfg, rows=3.0, experts_touched=6 * 2.5, context_tokens=1400.0)
    state = 3.0 * 2 * 320 * 1400.0 * 6
    assert step["bytes"] == pytest.approx(2 * (15 * p["one_expert"] + outside + p["head"] + 3 * 4096) + state)
    assert 1.6e9 < step["bytes"] < 1.8e9 and step["bytes"] / 819e9 > step["flops"] / 197e12  # memory bound
    # a row's attention in the absorbed form: 320 numbers a position for the scores, 256 for the mix
    row = work.decode_step(lm_cfg, 1.0, 0.0, 1400.0)["flops"] - work.decode_step(lm_cfg, 1.0, 0.0, 0.0)["flops"]
    assert row == pytest.approx(2 * 1400 * 32 * (320 + 256) * 6)
    # a step that touches every held expert reads every weight but the table once, and never more
    full = work.decode_step(lm_cfg, 16.0, 6 * 32.0, 0.0)
    assert full["bytes"] == pytest.approx(2 * (5_422_771_712 - p["table"] + 16 * 4096))
    call = work.prefill_call(lm_cfg, tokens=1400.0, experts_touched=6 * 32.0)
    assert call["bytes"] == pytest.approx(2 * (5_422_771_712 - p["table"] + 1400 * 4096))
    met = outside + 6 * p["one_expert"]
    assert call["flops"] == pytest.approx(2.0 * (1400 * met + p["head"]) + 2 * (1400 * 1401 / 2) * 32 * 256 * 6)
    assert work.reply_flops(lm_cfg, 1400.0, 64) > call["flops"] - 1e6
    assert work.reply_flops(lm_cfg, 1400.0, 64) == pytest.approx(
        work.prefill_call(lm_cfg, 1400.0, 0.0)["flops"]
        + sum(work.decode_step(lm_cfg, 1.0, 0.0, 1400.0 + j)["flops"] for j in range(1, 64)))


def hand_made_context():
    system = run.load_module("systems", "rag_answer_mistral4")
    cfg = run.load_json("configs", CONFIG)
    span = lambda kind, sid, parent, start, dur: {"kind": kind, "span_id": sid, "parent_id": parent, "ts_mono": start,
                                                  "duration_s": dur, "trace_id": "t", "attrs": {}, "links": []}
    spans = [span("generate", "g1", "c1", 1.0, 0.300), span("generate", "g2", "c1", 1.1, 0.500),
             span("generate", "g3", "c2", 2.0, 0.400)]
    for i in range(5):
        spans += [span("lm.decode_step", f"s{i}", None, 1.0 + i * 0.01, 0.009),
                  span("lm.decode_step.device_wait", f"w{i}", f"s{i}", 1.0005 + i * 0.01, 0.008)]
    context = [(7, "doc7 " + "w " * 55, 0.9)] * 24
    records = [{"done": 1.5, "status": 200, "query": "w001 w002 q1", "answer": {"ids": [2000] * 64, "context": context}},
               {"done": 9.0, "status": 200, "query": "w003 q2", "answer": {"ids": [2000] * 64, "context": context}}]
    return {
        "spec": {"config": cfg, "cell": {"name": CELL}}, "gen": {"records": records, "start_at": 0.0},
        "trace_span": {"t0": 0.0, "t1": 4.0}, "spans": spans, "percentile": run.percentile,
        "peaks": run.load_json("peaks.json")["TPU v5 lite"], "work": run.load_module("work", "mistral4_moe"),
        "trace": {"busy_s": 3.0, "window_s": 4.0,
                  "programs": {"jit_lm_decode": {"seconds": 2.4, "calls": 600.0},
                               "jit_lm_prefill": {"seconds": 0.6, "calls": 15.0}}},
        "counters_before": {"lm_decode_steps": 100.0, "lm_decode_rows": 300.0, "lm_experts_touched": 1500.0,
                            "lm_routed_pairs_held": 1800.0, "lm_routed_pairs": 7200.0,
                            "lm_prefill_calls": 10.0, "lm_prefill_tokens": 14000.0,
                            "lm_prefill_experts_touched": 1920.0, "lm_slots": 16.0},
        "counters_after": {"lm_decode_steps": 3100.0, "lm_decode_rows": 9300.0, "lm_experts_touched": 46500.0,
                           "lm_routed_pairs_held": 55800.0, "lm_routed_pairs": 223200.0,
                           "lm_prefill_calls": 160.0, "lm_prefill_tokens": 224000.0,
                           "lm_prefill_experts_touched": 30720.0, "lm_slots": 16.0},
        **system.metric_context(cfg),
    }


def new_entries():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in manifest["per_layer"][-9:]] == list(NEW_METRICS)  # appended, in the issue's order
    assert all(m["workloads"] == [CELL] and m["moves"] == "retrieve_p50_ms" for m in entries)
    # and nothing that was there names the new cell: its own entries and those without a list hold for it
    assert all(CELL not in m.get("workloads", []) for m in manifest["per_layer"][:-9])
    return entries


def test_the_new_readers_on_a_hand_made_context():
    ctx = hand_made_context()
    got = {name: m["value"] for name, m in run.read_metrics(new_entries(), ctx).items()}
    assert set(got) == set(NEW_METRICS)
    assert got["lm_decode_ms_per_step.mistral4"] == pytest.approx(4.0)
    assert got["lm_prefill_ms_per_call.mistral4"] == pytest.approx(40.0)
    assert got["lm_slot_fill.mistral4"] == pytest.approx(100.0 * 9000 / (3000 * 16))
    assert got["lm_generate_wait_p50_ms.mistral4"] == pytest.approx(400.0)
    assert got["lm_step_host_p50_ms.mistral4"] == pytest.approx(1.0)
    assert got["lm_decode_rows_per_expert.mistral4"] == pytest.approx(54000.0 / 45000.0)
    work, lm_cfg = ctx["work"], ctx["lm_config"]
    step = work.decode_step(lm_cfg, 3.0, 15.0, 1400.0 + 32)
    assert got["lm_decode_roofline.mistral4"] == pytest.approx(100.0 * step["bytes"] / 819e9 / 4e-3)
    call = work.prefill_call(lm_cfg, 1400.0, 192.0)
    bound = max(call["bytes"] / 819e9, call["flops"] / 197e12)
    assert got["lm_prefill_roofline.mistral4"] == pytest.approx(100.0 * bound / 40e-3)
    assert 0 < got["lm_decode_roofline.mistral4"] < 100 and 0 < got["lm_prefill_roofline.mistral4"] < 100
    # one reply completed in the span: its question's encoding, the scan, its prefill and its 63 rows
    prompt_tokens = ctx["lm_reply_tokens"](ctx["gen"]["records"][0])
    assert prompt_tokens == 24 * 56 + 3 + 31  # the passages, the question, the template's own words
    assert got["answer_mfu.mistral4"] == pytest.approx(
        100.0 * (work.reply_flops(lm_cfg, prompt_tokens, 64) + 2.0 * 4096 * 384
                 + 5 * 6 * (8 * 384 * 384 + 4 * 384 * 1536 + 4 * 5 * 384)) / (4.0 * 197e12))
    assert 0 < got["answer_mfu.mistral4"] < 100


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    entries, ctx = new_entries(), hand_made_context()
    # an untraced run: only the counters are there
    untraced = dict(ctx, trace=None, spans=None)
    del untraced["trace_span"]
    assert set(run.read_metrics(entries, untraced)) == {"lm_slot_fill.mistral4", "lm_decode_rows_per_expert.mistral4"}
    # a program without the generator's new counts (the parent commit): the ninth reader is silent, and does not raise
    parent = {k: v for k, v in ctx["counters_after"].items() if "routed_pairs" not in k}
    got = run.read_metrics(entries, dict(ctx, counters_after=parent))
    assert "lm_decode_rows_per_expert.mistral4" not in got and "lm_slot_fill.mistral4" in got
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
    system = run.load_module("systems", "rag_answer_mistral4")
    traffic = run.load_json("workloads", CELL + ".json")
    docs = [{"text": f"doc{i} w{i:03d}", "metadata": {}, "dist": -0.9 + 0.01 * i} for i in range(24)]
    body = lambda response, context=docs: json.dumps({"response": response, "context_docs": context})
    answer = system.parse_reply(body(" ".join(f"t{i}" for i in range(64))))
    assert answer["ids"] == list(range(64)) and len(answer["context"]) == 24
    assert system.good(answer, traffic)
    assert not system.good(system.parse_reply(body(" ".join(f"t{i}" for i in range(63)))), traffic)
    assert not system.good(system.parse_reply(body(" ".join(f"t{i}" for i in range(64)), docs[:23])), traffic)
    assert traffic["request"] == {"route": "/v2/answer", "text_key": "prompt", "fixed": {"return_context_docs": True}}
    assert set(traffic["limits"]) == {"bad_replies", "text_mismatch", "compiles_in_window", "kth_score_err",
                                      "score_err", "score_err_mean", "rank_gap"} | GAPS
    assert traffic["limits"]["compiles_in_window"] == 0 and traffic["rate_rps"] == int(traffic["rate_rps"])
