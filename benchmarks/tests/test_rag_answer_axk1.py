"""The cell ``answer-axk1-steady`` and what it brought, at sizes a test run can
hold: the rehearsal (``run.py --rehearse``: the configuration's ``rehearse``
group, hidden 64, 3 layers of which the first is dense, heads of 16 + 8 and 16,
2 of a router's 32 experts in 8 groups held, top 8 inside 4 groups, vocabulary
4,096) reads ``correct: true``, and ``false`` with each planted fault
(``faulty_axk1_run.py``) and for each control (``run.py --calibrate``); the
benchmark's own copy of the plain reference gives what the repository's gives;
the work file counts one chip's share of the published model; the new readers
read a hand-made context and return nothing where there is nothing to read; the
configuration's file holds the published widths; a program whose decoder reads
none of A.X-K1's keys ends before any set-up.
"""

import dataclasses
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
from faulty_axk1_run import FAULTS  # noqa: E402

CELL, CONFIG, SYSTEM = "answer-axk1-steady", "axk1-519b-rag.json", "rag_answer_axk1"
GAPS = {"logit_gap_max", "logit_gap_mean", "burst_logit_gap_max", "burst_logit_gap_mean"}
TWINS = ("lm_prefill_ms_per_call", "lm_decode_ms_per_step", "answer_mfu", "lm_slot_fill", "lm_generate_wait_p50_ms",
         "lm_step_host_p50_ms", "lm_decode_rows_per_expert", "lm_prefill_batched_layers", "lm_tpot_ms",
         "lm_prefill_roofline", "lm_decode_roofline")
NEW_METRICS = tuple(name + ".axk1" for name in TWINS) + ("lm_decode_held_pair_share.axk1",
                                                          "lm_decode_group_share.axk1")


def lines_of(command, *more):
    out = subprocess.run([sys.executable] + command + ["--workload", CELL, "--seed", "2147483777", "--seconds", "2",
                                                        "--trace", "0", *more],
                         capture_output=True, text=True, timeout=1200, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line) for line in out.stdout.strip().splitlines()]


def over_limit(compared):
    return [n for n, row in compared.items() if row["limit"] is not None and row["value"] > row["limit"]]


def test_the_rehearsal_reads_correct():
    [result] = lines_of([os.path.join(HERE, "faulty_axk1_run.py"), "--fault", "none"])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert {"bad_replies", "text_mismatch", "rank_gap"} | GAPS <= set(result["compared"])


@pytest.mark.parametrize("fault", [f for f in FAULTS if f != "none"])
def test_a_planted_fault_reads_not_correct(fault):
    [result] = lines_of([os.path.join(HERE, "faulty_axk1_run.py"), "--fault", fault])
    assert result["correct"] is False and result["failed"] == 0
    # whole replies of 24 passages and 128 readable ids: only the reference's logits say that the program was wrong
    assert result["compared"]["bad_replies"]["value"] == 0
    assert set(over_limit(result["compared"])) <= GAPS and over_limit(result["compared"])
    # the warm-up's last burst fills every slot, so a fault that needs rows live together shows there
    assert {"burst_logit_gap_max", "burst_logit_gap_mean"} & set(over_limit(result["compared"]))


def test_every_control_reads_not_correct_where_the_program_reads_correct():
    [line] = lines_of([os.path.join(BENCH, "run.py"), "--rehearse"], "--calibrate", "1")
    assert line["correct"] is True and line["failed"] == 0, line["program"]
    system = run.load_module("systems", SYSTEM)
    assert set(line["controls"]) == set(system.CONTROLS) and {"no_group_limit", "no_routed_scaling"} <= set(
        system.CONTROLS)
    limits = run.load_json("workloads", CELL + ".json")["limits"]
    for name, read in line["controls"].items():
        assert read["correct"] is False, (name, read)
        assert read["logit_gap_max"] > limits["logit_gap_max"] or read["logit_gap_mean"] > limits["logit_gap_mean"]
        assert (read["burst_logit_gap_max"] > limits["burst_logit_gap_max"]
                or read["burst_logit_gap_mean"] > limits["burst_logit_gap_mean"])


def tiny_config():
    cfg = run.load_json("configs", CONFIG)
    cfg = run.merged(cfg, cfg["rehearse"])
    system = run.load_module("systems", SYSTEM)
    return cfg, system.lm_config(cfg), system.reference_config(cfg)


def test_the_benchmarks_reference_gives_what_the_repositorys_gives():
    """Two copies of one mathematics, written apart: the benchmark's (layer by
    layer over a padded batch, one matrix in float32 at a time, eight heads at
    a time, here two of the four) and ``pathway_tpu/models/axk1_reference.py`` (one
    sequence, whole) agree to float32 rounding on seeded weights, over a share
    that is not the first (experts 10-11 of 32, group 2's second half)."""
    import jax.numpy as jnp

    import axk1_reference
    import axk1_weights
    from pathway_tpu.models import axk1_reference as repo_reference
    from pathway_tpu.models import mistral4

    cfg, lm_cfg, ref_cfg = tiny_config()
    lm_cfg, ref_cfg = dict(lm_cfg, first_expert=10), dict(ref_cfg, first_expert=10)
    params = axk1_weights.make_params(2**31 + 5, lm_cfg, cfg["assumed"]["weights_init"], "bfloat16")
    model = mistral4.Mistral4Config.from_dict(lm_cfg)
    assert (model.first_expert, model.router_width, model.n_routed_experts, model.n_group) == (10, 32, 2, 8)
    assert model.first_k_dense_replace == 1 and "gate" not in params["layers"][0]
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2000, 3096, size=n).tolist() for n in (9, 30, 17)]
    served = [rng.integers(2000, 3096, size=5).tolist() for _ in prompts]
    old, axk1_reference.HEADS_AT_ONCE = axk1_reference.HEADS_AT_ONCE, 2  # two blocks of the tiny four heads
    try:
        rows, chosen = axk1_reference.hidden_rows(params, ref_cfg, prompts, served, chunk=2, pad_to=16)
    finally:
        axk1_reference.HEADS_AT_ONCE = old
    read = axk1_reference.read_head(params, ref_cfg, rows, served)
    for i, (prompt, tokens) in enumerate(zip(prompts, served)):
        logits, picked, _ = repo_reference.forward(params, jnp.asarray(prompt + tokens, jnp.int32), model)
        at = np.asarray(logits)[len(prompt) - 1 : len(prompt) + 4]
        np.testing.assert_allclose(read["top"][i], at.max(-1), atol=1e-5)
        np.testing.assert_allclose(read["at"][i], at[np.arange(5), tokens], atol=1e-5)
        np.testing.assert_allclose(read["spread"][i], at.std(-1), rtol=1e-4)
        assert read["argmax"][i].tolist() == at.argmax(-1).tolist()
        assert len(picked) == len(chosen) == 2  # the expert layers: layer 0 is dense
        for layer, c in enumerate(picked):
            assert np.array_equal(np.sort(chosen[layer][i]), np.sort(np.asarray(c)[len(prompt) - 1 : len(prompt) + 4]))
    assert axk1_reference.logit_gaps(read).min() >= 0.0
    assert max(c.max() for c in chosen) > 11  # experts are numbered over the router's whole width
    assert axk1_reference.softmax_scale(ref_cfg) == pytest.approx(model.softmax_scale)
    # and each control is another computation: its own greedy choice differs somewhere
    for variant in run.load_module("systems", SYSTEM).CONTROLS:
        low, _ = axk1_reference.hidden_rows(params, ref_cfg, prompts, served, variant=variant, chunk=2, pad_to=16)
        assert np.abs(low - rows).max() > 1e-3, variant


def test_the_configuration_holds_the_published_widths_and_states_its_cut():
    cfg = run.load_json("configs", CONFIG)
    catalog = {  # skt/A.X-K1's config.json, every number and group as published but the three cut
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 131072, "model_type": "axk1",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8, "num_key_value_heads": 64,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none", "v_head_dim": 128,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096, "type": "yarn"},
    }
    assert {k: cfg[k] for k in catalog} == catalog
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (7, 12, 20480)
    assert (cfg["n_router_experts"], cfg["first_expert"]) == (192, 0)
    assert {k: cfg["published"][k] for k in ("num_hidden_layers", "n_routed_experts", "vocab_size")} == {
        "num_hidden_layers": 61, "n_routed_experts": 192, "vocab_size": 163840}
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size", "corpus"}
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    [entry] = [c for c in manifest["configs"] if c["name"] == "axk1-519b-rag"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size", "corpus"]
    assert entry["source"] == cfg["source"] == "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    [cell] = [w for w in manifest["workloads"] if w["config"] == "axk1-519b-rag"]
    assert cell["name"] == CELL and cell["chips"] == 1 and len(cell["why"]) <= 200
    # the floors of a cut: four layers of the period after the dense one, eight experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 == 163840
    assert {"topk_method", "rope", "softmax_scale", "shared_expert_width", "seq_aux"} <= set(cfg["assumed"])
    assert cfg["serving"]["max_new_tokens"] == 128 and cfg["serving"]["slots"] == 16


def test_the_work_file_counts_one_chips_share_of_the_published_model():
    work = run.load_module("work", "axk1_moe")
    lm_cfg = run.load_module("systems", SYSTEM).lm_config(run.load_json("configs", CONFIG))
    p = work.parameters(lm_cfg)
    outside = p["attention"] + p["dense"] + p["routers"] + p["shared_experts"] + p["norms"]
    # layer 0 whole, six expert layers outside their routed experts, the last norm
    assert outside == 497_500_160 + 6 * 146_554_880 + 7_168 and p["one_expert"] == 44_040_192
    assert outside + 6 * 12 * p["one_expert"] + p["table"] + p["head"] == 4_841_331_712
    assert p["held_pairs_per_token"] == 0.5  # 8 of 192 chosen, 12 held
    step = work.decode_step(lm_cfg, rows=3.0, experts_touched=6 * 1.4, context_tokens=1400.0)
    state = 3.0 * 2 * 576 * 1400.0 * 7
    assert step["bytes"] == pytest.approx(2 * (8.4 * p["one_expert"] + outside + p["head"] + 3 * 7168) + state)
    assert 3.5e9 < step["bytes"] < 4.0e9 and step["bytes"] / 819e9 > step["flops"] / 197e12  # memory bound
    # a row's attention in the absorbed form: 576 numbers a position for the scores, 512 for the mix
    row = work.decode_step(lm_cfg, 1.0, 0.0, 1400.0)["flops"] - work.decode_step(lm_cfg, 1.0, 0.0, 0.0)["flops"]
    assert row == pytest.approx(2 * 1400 * 64 * (576 + 512) * 7)
    call = work.prefill_call(lm_cfg, tokens=1400.0, experts_touched=6 * 12.0)
    assert call["bytes"] == pytest.approx(2 * (4_841_331_712 - p["table"] + 1400 * 7168))
    met = outside + 6 * 0.5 * p["one_expert"]
    assert call["flops"] == pytest.approx(2.0 * (1400 * met + p["head"]) + 2 * (1400 * 1401 / 2) * 64 * 320 * 7)
    assert call["flops"] / 197e12 > call["bytes"] / 819e9  # the expanded prefill is compute bound here
    assert work.reply_flops(lm_cfg, 1400.0, 128) == pytest.approx(
        work.prefill_call(lm_cfg, 1400.0, 0.0)["flops"]
        + sum(work.decode_step(lm_cfg, 1.0, 0.0, 1400.0 + j)["flops"] for j in range(1, 128)))


def hand_made_context():
    system = run.load_module("systems", SYSTEM)
    cfg = run.load_json("configs", CONFIG)
    span = lambda kind, sid, parent, start, dur: {"kind": kind, "span_id": sid, "parent_id": parent, "ts_mono": start,
                                                  "duration_s": dur, "trace_id": "t", "attrs": {}, "links": []}
    spans = [span("generate", "g1", "c1", 1.0, 0.300), span("generate", "g2", "c1", 1.1, 0.500),
             span("generate", "g3", "c2", 2.0, 0.400)]
    for i in range(5):
        spans += [span("lm.decode_step", f"s{i}", None, 1.0 + i * 0.01, 0.009),
                  span("lm.decode_step.device_wait", f"w{i}", f"s{i}", 1.0005 + i * 0.01, 0.008)]
    context = [(7, "doc7 " + "w " * 55, 0.9)] * 24
    answer = {"ids": [2000] * 128, "context": context}
    records = [{"done": 1.5, "status": 200, "query": "w001 w002 q1", "answer": answer},
               {"done": 9.0, "status": 200, "query": "w003 q2", "answer": answer}]
    return {
        "spec": {"config": cfg, "cell": {"name": CELL}}, "gen": {"records": records, "start_at": 0.0},
        "trace_span": {"t0": 0.0, "t1": 4.0}, "spans": spans, "percentile": run.percentile,
        "peaks": run.load_json("peaks.json")["TPU v5 lite"], "work": run.load_module("work", "axk1_moe"),
        "trace": {"busy_s": 3.0, "window_s": 4.0,
                  "programs": {"jit_lm_decode": {"seconds": 2.4, "calls": 300.0},
                               "jit_lm_prefill": {"seconds": 0.6, "calls": 6.0}}},
        "counters_before": {"lm_decode_steps": 100.0, "lm_decode_rows": 300.0, "lm_experts_touched": 800.0,
                            "lm_routed_pairs_held": 900.0, "lm_routed_pairs": 14400.0, "lm_group_rows": 900.0,
                            "lm_prefill_calls": 10.0, "lm_prefill_tokens": 14000.0, "lm_prefill_batched_layers": 60.0,
                            "lm_prefill_experts_touched": 720.0, "lm_slots": 16.0, "lm_decode_phase_s": 10.0,
                            "lm_reply_tokens": 1280.0, "lm_requests_done": 10.0},
        "counters_after": {"lm_decode_steps": 3100.0, "lm_decode_rows": 9300.0, "lm_experts_touched": 24800.0,
                           "lm_routed_pairs_held": 27900.0, "lm_routed_pairs": 446400.0, "lm_group_rows": 27900.0,
                           "lm_prefill_calls": 160.0, "lm_prefill_tokens": 224000.0, "lm_prefill_batched_layers": 960.0,
                           "lm_prefill_experts_touched": 11520.0, "lm_slots": 16.0, "lm_decode_phase_s": 120.0,
                           "lm_reply_tokens": 20480.0, "lm_requests_done": 160.0},
        **system.metric_context(cfg),
    }


def new_entries():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in manifest["per_layer"][-13:]] == list(NEW_METRICS)  # appended, in this order
    assert all(m["workloads"] == [CELL] and m["moves"] == "retrieve_p50_ms" for m in entries)
    # and nothing that was there names the new cell: its own entries and those without a list hold for it
    assert all(CELL not in m.get("workloads", []) for m in manifest["per_layer"][:-13])
    return entries


def test_the_new_readers_on_a_hand_made_context():
    ctx = hand_made_context()
    got = {name: m["value"] for name, m in run.read_metrics(new_entries(), ctx).items()}
    assert set(got) == set(NEW_METRICS)
    assert got["lm_decode_ms_per_step.axk1"] == pytest.approx(8.0)
    assert got["lm_prefill_ms_per_call.axk1"] == pytest.approx(100.0)
    assert got["lm_slot_fill.axk1"] == pytest.approx(100.0 * 9000 / (3000 * 16))
    assert got["lm_generate_wait_p50_ms.axk1"] == pytest.approx(400.0)
    assert got["lm_step_host_p50_ms.axk1"] == pytest.approx(1.0)
    assert got["lm_decode_rows_per_expert.axk1"] == pytest.approx(27000.0 / 24000.0)
    assert got["lm_prefill_batched_layers.axk1"] == pytest.approx(6.0)
    assert got["lm_tpot_ms.axk1"] == pytest.approx(1e3 * 110.0 / (19200.0 - 150.0))
    assert got["lm_decode_held_pair_share.axk1"] == pytest.approx(27000.0 / 432000.0)  # 1/16
    assert got["lm_decode_group_share.axk1"] == pytest.approx(27000.0 / (432000.0 / 8))  # 1/2
    work, lm_cfg = ctx["work"], ctx["lm_config"]
    step = work.decode_step(lm_cfg, 3.0, 8.0, 1400.0 + 64)
    assert got["lm_decode_roofline.axk1"] == pytest.approx(100.0 * step["bytes"] / 819e9 / 8e-3)
    call = work.prefill_call(lm_cfg, 1400.0, 72.0)
    assert got["lm_prefill_roofline.axk1"] == pytest.approx(100.0 * call["flops"] / 197e12 / 100e-3)
    assert 0 < got["lm_decode_roofline.axk1"] < 100 and 0 < got["lm_prefill_roofline.axk1"] < 100
    prompt_tokens = ctx["lm_reply_tokens"](ctx["gen"]["records"][0])
    assert got["answer_mfu.axk1"] == pytest.approx(
        100.0 * (work.reply_flops(lm_cfg, prompt_tokens, 128) + 2.0 * 4096 * 384
                 + 5 * 6 * (8 * 384 * 384 + 4 * 384 * 1536 + 4 * 5 * 384)) / (4.0 * 197e12))


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    entries, ctx = new_entries(), hand_made_context()
    # a program without the group count (the parent commit's decoder): its reader is silent, and does not raise
    parent = {k: v for k, v in ctx["counters_after"].items() if k != "lm_group_rows"}
    got = run.read_metrics(entries, dict(ctx, counters_after=parent))
    assert "lm_decode_group_share.axk1" not in got and "lm_decode_held_pair_share.axk1" in got
    # a program without the generator: no such program, span or counter
    bare = dict(ctx, counters_before={}, counters_after={}, spans=[],
                trace={"busy_s": 1.0, "window_s": 4.0,
                       "programs": {"jit__search_kernel": {"seconds": 1.0, "calls": 9.0}}})
    bare["gen"] = {"records": [dict(r, answer=None) for r in ctx["gen"]["records"]], "start_at": 0.0}
    assert run.read_metrics(entries, bare) == {}
    other = {k: v for k, v in bare.items() if not k.startswith(("lm_", "live_rows"))}
    assert run.read_metrics(entries, dict(other, work=None)) == {}


def test_a_program_whose_decoder_reads_none_of_the_keys_ends_before_set_up(monkeypatch):
    """The parent's decoder reads no ``first_k_dense_replace``: the system says so
    and ends at once, as a run that cannot serve the configuration."""
    from pathway_tpu.models import mistral4

    system = run.load_module("systems", SYSTEM)
    drop = {"first_k_dense_replace", "intermediate_size", "scoring_func", "n_group", "topk_group", "topk_method"}
    fields = [f for f in dataclasses.fields(mistral4.Mistral4Config) if f.name not in drop]
    monkeypatch.setattr(mistral4, "Mistral4Config", dataclasses.make_dataclass(
        "Mistral4Config", [(f.name, f.type, f) for f in fields]))
    with pytest.raises(SystemExit, match="reads no"):
        system.System(run.load_json("configs", CONFIG), 1, 0, [], print)


def test_a_reply_is_read_and_judged_whole():
    system = run.load_module("systems", SYSTEM)
    traffic = run.load_json("workloads", CELL + ".json")
    docs = [{"text": f"doc{i} w{i:03d}", "metadata": {}, "dist": -0.9 + 0.01 * i} for i in range(24)]
    body = lambda response, context=docs: json.dumps({"response": response, "context_docs": context})
    answer = system.parse_reply(body(" ".join(f"t{i}" for i in range(128))))
    assert answer["ids"] == list(range(128)) and len(answer["context"]) == 24
    assert system.good(answer, traffic)
    assert not system.good(system.parse_reply(body(" ".join(f"t{i}" for i in range(127)))), traffic)
    assert traffic["request"] == {"route": "/v2/answer", "text_key": "prompt", "fixed": {"return_context_docs": True}}
    assert set(traffic["limits"]) == {"bad_replies", "text_mismatch", "compiles_in_window", "kth_score_err",
                                      "score_err", "score_err_mean", "rank_gap"} | GAPS
    assert traffic["limits"]["compiles_in_window"] == 0 and traffic["rate_rps"] == int(traffic["rate_rps"])
