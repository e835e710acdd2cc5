"""The cell ``answer-lfm2-steady`` and what it brought, at sizes a test run can
hold: the rehearsal (``run.py --rehearse``: the configuration's ``rehearse``
group, hidden 64, 2 dense + 4 expert layers, 8 experts, top 2, vocabulary 4,096)
reads ``correct: true``, and ``false`` with each planted fault
(``faulty_answer_run.py``) and for each control (``run.py --calibrate``); the
benchmark's own copy of the plain reference gives what the repository's gives;
the work file counts the published model; the new readers read a hand-made
context and return nothing where there is nothing to read.
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
from faulty_answer_run import FAULTS  # noqa: E402

CELL = "answer-lfm2-steady"
GAPS = {"logit_gap_max", "logit_gap_mean", "burst_logit_gap_max", "burst_logit_gap_mean"}
NEW_METRICS = ("lm_decode_ms_per_step", "lm_prefill_ms_per_call", "lm_decode_roofline", "lm_prefill_roofline",
               "answer_mfu", "lm_slot_fill", "lm_generate_wait_p50_ms", "lm_step_host_p50_ms")


def lines_of(command, *more):
    out = subprocess.run([sys.executable] + command + ["--workload", CELL, "--seed", "2147483777", "--seconds", "2",
                                                        "--trace", "0", *more],
                         capture_output=True, text=True, timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line) for line in out.stdout.strip().splitlines()]


def over_limit(compared):
    return [n for n, row in compared.items() if row["limit"] is not None and row["value"] > row["limit"]]


def test_the_rehearsal_reads_correct():
    [result] = lines_of([os.path.join(HERE, "faulty_answer_run.py"), "--fault", "none"])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert {"bad_replies", "text_mismatch", "rank_gap"} | GAPS <= set(result["compared"])


@pytest.mark.parametrize("fault", [f for f in FAULTS if f != "none"])
def test_a_planted_fault_reads_not_correct(fault):
    [result] = lines_of([os.path.join(HERE, "faulty_answer_run.py"), "--fault", fault])
    assert result["correct"] is False and result["failed"] == 0
    # whole replies of 6 passages and 32 readable ids: only the reference's logits say that the state was wrong
    assert result["compared"]["bad_replies"]["value"] == 0
    assert set(over_limit(result["compared"])) <= GAPS and over_limit(result["compared"])
    # the warm-up's last burst fills every slot, so a fault that needs rows live together shows there
    assert {"burst_logit_gap_max", "burst_logit_gap_mean"} <= set(over_limit(result["compared"]))


def test_every_control_reads_not_correct_where_the_program_reads_correct():
    [line] = lines_of([os.path.join(BENCH, "run.py"), "--rehearse"], "--calibrate", "1")
    assert line["correct"] is True and line["failed"] == 0, line["program"]
    rag_answer = run.load_module("systems", "rag_answer")
    assert set(line["controls"]) == set(rag_answer.CONTROLS)
    limits = run.load_json("workloads", CELL + ".json")["limits"]
    for name, read in line["controls"].items():
        assert read["correct"] is False, (name, read)
        assert read["logit_gap_max"] > limits["logit_gap_max"] or read["logit_gap_mean"] > limits["logit_gap_mean"]
        assert (read["burst_logit_gap_max"] > limits["burst_logit_gap_max"]
                or read["burst_logit_gap_mean"] > limits["burst_logit_gap_mean"])


def tiny_config():
    cfg = run.merged(run.load_json("configs", "lfm2-8b-a1b-rag.json"),
                     run.load_json("configs", "lfm2-8b-a1b-rag.json")["rehearse"])
    return cfg, run.load_module("systems", "rag_answer").lm_config(cfg)


def test_the_benchmarks_reference_gives_what_the_repositorys_gives():
    """Two copies of one mathematics, written apart: the benchmark's (layer by
    layer over a padded batch) and ``pathway_tpu/models/lfm2_reference.py`` (one
    sequence, whole) agree to float32 rounding on seeded weights."""
    import jax.numpy as jnp

    import lfm2_reference
    import lfm2_weights
    from pathway_tpu.models import lfm2
    from pathway_tpu.models import lfm2_reference as repo_reference

    cfg, lm_cfg = tiny_config()
    params = lfm2_weights.make_params(2**31 + 5, lm_cfg, cfg["assumed"]["weights_init"], "bfloat16")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2000, 3096, size=n).tolist() for n in (9, 30, 17)]
    served = [rng.integers(2000, 3096, size=5).tolist() for _ in prompts]
    rows, chosen = lfm2_reference.hidden_rows(params, lm_cfg, prompts, served, chunk=2, pad_to=16)
    read = lfm2_reference.read_head(params, lm_cfg, rows, served)
    model = lfm2.Lfm2Config.from_dict(lm_cfg)
    for i, (prompt, tokens) in enumerate(zip(prompts, served)):
        logits, picked = repo_reference.forward(params, jnp.asarray(prompt + tokens, jnp.int32), model)
        at = np.asarray(logits)[len(prompt) - 1 : len(prompt) + 4]
        np.testing.assert_allclose(read["top"][i], at.max(-1), atol=1e-5)
        np.testing.assert_allclose(read["at"][i], at[np.arange(5), tokens], atol=1e-5)
        np.testing.assert_allclose(read["spread"][i], at.std(-1), rtol=1e-4)
        assert read["argmax"][i].tolist() == at.argmax(-1).tolist()
        for layer, c in enumerate(picked):
            assert np.array_equal(np.sort(chosen[layer][i]), np.sort(np.asarray(c)[len(prompt) - 1 : len(prompt) + 4]))
    assert lfm2_reference.logit_gaps(read).min() >= 0.0
    # and each control is another computation: its own greedy choice differs somewhere
    for variant in run.load_module("systems", "rag_answer").CONTROLS:
        low, _ = lfm2_reference.hidden_rows(params, lm_cfg, prompts, served, variant=variant, chunk=2, pad_to=16)
        assert np.abs(low - rows).max() > 1e-3, variant


def test_the_tokenizer_and_the_template_are_the_programs():
    import lfm2_reference
    from pathway_tpu.models.encoder import HashTokenizer
    from pathway_tpu.xpacks.llm import prompts

    docs = [{"text": "doc3 w001 W002"}, {"text": "doc9 w077"}]
    text = prompts.prompt_qa("w001 w002 q7", tuple(docs))
    assert lfm2_reference.prompt_qa("w001 w002 q7", [d["text"] for d in docs]) == text
    ids, _ = HashTokenizer(vocab_size=65536, max_length=1 << 30)([text])
    assert lfm2_reference.tokenize(text, 65536) == ids[0, 1:-1].tolist()


def test_the_work_file_counts_the_published_model():
    work = run.load_module("work", "lfm2_moe")
    lm_cfg = run.load_module("systems", "rag_answer").lm_config(run.load_json("configs", "lfm2-8b-a1b-rag.json"))
    p = work.parameters(lm_cfg)
    total = p["operators"] + p["dense_ffn"] + p["routers"] + p["table"] + p["expert_layers"] * 32 * p["one_expert"]
    assert total == 4_667_077_376  # issue 28's arithmetic, and the program's own tree (tests/test_lfm2.py)
    assert (p["expert_layers"], p["attention_layers"], p["conv_layers"], p["one_expert"]) == (12, 3, 11, 11_010_048)
    step = work.decode_step(lm_cfg, rows=6.0, experts_touched=12 * 17.0, context_tokens=400.0)
    other = 2 * (total - 12 * 32 * p["one_expert"])
    state = 6.0 * 2 * (2 * 8 * 64 * 400.0 * 3 + 2 * 2048 * 11)
    assert step["bytes"] == pytest.approx(2 * 12 * 17 * p["one_expert"] + other + state)
    assert 5.3e9 < step["bytes"] < 5.5e9 and step["bytes"] / 819e9 > step["flops"] / 197e12  # memory bound
    # a step that touches every expert reads every weight once, and never more
    full = work.decode_step(lm_cfg, 16.0, 12 * 32.0, 0.0)
    assert full["bytes"] == pytest.approx(2 * total + 16 * 2 * 2 * 2048 * 11)
    call = work.prefill_call(lm_cfg, tokens=380.0, experts_touched=12 * 32.0)
    assert call["bytes"] == pytest.approx(2 * total) and call["bytes"] / 819e9 > call["flops"] / 197e12
    assert work.reply_flops(lm_cfg, 380.0, 32) > work.prefill_call(lm_cfg, 380.0, 0.0)["flops"]


def hand_made_context():
    rag_answer = run.load_module("systems", "rag_answer")
    cfg = run.load_json("configs", "lfm2-8b-a1b-rag.json")
    span = lambda kind, sid, parent, start, dur: {"kind": kind, "span_id": sid, "parent_id": parent, "ts_mono": start,
                                                  "duration_s": dur, "trace_id": "t", "attrs": {}, "links": []}
    spans = [span("generate", "g1", "c1", 1.0, 0.300), span("generate", "g2", "c1", 1.1, 0.500),
             span("generate", "g3", "c2", 2.0, 0.400)]
    for i in range(5):
        spans += [span("lm.decode_step", f"s{i}", None, 1.0 + i * 0.01, 0.009),
                  span("lm.decode_step.device_wait", f"w{i}", f"s{i}", 1.0005 + i * 0.01, 0.008)]
    context = [(7, "doc7 " + "w " * 59, 0.9)] * 6
    records = [{"done": 1.5, "status": 200, "query": "w001 w002 q1", "answer": {"ids": [2000] * 32, "context": context}},
               {"done": 9.0, "status": 200, "query": "w003 q2", "answer": {"ids": [2000] * 32, "context": context}}]
    return {
        "spec": {"config": cfg, "cell": {"name": CELL}}, "gen": {"records": records, "start_at": 0.0},
        "trace_span": {"t0": 0.0, "t1": 4.0}, "spans": spans, "percentile": run.percentile,
        "peaks": run.load_json("peaks.json")["TPU v5 lite"], "work": run.load_module("work", "lfm2_moe"),
        "trace": {"busy_s": 3.0, "window_s": 4.0,
                  "programs": {"jit_lm_decode": {"seconds": 2.4, "calls": 300.0},
                               "jit_lm_prefill": {"seconds": 0.6, "calls": 30.0}}},
        "counters_before": {"lm_decode_steps": 100.0, "lm_decode_rows": 500.0, "lm_experts_touched": 20000.0,
                            "lm_prefill_calls": 10.0, "lm_prefill_tokens": 4000.0, "lm_prefill_experts_touched": 3840.0,
                            "lm_slots": 16.0},
        "counters_after": {"lm_decode_steps": 3100.0, "lm_decode_rows": 18500.0, "lm_experts_touched": 632000.0,
                           "lm_prefill_calls": 310.0, "lm_prefill_tokens": 118000.0,
                           "lm_prefill_experts_touched": 119040.0, "lm_slots": 16.0},
        **rag_answer.metric_context(cfg),
    }


def test_the_new_readers_on_a_hand_made_context():
    ctx = hand_made_context()
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    assert len(entries) == 8 and all(m["workloads"] == [CELL] and m["moves"] == "retrieve_p50_ms" for m in entries)
    got = {name: m["value"] for name, m in run.read_metrics(entries, ctx).items()}
    assert set(got) == set(NEW_METRICS)
    assert got["lm_decode_ms_per_step"] == pytest.approx(8.0) and got["lm_prefill_ms_per_call"] == pytest.approx(20.0)
    assert got["lm_slot_fill"] == pytest.approx(100.0 * 18000 / (3000 * 16))
    assert got["lm_generate_wait_p50_ms"] == pytest.approx(400.0) and got["lm_step_host_p50_ms"] == pytest.approx(1.0)
    work, lm_cfg = ctx["work"], ctx["lm_config"]
    step = work.decode_step(lm_cfg, 6.0, 204.0, 380.0 + 16)
    assert got["lm_decode_roofline"] == pytest.approx(100.0 * step["bytes"] / 819e9 / 8e-3)
    call = work.prefill_call(lm_cfg, 380.0, 384.0)
    assert got["lm_prefill_roofline"] == pytest.approx(100.0 * call["bytes"] / 819e9 / 20e-3)
    assert 0 < got["lm_decode_roofline"] < 100 and 0 < got["lm_prefill_roofline"] < 100
    # one reply completed in the span: its question's encoding, the scan, its prefill and its 31 rows
    prompt_tokens = ctx["lm_reply_tokens"](ctx["gen"]["records"][0])
    assert prompt_tokens == 6 * 60 + 3 + len("Please provide an answer based solely on the provided sources. Keep your "
                                             "answer concise and accurate. If the sources do not contain the answer, "
                                             "say: No information found. Sources: Question: Answer:".split())
    assert got["answer_mfu"] == pytest.approx(100.0 * (work.reply_flops(lm_cfg, prompt_tokens, 32) + 2.0 * 4096 * 384
                                                       + 5 * 6 * (8 * 384 * 384 + 4 * 384 * 1536 + 4 * 5 * 384))
                                              / (4.0 * 197e12))


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    ctx = hand_made_context()
    # an untraced run: only the counters are there
    untraced = dict(ctx, trace=None, spans=None)
    del untraced["trace_span"]
    assert set(run.read_metrics(entries, untraced)) == {"lm_slot_fill"}
    # a program without the generator (the parent commit): no such program, span or counter
    bare = dict(ctx, counters_before={}, counters_after={}, spans=[s for s in ctx["spans"] if False],
                trace={"busy_s": 1.0, "window_s": 4.0, "programs": {"jit__search_kernel": {"seconds": 1.0, "calls": 9.0}}})
    bare["gen"] = {"records": [dict(r, answer=None) for r in ctx["gen"]["records"]], "start_at": 0.0}
    assert run.read_metrics(entries, bare) == {}
    # another system's context: none of this system's keys
    other = {k: v for k, v in bare.items() if not k.startswith(("lm_", "live_rows"))}
    assert run.read_metrics(entries, dict(other, work=None)) == {}
    assert run.read_metrics(entries, dict(other, work=run.load_module("work", "dense_scan"))) == {}


def test_a_reply_is_read_and_judged_whole():
    rag_answer = run.load_module("systems", "rag_answer")
    traffic = run.load_json("workloads", CELL + ".json")
    docs = [{"text": f"doc{i} w{i:03d}", "metadata": {}, "dist": -0.9 + 0.01 * i} for i in range(6)]
    body = lambda response, context=docs: json.dumps({"response": response, "context_docs": context})
    answer = rag_answer.parse_reply(body(" ".join(f"t{i}" for i in range(32))))
    assert answer["ids"] == list(range(32)) and answer["context"][0] == (0, "doc0 w000", 0.9)
    assert rag_answer.good(answer, traffic)
    assert not rag_answer.good(rag_answer.parse_reply(body(" ".join(f"t{i}" for i in range(31)))), traffic)
    assert not rag_answer.good(rag_answer.parse_reply(body(" ".join(f"t{i}" for i in range(32)), docs[:5])), traffic)
    for broken in (None, "", "not json", body("t1 x2 t3"), body("t1 t-"), json.dumps({"response": "t1"})):
        assert rag_answer.parse_reply(broken) is None
    assert traffic["request"] == {"route": "/v2/answer", "text_key": "prompt", "fixed": {"return_context_docs": True}}
