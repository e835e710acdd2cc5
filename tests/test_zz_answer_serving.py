"""``/v2/answer`` through the normal path, with the generator on the device:
``QARestServer`` over ``BaseRAGQuestionAnswerer`` with ``Lfm2Chat`` (the tiny
``lfm2_moe`` decoder of ``test_lfm2.py``, float32, the CPU). The reply's ids
are the plain reference's greedy tokens for the prompt rebuilt from the reply's
own context, and while a ``jax.profiler`` session is on the request leaves the
generation service's spans and counters with the values it implies.

Lives at the end of the suite's alphabetical order on purpose: REST sources
stream forever (daemon threads); see ``test_zz_trace_serving.py``.
"""

from __future__ import annotations

import json
import socket
import time
import urllib.request

import jax.numpy as jnp
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import telemetry, tracing
from pathway_tpu.models import lfm2

from .test_lfm2 import CFG, TINY, assert_greedy

pytestmark = pytest.mark.trace

NEW_TOKENS = 8
QUESTION = "w007 w008 w009 q1"


def _post(port: int, route: str, payload: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server, one warm request, then ONE request inside a profiler session."""
    import jax

    from pathway_tpu.internals import parse_graph as pg
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.stdlib.indexing import nearest_neighbors as nn
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.llms import Lfm2Chat
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer
    from pathway_tpu.xpacks.llm.servers import QARestServer

    mp = pytest.MonkeyPatch()
    mp.delenv("PATHWAY_TRACE", raising=False)
    tracing.reset_tracing()
    pg.G.clear()
    params = lfm2.init_params(CFG, seed=5, dtype=jnp.float32)
    chat = Lfm2Chat(TINY, params, slots=4, max_prompt_tokens=256, max_new_tokens=NEW_TOKENS,
                    prefill_buckets=(64, 256))
    embedder = SentenceTransformerEmbedder(encoder_config=EncoderConfig(
        vocab_size=30522, hidden_size=32, num_layers=1, num_heads=2, intermediate_size=64,
    ))
    docs = [f"doc{i} " + " ".join(f"w{(i * 7 + j) % 50:03d}" for j in range(12)) for i in range(20)]
    table = pw.debug.table_from_rows(
        pw.schema_builder({"data": str, "_metadata": str}),
        [(text, json.dumps({"path": f"doc{i}"})) for i, text in enumerate(docs)],
    )
    store = DocumentStore(table, retriever_factory=nn.BruteForceKnnFactory(
        embedder=embedder, metric=nn.BruteForceKnnMetricKind.COS, reserved_space=64,
    ))
    qa = BaseRAGQuestionAnswerer(llm=chat, indexer=store, search_topk=6)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    QARestServer("127.0.0.1", port, qa).run(threaded=True)
    deadline = time.monotonic() + 120
    while True:
        try:
            if int(_post(port, "/v1/statistics", {}).get("file_count", 0)) == len(docs):
                break
        except OSError:
            pass
        assert time.monotonic() < deadline, "the corpus was never indexed"
        time.sleep(0.2)
    body = {"prompt": "w001 w002 warm", "return_context_docs": True}
    _post(port, "/v2/answer", body)  # compiles the bucket and the step outside the session
    before = (chat.service.stats(), telemetry.stage_snapshot("lm."))
    assert tracing.get_tracer().recent_spans() == []  # nothing records yet

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("profile")), profiler_options=opts)
    try:
        reply = _post(port, "/v2/answer", {"prompt": QUESTION, "return_context_docs": True})
    finally:
        jax.profiler.stop_trace()
    after = (chat.service.stats(), telemetry.stage_snapshot("lm."))
    spans = tracing.get_tracer().recent_spans(limit=1 << 20)
    yield {"chat": chat, "params": params, "reply": reply, "before": before, "after": after, "spans": spans}
    tracing.reset_tracing()
    mp.undo()


def rebuilt_prompt_ids(chat, reply):
    from pathway_tpu.xpacks.llm import prompts

    return chat.tokenize(prompts.prompt_qa(QUESTION, tuple(reply["context_docs"])))


def test_zz_the_replys_ids_are_the_references_greedy_tokens(served):
    chat, reply = served["chat"], served["reply"]
    assert len(reply["context_docs"]) == 6 and "doc" in reply["context_docs"][0]["text"]
    ids = chat.reply_ids(reply["response"])
    assert len(ids) == NEW_TOKENS and reply["response"] == " ".join(f"t{t}" for t in ids)
    prompt = rebuilt_prompt_ids(chat, reply)
    assert 64 < len(prompt) <= 256  # the sources' 6 x 13 words and the template's
    assert_greedy(served["params"], prompt, ids)


def test_zz_counters_and_spans_carry_what_the_request_implies(served):
    chat, spans = served["chat"], served["spans"]
    n_prompt = len(rebuilt_prompt_ids(chat, served["reply"]))
    grew = {k: served["after"][0][k] - v for k, v in served["before"][0].items()}
    assert grew["lm_prefill_calls"] == 1 and grew["lm_prefill_tokens"] == n_prompt
    assert grew["lm_prefill_padded_tokens"] == 256 and grew["lm_slots"] == 0 and served["after"][0]["lm_slots"] == 4
    assert grew["lm_decode_steps"] == grew["lm_decode_rows"] == NEW_TOKENS - 1
    # four expert layers, two experts a token: one row a step chooses eight
    assert grew["lm_experts_touched"] == 8 * (NEW_TOKENS - 1) and grew["lm_compiled_programs"] == 0
    stage = {k: served["after"][1][k] - served["before"][1].get(k, 0.0) for k in served["after"][1]}
    assert stage == {"lm.prefill_calls": 1.0, "lm.prefill_tokens": float(n_prompt), "lm.prefill_padded_tokens": 256.0,
                     "lm.decode_steps": NEW_TOKENS - 1.0, "lm.decode_rows": NEW_TOKENS - 1.0,
                     "lm.experts_touched": 8.0 * (NEW_TOKENS - 1)}

    by_kind: dict = {}
    for s in spans:
        by_kind.setdefault(s["kind"], []).append(s)
    [generate] = by_kind["generate"]
    [prefill] = by_kind["lm.prefill"]
    steps = by_kind["lm.decode_step"]
    assert generate["attrs"]["prompt_tokens"] == n_prompt and prefill["attrs"]["tokens"] == n_prompt
    assert prefill["parent_id"] == generate["span_id"] and prefill["trace_id"] == generate["trace_id"]
    # the generation is a child of the commit that evaluated the chat
    [commit] = [s for s in by_kind["commit"] if s["span_id"] == generate["parent_id"]]
    assert commit["attrs"]["queries"] == 1
    assert len(steps) == NEW_TOKENS - 1 and all(s["attrs"]["rows"] == 1 for s in steps)
    assert all(any(link["span_id"] == generate["span_id"] for link in s["links"]) for s in steps)
    waits = {s["parent_id"] for s in by_kind["lm.decode_step.device_wait"]}
    assert waits == {s["span_id"] for s in steps}
    assert [s["parent_id"] for s in by_kind["lm.prefill.device_wait"]] == [prefill["span_id"]]
    # prefill and every step lie inside the generation, one after the other
    end = lambda s: s["ts_mono"] + s["duration_s"]
    inside = sorted([prefill] + steps, key=lambda s: s["ts_mono"])
    assert inside[0] is prefill and generate["ts_mono"] <= prefill["ts_mono"] and end(inside[-1]) <= end(generate)
    assert all(end(a) <= b["ts_mono"] for a, b in zip(inside, inside[1:]))
