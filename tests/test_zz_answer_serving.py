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

import concurrent.futures
import contextlib
import json
import socket
import threading
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


@contextlib.contextmanager
def profiler_session(directory):
    """A ``jax.profiler`` session: while it is on, every request leaves its spans."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


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

    with profiler_session(tmp_path_factory.mktemp("profile")):
        reply = _post(port, "/v2/answer", {"prompt": QUESTION, "return_context_docs": True})
        # the reply leaves before the commit that carried the answer in has ended, and that commit
        # records the request's second ``queue`` span, and then itself, at its end, if the session
        # is still on: ending the session at once races it (lost under the suite's six workers)
        deadline = time.monotonic() + 60
        while sum(s["kind"] == "commit" and s["attrs"].get("queries", 0) > 0
                  for s in tracing.get_tracer().recent_spans(limit=1 << 20)) < 2:
            assert time.monotonic() < deadline, "the commit that carried the answer in never ended"
            time.sleep(0.01)
    after = (chat.service.stats(), telemetry.stage_snapshot("lm."))
    spans = tracing.get_tracer().recent_spans(limit=1 << 20)
    yield {"chat": chat, "params": params, "reply": reply, "before": before, "after": after, "spans": spans,
           "port": port}
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
    # the 256 bucket gives an expert 64 rows: the four layers' products ran batched in the prefill, in no step
    assert grew["lm_prefill_batched_layers"] == 4 and grew["lm_batched_layers"] == 0
    # another decoder of this process may have named counts of its own: they did not move here
    stage = {k: grown for k in served["after"][1] if (grown := served["after"][1][k] - served["before"][1].get(k, 0.0))}
    # one reply resolved, its tokens all its own steps' and no other prompt prefilled inside it
    assert grew["lm_requests_done"] == 1 and grew["lm_reply_tokens"] == NEW_TOKENS and grew["lm_prefills_in_decode"] == 0
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
    end = lambda s: s["ts_mono"] + s["duration_s"]
    # the generation is the request's own: the commit that took the question handed the prompt out,
    # and the commit that carried the answer in links the same request
    [rest] = [s for s in by_kind["rest"] if s["span_id"] == generate["parent_id"]]
    assert rest["trace_id"] == generate["trace_id"] and rest["attrs"]["route"] == "/v2/answer"
    took, carried = sorted((s for s in by_kind["commit"] if any(link["span_id"] == rest["span_id"] for link in s["links"])),
                           key=lambda s: s["ts_mono"])
    assert took["attrs"]["queries"] == carried["attrs"]["queries"] == 1
    # only what one event causes in another is ordered here: a commit's span opens before it takes
    # its rows (a push may land just inside it), and whether the first commit ENDS before the
    # generation does is a race between two threads; that no commit holds a generation is the next
    # test's, without a clock. (The reply may leave before the commit that resolved it has ended.)
    assert took["ts_mono"] < generate["ts_mono"] and end(took) <= carried["ts_mono"]
    assert end(generate) <= end(carried) and carried["ts_mono"] < end(rest)
    queues = [s for s in by_kind["queue"] if s["parent_id"] == rest["span_id"]]
    assert sorted(s["attrs"]["commit"] for s in queues) == [took["attrs"]["commit"], carried["attrs"]["commit"]]
    assert len(steps) == NEW_TOKENS - 1 and all(s["attrs"]["rows"] == 1 for s in steps)
    assert all(any(link["span_id"] == generate["span_id"] for link in s["links"]) for s in steps)
    # each call's span holds exactly one fetch: that of the call before it (none before the prefill)
    step_waits, [prefill_wait] = by_kind["lm.decode_step.device_wait"], by_kind["lm.prefill.device_wait"]
    assert sorted(s["parent_id"] for s in step_waits) == sorted(s["span_id"] for s in steps)
    assert prefill_wait["parent_id"] == prefill["span_id"]
    # the service's spans are one thread's: the prefill, then every step, one after the other, none
    # inside another; all of it inside the generation, which ends only after the last fetch, and that
    # one (the last step's own tokens) is made after the last step's span, with nothing more to enqueue
    inside = sorted([prefill] + steps, key=lambda s: s["ts_mono"])
    assert inside[0] is prefill and all(end(a) <= b["ts_mono"] for a, b in zip(inside, inside[1:]))
    by_id = {s["span_id"]: s for s in inside}
    assert all(by_id[w["parent_id"]]["ts_mono"] <= w["ts_mono"] and end(w) <= end(by_id[w["parent_id"]])
               for w in step_waits + [prefill_wait])
    assert generate["ts_mono"] <= prefill["ts_mono"] and end(inside[-1]) <= end(generate)
    # the request's timeline: three children of its generate span end to end, from the submission
    # (inside generate) through its slot, its prefill's token read and its last token read, and the
    # same stamps summed in stats()
    [queue], [first], [decode] = ([s for s in by_kind[k] if s["parent_id"] == generate["span_id"]]
                                  for k in ("lm.queue", "lm.first_token", "lm.decode"))
    assert generate["ts_mono"] <= queue["ts_mono"]
    assert end(queue) == pytest.approx(first["ts_mono"], abs=1e-9) and end(first) == pytest.approx(decode["ts_mono"], abs=1e-9)
    assert queue["ts_mono"] <= prefill["ts_mono"] and end(prefill_wait) <= end(first) and end(inside[-1]) <= end(decode)
    assert end(decode) <= end(generate) and decode["attrs"] == {"tokens": NEW_TOKENS, "prefills": 0}
    assert grew["lm_queue_s"] == pytest.approx(queue["duration_s"])
    assert grew["lm_ttft_s"] == pytest.approx(queue["duration_s"] + first["duration_s"])
    assert grew["lm_decode_phase_s"] == pytest.approx(decode["duration_s"])


def test_zz_a_second_question_is_answered_while_the_first_still_generates(served, tmp_path, monkeypatch):
    """Two questions back to back, the first's tokens held back by the test: the
    second is retrieved, prefilled and answered meanwhile, because no commit
    holds the first's generation."""
    chat, port = served["chat"], served["port"]
    release, first_in, submitted = threading.Event(), threading.Event(), []
    submit = chat.service.submit

    def held_submit(ids, *, ctx=None):
        future = submit(ids, ctx=ctx)
        submitted.append(future)
        if len(submitted) > 1:
            return future
        gated: concurrent.futures.Future = concurrent.futures.Future()
        threading.Thread(target=lambda: (release.wait(120), gated.set_result(future.result(120))), daemon=True).start()
        first_in.set()
        return gated

    monkeypatch.setattr(chat.service, "submit", held_submit)
    counted = lambda: telemetry.stage_snapshot("eval.fully_async_")
    before = counted()
    replies: dict = {}
    first = threading.Thread(target=lambda: replies.update(
        first=_post(port, "/v2/answer", {"prompt": "w011 w012 q2", "return_context_docs": True})))
    try:
        with profiler_session(tmp_path):
            first.start()
            assert first_in.wait(60)
            replies["second"] = _post(port, "/v2/answer", {"prompt": "w021 w022 q3", "return_context_docs": True})
            assert "first" not in replies and first.is_alive()
            in_flight = counted()
            release.set()
            first.join(120)
            retrieved = _post(port, "/v1/retrieve", {"query": "w001 w002", "k": 3})
    finally:
        release.set()
    assert len(retrieved) == 3 and len(submitted) == 2
    assert all(len(chat.reply_ids(replies[k]["response"])) == NEW_TOKENS for k in ("first", "second"))
    # one call handed out and one result back a question, nothing for a retrieval
    grew = lambda now: {k.rsplit("_", 1)[1]: now[k] - before.get(k, 0.0) for k in now}
    assert grew(in_flight) == {"rows": 2.0, "returned": 1.0} and grew(counted()) == {"rows": 2.0, "returned": 2.0}

    end = lambda s: s["ts_mono"] + s["duration_s"]
    spans = tracing.get_tracer().recent_spans(limit=1 << 20)
    # the ring still holds the fixture's request: this session's two are the newest
    held, other = sorted((s for s in spans if s["kind"] == "generate"), key=lambda s: s["ts_mono"])[-2:]
    [prefill] = [s for s in spans if s["kind"] == "lm.prefill" and s["parent_id"] == other["span_id"]]
    [rest] = [s for s in spans if s["kind"] == "rest" and s["span_id"] == other["parent_id"]]
    assert held["ts_mono"] < prefill["ts_mono"] < end(held) and end(rest) < end(held)
