"""The system under test ``rag_answer``: ``QARestServer`` over
``BaseRAGQuestionAnswerer`` in a thread of this process, with the generator on
the device (``Lfm2Chat``: the ``lfm2_moe`` decoder behind the generation
service) beside the MiniLM encoder and the dense index. A reply to
``POST /v2/answer`` is "the question's exact cosine top-6 of the live passages,
then 32 greedy tokens of the whole model over the prompt built from them".

What belongs to that reply is here: how it is read (``parse_reply``, ``good``),
the plain references it is judged by (``reference.py`` for the retrieval stage,
``lfm2_reference.py`` for the generator, over the inputs of ``weights.py`` and
``lfm2_weights.py``), the numbers compared and the controls. The server is
built through the program's normal constructors, as ``systems/vector_store.py``
builds its own; this configuration has no resident rows, since a row with no
text cannot go into a prompt.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import compare
import lfm2_reference
import lfm2_weights
import loadgen
import reference
import trace_reduce
import weights as weights_mod
from systems.vector_store import index_factory, post

READY_DEADLINE_S = 1100.0
PUBLISHED_KEYS = (
    "conv_L_cache", "hidden_size", "intermediate_size", "layer_types", "moe_intermediate_size", "norm_eps",
    "norm_topk_prob", "num_attention_heads", "num_dense_layers", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "rope_theta", "routed_scaling_factor", "use_expert_bias",
    "vocab_size",
)
# what --calibrate puts in the program's place: the reference's own greedy choice at every
# position, computed this way (``lfm2_reference.VARIANTS``)
CONTROLS = ("fp8_matmul", "top3_experts", "no_expert_bias")
# the counters whose growth is a program compiled: the search kernels' caches, the encoder's
# pre-warm, the language model's two jitted functions
COMPILE_COUNTERS = ("kernel.", "svc_prewarm_compiles", "lm_compiled_programs")

Answer = Optional[Dict[str, Any]]  # {"ids": generated ids, "context": compare.Answer of the passages}


def lm_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published ``config.json`` keys of the configuration's file that shape the model."""
    return {k: cfg[k] for k in PUBLISHED_KEYS}


def parse_reply(body: Optional[str]) -> Answer:
    """A ``/v2/answer`` body: the generated ids read back from the response's
    words (``t<id>`` each) and the context passages as (document, text, score)
    per rank, as ``compare.parse_reply`` reads a ``/v1/retrieve`` body."""
    try:
        reply = json.loads(body)
        ids = [int(word[1:]) for word in reply["response"].split() if word[0] == "t"]
        if len(ids) != len(reply["response"].split()):
            return None
        context = compare.parse_reply(json.dumps(reply["context_docs"]))
    except (TypeError, ValueError, KeyError, IndexError, AttributeError):
        return None
    return None if context is None else {"ids": ids, "context": context}


def good(answer: Answer, traffic: Dict[str, Any]) -> bool:
    """A reply counts where it holds the asked passages and exactly the asked number of readable ids."""
    want = traffic["reply"]
    return (answer is not None and len(answer["context"]) == int(want["context_docs"])
            and len(answer["ids"]) == int(want["new_tokens"]))


class System:
    """The running server and the handles the harness reads."""

    def __init__(self, cfg: Dict[str, Any], seed: int, port: int, docs: List[str],
                 log: Callable[[str], None]):
        import jax

        import pathway_tpu as pw
        from pathway_tpu.internals import parse_graph as pg
        from pathway_tpu.models import lfm2
        from pathway_tpu.models.encoder import EncoderConfig
        from pathway_tpu.xpacks.llm.document_store import DocumentStore
        from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
        from pathway_tpu.xpacks.llm.llms import Lfm2Chat
        from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer
        from pathway_tpu.xpacks.llm.servers import QARestServer

        self.cfg, self.seed, self.port, self.docs, self.log = cfg, seed, port, docs, log
        self.timings: Dict[str, float] = {}
        enc_model, serving = cfg["encoder"], cfg["serving"]
        t0 = time.monotonic()
        pg.G.clear()
        published = EncoderConfig()
        enc_cfg = EncoderConfig(
            vocab_size=enc_model["vocab_size"], hidden_size=enc_model["hidden_size"],
            num_layers=enc_model["num_hidden_layers"], num_heads=enc_model["num_attention_heads"],
            intermediate_size=enc_model["intermediate_size"], max_position=enc_model["max_position_embeddings"],
            type_vocab_size=enc_model["type_vocab_size"], layer_norm_eps=enc_model["layer_norm_eps"],
        )
        self.embedder = SentenceTransformerEmbedder(encoder_config=None if enc_cfg == published else enc_cfg)
        enc = self.embedder.encoder
        assert enc.weights_source == "random-init" and enc.tokenizer_source == "hash", (
            enc.weights_source, enc.tokenizer_source)
        self.timings["embedder_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        self.enc_weights, tree = weights_mod.make_weights(seed, enc_model, cfg["assumed"]["encoder_weights_init"])
        same = jax.tree.map(lambda a, b: a.shape == b.shape and a.dtype == b.dtype, tree, enc.params)
        assert all(jax.tree.leaves(same)), "seeded encoder weights differ from the program's tree"
        enc.params = tree
        self.lm_cfg = lm_config(cfg)
        self.lm_params = lfm2_weights.make_params(seed, self.lm_cfg, cfg["assumed"]["weights_init"],
                                                  serving["weights_dtype"])
        want = lfm2.param_shapes(lfm2.Lfm2Config.from_dict(self.lm_cfg), serving["weights_dtype"])
        same = jax.tree.map(lambda a, b: a.shape == b.shape and a.dtype == b.dtype, self.lm_params, want)
        assert all(jax.tree.leaves(same)), "seeded generator weights differ from the program's tree"
        jax.block_until_ready(self.lm_params)
        self.timings["weights_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        # the plain reference's embeddings of the live passages: the comparison reads them
        self.doc_vecs = reference.embed_texts(self.enc_weights, docs, enc_model)
        self.doc_vecs.block_until_ready()
        self.timings["reference_docs_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        self.chat = Lfm2Chat(
            self.lm_cfg, self.lm_params, slots=serving["slots"], max_prompt_tokens=serving["max_prompt_tokens"],
            max_new_tokens=serving["max_new_tokens"], prefill_buckets=tuple(serving["prefill_buckets"]),
        )
        assert self.chat.decoder.weights_source == "given"
        n_params = sum(int(a.size) for a in jax.tree.leaves(self.lm_params))
        # every program the service can call, before anything is submitted: no other thread drives the decoder yet
        self.chat.decoder.warm()
        stats = jax.devices()[0].memory_stats() or {}
        self.timings["lm_compile_s"] = time.monotonic() - t0
        log(f"generator: {len(self.lm_cfg['layer_types'])} layers, {n_params / 1e6:.1f}M parameters, "
            f"{serving['slots']} slots of {self.chat.decoder.max_len} positions, prefill buckets "
            f"{serving['prefill_buckets']}; {self.chat.decoder.compiled_programs()} programs compiled in "
            f"{self.timings['lm_compile_s']:.1f} s; device bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")

        doc_table = pw.debug.table_from_rows(
            pw.schema_builder({"data": str, "_metadata": str}),
            [(text, json.dumps({"path": f"doc{i}"})) for i, text in enumerate(docs)],
        )
        store = DocumentStore(doc_table, retriever_factory=index_factory(cfg, self.embedder))
        self.qa = BaseRAGQuestionAnswerer(llm=self.chat, indexer=store, search_topk=serving["search_topk"])
        self.thread = QARestServer("127.0.0.1", port, self.qa).run(threaded=True)

    def wait_ready(self) -> None:
        """Until ``/v1/statistics`` counts every live passage and the encoder's pre-warm is done."""
        t0 = time.monotonic()
        while True:
            if not self.thread.is_alive():
                raise RuntimeError("the server thread died before the corpus was indexed")
            if time.monotonic() - t0 > READY_DEADLINE_S:
                raise TimeoutError(f"corpus not indexed within {READY_DEADLINE_S:.0f} s")
            try:
                stats = post(self.port, "/v1/statistics", {}, timeout=30.0)
            except OSError:
                stats = None  # not listening yet, or busy inside the ingest commit
            if stats is not None and int(stats.get("file_count", 0)) == len(self.docs):
                break
            time.sleep(0.25)
        self.timings["ready_s"] = time.monotonic() - t0
        svc = self.embedder.pipeline.service
        assert svc is not None and svc.wait_warm(timeout_s=READY_DEADLINE_S), "encoder pre-warm did not finish"
        self.timings["prewarm_s"] = float(svc.prewarm_s)
        self.log(f"ready: file_count={len(self.docs)}; pre-warm {svc.prewarm_compiles} buckets in "
                 f"{svc.prewarm_s:.1f} s (set-up: {self.timings['ready_s']:.1f} s to ready)")

    def warm_up(self, traffic: Dict[str, Any]) -> None:
        """Bursts over the traffic's own route, so that REST, the commit, the
        search program's query buckets and the reply have run (the language
        model's programs are compiled in set-up already). The last burst's
        replies are kept for ``judge``."""
        t0 = time.monotonic()
        request = traffic["request"]
        n = 0
        for burst in (1, 4, 16, 32):
            reqs = [{"i": j, "phase": "warm", "due": 0.0, **request["fixed"],
                     "query": f"{self.docs[(n + j) % len(self.docs)].split(' ', 1)[1][:40]} warm{n + j}"}
                    for j in range(burst)]
            n += burst
            recs = asyncio.run(loadgen.drive(reqs, "127.0.0.1", self.port, request, time.monotonic(), 300.0))
            for r in recs:
                r["answer"] = parse_reply(r["body"])
            bad = [r for r in recs if not good(r["answer"], traffic)]
            assert not bad, f"warm-up burst of {burst}: {bad[0]}"
        # the last burst asks for twice the slots at once: every slot live in most of its steps, each
        # freed and filled again. ``judge`` holds its replies to the reference too, since the
        # window's own requests seldom share a step with more than a few others
        self.burst = recs
        self.timings["warm_http_s"] = time.monotonic() - t0

    def counters(self) -> Dict[str, float]:
        """The program's own counts, read before and after the window."""
        from pathway_tpu.ops.knn import kernel_cache_sizes

        out = {f"kernel.{k}": float(v) for k, v in kernel_cache_sizes().items()}
        for stats in (self.embedder.pipeline.stats(), self.chat.service.stats()):
            for name, value in stats.items():
                if isinstance(value, (int, float)) and not isinstance(value, bool):
                    out[name] = float(value)
        return out


def prompt_ids(cfg: Dict[str, Any], question: str, answer: Dict[str, Any]) -> List[int]:
    """The prompt a reply was generated from, rebuilt from its own context and
    question: the deployment's template over the passages' texts, one token a
    word, the last ``max_prompt_tokens`` of them."""
    text = lfm2_reference.prompt_qa(question, [entry[1] for entry in answer["context"]])
    return lfm2_reference.tokenize(text, cfg["vocab_size"])[-int(cfg["serving"]["max_prompt_tokens"]):]


def retrieval_numbers(spec: Dict[str, Any], system: System, sample: List[dict],
                      contexts: List[compare.Answer]) -> Dict[str, float]:
    """The retrieval stage, judged as ``serve-dense-2m`` judges a ``/v1/retrieve``
    reply: each context against the plain reference's exact cosine top-k over
    the live passages (``compare.compare``, its numbers under its names)."""
    k = int(spec["traffic"]["reply"]["context_docs"])
    query_vecs = reference.embed_texts(system.enc_weights, [r["query"] for r in sample], spec["config"]["encoder"])
    ref_topk, _ = reference.exact_topk(query_vecs, [lambda: (system.doc_vecs, 0)], k)
    return compare.compare(contexts, k, system.docs, reference.cosine_to(query_vecs, system.doc_vecs), ref_topk)


def generator_gaps(spec: Dict[str, Any], system: System, what: str, records: List[dict], controls=()):
    """(the program's gaps, {control: its gaps}) over ``records`` (each a
    request's ``query`` and its readable ``answer``): the plain reference over
    each reply's rebuilt prompt and its served tokens (teacher forcing), and at
    each of the served positions how far the served token's logit lies under
    the reference's largest, in units of that position's logit spread. A
    control's tokens are that variant of the reference's own greedy choice at
    every position, given the served tokens before it (one pass a control, not
    one a token), judged like served tokens; a control is read on the first
    ``control_sample`` records."""
    cfg = spec["config"]
    lm_cfg, few = lm_config(cfg), int(spec["traffic"]["control_sample"])
    prompts = [prompt_ids(cfg, r["query"], r["answer"]) for r in records]
    served = [r["answer"]["ids"] for r in records]
    t0 = time.monotonic()
    rows, chosen = lfm2_reference.hidden_rows(system.lm_params, lm_cfg, prompts, served)
    read = lfm2_reference.read_head(system.lm_params, lm_cfg, rows, served)
    gaps = lfm2_reference.logit_gaps(read)
    lengths = [len(p) for p in prompts]
    system.log(f"generator reference over {len(records)} replies of {what}, {min(lengths)}-{max(lengths)} prompt "
               f"tokens + {len(served[0])}: {time.monotonic() - t0:.1f} s; served token is the reference's own in "
               f"{100.0 * float(np.mean(read['argmax'] == np.asarray(served))):.2f} % of {gaps.size} positions; "
               f"logit spread {float(read['spread'].min()):.3f}-{float(read['spread'].max()):.3f}; distinct experts "
               f"a position and layer {np.mean([len(set(c.ravel().tolist())) for c in chosen]):.1f} over them")
    control_gaps = {}
    for name in controls:
        t0 = time.monotonic()
        low_rows, _ = lfm2_reference.hidden_rows(system.lm_params, lm_cfg, prompts[:few], served[:few], variant=name)
        own = lfm2_reference.read_head(system.lm_params, lm_cfg, low_rows, served[:few], variant=name)["argmax"]
        low = lfm2_reference.logit_gaps(lfm2_reference.read_head(system.lm_params, lm_cfg, rows[:few], own))
        control_gaps[name] = low
        system.log(f"control {name} over {min(few, len(records))} replies of {what}: {time.monotonic() - t0:.1f} s; "
                   f"its token is the reference's own in {100.0 * float(np.mean(low == 0.0)):.2f} % of {low.size} "
                   f"positions")
    return gaps, control_gaps


def judge(spec: Dict[str, Any], system: System, sample: List[dict], controls=()):
    """(the program's numbers, {control: its numbers}).

    (a) the retrieval stage, ``retrieval_numbers`` (``bad_replies`` also counts a
    reply whose ids cannot be read or lie outside the vocabulary); (b) the
    generator, ``generator_gaps``: ``logit_gap_max`` and ``logit_gap_mean`` over
    all positions of all sampled replies of the window, and
    ``burst_logit_gap_max`` and ``burst_logit_gap_mean`` over the warm-up's last
    burst, where every slot held a request at once."""
    traffic, vocab = spec["traffic"], int(spec["config"]["vocab_size"])

    def readable(records: List[dict]) -> List[Optional[dict]]:
        return [r if good(r["answer"], traffic) and all(0 <= t < vocab for t in r["answer"]["ids"]) else None
                for r in records]

    window, burst = readable(sample), readable(system.burst)
    # a reply that cannot be read is bad once: compare counts a sampled one by its missing context
    numbers = retrieval_numbers(spec, system, sample, [r["answer"]["context"] if r else None for r in window])
    numbers["bad_replies"] += sum(r is None for r in burst)
    control_numbers: Dict[str, Dict[str, float]] = {name: {} for name in controls}
    for prefix, what, records in (("logit_gap", "the window's sample", window),
                                  ("burst_logit_gap", "the warm-up's last burst", burst)):
        kept = [r for r in records if r is not None]
        if not kept:
            numbers[prefix + "_max"] = numbers[prefix + "_mean"] = float("inf")
            continue
        gaps, control_gaps = generator_gaps(spec, system, what, kept, controls)
        numbers[prefix + "_max"], numbers[prefix + "_mean"] = float(gaps.max()), float(gaps.mean())
        for name, low in control_gaps.items():
            control_numbers[name].update({prefix + "_max": float(low.max()), prefix + "_mean": float(low.mean())})
    return numbers, {name: dict(numbers, **own) for name, own in control_numbers.items()}


def program_time(ctx: Dict[str, Any], pattern: str):
    """(device seconds, calls) of one of the language model's programs inside the traced span; None where the trace shows none."""
    if ctx.get("trace") is None:
        return None
    seconds, calls = trace_reduce.program_seconds(ctx["trace"], [pattern])
    return (seconds, calls) if calls > 0 and seconds > 0 else None


def reply_tokens(record: Dict[str, Any]) -> Optional[int]:
    """Prompt tokens of one answered request of the generator's log, from its own context and question."""
    answer = record.get("answer")
    if answer is None:
        return None
    return len(lfm2_reference.prompt_qa(record["query"], [entry[1] or "" for entry in answer["context"]]).split())


def metric_context(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What this system's metric readers need beyond the common context."""
    return {"lm_config": lm_config(cfg), "lm_serving": cfg["serving"], "lm_program_time": program_time,
            "lm_reply_tokens": reply_tokens, "live_rows": int(cfg["corpus"]["live_docs"])}
