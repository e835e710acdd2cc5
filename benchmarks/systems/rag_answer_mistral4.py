"""The system under test ``rag_answer_mistral4``: ``systems/rag_answer.py``'s
deployment (``QARestServer`` over ``BaseRAGQuestionAnswerer`` in a thread of
this process, the MiniLM encoder and the dense index beside the generator) with
``Mistral4Chat`` as the generator: one chip's share of the ``mistral4`` decoder
(latent attention over a compressed cache, a shared expert beside the held
routed experts, a slice of the vocabulary) behind the same generation service.
A reply to ``POST /v2/answer`` is "the question's exact cosine top-24 of the
live passages, then 64 greedy tokens of the held share over the prompt built
from them".

What holds of ``systems/rag_answer.py`` as it stands is imported from it: how a
reply is read (``parse_reply``, ``good``), the retrieval stage's comparison, the
prompt rebuilt from a reply, a program's time in the trace, and its
``System``'s ``wait_ready``, ``warm_up`` and ``counters``. What names the other
generator is this file's own: the set-up, the published keys, the plain
reference (``mistral4_reference.py`` over the inputs of ``mistral4_weights.py``),
the controls.
"""

from __future__ import annotations

import json
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import mistral4_reference
import mistral4_weights
import reference
import weights as weights_mod
from systems import rag_answer
from systems.rag_answer import (  # noqa: F401 - run.py asks the module for parse_reply and good
    good, parse_reply, program_time, prompt_ids, reply_tokens, retrieval_numbers,
)
from systems.vector_store import index_factory

PUBLISHED_KEYS = (
    "hidden_size", "kv_lora_rank", "moe_intermediate_size", "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "rms_norm_eps", "rope_parameters", "routed_scaling_factor", "v_head_dim", "vocab_size",
)
SHARE_KEYS = ("n_router_experts", "first_expert")  # this repository's: the router's width, the first held expert
# what --calibrate puts in the program's place: the reference's own greedy choice at every
# position, computed this way (``mistral4_reference.VARIANTS``)
CONTROLS = ("fp8_matmul", "top3_experts", "no_shared_expert", "rope_rotate_half")
COMPILE_COUNTERS = rag_answer.COMPILE_COUNTERS


def lm_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published ``config.json`` keys of the configuration's file that shape
    the model, with the share of it held here."""
    return {k: cfg[k] for k in PUBLISHED_KEYS + SHARE_KEYS}


def reference_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``lm_config`` with ``rope_parameters``' keys beside the others, as the reference reads them."""
    lm_cfg = lm_config(cfg)
    return dict(lm_cfg, **lm_cfg["rope_parameters"])


class System(rag_answer.System):
    """The running server and the handles the harness reads."""

    def __init__(self, cfg: Dict[str, Any], seed: int, port: int, docs: List[str],
                 log: Callable[[str], None]):
        import jax

        import pathway_tpu as pw
        from pathway_tpu.internals import parse_graph as pg
        from pathway_tpu.models import mistral4
        from pathway_tpu.models.encoder import EncoderConfig
        from pathway_tpu.xpacks.llm.document_store import DocumentStore
        from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
        from pathway_tpu.xpacks.llm.llms import Mistral4Chat
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
        self.lm_params = mistral4_weights.make_params(seed, self.lm_cfg, cfg["assumed"]["weights_init"],
                                                      serving["weights_dtype"])
        want = mistral4.param_shapes(mistral4.Mistral4Config.from_dict(self.lm_cfg), serving["weights_dtype"])
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
        self.chat = Mistral4Chat(
            self.lm_cfg, self.lm_params, slots=serving["slots"], max_prompt_tokens=serving["max_prompt_tokens"],
            max_new_tokens=serving["max_new_tokens"], prefill_buckets=tuple(serving["prefill_buckets"]),
        )
        assert self.chat.decoder.weights_source == "given"
        n_params = sum(int(a.size) for a in jax.tree.leaves(self.lm_params))
        # every program the service can call, before anything is submitted: no other thread drives the decoder yet
        self.chat.decoder.warm()
        stats = jax.devices()[0].memory_stats() or {}
        self.timings["lm_compile_s"] = time.monotonic() - t0
        model = self.chat.config
        log(f"generator: {model.num_hidden_layers} layers, experts {model.first_expert}-"
            f"{model.first_expert + model.n_routed_experts - 1} of a router's {model.router_width}, "
            f"{model.vocab_size} vocabulary rows, {n_params / 1e6:.1f}M parameters, "
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


def generator_gaps(spec: Dict[str, Any], system: System, what: str, records: List[dict], controls=()):
    """(the program's gaps, {control: its gaps}) over ``records``, as
    ``rag_answer.generator_gaps`` reads them, through this generator's plain
    reference: teacher forcing over each reply's rebuilt prompt and its served
    tokens, and at each served position how far the served token's logit lies
    under the reference's largest over the held slice, in units of that
    position's logit spread. A control's tokens are that variant of the
    reference's own greedy choice at every position, judged like served tokens,
    on the first ``control_sample`` records."""
    cfg = spec["config"]
    ref_cfg, few = reference_config(cfg), int(spec["traffic"]["control_sample"])
    prompts = [prompt_ids(cfg, r["query"], r["answer"]) for r in records]
    served = [r["answer"]["ids"] for r in records]
    t0 = time.monotonic()
    rows, chosen = mistral4_reference.hidden_rows(system.lm_params, ref_cfg, prompts, served)
    read = mistral4_reference.read_head(system.lm_params, ref_cfg, rows, served)
    gaps = mistral4_reference.logit_gaps(read)
    lengths = [len(p) for p in prompts]
    lo, hi = ref_cfg["first_expert"], ref_cfg["first_expert"] + ref_cfg["n_routed_experts"]
    held = float(np.mean([(c >= lo) & (c < hi) for c in chosen])) * ref_cfg["num_experts_per_tok"]
    system.log(f"generator reference over {len(records)} replies of {what}, {min(lengths)}-{max(lengths)} prompt "
               f"tokens + {len(served[0])}: {time.monotonic() - t0:.1f} s; served token is the reference's own in "
               f"{100.0 * float(np.mean(read['argmax'] == np.asarray(served))):.2f} % of {gaps.size} positions; "
               f"logit spread {float(read['spread'].min()):.3f}-{float(read['spread'].max()):.3f}; routed pairs "
               f"held a position and layer {held:.2f} of {ref_cfg['num_experts_per_tok']}")
    control_gaps = {}
    for name in controls:
        t0 = time.monotonic()
        low_rows, _ = mistral4_reference.hidden_rows(system.lm_params, ref_cfg, prompts[:few], served[:few], variant=name)
        own = mistral4_reference.read_head(system.lm_params, ref_cfg, low_rows, served[:few], variant=name)["argmax"]
        low = mistral4_reference.logit_gaps(mistral4_reference.read_head(system.lm_params, ref_cfg, rows[:few], own))
        control_gaps[name] = low
        system.log(f"control {name} over {min(few, len(records))} replies of {what}: {time.monotonic() - t0:.1f} s; "
                   f"its token is the reference's own in {100.0 * float(np.mean(low == 0.0)):.2f} % of {low.size} "
                   f"positions")
    return gaps, control_gaps


def judge(spec: Dict[str, Any], system: System, sample: List[dict], controls=()):
    """(the program's numbers, {control: its numbers}), as ``rag_answer.judge``:
    (a) the retrieval stage, ``retrieval_numbers``; (b) the generator,
    ``generator_gaps``: ``logit_gap_max`` and ``logit_gap_mean`` over all
    positions of all sampled replies of the window, and ``burst_logit_gap_max``
    and ``burst_logit_gap_mean`` over the warm-up's last burst, where every slot
    held a request at once."""
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


def metric_context(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What this system's metric readers need beyond the common context."""
    return {"lm_config": lm_config(cfg), "lm_serving": cfg["serving"], "lm_program_time": program_time,
            "lm_reply_tokens": reply_tokens, "live_rows": int(cfg["corpus"]["live_docs"])}
