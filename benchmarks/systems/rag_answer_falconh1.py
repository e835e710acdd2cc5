"""The system under test ``rag_answer_falconh1``: ``systems/rag_answer.py``'s
deployment (``QARestServer`` over ``BaseRAGQuestionAnswerer`` in a thread of
this process, the MiniLM encoder and the dense index beside the generator) with
``FalconH1Chat`` as the generator: the first six blocks of the ``falcon_h1``
decoder (a Mamba-2 state-space mixer and grouped-query attention side by side
in every block, the mixer's recurrent state kept in the slot) behind the same
generation service. A reply to ``POST /v2/answer`` is "the question's exact
cosine top-6 of the live passages, then 128 greedy tokens of the six blocks
over the prompt built from them".

What holds of ``systems/rag_answer.py`` as it stands is imported from it: how a
reply is read (``parse_reply``, ``good``), the retrieval stage's comparison, the
prompt rebuilt from a reply, a program's time in the trace, and its
``System``'s ``wait_ready`` and ``counters``. What names the other generator is
this file's own: the set-up, the published keys, the plain reference
(``falcon_h1_reference.py`` over the inputs of ``falcon_h1_weights.py``), the
controls; and the warm-up, whose last burst asks for twice this configuration's
32 slots.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import falcon_h1_reference
import falcon_h1_weights
import loadgen
import reference
import weights as weights_mod
from systems import rag_answer
from systems.rag_answer import (  # noqa: F401 - run.py asks the module for parse_reply and good
    good, parse_reply, program_time, prompt_ids, reply_tokens, retrieval_numbers,
)
from systems.vector_store import index_factory

PUBLISHED_KEYS = (
    "attention_bias", "attention_in_multiplier", "attention_out_multiplier", "attn_layer_indices",
    "embedding_multiplier", "head_dim", "hidden_size", "intermediate_size", "key_multiplier", "lm_head_multiplier",
    "mamba_chunk_size", "mamba_conv_bias", "mamba_d_conv", "mamba_d_head", "mamba_d_ssm", "mamba_d_state",
    "mamba_n_groups", "mamba_n_heads", "mamba_norm_before_gate", "mamba_proj_bias", "mamba_rms_norm", "mlp_bias",
    "mlp_multipliers", "num_attention_heads", "num_hidden_layers", "num_key_value_heads", "projectors_bias",
    "rms_norm_eps", "rope_scaling", "rope_theta", "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
    "tie_word_embeddings", "vocab_size",
)
# what --calibrate puts in the program's place: the reference's own greedy choice at every
# position, computed this way (``falcon_h1_reference.VARIANTS``)
CONTROLS = ("fp8_matmul", "no_attention_branch", "no_ssm_multipliers", "state_one_token_behind")
COMPILE_COUNTERS = rag_answer.COMPILE_COUNTERS


def lm_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published ``config.json`` keys of the configuration's file that shape the model."""
    return {k: cfg[k] for k in PUBLISHED_KEYS}


class System(rag_answer.System):
    """The running server and the handles the harness reads."""

    def __init__(self, cfg: Dict[str, Any], seed: int, port: int, docs: List[str],
                 log: Callable[[str], None]):
        # the generator first: a program without it ends here, before any set-up
        from pathway_tpu.models import falcon_h1
        from pathway_tpu.xpacks.llm.llms import FalconH1Chat

        import jax

        import pathway_tpu as pw
        from pathway_tpu.internals import parse_graph as pg
        from pathway_tpu.models.encoder import EncoderConfig
        from pathway_tpu.xpacks.llm.document_store import DocumentStore
        from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
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
        self.lm_params = falcon_h1_weights.make_params(seed, self.lm_cfg, cfg["assumed"]["weights_init"],
                                                       serving["weights_dtype"])
        want = falcon_h1.param_shapes(falcon_h1.FalconH1Config.from_dict(self.lm_cfg), serving["weights_dtype"])
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
        self.chat = FalconH1Chat(
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
        state_bytes = sum(int(a.size) * a.dtype.itemsize for a in jax.tree.leaves(self.chat.decoder.state))
        log(f"generator: {model.num_hidden_layers} blocks, each {model.mamba_n_heads} state-space heads of "
            f"{model.mamba_d_head} x {model.mamba_d_state} beside {model.num_attention_heads} / "
            f"{model.num_key_value_heads} attention heads, {model.vocab_size} vocabulary rows, "
            f"{n_params / 1e6:.1f}M parameters, {serving['slots']} slots of {self.chat.decoder.max_len} positions "
            f"({state_bytes / 1e9:.2f} GB of state), prefill buckets {serving['prefill_buckets']}; "
            f"{self.chat.decoder.compiled_programs()} programs compiled in {self.timings['lm_compile_s']:.1f} s; "
            f"device bytes_in_use={stats.get('bytes_in_use')} peak_bytes_in_use={stats.get('peak_bytes_in_use')}")

        doc_table = pw.debug.table_from_rows(
            pw.schema_builder({"data": str, "_metadata": str}),
            [(text, json.dumps({"path": f"doc{i}"})) for i, text in enumerate(docs)],
        )
        store = DocumentStore(doc_table, retriever_factory=index_factory(cfg, self.embedder))
        self.qa = BaseRAGQuestionAnswerer(llm=self.chat, indexer=store, search_topk=serving["search_topk"])
        self.thread = QARestServer("127.0.0.1", port, self.qa).run(threaded=True)

    def warm_up(self, traffic: Dict[str, Any]) -> None:
        """``rag_answer.System.warm_up`` with a last burst of twice this
        configuration's slots (64, where that one asks for 32): every slot live
        in most of its steps, each freed and filled again. The last burst's
        replies are kept for ``judge``."""
        t0 = time.monotonic()
        request = traffic["request"]
        n = 0
        for burst in (1, 4, 16, 2 * int(self.cfg["serving"]["slots"])):
            reqs = [{"i": j, "phase": "warm", "due": 0.0, **request["fixed"],
                     "query": f"{self.docs[(n + j) % len(self.docs)].split(' ', 1)[1][:40]} warm{n + j}"}
                    for j in range(burst)]
            n += burst
            recs = asyncio.run(loadgen.drive(reqs, "127.0.0.1", self.port, request, time.monotonic(), 300.0))
            for r in recs:
                r["answer"] = parse_reply(r["body"])
            bad = [r for r in recs if not good(r["answer"], traffic)]
            assert not bad, f"warm-up burst of {burst}: {bad[0]}"
        self.burst = recs
        self.timings["warm_http_s"] = time.monotonic() - t0


def generator_gaps(spec: Dict[str, Any], system: System, what: str, records: List[dict], controls=()):
    """(the program's gaps, {control: its gaps}) over ``records``, as
    ``rag_answer.generator_gaps`` reads them, through this generator's plain
    reference: its full forward pass (the recurrence over tokens, no state kept)
    over each reply's rebuilt prompt and its served tokens, which the program
    produced by a prefill and then decoding through the slot's state; at each
    served position how far the served token's logit lies under the reference's
    largest, in units of that position's logit spread. Logits are compared, not
    tokens. A control's tokens are that variant of the reference's own greedy
    choice at every position, judged like served tokens, on the first
    ``control_sample`` records."""
    cfg = spec["config"]
    lm_cfg, few = lm_config(cfg), int(spec["traffic"]["control_sample"])
    prompts = [prompt_ids(cfg, r["query"], r["answer"]) for r in records]
    served = [r["answer"]["ids"] for r in records]
    t0 = time.monotonic()
    rows = falcon_h1_reference.hidden_rows(system.lm_params, lm_cfg, prompts, served)
    read = falcon_h1_reference.read_head(system.lm_params, lm_cfg, rows, served)
    gaps = falcon_h1_reference.logit_gaps(read)
    lengths = [len(p) for p in prompts]
    system.log(f"generator reference over {len(records)} replies of {what}, {min(lengths)}-{max(lengths)} prompt "
               f"tokens + {len(served[0])}: {time.monotonic() - t0:.1f} s; served token is the reference's own in "
               f"{100.0 * float(np.mean(read['argmax'] == np.asarray(served))):.2f} % of {gaps.size} positions; "
               f"logit spread {float(read['spread'].min()):.3f}-{float(read['spread'].max()):.3f}")
    control_gaps = {}
    for name in controls:
        t0 = time.monotonic()
        low_rows = falcon_h1_reference.hidden_rows(system.lm_params, lm_cfg, prompts[:few], served[:few], variant=name)
        own = falcon_h1_reference.read_head(system.lm_params, lm_cfg, low_rows, served[:few], variant=name)["argmax"]
        low = falcon_h1_reference.logit_gaps(falcon_h1_reference.read_head(system.lm_params, lm_cfg, rows[:few], own))
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
    held a request at once and each was filled twice."""
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
