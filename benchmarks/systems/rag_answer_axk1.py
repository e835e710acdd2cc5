"""The system under test ``rag_answer_axk1``: ``systems/rag_answer_mistral4.py``'s
deployment (``QARestServer`` over ``BaseRAGQuestionAnswerer`` in a thread of
this process, the MiniLM encoder and the dense index beside the generator,
``Mistral4Chat`` behind the generation service) with one chip's share of A.X-K1
as the generator: the latent-attention decoder that serves Mistral-Small-4,
here reading A.X-K1's keys (a dense first layer, a sigmoid router limited to its
best groups, query and key heads larger than the values). A reply to ``POST
/v2/answer`` is "the question's exact cosine top-24 of the live passages, then
128 greedy tokens of the held share over the prompt built from them".

What holds of ``systems/rag_answer_mistral4.py`` as it stands is taken from it
whole: the set-up (``System``), the judgement (``judge``, ``generator_gaps``)
and how a reply is read. Its module is loaded afresh, under a name of its own,
with the names that say which generator it is replaced: the published keys
(``lm_config``, ``reference_config``), the weights (``axk1_weights.py``) and
the plain reference (``axk1_reference.py``). The rest is this file's: the keys,
the controls, and a first check that the program's decoder reads A.X-K1's keys
at all (one that does not ends there, before any set-up).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
from typing import Any, Callable, Dict, List

import axk1_reference
import axk1_weights
from systems import rag_answer
from systems.rag_answer import good, parse_reply, program_time, reply_tokens  # noqa: F401 - run.py asks for them

PUBLISHED_KEYS = (
    "first_k_dense_replace", "hidden_size", "intermediate_size", "kv_lora_rank", "moe_intermediate_size", "n_group",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_scaling",
    "rope_theta", "routed_scaling_factor", "scoring_func", "topk_group", "topk_method", "v_head_dim", "vocab_size",
)
SHARE_KEYS = ("n_router_experts", "first_expert")  # this repository's: the router's width, the first held expert
# what --calibrate puts in the program's place: the reference's own greedy choice at every
# position, computed this way (``axk1_reference.VARIANTS``)
CONTROLS = ("fp8_matmul", "top7_experts", "no_shared_expert", "rope_rotate_half", "no_group_limit",
            "no_routed_scaling")
COMPILE_COUNTERS = rag_answer.COMPILE_COUNTERS


def lm_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published ``config.json`` keys of the configuration's file that shape
    the model, with the share of it held here."""
    return {k: cfg[k] for k in PUBLISHED_KEYS + SHARE_KEYS}


def reference_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``lm_config`` with ``rope_scaling``'s keys beside the others, as the reference reads them."""
    lm_cfg = lm_config(cfg)
    return dict(lm_cfg, **lm_cfg["rope_scaling"])


def _mistral4_system() -> Any:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "rag_answer_mistral4.py")
    spec = importlib.util.spec_from_file_location("bench_systems_rag_answer_mistral4_as_axk1", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.lm_config, module.reference_config = lm_config, reference_config
    module.mistral4_weights, module.mistral4_reference = axk1_weights, axk1_reference
    return module


_base = _mistral4_system()
generator_gaps, judge = _base.generator_gaps, _base.judge


class System(_base.System):
    """The running server and the handles the harness reads."""

    def __init__(self, cfg: Dict[str, Any], seed: int, port: int, docs: List[str],
                 log: Callable[[str], None]):
        from pathway_tpu.models import mistral4

        read = {f.name for f in dataclasses.fields(mistral4.Mistral4Config)}
        missing = sorted(set(PUBLISHED_KEYS) - read - {"rope_scaling"})
        if missing:
            raise SystemExit(f"rag_answer_axk1: the program's latent-attention decoder reads no {missing}; no result")
        super().__init__(cfg, seed, port, docs, log)


def metric_context(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What this system's metric readers need beyond the common context."""
    return {"lm_config": lm_config(cfg), "lm_serving": cfg["serving"], "lm_program_time": program_time,
            "lm_reply_tokens": reply_tokens, "live_rows": int(cfg["corpus"]["live_docs"])}
