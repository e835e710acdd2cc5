"""The ``mistral4`` generator's weights, made on the device from ``--seed``.

An input of a run, made by the benchmark and handed to the program and to the
plain reference alike (``lfm2_weights.py`` does the same for the other
generator). The tree is the one the program serves from
(``pathway_tpu/models/mistral4.py:param_shapes``, which the system module
checks it against): ``embed``, ``final_norm``, ``lm_head`` and ``layers``, one
dict a layer, holding this chip's share: ``n_routed_experts`` experts a layer
and ``vocab_size`` rows of the table and of the head. Matrices in the served
type (bfloat16), norms and the router float32. One jitted draw per array, so
that no more than one array's float32 draw (1.07 GB for a layer's stack of 32
experts) lives beside the weights.

The init (the configuration's ``assumed.weights_init`` says why): every matrix
normal at ``1/sqrt(fan in)``, so that each operator keeps its input's scale and
the layers' outputs, not the token's own embedding, decide the logits; the
table normal at ``embed_std``; norm weights ``1 + norm_jitter * normal``, so
that leaving one out shows; the router normal at ``router_std / sqrt(hidden)``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

import weights as weights_mod
from lfm2_weights import _draw


def make_params(seed: int, cfg: Dict[str, Any], init: Dict[str, Any], dtype: str = "bfloat16") -> Dict[str, Any]:
    """``cfg``: the published ``config.json`` keys as the configuration's file states
    them, with the share held here; ``dtype``: the matrices' type as its ``serving`` group states it."""
    h, heads, f = cfg["hidden_size"], cfg["num_attention_heads"], cfg["moe_intermediate_size"]
    e, fs = cfg["n_routed_experts"], cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    root, count = weights_mod.seed_key(seed, 3), iter(range(1 << 20))

    def draw(shape, dtype, std, mean=0.0):
        return _draw(jax.random.fold_in(root, next(count)), shape=shape, dtype=dtype, std=float(std), mean=mean)

    def mat(*shape):  # the axis before the last is the one summed over
        return draw(shape, jnp.dtype(dtype), shape[-2] ** -0.5)

    def norm(n):
        return draw((n,), jnp.float32, init["norm_jitter"], 1.0)

    layers = []
    for _ in range(cfg["num_hidden_layers"]):
        layers.append({
            "attn_norm": norm(h), "ffn_norm": norm(h),
            "wdq": mat(h, cfg["q_lora_rank"]), "q_norm": norm(cfg["q_lora_rank"]),
            "wuq": mat(cfg["q_lora_rank"], heads * (nope + rope)),
            "wdkv": mat(h, cfg["kv_lora_rank"] + rope), "kv_norm": norm(cfg["kv_lora_rank"]),
            "wukv": mat(cfg["kv_lora_rank"], heads * (nope + vd)),
            "wo": mat(heads * vd, h),
            "gate": draw((h, cfg["n_router_experts"]), jnp.float32, init["router_std"] * h ** -0.5),
            "shared_w1": mat(h, fs), "shared_w3": mat(h, fs), "shared_w2": mat(fs, h),
            "w1": mat(e, h, f), "w3": mat(e, h, f), "w2": mat(e, f, h),
        })
    return {"embed": draw((cfg["vocab_size"], h), jnp.dtype(dtype), init["embed_std"]), "final_norm": norm(h),
            "lm_head": mat(h, cfg["vocab_size"]), "layers": layers}
