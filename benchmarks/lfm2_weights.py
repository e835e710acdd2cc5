"""The generator's weights, made on the device from ``--seed``.

An input of a run, made by the benchmark and handed to the program and to the
plain reference alike (``weights.py`` does the same for the encoder). The tree
is the one the program serves from (``pathway_tpu/models/lfm2.py:param_shapes``,
which the system module checks it against): ``embed``, ``final_norm`` and
``layers``, one dict a layer; matrices in the served type (bfloat16), norms, the
router and the experts' bias float32. One jitted draw per array, so that no more than one
array's float32 draw lives beside the weights.

The init (the configuration's ``assumed.weights_init`` says why): every matrix
normal at ``1/sqrt(fan in)``, so that each operator keeps its input's scale and
the layers' outputs, not the token's own embedding, decide the logits; the
table normal at ``embed_std``; norm weights ``1 + norm_jitter * normal``, so
that leaving one out shows; the per-head query and key norms around
``qk_norm_mean`` instead, so that the softmax is peaked and a wrong cache shows; the experts' bias normal at ``expert_bias_std``,
wide enough against the scores' spacing that choosing without it chooses
other experts.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

import weights as weights_mod


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std", "mean"))
def _draw(key, *, shape, dtype, std, mean):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_params(seed: int, cfg: Dict[str, Any], init: Dict[str, Any], dtype: str = "bfloat16") -> Dict[str, Any]:
    """``cfg``: the published ``config.json`` keys as the configuration's file states
    them; ``dtype``: the matrices' type as its ``serving`` group states it."""
    h, e = cfg["hidden_size"], cfg["num_experts"]
    hd = h // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    root, count = weights_mod.seed_key(seed, 2), iter(range(1 << 20))

    def draw(shape, dtype, std, mean=0.0):
        return _draw(jax.random.fold_in(root, next(count)), shape=shape, dtype=dtype, std=float(std), mean=mean)

    def mat(*shape):  # the axis before the last is the one summed over
        return draw(shape, jnp.dtype(dtype), shape[-2] ** -0.5)

    def norm(n, mean=1.0):
        return draw((n,), jnp.float32, init["norm_jitter"], mean)

    layers = []
    for i, kind in enumerate(cfg["layer_types"]):
        p = {"operator_norm": norm(h), "ffn_norm": norm(h)}
        if kind == "conv":
            width = cfg["conv_L_cache"]
            p.update(in_proj=mat(h, 3 * h), conv_w=draw((h, width), jnp.float32, width ** -0.5), out_proj=mat(h, h))
        else:
            p.update(wq=mat(h, nq), wk=mat(h, nkv), wv=mat(h, nkv), wo=mat(nq, h),
                     q_norm=norm(hd, init["qk_norm_mean"]), k_norm=norm(hd, init["qk_norm_mean"]))
        if i < cfg["num_dense_layers"]:
            f = cfg["intermediate_size"]
            p.update(w1=mat(h, f), w3=mat(h, f), w2=mat(f, h))
        else:
            f = cfg["moe_intermediate_size"]
            p.update(gate=draw((h, e), jnp.float32, h ** -0.5),
                     expert_bias=draw((e,), jnp.float32, init["expert_bias_std"]),
                     w1=mat(e, h, f), w3=mat(e, h, f), w2=mat(e, f, h))
        layers.append(p)
    return {"embed": draw((cfg["vocab_size"], h), jnp.dtype(dtype), init["embed_std"]), "final_norm": norm(h),
            "layers": layers}
