"""The ``falcon_h1`` generator's weights, made on the device from ``--seed``.

An input of a run, made by the benchmark and handed to the program and to the
plain reference alike (``lfm2_weights.py`` and ``mistral4_weights.py`` do the
same for the other generators). The tree is the one the program serves from
(``pathway_tpu/models/falcon_h1.py:param_shapes``, which the system module
checks it against): ``embed``, ``final_norm``, ``lm_head`` and ``layers``, one
dict a block. Matrices in the served type (bfloat16); norms, the convolution
and the state-space mixer's vectors float32. One jitted draw per array: the
chip's compiler fuses a draw into the write of its bfloat16 result (compiled
for the described chip, the table's 1.34 G numbers need no float32 temporary),
so nothing but the weights is on the device while they are made.

The init (the configuration's ``assumed.weights_init`` says why). **A matrix
that a multiplier follows is drawn normal at ``1 / (multiplier x sqrt(fan
in))``**, every other at ``1/sqrt(fan in)``: muP's own premise is that trained
weights are that much larger, and with every matrix at ``1/sqrt(fan in)`` the
published multipliers would shrink each branch to 0.01-0.09 of the stream,
flatten the softmax and the logits, and leave the token's own embedding to
decide every logit. So: ``in_proj``'s five column segments over
``ssm_in_multiplier x ssm_multipliers[i]``; ``out_proj`` over
``ssm_out_multiplier``; ``wq``, ``wk``, ``wv`` over ``attention_in_multiplier``,
``wk`` also over ``key_multiplier``, ``wq`` and ``wk`` times ``qk_gain`` (so that
the causal softmax is peaked, as a trained model's is); ``wo`` over
``attention_out_multiplier``; the gate ``w1`` and ``w2`` over the two
``mlp_multipliers``; ``lm_head`` over ``lm_head_multiplier``. The table is normal
at ``embed_std`` (the stream starts at ``embed_std x embedding_multiplier``).
Norm weights ``1 + norm_jitter x normal``, so that leaving one out shows. The
vectors as Mamba-2 initialises them: ``A_log = log U(1, 16)``, ``dt_bias`` the
inverse softplus of ``exp U(log dt_min, log dt_max)``, ``D = 1``; the
convolution's taps normal at ``1/sqrt(taps)`` and its bias at ``conv_bias_std``,
so that a bias left out shows.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

import weights as weights_mod
from lfm2_weights import _draw


@functools.partial(jax.jit, static_argnames=("n", "lo", "hi", "kind"))
def _draw_vector(key, *, n, lo, hi, kind):
    u = jax.random.uniform(key, (n,), jnp.float32, lo, hi)
    if kind == "log":  # A_log: the logarithm of a uniform draw
        return jnp.log(u)
    dt = jnp.exp(u)  # dt_bias: the inverse softplus of a log-uniform step
    return dt + jnp.log(-jnp.expm1(-dt))


def make_params(seed: int, cfg: Dict[str, Any], init: Dict[str, Any], dtype: str = "bfloat16") -> Dict[str, Any]:
    """``cfg``: the published ``config.json`` keys as the configuration's file states
    them; ``dtype``: the matrices' type as its ``serving`` group states it."""
    h, f, d, heads = cfg["hidden_size"], cfg["intermediate_size"], cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    gn, taps = cfg["mamba_n_groups"] * cfg["mamba_d_state"], cfg["mamba_d_conv"]
    nq, nkv = cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]
    attn_in, gain = cfg["attention_in_multiplier"], init["qk_gain"]
    root, count = weights_mod.seed_key(seed, 4), iter(range(1 << 20))

    def draw(shape, dtype, std, mean=0.0):
        return _draw(jax.random.fold_in(root, next(count)), shape=shape, dtype=dtype, std=float(std), mean=mean)

    def mat(rows, cols, multiplier=1.0):  # the first axis is the one summed over
        return draw((rows, cols), jnp.dtype(dtype), 1.0 / (multiplier * math.sqrt(rows)))

    def norm(n):
        return draw((n,), jnp.float32, init["norm_jitter"], 1.0)

    def vector(lo, hi, kind):
        return _draw_vector(jax.random.fold_in(root, next(count)), n=heads, lo=float(lo), hi=float(hi), kind=kind)

    layers = []
    for _ in range(cfg["num_hidden_layers"]):
        segments = zip((d, d, gn, gn, heads), cfg["ssm_multipliers"])
        layers.append({
            "input_norm": norm(h), "pre_ff_norm": norm(h),
            "in_proj": jnp.concatenate([mat(h, n, cfg["ssm_in_multiplier"] * m) for n, m in segments], axis=1),
            "conv_w": draw((d + 2 * gn, taps), jnp.float32, taps ** -0.5),
            "conv_b": draw((d + 2 * gn,), jnp.float32, init["conv_bias_std"]),
            "A_log": vector(init["a_min"], init["a_max"], "log"), "D": jnp.ones((heads,), jnp.float32),
            "dt_bias": vector(math.log(init["dt_min"]), math.log(init["dt_max"]), "inverse_softplus"),
            "ssm_norm": norm(d), "out_proj": mat(d, h, cfg["ssm_out_multiplier"]),
            "wq": mat(h, nq, attn_in / gain), "wk": mat(h, nkv, attn_in * cfg["key_multiplier"] / gain),
            "wv": mat(h, nkv, attn_in), "wo": mat(nq, h, cfg["attention_out_multiplier"]),
            "w1": mat(h, f, cfg["mlp_multipliers"][0]), "w3": mat(h, f), "w2": mat(f, h, cfg["mlp_multipliers"][1]),
        })
    return {"embed": draw((cfg["vocab_size"], h), jnp.dtype(dtype), init["embed_std"]), "final_norm": norm(h),
            "lm_head": mat(h, cfg["vocab_size"], cfg["lm_head_multiplier"]), "layers": layers}
