"""The plain reference of the ``lfm2_moe`` decoder (LFM2-8B-A1B's family).

The whole forward pass of one token sequence in straightforward ``jax.numpy``:
float32 under ``default_matmul_precision("highest")``, no cache, no batching, no
kernels, every expert's product an einsum over all experts. It follows
``transformers``' ``lfm2_moe`` as the repository's issue 28 wrote it down; the
device program (``models/lfm2.py``) is held to it by ``tests/test_lfm2.py``.

    block:  x = x + operator(rmsnorm(x));  x = x + ffn(rmsnorm(x))
    conv:   B, C, u = split(in_proj(x), 3);  v = B * u;
            y[t] = sum_j w[:, j] * v[t - (L - 1) + j]   (causal, depthwise, no bias)
            out_proj(C * y)
    attn:   grouped-query, RMSNorm over the head size on every query and key
            head, then RoPE (rotate-half over the whole head), causal softmax at
            1/sqrt(head size), out_proj
    dense:  w2(silu(w1 x) * w3 x)
    moe:    s = sigmoid(gate(x)); chosen = top-k of s + expert_bias; weights =
            s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor; each
            expert a SwiGLU; no shared expert, no capacity limit
    head:   rmsnorm, then the embedding table (tied)

Departures from the published model, each also in ``PERF.md``: the ``1e-6`` in the
weights' normaliser is ``transformers``' (the issue's text has none); the head
size is ``hidden_size // num_attention_heads`` (the config's ``head_dim`` is null).

The parameters are the program's own tree (``lfm2.init_params``): ``embed``,
``final_norm`` and ``layers``, one dict a layer.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rope_tables(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """cos and sin, ``(len(positions), head_dim)``: the frequencies repeated over both halves."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def conv_operator(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """``x``: (T, hidden). The convolution sees zeros before the sequence."""
    b, c, u = jnp.split(x @ p["in_proj"], 3, axis=-1)
    v = b * u
    width = p["conv_w"].shape[1]
    padded = jnp.concatenate([jnp.zeros((width - 1, v.shape[1]), v.dtype), v], axis=0)
    y = sum(p["conv_w"][:, j] * padded[j : j + v.shape[0]] for j in range(width))
    return (c * y) @ p["out_proj"]


def attention_operator(p: Dict[str, jax.Array], x: jax.Array, cfg: Any) -> jax.Array:
    t, hd = x.shape[0], cfg.head_dim
    q = (x @ p["wq"]).reshape(t, cfg.num_attention_heads, hd)
    k = (x @ p["wk"]).reshape(t, cfg.num_key_value_heads, hd)
    v = (x @ p["wv"]).reshape(t, cfg.num_key_value_heads, hd)
    q, k = rmsnorm(q, p["q_norm"], cfg.norm_eps), rmsnorm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_tables(jnp.arange(t), hd, cfg.rope_theta)
    q = q * cos[:, None] + rotate_half(q) * sin[:, None]
    k = k * cos[:, None] + rotate_half(k) * sin[:, None]
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, -1) @ p["wo"]


def dense_ffn(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def route(p: Dict[str, jax.Array], x: jax.Array, cfg: Any) -> Tuple[jax.Array, jax.Array]:
    """(chosen experts (T, k), their weights (T, k)): chosen by the biased
    scores, weighted by the unbiased ones."""
    scores = jax.nn.sigmoid(x @ p["gate"])
    biased = scores + p["expert_bias"] if cfg.use_expert_bias else scores
    _, chosen = jax.lax.top_k(biased, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return chosen, weights * cfg.routed_scaling_factor


def moe_ffn(p: Dict[str, jax.Array], x: jax.Array, cfg: Any) -> Tuple[jax.Array, jax.Array]:
    """Every expert's SwiGLU of every token, then the chosen ones' weighted sum.
    Returns the output and the chosen experts."""
    chosen, weights = route(p, x, cfg)
    per_expert = jnp.zeros((x.shape[0], cfg.num_experts), jnp.float32)
    per_expert = per_expert.at[jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    hidden = jax.nn.silu(jnp.einsum("th,ehf->etf", x, p["w1"])) * jnp.einsum("th,ehf->etf", x, p["w3"])
    return jnp.einsum("etf,efh,te->th", hidden, p["w2"], per_expert), chosen


def forward(params: Dict[str, Any], ids: jax.Array, cfg: Any) -> Tuple[jax.Array, List[jax.Array]]:
    """Logits ``(T, vocab)`` of the sequence ``ids`` at every position, and the
    experts each expert layer chose ``[(T, k), ...]``."""
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        x = p32["embed"][ids]
        chosen_by_layer = []
        for kind, p in zip(cfg.layer_types, p32["layers"]):
            h = rmsnorm(x, p["operator_norm"], cfg.norm_eps)
            x = x + (conv_operator(p, h) if kind == "conv" else attention_operator(p, h, cfg))
            h = rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
            if "gate" in p:
                out, chosen = moe_ffn(p, h, cfg)
                chosen_by_layer.append(chosen)
            else:
                out = dense_ffn(p, h)
            x = x + out
        return rmsnorm(x, p32["final_norm"], cfg.norm_eps) @ p32["embed"].T, chosen_by_layer

