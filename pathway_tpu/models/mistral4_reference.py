"""The plain reference of the ``mistral4`` decoder (Mistral-Small-4-119B-2603's family).

The whole forward pass of one token sequence in straightforward ``jax.numpy``:
float32 under ``default_matmul_precision("highest")``, no cache, no batching, no
kernels, attention in the expanded form only (keys and values per head from the
latent), every held expert's product an einsum over all held experts. The
equations are the published ``config.json``'s keys read as DeepSeek-V3's code
reads them (the family the keys come from), as the repository's issue 32 wrote
them down; the device program (``models/mistral4.py``) is held to it by
``tests/test_mistral4.py``.

    block:  x = x + mla(rmsnorm(x));  x = x + moe(rmsnorm(x))
    mla:    cq = rmsnorm(x Wdq);  q = cq Wuq -> heads of [q_nope | q_rope]
            [ckv | kr] = x Wdkv;  ckv = rmsnorm(ckv);  kr is one key for all heads
            q_rope, kr = rope(q_rope), rope(kr)   interleaved pairs (x[2i], x[2i+1]), YaRN frequencies
            [k_nope | v] per head = ckv Wukv;  k = [k_nope | kr]
            scores = q k^T * s,  s = qk_head_dim^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
            causal softmax;  out = (probs v) Wo
    yarn:   f_i = theta^(-2i/d), i < d/2;  low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
            c(n) = d ln(original / (2 pi n)) / (2 ln theta);  ramp_i = clip((i - low) / (high - low), 0, 1);
            inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i;  cos/sin scaled by
            mscale's ratio to mscale_all_dim's (1 as published)
    moe:    p = softmax(x Wg) over the router's whole width;  chosen = top k;
            w = p[chosen] / sum p[chosen] * routed_scaling_factor
            out = shared(x) + sum_j w_j expert_{chosen_j}(x);  every expert and the shared one a SwiGLU
    head:   rmsnorm, then lm_head (its own matrix)

**The held share.** ``cfg.n_routed_experts`` experts from ``cfg.first_expert`` on
are held (``w1``/``w3``/``w2`` stack those); the router is ``cfg.router_width``
wide. The sum over ``j`` runs over the chosen experts that are held; the
weights stay normalised over all the chosen; what the absent experts would add
is left out. With every expert held this is the whole model's layer.

Departures from the published model and what the config is silent on, each
also in ``PERF.md``: softmax scoring with no correction bias (no
``scoring_func``/``topk_method`` key; ``n_group = topk_group = 1`` makes the
grouping a no-op); the softmax scale's ``m^2``; the shared expert's width
``n_shared_experts * moe_intermediate_size``; ``llama_4_scaling_beta`` left out
(the query scale ``1 + beta ln(1 + floor(pos / original))`` is exactly 1 below
position 8,192); the vision tower left out.

The parameters are the program's own tree (``mistral4.init_params``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def yarn_inv_freq(cfg: Any) -> jax.Array:
    """``transformers``' ``_compute_yarn_parameters`` over ``qk_rope_head_dim``."""
    dim, base, factor = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor
    original = cfg.rope_original_max_position_embeddings

    def find_correction_dim(num_rotations: float) -> float:
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    linear = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - linear
    return inv_freq_interpolation * (1 - extrapolation_factor) + inv_freq_extrapolation * extrapolation_factor


def attention_factor(cfg: Any) -> float:
    """What cos and sin are scaled by: ``get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)``."""

    def get_mscale(scale: float, mscale: float) -> float:
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    if cfg.rope_mscale and cfg.rope_mscale_all_dim:
        return get_mscale(cfg.rope_factor, cfg.rope_mscale) / get_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return get_mscale(cfg.rope_factor, 1.0)


def rope_interleaved(x: jax.Array, positions: jax.Array, cfg: Any) -> jax.Array:
    """``x`` (tokens, ..., rope size): each pair ``(x[2i], x[2i+1])`` as one
    complex number, multiplied by ``exp(i position inv_freq[i])``."""
    angles = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None, :]
    turn = attention_factor(cfg) * jnp.exp(1j * angles.astype(jnp.complex64))
    turn = turn.reshape(turn.shape[:1] + (1,) * (x.ndim - 2) + turn.shape[1:])
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    turned = (pairs[..., 0] + 1j * pairs[..., 1]) * turn
    return jnp.stack([turned.real, turned.imag], axis=-1).reshape(x.shape)


def softmax_scale(cfg: Any) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_mscale_all_dim and cfg.rope_factor > 1:
        mscale = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
        scale = scale * mscale * mscale
    return scale


def mla_attention(p: Dict[str, jax.Array], x: jax.Array, cfg: Any) -> jax.Array:
    """``x``: (T, hidden). Keys and values expanded per head from the latent."""
    t, heads, nope = x.shape[0], cfg.num_attention_heads, cfg.qk_nope_head_dim
    positions = jnp.arange(t)
    q = (rmsnorm(x @ p["wdq"], p["q_norm"], cfg.rms_norm_eps) @ p["wuq"]).reshape(t, heads, -1)
    q_nope, q_rope = q[..., :nope], rope_interleaved(q[..., nope:], positions, cfg)
    compressed = x @ p["wdkv"]
    ckv = rmsnorm(compressed[:, : cfg.kv_lora_rank], p["kv_norm"], cfg.rms_norm_eps)
    kr = rope_interleaved(compressed[:, cfg.kv_lora_rank :], positions, cfg)
    kv = (ckv @ p["wukv"]).reshape(t, heads, nope + cfg.v_head_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, None, :], (t, heads, kr.shape[-1]))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * softmax_scale(cfg)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, -1) @ p["wo"]


def route(p: Dict[str, jax.Array], x: jax.Array, cfg: Any) -> Tuple[jax.Array, jax.Array]:
    """(chosen experts (T, k) among the router's whole width, their weights (T, k))."""
    probs = jax.nn.softmax(x @ p["gate"], axis=-1)
    weights, chosen = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights * cfg.routed_scaling_factor


def shared_ffn(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["shared_w1"]) * (x @ p["shared_w3"])) @ p["shared_w2"]


def routed_ffn(p: Dict[str, jax.Array], x: jax.Array, cfg: Any) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed sum: every held expert's SwiGLU of
    every token, then the chosen ones' weighted sum. Returns it and the chosen experts."""
    chosen, weights = route(p, x, cfg)
    per_expert = jnp.zeros((x.shape[0], cfg.router_width), jnp.float32)
    per_expert = per_expert.at[jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    held = per_expert[:, cfg.first_expert : cfg.first_expert + cfg.n_routed_experts]
    hidden = jax.nn.silu(jnp.einsum("th,ehf->etf", x, p["w1"])) * jnp.einsum("th,ehf->etf", x, p["w3"])
    return jnp.einsum("etf,efh,te->th", hidden, p["w2"], held), chosen


def forward(params: Dict[str, Any], ids: jax.Array, cfg: Any) -> Tuple[jax.Array, List[jax.Array]]:
    """Logits ``(T, vocab)`` of the sequence ``ids`` at every position, and the
    experts each layer's router chose ``[(T, k), ...]``."""
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        x = p32["embed"][ids]
        chosen_by_layer = []
        for p in p32["layers"]:
            x = x + mla_attention(p, rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps), cfg)
            h = rmsnorm(x, p["ffn_norm"], cfg.rms_norm_eps)
            out, chosen = routed_ffn(p, h, cfg)
            chosen_by_layer.append(chosen)
            x = x + shared_ffn(p, h) + out
        return rmsnorm(x, p32["final_norm"], cfg.rms_norm_eps) @ p32["lm_head"], chosen_by_layer
