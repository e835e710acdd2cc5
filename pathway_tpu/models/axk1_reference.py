"""The plain reference of A.X-K1 (``skt/A.X-K1``, ``model_type`` ``axk1``).

The whole forward pass of one token sequence in straightforward ``jax.numpy``:
float32 under ``default_matmul_precision("highest")``, no cache, no batching, no
kernels, every held expert's product an einsum over all held experts. The
equations are the published ``config.json``'s keys read as DeepSeek-V3's code
reads them (the family the keys come from); the device program
(``models/mistral4.py``, which serves both latent-attention families) is held
to it by ``tests/test_axk1.py``.

    block:   x = x + mla(rmsnorm(x));  x = x + ffn_i(rmsnorm(x))
    mla:     models/mistral4_reference.py's latent attention, here with query and
             key heads of qk_nope + qk_rope = 128 + 64 and values of v_head_dim = 128
    ffn_i:   i < first_k_dense_replace:  silu(x W1) * (x W3) W2, of intermediate_size
             otherwise the expert layer:
    route:   s = sigmoid(x Wg) over the router's whole width (192), float32
             group g's score = the sum of the two largest s over its experts (8 groups of 24)
             keep the topk_group (4) best groups;  chosen = top k (8) of s, s = 0 outside the kept groups
             w = s[chosen] / sum s[chosen] * routed_scaling_factor (2.5)
    moe:     out = shared(x) + sum_j w_j expert_{chosen_j}(x);  every expert and the shared one a SwiGLU
    head:    rmsnorm, then lm_head (its own matrix)

**The held share** is ``models/mistral4_reference.py``'s: ``cfg.n_routed_experts``
experts from ``cfg.first_expert`` on, a router ``cfg.router_width`` wide; the
weights stay normalised over all the chosen; what the absent experts would add
is left out. ``vocab_size`` rows of the table and of the head.

Departures and what the config is silent on, each also in the configuration's
``assumed``: ``topk_method`` "none" read as the choice on the raw sigmoid scores
with no correction bias, the group scored by the sum of its two best
(DeepSeek-V3's; DeepSeek-V2's group-limited choice takes the group's best alone);
the normaliser's ``1e-20`` left out (sigmoid scores are positive); RoPE on
interleaved pairs; the softmax scale ``qk_head_dim^-0.5 m^2``, ``m = 0.1 ln(32) +
1``; the shared expert's width ``n_shared_experts * moe_intermediate_size``;
``seq_aux``, a training loss, read by nothing.

The parameters are the program's own tree (``mistral4.init_params``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from pathway_tpu.models.mistral4_reference import mla_attention, rmsnorm, shared_ffn


def route(p: Dict[str, jax.Array], x: jax.Array, cfg: Any) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(chosen experts (T, k) among the router's whole width, their weights (T, k),
    the groups kept (T, n_group) bool)."""
    t = x.shape[0]
    scores = jax.nn.sigmoid(x @ p["gate"])
    groups = scores.reshape(t, cfg.n_group, -1)
    top_two = jnp.sort(groups, axis=-1)[..., -2:].sum(axis=-1)
    best = jnp.argsort(-top_two, axis=-1)[:, : cfg.topk_group]
    kept = jnp.zeros((t, cfg.n_group), bool).at[jnp.arange(t)[:, None], best].set(True)
    for_choice = jnp.where(kept[:, :, None], groups, 0.0).reshape(t, -1)
    chosen = jnp.argsort(-for_choice, axis=-1)[:, : cfg.num_experts_per_tok]
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, weights / jnp.sum(weights, axis=-1, keepdims=True) * cfg.routed_scaling_factor, kept


def routed_ffn(p: Dict[str, jax.Array], x: jax.Array, cfg: Any) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The held experts' part of the routed sum: every held expert's SwiGLU of
    every token, then the chosen ones' weighted sum. Returns it, the chosen
    experts and the groups kept."""
    chosen, weights, kept = route(p, x, cfg)
    per_expert = jnp.zeros((x.shape[0], cfg.router_width), jnp.float32)
    per_expert = per_expert.at[jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    held = per_expert[:, cfg.first_expert : cfg.first_expert + cfg.n_routed_experts]
    hidden = jax.nn.silu(jnp.einsum("th,ehf->etf", x, p["w1"])) * jnp.einsum("th,ehf->etf", x, p["w3"])
    return jnp.einsum("etf,efh,te->th", hidden, p["w2"], held), chosen, kept


def dense_ffn(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def forward(params: Dict[str, Any], ids: jax.Array, cfg: Any) -> Tuple[jax.Array, List[jax.Array], List[jax.Array]]:
    """Logits ``(T, vocab)`` of the sequence ``ids`` at every position, and for
    each expert layer the experts its router chose ``(T, k)`` and the groups it
    kept ``(T, n_group)``."""
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        x = p32["embed"][ids]
        chosen_by_layer, kept_by_layer = [], []
        for i, p in enumerate(p32["layers"]):
            x = x + mla_attention(p, rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps), cfg)
            h = rmsnorm(x, p["ffn_norm"], cfg.rms_norm_eps)
            if i < cfg.first_k_dense_replace:
                x = x + dense_ffn(p, h)
                continue
            out, chosen, kept = routed_ffn(p, h, cfg)
            chosen_by_layer.append(chosen)
            kept_by_layer.append(kept)
            x = x + shared_ffn(p, h) + out
        return rmsnorm(x, p32["final_norm"], cfg.rms_norm_eps) @ p32["lm_head"], chosen_by_layer, kept_by_layer
