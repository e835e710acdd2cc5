"""The plain reference of the ``falcon_h1`` decoder (Falcon-H1-34B-Instruct's family).

The whole forward pass of one token sequence in straightforward ``jax.numpy``:
float32 under ``default_matmul_precision("highest")``, no cache, no slots, no
chunks: the state-space recurrence is a ``lax.scan`` over the tokens. The
equations are the published ``config.json``'s keys read as ``transformers``'
``falcon_h1`` reads them, as the repository's issue 35 wrote them down; the
device program (``models/falcon_h1.py``) is held to it by
``tests/test_falcon_h1.py``.

    table:  x = embed[ids] * embedding_multiplier
    block:  h = rmsnorm(x; input_norm)
            x = x + ssm_out_multiplier * ssm(ssm_in_multiplier * h)
                  + attention_out_multiplier * attn(attention_in_multiplier * h)
            x = x + mlp(rmsnorm(x; pre_ff_norm))           both mixers read the same h, in every block
    mlp:    down(up(u) * silu(gate(u) * mlp_multipliers[0])) * mlp_multipliers[1]
    attn:   q = u Wq;  k = (u Wk) * key_multiplier;  v = u Wv;  rotate-half RoPE over the whole
            head at rope_theta; causal softmax of q k^T / sqrt(head_dim), grouped-query; Wo
    ssm:    zxBCdt = (u W_in) * mup,  mup = ssm_multipliers[0..4] over the places of
            [z: d_ssm | x: d_ssm | B: groups x state | C: groups x state | dt: heads]
            (x, B, C) through a depthwise causal convolution of mamba_d_conv taps with bias, then silu
            dt = softplus(dt + dt_bias);  A = -exp(A_log)
            head j (group j // (heads / groups)) carries S (d_head, state):
              S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;   y_t = S_t C_t + D x_t
            y = y * silu(z), then rmsnorm per group (one weight of d_ssm);  out = y W_out
    head:   logits = (rmsnorm(x; final_norm) @ lm_head) * lm_head_multiplier   (its own matrix)

What the config is silent on, each also in ``PERF.md``: the order "multiply by
``mup``, then split" (``transformers``' modelling code); no clamp on ``dt``
(``time_step_limit`` (0, inf)); the gated norm's group count equals
``mamba_n_groups`` and the gate comes before the norm (``mamba_norm_before_gate``
false); ``mamba_d_ssm`` given, so ``mamba_expand`` is read by nothing;
``attn_layer_indices`` null, so every block has both mixers.

The head is applied in blocks of vocabulary columns, so that at published width
(261,120 rows) no more than one block of it is float32 at once.

The parameters are the program's own tree (``falcon_h1.init_params``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def attention(p: Dict[str, jax.Array], u: jax.Array, cfg: Any) -> jax.Array:
    """``u``: (T, hidden), the normed input times ``attention_in_multiplier``."""
    t, hd = u.shape[0], cfg.head_dim
    q = (u @ p["wq"]).reshape(t, cfg.num_attention_heads, hd)
    k = ((u @ p["wk"]) * cfg.key_multiplier).reshape(t, cfg.num_key_value_heads, hd)
    v = (u @ p["wv"]).reshape(t, cfg.num_key_value_heads, hd)
    inv_freq = 1.0 / (float(cfg.rope_theta) ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    q = q * jnp.cos(angles) + rotate_half(q) * jnp.sin(angles)
    k = k * jnp.cos(angles) + rotate_half(k) * jnp.sin(angles)
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, -1) @ p["wo"]


def mup_vector(cfg: Any) -> jax.Array:
    """``ssm_multipliers[0..4]`` over the places of z, x, B, C and dt, in that order."""
    d, gn = cfg.mamba_d_ssm, cfg.mamba_n_groups * cfg.mamba_d_state
    sizes = (d, d, gn, gn, cfg.mamba_n_heads)
    return jnp.concatenate([jnp.full((n,), m, jnp.float32) for n, m in zip(sizes, cfg.ssm_multipliers)])


def ssm(p: Dict[str, jax.Array], u: jax.Array, cfg: Any) -> Tuple[jax.Array, jax.Array]:
    """``u``: (T, hidden), the normed input times ``ssm_in_multiplier``. Returns
    the mixer's output (T, hidden) and the state after the last token
    (heads, d_head, state)."""
    t, d, heads, hd = u.shape[0], cfg.mamba_d_ssm, cfg.mamba_n_heads, cfg.mamba_d_head
    groups, n, taps = cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_d_conv
    zxbcdt = (u @ p["in_proj"]) * mup_vector(cfg)
    z, xbc, dt = zxbcdt[:, :d], zxbcdt[:, d : 2 * d + 2 * groups * n], zxbcdt[:, 2 * d + 2 * groups * n :]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    xbc = jax.nn.silu(sum(p["conv_w"][:, j] * padded[j : j + t] for j in range(taps)) + p["conv_b"])
    x = xbc[:, :d].reshape(t, heads, hd)
    b = jnp.repeat(xbc[:, d : d + groups * n].reshape(t, groups, n), heads // groups, axis=1)  # head j: group j // (heads / groups)
    c = jnp.repeat(xbc[:, d + groups * n :].reshape(t, groups, n), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])

    def token(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + p["D"][:, None] * x_t

    state, y = jax.lax.scan(token, jnp.zeros((heads, hd, n), jnp.float32), (x, b, c, dt))
    y = y.reshape(t, d) * jax.nn.silu(z)
    y = y.reshape(t, groups, d // groups)
    y = (y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.rms_norm_eps)).reshape(t, d)
    return (y * p["ssm_norm"]) @ p["out_proj"], state


def mlp(p: Dict[str, jax.Array], u: jax.Array, cfg: Any) -> jax.Array:
    gate_multiplier, down_multiplier = cfg.mlp_multipliers
    return ((u @ p["w3"]) * jax.nn.silu((u @ p["w1"]) * gate_multiplier)) @ p["w2"] * down_multiplier


def head(params: Dict[str, Any], x: jax.Array, cfg: Any, block: int = 32768) -> jax.Array:
    """Logits of the rows ``x`` (T, hidden), the head taken ``block`` columns at a time."""
    rows = rmsnorm(x, params["final_norm"].astype(jnp.float32), cfg.rms_norm_eps)
    parts = [rows @ params["lm_head"][:, lo : lo + block].astype(jnp.float32)
             for lo in range(0, params["lm_head"].shape[1], block)]
    return jnp.concatenate(parts, axis=-1) * cfg.lm_head_multiplier


def forward(params: Dict[str, Any], ids: jax.Array, cfg: Any) -> Tuple[jax.Array, List[jax.Array]]:
    """Logits ``(T, vocab)`` of the sequence ``ids`` at every position, and each
    block's state-space state after the last token ``[(heads, d_head, state), ...]``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][ids].astype(jnp.float32) * cfg.embedding_multiplier
        states = []
        for p in params["layers"]:
            p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p)
            h = rmsnorm(x, p["input_norm"], cfg.rms_norm_eps)
            mixed, state = ssm(p, cfg.ssm_in_multiplier * h, cfg)
            states.append(state)
            x = (x + cfg.ssm_out_multiplier * mixed
                 + cfg.attention_out_multiplier * attention(p, cfg.attention_in_multiplier * h, cfg))
            x = x + mlp(p, rmsnorm(x, p["pre_ff_norm"], cfg.rms_norm_eps), cfg)
        return head(params, x, cfg), states
