"""The benchmark's own plain reference of the ``falcon_h1`` decoder
(Falcon-H1-34B-Instruct), over the blocks the configuration holds.

It imports nothing of the program. Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no slots, no chunks: **the
state-space recurrence is a ``lax.scan`` over the tokens**, a full forward pass
of prompt + served tokens (teacher forcing). So that six blocks at published
width fit beside the served model, it runs block by block over ``chunk``
sequences at a time, takes the SwiGLU's 21,504 columns a quarter at a time, and
applies the head (261,120 columns) in column blocks, keeping of each only what
the comparison reads. Sequences are padded on the right to one length; the model
is causal and recurrent, so no position sees the padding.

    table:  x = embed[ids] * embedding_multiplier
    block:  h = rmsnorm(x; input_norm)
            x = x + ssm_out_multiplier * ssm(ssm_in_multiplier * h)
                  + attention_out_multiplier * attn(attention_in_multiplier * h)
            x = x + mlp(rmsnorm(x; pre_ff_norm))
    mlp:    down(up(u) * silu(gate(u) * mlp_multipliers[0])) * mlp_multipliers[1]
    attn:   q = u Wq;  k = (u Wk) * key_multiplier;  v = u Wv;  rotate-half RoPE over the whole head;
            causal softmax of q k^T / sqrt(head_dim), five query heads a key/value head;  Wo
    ssm:    zxBCdt = (u W_in) * mup,  mup = ssm_multipliers[0..4] over [z | x | B | C | dt]
            (x, B, C): depthwise causal convolution of mamba_d_conv taps with bias, then silu
            dt = softplus(dt + dt_bias);  A = -exp(A_log);  head j is of group j // (heads / groups)
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;   y_t = S_t C_t + D x_t
            y = y * silu(z);  rmsnorm per group (one weight of d_ssm);  out = y W_out
    head:   (rmsnorm(x; final_norm) @ lm_head) * lm_head_multiplier

``variant`` is a control in the program's place: ``"fp8_matmul"`` rounds every
matrix product's operands to float8 (e4m3), the nearest precision below the
bfloat16 the configuration states; ``"no_attention_branch"`` leaves the
attention mixer's output out of every block; ``"no_ssm_multipliers"`` leaves
``mup`` out (all five at 1); ``"state_one_token_behind"`` reads each token's
output from the state before that token's update (``y_t = S_{t-1} C_t + D x_t``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from lfm2_reference import logit_gaps, prompt_qa, tokenize  # noqa: F401 - the deployment's template and tokenizer

VARIANTS = ("f32", "fp8_matmul", "no_attention_branch", "no_ssm_multipliers", "state_one_token_behind")
SHAPE_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
    "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier", "attention_out_multiplier",
    "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
)
MLP_BLOCKS = 4  # the SwiGLU's columns are taken a quarter at a time: a quarter of its float32 weights at once


def _op(x: jax.Array, variant: str) -> jax.Array:
    """``x`` as an operand of a matrix product."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if variant == "fp8_matmul" else x


def _f32(w: jax.Array, variant: str) -> jax.Array:
    return _op(w.astype(jnp.float32), variant)


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _attention(p, u, cfg, variant):
    mm = lambda a, w: _op(a, variant) @ _f32(w, variant)
    n, t = u.shape[:2]
    nq, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = mm(u, p["wq"]).reshape(n, t, nq, hd)
    k = (mm(u, p["wk"]) * cfg["key_multiplier"]).reshape(n, t, nkv, hd)
    v = mm(u, p["wv"]).reshape(n, t, nkv, hd)
    inv_freq = 1.0 / (float(cfg["rope_theta"]) ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    q = q * jnp.cos(angles) + _rotate_half(q) * jnp.sin(angles)
    k = k * jnp.cos(angles) + _rotate_half(k) * jnp.sin(angles)
    k, v = jnp.repeat(k, nq // nkv, axis=2), jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", _op(q, variant), _op(k, variant)) / np.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores, -jnp.inf)
    out = jnp.einsum("nhqk,nkhd->nqhd", _op(jax.nn.softmax(scores, axis=-1), variant), _op(v, variant))
    return mm(out.reshape(n, t, nq * hd), p["wo"])


def _ssm(p, u, cfg, variant):
    n, t = u.shape[:2]
    d, heads, hd = cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, state, taps = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    gn = groups * state
    zxbcdt = _op(u, variant) @ _f32(p["in_proj"], variant)
    if variant != "no_ssm_multipliers":
        zxbcdt = zxbcdt * jnp.concatenate([jnp.full((size,), m, jnp.float32) for size, m in
                                           zip((d, d, gn, gn, heads), cfg["ssm_multipliers"])])
    z, xbc, dt = zxbcdt[..., :d], zxbcdt[..., d : 2 * d + 2 * gn], zxbcdt[..., 2 * d + 2 * gn :]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv_w"][:, j] * padded[:, j : j + t] for j in range(taps)) + p["conv_b"])
    x = xbc[..., :d].reshape(n, t, heads, hd)
    b = jnp.repeat(xbc[..., d : d + gn].reshape(n, t, groups, state), heads // groups, axis=2)
    c = jnp.repeat(xbc[..., d + gn :].reshape(n, t, groups, state), heads // groups, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])

    def token(s, inputs):
        x_t, b_t, c_t, dt_t = inputs  # (sequences, heads, ...)
        new = jnp.exp(dt_t * a)[..., None, None] * s + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        read = s if variant == "state_one_token_behind" else new
        return new, jnp.sum(read * c_t[:, :, None, :], axis=-1) + p["D"][:, None] * x_t

    over_tokens = tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, c, dt))
    _, y = jax.lax.scan(token, jnp.zeros((n, heads, hd, state), jnp.float32), over_tokens)
    y = jnp.moveaxis(y, 0, 1).reshape(n, t, d) * jax.nn.silu(z)
    y = y.reshape(n, t, groups, d // groups)
    y = (y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg["rms_norm_eps"])).reshape(n, t, d)
    return _op(y * p["ssm_norm"], variant) @ _f32(p["out_proj"], variant)


def _mlp(p, u, cfg, variant):
    gate_multiplier, down_multiplier = cfg["mlp_multipliers"]
    uo, width = _op(u, variant), p["w1"].shape[1]
    out, step = jnp.zeros_like(u), -(-width // MLP_BLOCKS)
    for lo in range(0, width, step):
        cols = slice(lo, lo + step)
        mid = (uo @ _f32(p["w3"][:, cols], variant)) * jax.nn.silu((uo @ _f32(p["w1"][:, cols], variant)) * gate_multiplier)
        out = out + _op(mid, variant) @ _f32(p["w2"][cols], variant)
    return out * down_multiplier


@functools.partial(jax.jit, static_argnames=("cfg_items", "variant"))
def _layer(p, x, *, cfg_items, variant):
    """One block over ``x`` (sequences, positions, hidden); each matrix is cast to float32 where it is multiplied."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, p["input_norm"], cfg["rms_norm_eps"])
        x = x + cfg["ssm_out_multiplier"] * _ssm(p, cfg["ssm_in_multiplier"] * h, cfg, variant)
        if variant != "no_attention_branch":
            x = x + cfg["attention_out_multiplier"] * _attention(p, cfg["attention_in_multiplier"] * h, cfg, variant)
        return x + _mlp(p, _rmsnorm(x, p["pre_ff_norm"], cfg["rms_norm_eps"]), cfg, variant)


@functools.partial(jax.jit, static_argnames=("variant",))
def _head_block(lm_head, normed, tokens, first, *, variant):
    """One column block of the head over the normed rows: its largest logit and
    where, the logit of ``tokens`` where the block holds it (else 0), and the
    logits' sum and sum of squares (before ``lm_head_multiplier``)."""
    with jax.default_matmul_precision("highest"):
        logits = _op(normed, variant) @ _f32(lm_head, variant)
        local = tokens - first
        inside = (local >= 0) & (local < lm_head.shape[1])
        at = jnp.take_along_axis(logits, jnp.clip(local, 0, lm_head.shape[1] - 1)[..., None], axis=-1)[..., 0]
        return (jnp.max(logits, axis=-1), first + jnp.argmax(logits, axis=-1), jnp.where(inside, at, 0.0),
                jnp.sum(logits, axis=-1), jnp.sum(jnp.square(logits), axis=-1))


def shape_config(cfg: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    """What shapes a block's program, hashable."""
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k]) for k in SHAPE_KEYS)


def hidden_rows(params: Dict[str, Any], cfg: Dict[str, Any], prompts: List[List[int]], served: List[List[int]],
                variant: str = "f32", chunk: int = 4, pad_to: int = 128) -> np.ndarray:
    """Run prompt + served tokens of every reply through the blocks (teacher
    forcing). Returns the last block's output at each position that produced a
    served token ``(replies, tokens, hidden)``: position ``len(prompt) - 1 + j``
    chose served token ``j``."""
    assert variant in VARIANTS, variant
    n_new = len(served[0])
    assert all(len(s) == n_new for s in served)
    width = -(-(max(len(p) for p in prompts) + n_new) // pad_to) * pad_to
    ids = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        ids[i, : len(p) + n_new] = list(p) + list(s)
    starts = [len(p) - 1 for p in prompts]
    items, rows = shape_config(cfg), []
    for lo in range(0, len(prompts), chunk):  # one chunk through every block: one chunk's activations live at a time
        x = params["embed"][jnp.asarray(ids[lo : lo + chunk])].astype(jnp.float32) * cfg["embedding_multiplier"]
        for p in params["layers"]:
            x = _layer(p, x, cfg_items=items, variant=variant)
        rows += [np.asarray(x[j, s : s + n_new]) for j, s in enumerate(starts[lo : lo + chunk])]
    return np.stack(rows)


def read_head(params: Dict[str, Any], cfg: Dict[str, Any], rows: np.ndarray, tokens: Any, variant: str = "f32",
              block: int = 32640) -> Dict[str, np.ndarray]:
    """The head over ``rows`` (``hidden_rows``), ``block`` vocabulary columns at a
    time: at every position ``top`` (the largest logit), ``argmax`` (its token),
    ``at`` (the logit of ``tokens`` there) and ``spread`` (the standard deviation
    of the position's logits)."""
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    with jax.default_matmul_precision("highest"):
        normed = _rmsnorm(jnp.asarray(rows), params["final_norm"], cfg["rms_norm_eps"])
    vocab = params["lm_head"].shape[1]
    block = block if vocab % block == 0 else vocab
    parts = [_head_block(params["lm_head"][:, lo : lo + block], normed, tokens, jnp.int32(lo), variant=variant)
             for lo in range(0, vocab, block)]
    tops, args, ats, sums, squares = (np.stack([np.asarray(part[i]) for part in parts]) for i in range(5))
    best = np.argmax(tops, axis=0)[None]
    mean = sums.sum(0) / vocab
    scale = cfg["lm_head_multiplier"]
    return {"top": np.take_along_axis(tops, best, 0)[0] * scale, "argmax": np.take_along_axis(args, best, 0)[0],
            "at": ats.sum(0) * scale, "spread": np.sqrt(np.maximum(squares.sum(0) / vocab - mean * mean, 0.0)) * scale}
