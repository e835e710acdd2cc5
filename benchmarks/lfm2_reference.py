"""The benchmark's own plain reference of the ``lfm2_moe`` decoder (LFM2-8B-A1B).

It imports nothing of the program. Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no grouped product, every
expert's SwiGLU computed for every token (a loop over the experts) and weighted
by the router's choice. So that layers 0-13 at published width fit beside the
served model, it runs layer by layer: one layer's weights are cast to float32,
every sequence of the batch goes through that layer, then the next. Sequences
are padded on the right to one length; the model is causal, so no position sees
the padding.

    block:  x = x + operator(rmsnorm(x));  x = x + ffn(rmsnorm(x))
    conv:   B, C, u = split(in_proj(x), 3);  v = B * u;
            y[t] = sum_j w[:, j] * v[t - (L - 1) + j];  out_proj(C * y)
    attn:   grouped-query; RMSNorm over the head size on every query and key
            head, then RoPE (rotate-half over the whole head); causal softmax at
            1/sqrt(head size); out_proj
    dense:  w2(silu(w1 x) * w3 x)
    moe:    s = sigmoid(gate(x)); chosen = top-k of s + expert_bias; weights =
            s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor
    head:   rmsnorm, then the embedding table (tied)

``variant`` is a control in the program's place: ``"fp8_matmul"`` rounds every
matrix product's operands to float8 (e4m3), the nearest precision below the
bfloat16 the configuration states; ``"top3_experts"`` routes to one expert fewer;
``"no_expert_bias"`` chooses by the unbiased scores.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np
import xxhash

import jax
import jax.numpy as jnp

VARIANTS = ("f32", "fp8_matmul", "top3_experts", "no_expert_bias")


def tokenize(text: str, vocab_size: int) -> List[int]:
    """One id a lower-cased whitespace word, ``2000 + xxh32(word) mod (vocab - 3000)``,
    as the configuration states it."""
    return [2000 + xxhash.xxh32_intdigest(w) % (vocab_size - 3000) for w in str(text).lower().split()]


def prompt_qa(question: str, passages: Sequence[str]) -> str:
    """The upstream ``prompts.prompt_qa`` over the retrieved passages, as the
    deployment's default template has it."""
    context = "\n\n".join(passages)
    return (
        "Please provide an answer based solely on the provided sources. "
        "Keep your answer concise and accurate. "
        "If the sources do not contain the answer, say: No information found.\n"
        "\n"
        f"Sources:\n{context}\n\n"
        f"Question: {question}\n"
        "Answer:"
    )


def _op(x: jax.Array, variant: str) -> jax.Array:
    """``x`` as an operand of a matrix product."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if variant == "fp8_matmul" else x


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _conv(p, x, variant):
    mm = lambda a, w: _op(a, variant) @ _op(w, variant)
    b, c, u = jnp.split(mm(x, p["in_proj"]), 3, axis=-1)
    v = b * u
    width, t = p["conv_w"].shape[1], x.shape[1]
    padded = jnp.pad(v, ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(p["conv_w"][:, j] * padded[:, j : j + t] for j in range(width))
    return mm(c * y, p["out_proj"])


def _attention(p, x, cfg, variant):
    mm = lambda a, w: _op(a, variant) @ _op(w, variant)
    n, t = x.shape[:2]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nq
    q = mm(x, p["wq"]).reshape(n, t, nq, hd)
    k = mm(x, p["wk"]).reshape(n, t, nkv, hd)
    v = mm(x, p["wv"]).reshape(n, t, nkv, hd)
    q, k = _rmsnorm(q, p["q_norm"], cfg["norm_eps"]), _rmsnorm(k, p["k_norm"], cfg["norm_eps"])
    inv_freq = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    q = q * jnp.cos(angles) + _rotate_half(q) * jnp.sin(angles)
    k = k * jnp.cos(angles) + _rotate_half(k) * jnp.sin(angles)
    k, v = jnp.repeat(k, nq // nkv, axis=2), jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", _op(q, variant), _op(k, variant)) / np.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nhqk,nkhd->nqhd", _op(probs, variant), _op(v, variant)).reshape(n, t, nq * hd)
    return mm(out, p["wo"])


def _dense(p, x, variant):
    mm = lambda a, w: _op(a, variant) @ _op(w, variant)
    return mm(jax.nn.silu(mm(x, p["w1"])) * mm(x, p["w3"]), p["w2"])


def _moe(p, x, cfg, variant):
    """Returns the output and the experts chosen ``(sequences, positions, k)``."""
    k = cfg["num_experts_per_tok"] - (variant == "top3_experts")
    scores = jax.nn.sigmoid(_op(x, variant) @ _op(p["gate"], variant))
    biased = scores + p["expert_bias"] if cfg["use_expert_bias"] and variant != "no_expert_bias" else scores
    _, chosen = jax.lax.top_k(biased, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    weights = weights * cfg["routed_scaling_factor"]
    per_expert = jnp.sum(jax.nn.one_hot(chosen, cfg["num_experts"], dtype=jnp.float32) * weights[..., None], axis=-2)
    xo = _op(x, variant)

    def one_expert(total, expert):
        w1, w3, w2, weight = expert
        hidden = jax.nn.silu(xo @ _op(w1, variant)) * (xo @ _op(w3, variant))
        return total + weight[..., None] * (_op(hidden, variant) @ _op(w2, variant)), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (p["w1"], p["w3"], p["w2"], jnp.moveaxis(per_expert, -1, 0)))
    return out, chosen


@functools.partial(jax.jit, static_argnames=("kind", "cfg_items", "variant"))
def _layer(p, x, *, kind, cfg_items, variant):
    """One block over ``x`` (sequences, positions, hidden), the layer's weights
    cast to float32 here: one layer's at a time."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        h = _rmsnorm(x, p["operator_norm"], cfg["norm_eps"])
        x = x + (_conv(p, h, variant) if kind == "conv" else _attention(p, h, cfg, variant))
        h = _rmsnorm(x, p["ffn_norm"], cfg["norm_eps"])
        if "gate" in p:
            out, chosen = _moe(p, h, cfg, variant)
        else:
            out, chosen = _dense(p, h, variant), jnp.zeros(x.shape[:2] + (0,), jnp.int32)
        return x + out, chosen


@functools.partial(jax.jit, static_argnames=("eps", "variant"))
def _head(embed, final_norm, rows, tokens, *, eps, variant):
    with jax.default_matmul_precision("highest"):
        logits = _op(_rmsnorm(rows, final_norm, eps), variant) @ _op(embed.astype(jnp.float32), variant).T
        at_tokens = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        return jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1), at_tokens, jnp.std(logits, axis=-1)


def shape_config(cfg: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads", "num_experts", "num_experts_per_tok",
            "norm_eps", "rope_theta", "norm_topk_prob", "use_expert_bias", "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys)


def hidden_rows(params: Dict[str, Any], cfg: Dict[str, Any], prompts: List[List[int]], served: List[List[int]],
                variant: str = "f32", chunk: int = 4, pad_to: int = 128) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Run prompt + served tokens of every reply through the layers (teacher
    forcing). Returns the last layer's output at each position that produced a
    served token ``(replies, tokens, hidden)`` (position ``len(prompt) - 1 + j``
    chose served token ``j``), and per expert layer the experts chosen there
    ``(replies, tokens, k)``."""
    assert variant in VARIANTS, variant
    n_new = len(served[0])
    assert all(len(s) == n_new for s in served)
    width = -(-(max(len(p) for p in prompts) + n_new) // pad_to) * pad_to
    ids = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        ids[i, : len(p) + n_new] = list(p) + list(s)
    starts = [len(p) - 1 for p in prompts]
    spans = [slice(lo, lo + chunk) for lo in range(0, len(prompts), chunk)]
    xs = [params["embed"][jnp.asarray(ids[s])].astype(jnp.float32) for s in spans]
    items, chosen = shape_config(cfg), []
    for kind, p in zip(cfg["layer_types"], params["layers"]):
        out = [_layer(p, x, kind=kind, cfg_items=items, variant=variant) for x in xs]
        xs = [x for x, _ in out]
        if "gate" in p:
            picked = np.concatenate([np.asarray(c) for _, c in out])
            chosen.append(np.stack([picked[i, s : s + n_new] for i, s in enumerate(starts)]))
    last = np.concatenate([np.asarray(x) for x in xs])
    return np.stack([last[i, s : s + n_new] for i, s in enumerate(starts)]), chosen


def read_head(params: Dict[str, Any], cfg: Dict[str, Any], rows: np.ndarray, tokens: Any,
              variant: str = "f32") -> Dict[str, np.ndarray]:
    """The head over ``rows`` (``hidden_rows``): at every position ``top`` (the
    largest logit), ``argmax`` (its token), ``at`` (the logit of ``tokens``
    there) and ``spread`` (the standard deviation of the position's logits)."""
    top, argmax, at, spread = _head(params["embed"], params["final_norm"], jnp.asarray(rows),
                                    jnp.asarray(np.asarray(tokens, np.int32)), eps=cfg["norm_eps"], variant=variant)
    return {"top": np.asarray(top), "argmax": np.asarray(argmax), "at": np.asarray(at), "spread": np.asarray(spread)}


def logit_gaps(read: Dict[str, np.ndarray]) -> np.ndarray:
    """How far under the reference's largest logit each token's logit lies, in
    units of its position's logit spread: 0 where the token is the reference's
    own greedy choice."""
    return (read["top"] - read["at"]) / read["spread"]
