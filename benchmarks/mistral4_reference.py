"""The benchmark's own plain reference of the ``mistral4`` decoder
(Mistral-Small-4-119B-2603), over one chip's share of it.

It imports nothing of the program. Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, attention in the expanded
form only (keys and values per head from the latent), no grouped product, every
held expert's SwiGLU computed for every token (a scan over the held experts)
and weighted by the router's choice. So that six layers at published width fit
beside the served model, it runs layer by layer and, inside a layer, casts one
expert to float32 at a time: what is float32 at once is a layer's attention,
router and shared expert (215 MB) and one expert (100 MB), beside the
activations of ``chunk`` sequences. Sequences are padded on the right to one
length; the model is causal, so no position sees the padding.

    block:  x = x + mla(rmsnorm(x));  x = x + moe(rmsnorm(x))
    mla:    cq = rmsnorm(x Wdq);  q = cq Wuq -> heads of [q_nope | q_rope]
            [ckv | kr] = x Wdkv;  ckv = rmsnorm(ckv);  kr is one key for all heads
            q_rope, kr = rope(q_rope), rope(kr)   interleaved pairs (x[2i], x[2i+1]), YaRN frequencies
            [k_nope | v] per head = ckv Wukv;  k = [k_nope | kr]
            scores = q k^T * s,  s = qk_head_dim^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
            causal softmax;  out = (probs v) Wo
    yarn:   f_i = theta^(-2i/d);  low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
            c(n) = d ln(original / (2 pi n)) / (2 ln theta);  ramp_i = clip((i - low) / (high - low), 0, 1);
            inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i
    moe:    p = softmax(x Wg) over the router's whole width (128);  chosen = top k;
            w = p[chosen] / sum p[chosen] * routed_scaling_factor
            out = shared(x) + sum over the chosen experts that are held here of w_j expert_j(x)
    head:   rmsnorm, then lm_head (its own matrix), over the rows of the vocabulary held here

**The share** is the program's: ``n_routed_experts`` experts from ``first_expert``
on of a router ``n_router_experts`` wide; the weights stay normalised over all
the chosen; what the absent experts would add is left out. ``vocab_size`` rows
of the table and of the head.

``variant`` is a control in the program's place: ``"fp8_matmul"`` rounds every
matrix product's operands to float8 (e4m3), the nearest precision below the
bfloat16 the configuration states; ``"top3_experts"`` routes to one expert
fewer; ``"no_shared_expert"`` leaves the shared expert out; ``"rope_rotate_half"``
turns the pairs ``(x[i], x[i + half])`` instead of the neighbours.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from lfm2_reference import logit_gaps, prompt_qa, tokenize  # noqa: F401 - the deployment's template and tokenizer

VARIANTS = ("f32", "fp8_matmul", "top3_experts", "no_shared_expert", "rope_rotate_half")
SHAPE_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_routed_experts", "n_router_experts", "first_expert", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "rms_norm_eps",
)
ROPE_KEYS = ("rope_theta", "factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale",
             "mscale_all_dim")


def _op(x: jax.Array, variant: str) -> jax.Array:
    """``x`` as an operand of a matrix product."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if variant == "fp8_matmul" else x


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def yarn_inv_freq(cfg: Dict[str, Any]) -> np.ndarray:
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])

    def c(n: float) -> float:
        return dim * math.log(cfg["original_max_position_embeddings"] / (2 * math.pi * n)) / (2 * math.log(theta))

    low, high = max(math.floor(c(cfg["beta_fast"])), 0), min(math.ceil(c(cfg["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f * (1.0 - ramp) + f / cfg["factor"] * ramp).astype(np.float32)


def softmax_scale(cfg: Dict[str, Any]) -> float:
    m = 0.1 * cfg["mscale_all_dim"] * math.log(cfg["factor"]) + 1.0 if cfg["mscale_all_dim"] and cfg["factor"] > 1 else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, cfg, variant):
    """``x`` (sequences, positions, ..., rope size) at positions 0, 1, ...: the
    neighbours ``(x[2i], x[2i+1])`` turned by ``position * inv_freq[i]``."""
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))[None, :]
    angle = angle.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (-1,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if variant == "rope_rotate_half":
        half = x.shape[-1] // 2
        lo, hi = x[..., :half], x[..., half:]
        return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _mla(p, x, cfg, variant):
    mm = lambda a, w: _op(a, variant) @ _op(w, variant)
    n, t = x.shape[:2]
    heads, nope, rank = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    q = mm(_rmsnorm(mm(x, p["wdq"]), p["q_norm"], cfg["rms_norm_eps"]), p["wuq"]).reshape(n, t, heads, -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg, variant)], axis=-1)
    both = mm(x, p["wdkv"])
    ckv = _rmsnorm(both[..., :rank], p["kv_norm"], cfg["rms_norm_eps"])
    kr = _rope(both[..., rank:], cfg, variant)
    kv = mm(ckv, p["wukv"]).reshape(n, t, heads, nope + cfg["v_head_dim"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kr[:, :, None, :], (n, t, heads, kr.shape[-1]))], axis=-1)
    scores = jnp.einsum("nqhd,nkhd->nhqk", _op(q, variant), _op(k, variant)) * softmax_scale(cfg)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nhqk,nkhd->nqhd", _op(probs, variant), _op(kv[..., nope:], variant))
    return mm(out.reshape(n, t, -1), p["wo"])


def _moe(p, experts, x, cfg, variant):
    """Returns the output and the experts chosen ``(sequences, positions, k)``
    among the router's whole width. ``experts``: the held stacks, in the served type."""
    k = cfg["num_experts_per_tok"] - (variant == "top3_experts")
    first, held = cfg["first_expert"], cfg["n_routed_experts"]
    xo = _op(x, variant)
    probs = jax.nn.softmax(xo @ _op(p["gate"], variant), axis=-1)
    weights, chosen = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * cfg["routed_scaling_factor"]
    per_expert = jnp.sum(jax.nn.one_hot(chosen, cfg["n_router_experts"], dtype=jnp.float32) * weights[..., None], axis=-2)
    per_held = jnp.moveaxis(per_expert[..., first : first + held], -1, 0)

    def one_expert(total, expert):
        w1, w3, w2, weight = expert  # one expert cast to float32 at a time
        w1, w3, w2 = (_op(w.astype(jnp.float32), variant) for w in (w1, w3, w2))
        hidden = jax.nn.silu(xo @ w1) * (xo @ w3)
        return total + weight[..., None] * (_op(hidden, variant) @ w2), None

    if variant == "no_shared_expert":
        start = jnp.zeros_like(x)
    else:
        hidden = jax.nn.silu(xo @ _op(p["shared_w1"], variant)) * (xo @ _op(p["shared_w3"], variant))
        start = _op(hidden, variant) @ _op(p["shared_w2"], variant)
    out, _ = jax.lax.scan(one_expert, start, (experts["w1"], experts["w3"], experts["w2"], per_held))
    return out, chosen


@functools.partial(jax.jit, static_argnames=("cfg_items", "variant"))
def _layer(p, experts, x, *, cfg_items, variant):
    """One block over ``x`` (sequences, positions, hidden). ``p``: the layer
    without its routed experts, cast to float32 here; ``experts``: their stacks."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        x = x + _mla(p, _rmsnorm(x, p["attn_norm"], cfg["rms_norm_eps"]), cfg, variant)
        out, chosen = _moe(p, experts, _rmsnorm(x, p["ffn_norm"], cfg["rms_norm_eps"]), cfg, variant)
        return x + out, chosen


@functools.partial(jax.jit, static_argnames=("eps", "variant"))
def _head(lm_head, final_norm, rows, tokens, *, eps, variant):
    with jax.default_matmul_precision("highest"):
        logits = _op(_rmsnorm(rows, final_norm, eps), variant) @ _op(lm_head.astype(jnp.float32), variant)
        at_tokens = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        return jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1), at_tokens, jnp.std(logits, axis=-1)


def shape_config(cfg: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    """What shapes a layer's program, hashable: the published keys and ``rope_parameters``' own."""
    return tuple((k, cfg[k]) for k in SHAPE_KEYS) + tuple((k, cfg["rope_parameters"][k]) for k in ROPE_KEYS)


def hidden_rows(params: Dict[str, Any], cfg: Dict[str, Any], prompts: List[List[int]], served: List[List[int]],
                variant: str = "f32", chunk: int = 1, pad_to: int = 128) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Run prompt + served tokens of every reply through the layers (teacher
    forcing). Returns the last layer's output at each position that produced a
    served token ``(replies, tokens, hidden)`` (position ``len(prompt) - 1 + j``
    chose served token ``j``), and per layer the experts chosen there
    ``(replies, tokens, k)``, numbered over the router's whole width."""
    assert variant in VARIANTS, variant
    n_new = len(served[0])
    assert all(len(s) == n_new for s in served)
    width = -(-(max(len(p) for p in prompts) + n_new) // pad_to) * pad_to
    ids = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        ids[i, : len(p) + n_new] = list(p) + list(s)
    starts = [len(p) - 1 for p in prompts]
    items = shape_config(cfg)
    split = [({k: v for k, v in layer.items() if k not in ("w1", "w3", "w2")}, {k: layer[k] for k in ("w1", "w3", "w2")})
             for layer in params["layers"]]
    rows, chosen = [], [[] for _ in split]
    for lo in range(0, len(prompts), chunk):  # one chunk through every layer: one chunk's activations live at a time
        x = params["embed"][jnp.asarray(ids[lo : lo + chunk])].astype(jnp.float32)
        for i, (rest, experts) in enumerate(split):
            x, picked = _layer(rest, experts, x, cfg_items=items, variant=variant)
            chosen[i] += [np.asarray(picked[j, s : s + n_new]) for j, s in enumerate(starts[lo : lo + chunk])]
        rows += [np.asarray(x[j, s : s + n_new]) for j, s in enumerate(starts[lo : lo + chunk])]
    return np.stack(rows), [np.stack(c) for c in chosen]


def read_head(params: Dict[str, Any], cfg: Dict[str, Any], rows: np.ndarray, tokens: Any,
              variant: str = "f32") -> Dict[str, np.ndarray]:
    """The head over ``rows`` (``hidden_rows``): at every position ``top`` (the
    largest logit of the slice), ``argmax`` (its token), ``at`` (the logit of
    ``tokens`` there) and ``spread`` (the standard deviation of the position's logits)."""
    top, argmax, at, spread = _head(params["lm_head"], params["final_norm"], jnp.asarray(rows),
                                    jnp.asarray(np.asarray(tokens, np.int32)), eps=cfg["rms_norm_eps"],
                                    variant=variant)
    return {"top": np.asarray(top), "argmax": np.asarray(argmax), "at": np.asarray(at), "spread": np.asarray(spread)}
