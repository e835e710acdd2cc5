"""The benchmark's own plain reference of A.X-K1 (``skt/A.X-K1``, ``model_type``
``axk1``), over one chip's share of it.

It imports nothing of the program. Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, attention in the expanded
form only (keys and values per head from the latent), no grouped product, every
held expert's SwiGLU computed for every token (a scan over the held experts)
and weighted by the router's choice. So that seven layers at published width
fit beside the served model, it runs layer by layer and casts one matrix of
the biggest to float32 at a time: a layer's attention (0.40 GB in float32),
then its router and shared expert with one routed expert (0.18 GB each), or one
of layer 0's three dense matrices (0.53 GB); attention is mixed eight heads at
a time (scores of 0.15 GB a sequence at 2,176 positions). Sequences are padded
on the right to one length; the model is causal, so no position sees the
padding.

    block:  x = x + mla(rmsnorm(x));  x = x + ffn_i(rmsnorm(x))
    mla:    mistral4_reference.py's latent attention (DeepSeek-V3's), with query and key
            heads of qk_nope + qk_rope = 128 + 64 and values of v_head_dim = 128
    ffn_i:  layers under first_k_dense_replace (layer 0):  silu(x W1) * (x W3) W2, 18,432 wide
            the others:  shared(x) + sum over the chosen experts held here of w_j expert_j(x)
    route:  s = sigmoid(x Wg) over the router's whole width (192)
            group g's score = the sum of its two largest s (8 groups of 24);  the best topk_group (4) kept
            chosen = top k (8) of s, with s = 0 outside the kept groups
            w = s[chosen] / sum s[chosen] * routed_scaling_factor (2.5)
    head:   rmsnorm, then lm_head (its own matrix), over the rows of the vocabulary held here

**The share** is the program's: ``n_routed_experts`` experts from ``first_expert``
on of a router ``n_router_experts`` wide; the weights stay normalised over all
the chosen; what the absent experts would add is left out. ``vocab_size`` rows
of the table and of the head. What the configuration's ``assumed`` names is
assumed here too: ``topk_method`` "none" as the choice on the raw scores with no
correction bias, a group scored by its two best (DeepSeek-V3's reading; V2's
takes the best alone), RoPE on interleaved pairs, the scale ``192^-0.5 m^2``.

``variant`` is a control in the program's place: ``"fp8_matmul"`` rounds every
matrix product's operands to float8 (e4m3), the nearest precision below the
bfloat16 the configuration states; ``"top7_experts"`` routes to one expert
fewer; ``"no_shared_expert"`` leaves the shared expert out;
``"rope_rotate_half"`` turns the pairs ``(x[i], x[i + half])`` instead of the
neighbours; ``"no_group_limit"`` chooses the top 8 over all 192;
``"no_routed_scaling"`` leaves the 2.5 out.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from mistral4_reference import (  # noqa: F401 - the deployment's template, tokenizer and head
    _op, _rmsnorm, _rope, logit_gaps, prompt_qa, read_head, softmax_scale, tokenize,
)

VARIANTS = ("f32", "fp8_matmul", "top7_experts", "no_shared_expert", "rope_rotate_half", "no_group_limit",
            "no_routed_scaling")
SHAPE_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_routed_experts", "n_router_experts", "first_expert", "num_experts_per_tok", "n_group",
    "topk_group", "routed_scaling_factor", "rms_norm_eps", "rope_theta", "factor",
    "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
)
HEADS_AT_ONCE = 8
DENSE = ("w1", "w3", "w2")


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
    causal = jnp.tril(jnp.ones((t, t), bool))

    def some_heads(qkv):  # (n, t, heads at once, size) each
        q, k, v = qkv
        scores = jnp.einsum("nqhd,nkhd->nhqk", _op(q, variant), _op(k, variant)) * softmax_scale(cfg)
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("nhqk,nkhd->nqhd", _op(probs, variant), _op(v, variant))

    at_once = math.gcd(heads, HEADS_AT_ONCE)
    blocks = lambda a: jnp.moveaxis(a.reshape(n, t, heads // at_once, at_once, -1), 2, 0)
    out = jax.lax.map(some_heads, (blocks(q), blocks(k), blocks(kv[..., nope:])))
    return mm(jnp.moveaxis(out, 0, 2).reshape(n, t, -1), p["wo"])


def route(p, x, cfg, variant="f32"):
    """(chosen experts (..., k) among the router's whole width, their weights (..., k))."""
    k = cfg["num_experts_per_tok"] - (variant == "top7_experts")
    scores = jax.nn.sigmoid(_op(x, variant) @ _op(p["gate"], variant))
    n_group = 1 if variant == "no_group_limit" else cfg["n_group"]
    groups = scores.reshape(scores.shape[:-1] + (n_group, -1))
    top_two = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)
    _, best = jax.lax.top_k(top_two, min(cfg["topk_group"], n_group))
    kept = jnp.sum(jax.nn.one_hot(best, n_group, dtype=jnp.float32), axis=-2) > 0
    _, chosen = jax.lax.top_k(jnp.where(kept[..., None], groups, 0.0).reshape(scores.shape), k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    scale = 1.0 if variant == "no_routed_scaling" else cfg["routed_scaling_factor"]
    return chosen, weights / jnp.sum(weights, axis=-1, keepdims=True) * scale


@functools.partial(jax.jit, static_argnames=("cfg_items", "variant"))
def _attention(p, x, *, cfg_items, variant):
    """``x`` plus its attention, over (sequences, positions, hidden); ``p`` the layer's attention, cast here."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        return x + _mla(p, _rmsnorm(x, p["attn_norm"], cfg["rms_norm_eps"]), cfg, variant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "variant"))
def _experts(p, experts, x, *, cfg_items, variant):
    """``x`` plus its expert layer, and the experts chosen ``(sequences, positions, k)``.
    ``p``: the router, the shared expert and the norm, cast here; ``experts``: the held stacks, in the served type."""
    cfg = dict(cfg_items)
    first, held = cfg["first_expert"], cfg["n_routed_experts"]
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        h = _rmsnorm(x, p["ffn_norm"], cfg["rms_norm_eps"])
        ho = _op(h, variant)
        chosen, weights = route(p, h, cfg, variant)
        per_expert = jnp.sum(jax.nn.one_hot(chosen, cfg["n_router_experts"], dtype=jnp.float32) * weights[..., None],
                             axis=-2)
        per_held = jnp.moveaxis(per_expert[..., first : first + held], -1, 0)

        def one_expert(total, expert):
            w1, w3, w2, weight = expert  # one expert cast to float32 at a time
            w1, w3, w2 = (_op(w.astype(jnp.float32), variant) for w in (w1, w3, w2))
            return total + weight[..., None] * (_op(jax.nn.silu(ho @ w1) * (ho @ w3), variant) @ w2), None

        if variant == "no_shared_expert":
            start = jnp.zeros_like(h)
        else:
            hidden = jax.nn.silu(ho @ _op(p["shared_w1"], variant)) * (ho @ _op(p["shared_w3"], variant))
            start = _op(hidden, variant) @ _op(p["shared_w2"], variant)
        out, _ = jax.lax.scan(one_expert, start, (experts["w1"], experts["w3"], experts["w2"], per_held))
        return x + out, chosen


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, weight, *, eps):
    return _rmsnorm(x, weight, eps)


@functools.partial(jax.jit, static_argnames=("variant", "gate"))
def _product(x, w, *, variant, gate):
    """``x @ w`` with ``w`` cast to float32 here, one matrix at a time; ``silu`` of it where ``gate``."""
    with jax.default_matmul_precision("highest"):
        out = _op(x, variant) @ _op(w.astype(jnp.float32), variant)
        return jax.nn.silu(out) if gate else out


def _dense(p, x, cfg, variant):
    """``x`` plus layer 0's SwiGLU, each of its three matrices cast to float32 alone."""
    h = _normed(x, p["ffn_norm"], eps=cfg["rms_norm_eps"])
    mid = _product(h, p["w1"], variant=variant, gate=True) * _product(h, p["w3"], variant=variant, gate=False)
    return x + _product(mid, p["w2"], variant=variant, gate=False)


def shape_config(cfg: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    """What shapes a layer's program, hashable: ``cfg`` has ``rope_scaling``'s keys beside the others."""
    return tuple((k, cfg[k]) for k in SHAPE_KEYS)


def hidden_rows(params: Dict[str, Any], cfg: Dict[str, Any], prompts: List[List[int]], served: List[List[int]],
                variant: str = "f32", chunk: int = 1, pad_to: int = 128) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Run prompt + served tokens of every reply through the layers (teacher
    forcing). Returns the last layer's output at each position that produced a
    served token ``(replies, tokens, hidden)`` (position ``len(prompt) - 1 + j``
    chose served token ``j``), and per expert layer the experts chosen there
    ``(replies, tokens, k)``, numbered over the router's whole width."""
    assert variant in VARIANTS, variant
    n_new = len(served[0])
    assert all(len(s) == n_new for s in served)
    width = -(-(max(len(p) for p in prompts) + n_new) // pad_to) * pad_to
    ids = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        ids[i, : len(p) + n_new] = list(p) + list(s)
    starts = [len(p) - 1 for p in prompts]
    items, dense = shape_config(cfg), cfg["first_k_dense_replace"]
    ffn_keys = DENSE + ("gate", "shared_w1", "shared_w3", "shared_w2", "ffn_norm")
    rows, chosen = [], [[] for _ in params["layers"][dense:]]
    for lo in range(0, len(prompts), chunk):  # one chunk through every layer: one chunk's activations live at a time
        x = params["embed"][jnp.asarray(ids[lo : lo + chunk])].astype(jnp.float32)
        for i, layer in enumerate(params["layers"]):
            x = _attention({k: v for k, v in layer.items() if k not in ffn_keys}, x, cfg_items=items, variant=variant)
            if i < dense:
                x = _dense(layer, x, cfg, variant)
                continue
            rest = {k: layer[k] for k in ("gate", "shared_w1", "shared_w3", "shared_w2", "ffn_norm")}
            x, picked = _experts(rest, {k: layer[k] for k in DENSE}, x, cfg_items=items, variant=variant)
            chosen[i - dense] += [np.asarray(picked[j, s : s + n_new]) for j, s in enumerate(starts[lo : lo + chunk])]
        rows += [np.asarray(x[j, s : s + n_new]) for j, s in enumerate(starts[lo : lo + chunk])]
    return np.stack(rows), [np.stack(c) for c in chosen]
