"""The ``lfm2_moe`` decoder (LFM2-8B-A1B's family) on the device: two programs over slots.

A hybrid decoder: gated short convolutions and grouped-query attention as the
token mixers, a dense SwiGLU in the leading layers and a router over sparse
experts in the rest. ``models/lfm2_reference.py`` has the equations and the
departures from the published model; this file is the same mathematics as two
jitted programs that keep every request's state on the device, in *slots*:

- ``lm_prefill``: one prompt, padded on the right to a bucket, into one slot.
  It fills the slot's keys and values (after the per-head norm and RoPE), leaves
  the last ``conv_L_cache - 1`` convolution inputs as the slot's tail, and
  returns the first greedy token.
- ``lm_decode``: one step over all slots, one greedy token a slot. A slot that
  holds no request routes to no expert and writes no state.

Both also return how many distinct experts their tokens chose, summed over the
expert layers (the bytes a step has to read follow from it), and how many of
those layers' products ran batched (``COUNT_NAMES``).

Two kinds of state live side by side in a slot: ``k``/``v`` of every attention
layer ``(slots, max_len, kv heads, head size)`` and ``tail`` of every convolution
layer ``(slots, conv_L_cache - 1, hidden)``, beside ``pos`` (tokens the slot holds)
and ``last`` (the token to feed next). Nothing is zeroed when a slot is freed:
the next prefill overwrites the tail, ``pos`` and ``last``, and rewrites keys and
values from position 0; what lies past ``pos`` is never read.

Weights, and activations wherever they are a product's operand or a slot's
state, have the dtype of ``params["embed"]`` (bfloat16 as served); products
accumulate in float32, and so does the residual stream, the sum every layer
adds into (rounded to bfloat16 after each of 28 additions it alone moved a
router's choice about three times as often, on the chip); RMSNorm, the router,
RoPE, softmax and the logits are computed in float32. The expert products are XLA's own grouped
product (``jax.lax.ragged_dot`` over the tokens sorted by expert): no token is
dropped and no expert without a token is read. Layers are unrolled, so that no
expert matrix is sliced out of a stack in front of the grouped product.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from pathway_tpu.models.moe import grouped_experts, precision as _precision
from pathway_tpu.models.slot_decoder import SlotDecoder, random_params

# what a call counts beside its tokens, summed over the layers: distinct experts its tokens chose, and the expert
# layers whose product ran batched (``models/moe.py``: a prefill's, unless an expert overflowed; never a step's)
COUNT_NAMES = ("experts_touched", "batched_layers")

# https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24)
)


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The published ``config.json`` keys that shape the model, at LFM2-8B-A1B's values."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 32
    num_experts_per_tok: int = 4
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0

    def __post_init__(self) -> None:
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for {self.num_hidden_layers} layers")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "Lfm2Config":
        """From a ``config.json`` as published; keys this model does not read are left aside."""
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in config.items() if k in names}
        if "layer_types" in known:
            known["layer_types"] = tuple(known["layer_types"])
        return cls(**known)


def param_shapes(cfg: Lfm2Config, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """The parameter tree as shapes: matrices in ``dtype``; norms, the router and its bias float32."""
    h, hd = cfg.hidden_size, cfg.head_dim

    def mat(*shape: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, dtype)

    def vec(*shape: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    layers = []
    for i, kind in enumerate(cfg.layer_types):
        p = {"operator_norm": vec(h), "ffn_norm": vec(h)}
        if kind == "conv":
            p.update(in_proj=mat(h, 3 * h), conv_w=vec(h, cfg.conv_L_cache), out_proj=mat(h, h))
        else:
            nq, nkv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
            p.update(wq=mat(h, nq), wk=mat(h, nkv), wv=mat(h, nkv), wo=mat(nq, h), q_norm=vec(hd), k_norm=vec(hd))
        if i < cfg.num_dense_layers:
            f = cfg.intermediate_size
            p.update(w1=mat(h, f), w3=mat(h, f), w2=mat(f, h))
        else:
            e, f = cfg.num_experts, cfg.moe_intermediate_size
            p.update(gate=vec(h, e), expert_bias=vec(e), w1=mat(e, h, f), w3=mat(e, h, f), w2=mat(e, f, h))
        layers.append(p)
    return {"embed": mat(cfg.vocab_size, h), "final_norm": vec(h), "layers": layers}


def init_params(cfg: Lfm2Config, seed: int = 0, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Random parameters (``slot_decoder.random_params``): matrices normal at
    ``1/sqrt(fan in)``, the table at 0.02, norms around 1, the experts' bias at
    0.05. What a run serves when no parameter tree is given."""
    return random_params(param_shapes(cfg, dtype), seed)


def init_state(cfg: Lfm2Config, slots: int, max_len: int, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Empty slots: keys and values, convolution tails, ``pos`` and ``last``."""
    n_attn = sum(kind == "full_attention" for kind in cfg.layer_types)
    kv = (slots, max_len, cfg.num_key_value_heads, cfg.head_dim)
    tail = (slots, cfg.conv_L_cache - 1, cfg.hidden_size)
    return {
        "k": [jnp.zeros(kv, dtype) for _ in range(n_attn)],
        "v": [jnp.zeros(kv, dtype) for _ in range(n_attn)],
        "tail": [jnp.zeros(tail, dtype) for _ in range(cfg.num_hidden_layers - n_attn)],
        "pos": jnp.zeros((slots,), jnp.int32),
        "last": jnp.zeros((slots,), jnp.int32),
    }


def _mm(a: jax.Array, w: jax.Array) -> jax.Array:
    """``a @ w`` with operands in the weights' dtype and a float32 result."""
    return jnp.dot(a.astype(w.dtype), w, precision=_precision(w.dtype), preferred_element_type=jnp.float32)


def _norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE over the whole head. ``x``: (tokens, heads, head size) at
    ``positions`` (tokens,). Pair ``i`` of a head is (``x[i]``, ``x[i + half]``),
    turned by ``position * theta ** (-2 i / head size)``. Written here and not
    taken from the reference, which spells its own out: the two are held
    against each other by ``tests/test_lfm2.py``."""
    half = x.shape[-1] // 2
    freq = jnp.float32(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = (positions.astype(jnp.float32)[:, None] * freq)[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def _moe(p: Dict[str, jax.Array], h: jax.Array, valid: jax.Array, cfg: Lfm2Config) -> Tuple[jax.Array, jax.Array]:
    """The expert layer over ``h`` (tokens, hidden, float32, already normed).
    Tokens outside ``valid`` choose no expert. Returns the float32 output and
    this call's ``COUNT_NAMES``: how many distinct experts the valid tokens
    chose, and 1 where the product ran batched (``models/moe.py``). Every
    expert is held here, so a chosen expert's number is its place in the stack
    and the router's width is the stack's length."""
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(jnp.dot(h, p["gate"], precision=jax.lax.Precision.HIGHEST))
        biased = scores + p["expert_bias"] if cfg.use_expert_bias else scores
        _, chosen = jax.lax.top_k(biased, k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
        weights = weights * cfg.routed_scaling_factor
    out, group_sizes, batched = grouped_experts(p, h, jnp.where(valid[:, None], chosen, e), weights, e)
    return out, jnp.stack([jnp.sum(group_sizes > 0, dtype=jnp.int32), batched])


def _ffn(p: Dict[str, jax.Array], h: jax.Array, valid: jax.Array, cfg: Lfm2Config) -> Tuple[jax.Array, jax.Array]:
    if "gate" in p:
        return _moe(p, h, valid, cfg)
    dtype = p["w1"].dtype
    mid = jax.nn.silu(_mm(h, p["w1"])) * _mm(h, p["w3"])
    return _mm(mid.astype(dtype), p["w2"]), jnp.zeros((len(COUNT_NAMES),), jnp.int32)


def _qkv(p: Dict[str, jax.Array], h: jax.Array, positions: jax.Array, cfg: Lfm2Config):
    """Queries, keys (both after the per-head norm and RoPE) and values of ``h``
    (tokens, hidden) at ``positions``, in the weights' dtype."""
    n, hd, dtype = h.shape[0], cfg.head_dim, p["wq"].dtype
    q = _mm(h, p["wq"]).reshape(n, cfg.num_attention_heads, hd)
    k = _mm(h, p["wk"]).reshape(n, cfg.num_key_value_heads, hd)
    v = _mm(h, p["wv"]).reshape(n, cfg.num_key_value_heads, hd)
    q = _rope(_norm(q, p["q_norm"], cfg.norm_eps), positions, cfg.rope_theta)
    k = _rope(_norm(k, p["k_norm"], cfg.norm_eps), positions, cfg.rope_theta)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def prefill_logits(params: Dict[str, Any], state: Dict[str, Any], ids: jax.Array, length: jax.Array,
                   slot: jax.Array, cfg: Lfm2Config) -> Tuple[Dict[str, Any], jax.Array, jax.Array]:
    """One prompt into one slot. ``ids`` (bucket,) holds ``length`` tokens and
    padding after them. Returns (the state, the logits of the prompt's last
    token (vocab,), the counts (2,))."""
    t, dtype, eps = ids.shape[0], params["embed"].dtype, cfg.norm_eps
    valid = jnp.arange(t) < length
    x = params["embed"][ids].astype(jnp.float32)
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    state = dict(state, k=list(state["k"]), v=list(state["v"]), tail=list(state["tail"]))
    counts = jnp.zeros((len(COUNT_NAMES),), jnp.int32)
    i_attn = i_conv = 0
    for kind, p in zip(cfg.layer_types, params["layers"]):
        h = _norm(x, p["operator_norm"], eps)
        if kind == "conv":
            with jax.named_scope("conv_op"):
                b, c, u = jnp.split(_mm(h, p["in_proj"]), 3, axis=-1)
                v = (b * u).astype(dtype)
                width = cfg.conv_L_cache
                padded = jnp.concatenate([jnp.zeros((width - 1, v.shape[1]), dtype), v], axis=0)
                y = sum(p["conv_w"][:, j] * padded[j : j + t].astype(jnp.float32) for j in range(width))
                out = _mm(c * y, p["out_proj"])
                # the inputs at length - (width - 1) .. length - 1: zeros where the prompt is shorter
                tail = jax.lax.dynamic_slice_in_dim(padded, length, width - 1, axis=0)
                state["tail"][i_conv] = jax.lax.dynamic_update_slice_in_dim(
                    state["tail"][i_conv], tail[None], slot, axis=0)
            i_conv += 1
        else:
            with jax.named_scope("attn_op"):
                q, k, v = _qkv(p, h, jnp.arange(t), cfg)
                qg = q.reshape(t, cfg.num_key_value_heads, group, cfg.head_dim)
                scores = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=_precision(dtype),
                                    preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(cfg.head_dim))
                probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
                mixed = jnp.einsum("kgqs,skd->qkgd", probs.astype(dtype), v, precision=_precision(dtype),
                                   preferred_element_type=jnp.float32)
                out = _mm(mixed.reshape(t, -1), p["wo"])
                state["k"][i_attn] = jax.lax.dynamic_update_slice(state["k"][i_attn], k[None], (slot, 0, 0, 0))
                state["v"][i_attn] = jax.lax.dynamic_update_slice(state["v"][i_attn], v[None], (slot, 0, 0, 0))
            i_attn += 1
        x = x + out
        out, n = _ffn(p, _norm(x, p["ffn_norm"], eps), valid, cfg)
        x = x + out
        counts = counts + n
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=False)
    logits = _mm(_norm(last, params["final_norm"], eps), params["embed"].T)
    return state, logits, counts


def decode_logits(params: Dict[str, Any], state: Dict[str, Any], active: jax.Array,
                  cfg: Lfm2Config) -> Tuple[Dict[str, Any], jax.Array, jax.Array]:
    """One token for every slot: feeds ``state["last"]`` at ``state["pos"]``.
    Rows outside ``active`` write nothing and choose no expert. Returns (the
    state with keys, values and tails extended but ``pos``/``last`` as they were,
    logits (slots, vocab), the counts (2,))."""
    dtype, eps = params["embed"].dtype, cfg.norm_eps
    pos = state["pos"]
    slots, max_len = pos.shape[0], (state["k"][0].shape[1] if state["k"] else 0)
    rows = jnp.arange(slots)
    write_at = jnp.where(active, pos, max_len)  # past the end: dropped
    x = params["embed"][state["last"]].astype(jnp.float32)
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    state = dict(state, k=list(state["k"]), v=list(state["v"]), tail=list(state["tail"]))
    counts = jnp.zeros((len(COUNT_NAMES),), jnp.int32)
    i_attn = i_conv = 0
    for kind, p in zip(cfg.layer_types, params["layers"]):
        h = _norm(x, p["operator_norm"], eps)
        if kind == "conv":
            with jax.named_scope("conv_op"):
                b, c, u = jnp.split(_mm(h, p["in_proj"]), 3, axis=-1)
                v = (b * u).astype(dtype)
                tail = state["tail"][i_conv]
                window = jnp.concatenate([tail, v[:, None]], axis=1).astype(jnp.float32)
                y = jnp.einsum("bjh,hj->bh", window, p["conv_w"], precision=jax.lax.Precision.HIGHEST)
                out = _mm(c * y, p["out_proj"])
                shifted = jnp.concatenate([tail[:, 1:], v[:, None]], axis=1)
                state["tail"][i_conv] = jnp.where(active[:, None, None], shifted, tail)
            i_conv += 1
        else:
            with jax.named_scope("attn_op"):
                q, k, v = _qkv(p, h, pos, cfg)
                keys = state["k"][i_attn].at[rows, write_at].set(k, mode="drop")
                values = state["v"][i_attn].at[rows, write_at].set(v, mode="drop")
                state["k"][i_attn], state["v"][i_attn] = keys, values
                qg = q.reshape(slots, cfg.num_key_value_heads, group, cfg.head_dim)
                scores = jnp.einsum("bkgd,bskd->bkgs", qg, keys, precision=_precision(dtype),
                                    preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(cfg.head_dim))
                seen = jnp.arange(max_len)[None, :] <= pos[:, None]
                probs = jax.nn.softmax(jnp.where(seen[:, None, None, :], scores, -jnp.inf), axis=-1)
                mixed = jnp.einsum("bkgs,bskd->bkgd", probs.astype(dtype), values, precision=_precision(dtype),
                                   preferred_element_type=jnp.float32)
                out = _mm(mixed.reshape(slots, -1), p["wo"])
            i_attn += 1
        x = x + out
        out, n = _ffn(p, _norm(x, p["ffn_norm"], eps), active, cfg)
        x = x + out
        counts = counts + n
    logits = _mm(_norm(x, params["final_norm"], eps), params["embed"].T)
    return state, logits, counts


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def lm_prefill(params, state, ids, length, slot, *, cfg):
    """The prefill program: the slot filled, its first greedy token, the counts."""
    with jax.named_scope("lm_prefill"):
        state, logits, counts = prefill_logits(params, state, ids, length, slot, cfg)
        token = jnp.argmax(logits).astype(jnp.int32)
        state["pos"] = state["pos"].at[slot].set(length)
        state["last"] = state["last"].at[slot].set(token)
        return state, token, counts


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def lm_decode(params, state, active, *, cfg):
    """The decode program: one greedy token a slot (only ``active`` rows advance), the counts."""
    with jax.named_scope("lm_decode"):
        state, logits, counts = decode_logits(params, state, active, cfg)
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        state["pos"] = jnp.where(active, state["pos"] + 1, state["pos"])
        state["last"] = jnp.where(active, tokens, state["last"])
        return state, tokens, counts


class Lfm2Decoder(SlotDecoder):
    """The ``lfm2_moe`` decoder as the generation service drives it
    (``models/slot_decoder.py``)."""

    count_names = COUNT_NAMES
    lm_prefill, lm_decode = staticmethod(lm_prefill), staticmethod(lm_decode)
    init_params, init_state = staticmethod(init_params), staticmethod(init_state)
