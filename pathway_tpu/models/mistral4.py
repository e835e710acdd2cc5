"""The ``mistral4`` decoder (Mistral-Small-4-119B-2603's family) on the device: two programs over slots.

Every layer is latent attention (MLA) then an expert layer with a shared
expert. ``models/mistral4_reference.py`` has the equations and what is left
out; this file is the same mathematics as two jitted programs that keep every
request's state on the device, in *slots* (``models/slot_decoder.py``,
``models/generation_service.py``):

- ``lm_prefill``: one prompt, padded on the right to a bucket, into one slot.
  The **expanded** form: keys and values per head from the latent (``ckv
  Wukv``), causal softmax over the bucket: plain attention over 32 heads of
  128, so where the program is lowered for a TPU in bfloat16 it is the Pallas
  flash kernel that ships with JAX, which keeps the bucket x bucket scores of
  a head in fast memory and skips the blocks above the diagonal
  (``_causal_attention``); elsewhere the same sums as XLA products. It leaves
  the slot's latents and rotated keys behind and returns the first greedy
  token.
- ``lm_decode``: one step over all slots, one greedy token a slot, in the
  **absorbed** form: ``Wuk`` is folded into the query (``q' = q_nope Wuk_h^T``,
  64 -> 256 a head) and ``Wuv`` applied after the mix, so the step reads the
  latent cache itself and never expands a key or a value. A slot that holds no
  request routes to no expert and writes no state.

**The slot state is the compressed cache**: per layer ``ckv`` ``(slots,
max_len, kv_lora_rank)``, the normed latent, and ``kr`` ``(slots, max_len,
qk_rope_head_dim)``, the one rotated key all heads share, beside ``pos`` and
``last``: 320 numbers a token and layer against 32 x (128 + 128) for full keys
and values. Nothing is zeroed when a slot is freed; what lies past ``pos`` is
never read.

**The expert layer is told what it holds.** The router scores all
``n_router_experts`` (the published width); this process holds
``n_routed_experts`` of them from ``first_expert`` on (one chip's share of a
layer under expert parallelism). A token's four experts are chosen over the
whole width and their weights normalised over all four; only the pairs whose
expert is held enter the expert product (``models/moe.py``, which is told the
router's width: a held expert's even share of a call's rows is over all the
router chooses among, and the form of the product follows from it), and what the
absent experts would add is left out, here and in the reference alike. The
shared expert, which every chip of the layer computes, is added whole.
``vocab_size`` is the rows of the table and of the (untied) head held here: ids,
logits and the greedy choice are over that slice.

Both programs return four counts beside their tokens (``COUNT_NAMES``):
distinct held experts their tokens chose, summed over the layers; routed pairs
whose expert is held; routed pairs in all; the layers whose expert product ran
batched (a prefill's, unless an expert overflowed its capacity; never a step's).

Precision as ``models/lfm2.py``: weights, operands and the cache in the dtype of
``params["embed"]`` (bfloat16 as served); products accumulate in float32; the
residual stream, RMSNorm, the router, RoPE, softmax and the logits in float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from pathway_tpu.models.moe import grouped_experts, precision as _precision
from pathway_tpu.models.slot_decoder import SlotDecoder, random_params

COUNT_NAMES = ("experts_touched", "routed_pairs_held", "routed_pairs", "batched_layers")


@dataclasses.dataclass(frozen=True)
class Mistral4Config:
    """The published ``config.json`` keys that shape the language model, at
    Mistral-Small-4-119B-2603's values, and the share of it held here
    (``n_router_experts``, ``first_expert``: unpublished, this repository's)."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128  # held here
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 128.0
    rope_original_max_position_embeddings: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    n_router_experts: int | None = None  # the router's width; None: every expert is held here
    first_expert: int = 0  # the first held expert's number among the router's

    def __post_init__(self) -> None:
        if not 0 <= self.first_expert <= self.router_width - self.n_routed_experts:
            raise ValueError(f"experts {self.first_expert}..{self.first_expert + self.n_routed_experts - 1} "
                             f"are not among a router's {self.router_width}")

    @property
    def router_width(self) -> int:
        return self.n_routed_experts if self.n_router_experts is None else self.n_router_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5 * m ** 2`` with YaRN's ``m = 0.1 mscale_all_dim ln(factor) + 1``
        (DeepSeek-V3's reading of ``mscale_all_dim``)."""
        m = 1.0
        if self.rope_mscale_all_dim and self.rope_factor > 1:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
        return self.qk_head_dim ** -0.5 * m * m

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "Mistral4Config":
        """From a ``config.json`` as published (``rope_parameters`` a nested
        group); keys this model does not read are left aside."""
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in config.items() if k in names}
        for key, value in (config.get("rope_parameters") or {}).items():
            name = key if key == "rope_theta" else "rope_" + key
            if name in names:
                known[name] = value
        return cls(**known)


def param_shapes(cfg: Mistral4Config, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """The parameter tree as shapes: matrices in ``dtype``; norms and the router float32."""
    h, heads, f = cfg.hidden_size, cfg.num_attention_heads, cfg.moe_intermediate_size
    e, fs = cfg.n_routed_experts, cfg.n_shared_experts * cfg.moe_intermediate_size

    def mat(*shape: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, dtype)

    def vec(*shape: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    layer = {
        "attn_norm": vec(h), "ffn_norm": vec(h),
        "wdq": mat(h, cfg.q_lora_rank), "q_norm": vec(cfg.q_lora_rank),
        "wuq": mat(cfg.q_lora_rank, heads * cfg.qk_head_dim),
        "wdkv": mat(h, cfg.kv_lora_rank + cfg.qk_rope_head_dim), "kv_norm": vec(cfg.kv_lora_rank),
        "wukv": mat(cfg.kv_lora_rank, heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": mat(heads * cfg.v_head_dim, h),
        "gate": vec(h, cfg.router_width),
        "shared_w1": mat(h, fs), "shared_w3": mat(h, fs), "shared_w2": mat(fs, h),
        "w1": mat(e, h, f), "w3": mat(e, h, f), "w2": mat(e, f, h),
    }
    return {"embed": mat(cfg.vocab_size, h), "final_norm": vec(h), "lm_head": mat(h, cfg.vocab_size),
            "layers": [dict(layer) for _ in range(cfg.num_hidden_layers)]}


def init_params(cfg: Mistral4Config, seed: int = 0, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Random parameters (``slot_decoder.random_params``): matrices normal at
    ``1/sqrt(fan in)``, the table at 0.02, norms around 1. What a run serves
    when no parameter tree is given."""
    return random_params(param_shapes(cfg, dtype), seed)


def init_state(cfg: Mistral4Config, slots: int, max_len: int, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Empty slots: the latent cache (``ckv``, ``kr``) of every layer, ``pos`` and ``last``."""
    n = cfg.num_hidden_layers
    return {
        "ckv": [jnp.zeros((slots, max_len, cfg.kv_lora_rank), dtype) for _ in range(n)],
        "kr": [jnp.zeros((slots, max_len, cfg.qk_rope_head_dim), dtype) for _ in range(n)],
        "pos": jnp.zeros((slots,), jnp.int32),
        "last": jnp.zeros((slots,), jnp.int32),
    }


def _mm(a: jax.Array, w: jax.Array) -> jax.Array:
    """``a @ w`` with operands in the weights' dtype and a float32 result."""
    return jnp.dot(a.astype(w.dtype), w, precision=_precision(w.dtype), preferred_element_type=jnp.float32)


def _einsum(spec: str, a: jax.Array, b: jax.Array, dtype: Any) -> jax.Array:
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), precision=_precision(dtype),
                      preferred_element_type=jnp.float32)


def _norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def yarn_inv_freq(cfg: Mistral4Config) -> jax.Array:
    """YaRN's frequencies over the rotated half of a head ``(qk_rope_head_dim / 2,)``:
    the published ones where a pair turns more than ``beta_fast`` times in the
    original context, divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, a linear ramp between."""
    dim, theta = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    freq = jnp.float32(theta) ** (-2.0 * i / dim)

    def turns_at(n: float) -> float:  # the pair that turns ``n`` times in the original context
        return dim * math.log(cfg.rope_original_max_position_embeddings / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(turns_at(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(turns_at(cfg.rope_beta_slow)), dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / cfg.rope_factor * ramp


def _rope(x: jax.Array, positions: jax.Array, cfg: Mistral4Config) -> jax.Array:
    """Interleaved RoPE: pair ``i`` of ``x`` (..., tokens, [heads,] rope size) is
    ``(x[2i], x[2i+1])``, turned by ``position * inv_freq[i]``. ``positions`` is
    (tokens,); a heads axis, where there is one, follows the tokens axis. Written
    here and not taken from the reference, which spells its own out (complex
    multiplication): ``tests/test_mistral4.py`` holds the two against each other."""
    angle = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)
    if x.ndim == 3:
        angle = angle[:, None, :]
    # cos and sin are scaled by mscale's ratio to mscale_all_dim's: 1 here, as published
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _queries(p: Dict[str, jax.Array], h: jax.Array, positions: jax.Array, cfg: Mistral4Config):
    """(``q_nope`` (tokens, heads, nope), ``q_rope`` rotated (tokens, heads, rope)), float32."""
    with jax.named_scope("mla_q"):
        cq = _norm(_mm(h, p["wdq"]), p["q_norm"], cfg.rms_norm_eps)
        q = _mm(cq, p["wuq"]).reshape(h.shape[0], cfg.num_attention_heads, cfg.qk_head_dim)
        return q[..., : cfg.qk_nope_head_dim], _rope(q[..., cfg.qk_nope_head_dim :], positions, cfg)


def _latents(p: Dict[str, jax.Array], h: jax.Array, positions: jax.Array, cfg: Mistral4Config):
    """What the cache keeps of ``h``: (the normed latent ``ckv`` (tokens, kv rank),
    the rotated shared key ``kr`` (tokens, rope)), in the weights' dtype."""
    with jax.named_scope("mla_kv"):
        dtype = p["wdkv"].dtype
        both = _mm(h, p["wdkv"])
        ckv = _norm(both[:, : cfg.kv_lora_rank], p["kv_norm"], cfg.rms_norm_eps)
        kr = _rope(both[:, cfg.kv_lora_rank :], positions, cfg)
        return ckv.astype(dtype), kr.astype(dtype)


def _up(p: Dict[str, jax.Array], cfg: Mistral4Config) -> Tuple[jax.Array, jax.Array]:
    """``Wukv`` as (``Wuk`` (kv rank, heads, nope), ``Wuv`` (kv rank, heads, v))."""
    w = p["wukv"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim :]


def _causal_attention(q_nope: jax.Array, q_rope: jax.Array, k_nope: jax.Array, kr: jax.Array, v: jax.Array,
                      cfg: Mistral4Config, dtype: Any) -> jax.Array:
    """One prompt's causal attention in the expanded form: queries ``[q_nope |
    q_rope]`` (tokens, heads, 64 + 64) over keys ``[k_nope | kr]`` (``kr``
    (tokens, rope) is every head's) and values ``v`` (tokens, heads, v size);
    float32 (tokens, heads, v size). Operands in ``dtype``, sums and the softmax
    in float32, either way. The scores of a bucket are heads x tokens x tokens
    float32 (0.3 GB a layer at 1,536), written and read back three times by
    the XLA form: on a TPU the flash kernel takes its place where the bucket is
    a whole number of its blocks."""
    t, heads = q_nope.shape[:2]

    def products(q_nope, q_rope, k_nope, kr, v):
        scores = (_einsum("qhd,shd->hqs", q_nope, k_nope, dtype)
                  + _einsum("qhr,sr->hqs", q_rope, kr, dtype)) * cfg.softmax_scale
        causal = jnp.tril(jnp.ones((t, t), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return _einsum("hqs,shd->qhd", probs, v, dtype)

    block = next((b for b in (512, 256, 128) if t % b == 0), None)
    if dtype != jnp.bfloat16 or block is None:
        return products(q_nope, q_rope, k_nope, kr, v)

    def flash(q_nope, q_rope, k_nope, kr, v):
        from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes, flash_attention

        def heads_first(x):  # (tokens, heads, size) -> (1, heads, tokens, size)
            return x.astype(dtype).transpose(1, 0, 2)[None]

        q = heads_first(jnp.concatenate([q_nope, q_rope], axis=-1))
        k = heads_first(jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, None, :], (t, heads, kr.shape[-1]))], axis=-1))
        sizes = BlockSizes(block_q=block, block_k_major=block, block_k=block, block_b=1)
        out = flash_attention(q, k, heads_first(v), causal=True, sm_scale=cfg.softmax_scale, block_sizes=sizes)
        return out[0].transpose(1, 0, 2).astype(jnp.float32)

    return jax.lax.platform_dependent(q_nope, q_rope, k_nope, kr, v, tpu=flash, default=products)


def shared_expert(p: Dict[str, jax.Array], h: jax.Array) -> jax.Array:
    """The SwiGLU every token goes through, whichever experts it chose."""
    with jax.named_scope("moe_shared"):
        mid = jax.nn.silu(_mm(h, p["shared_w1"])) * _mm(h, p["shared_w3"])
        return _mm(mid, p["shared_w2"])


def routed_experts(p: Dict[str, jax.Array], h: jax.Array, valid: jax.Array,
                   cfg: Mistral4Config) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed sum over ``h`` (tokens, hidden,
    float32, already normed). Tokens outside ``valid`` choose no expert.
    Returns the float32 output and this call's ``COUNT_NAMES`` (4,)."""
    k, held, first = cfg.num_experts_per_tok, cfg.n_routed_experts, cfg.first_expert
    with jax.named_scope("moe_route"):
        probs = jax.nn.softmax(jnp.dot(h, p["gate"], precision=jax.lax.Precision.HIGHEST), axis=-1)
        weights, chosen = jax.lax.top_k(probs, k)
        if cfg.norm_topk_prob:  # over all the chosen, held here or not
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights * cfg.routed_scaling_factor
        here = valid[:, None] & (chosen >= first) & (chosen < first + held)
        local = jnp.where(here, chosen - first, held)
    out, group_sizes, batched = grouped_experts(p, h, local, weights, cfg.router_width)
    counts = jnp.stack([jnp.sum(group_sizes > 0, dtype=jnp.int32), jnp.sum(group_sizes, dtype=jnp.int32),
                        k * jnp.sum(valid, dtype=jnp.int32), batched])
    return out, counts


def _moe(p: Dict[str, jax.Array], h: jax.Array, valid: jax.Array, cfg: Mistral4Config):
    out, counts = routed_experts(p, h, valid, cfg)
    return shared_expert(p, h) + out, counts


def prefill_logits(params: Dict[str, Any], state: Dict[str, Any], ids: jax.Array, length: jax.Array,
                   slot: jax.Array, cfg: Mistral4Config) -> Tuple[Dict[str, Any], jax.Array, jax.Array]:
    """One prompt into one slot, attention in the expanded form. ``ids``
    (bucket,) holds ``length`` tokens and padding after them. Returns (the
    state, the logits of the prompt's last token (vocab,), the counts (4,))."""
    t, dtype, eps = ids.shape[0], params["embed"].dtype, cfg.rms_norm_eps
    positions = jnp.arange(t)
    valid = positions < length
    x = params["embed"][ids].astype(jnp.float32)
    state = dict(state, ckv=list(state["ckv"]), kr=list(state["kr"]))
    counts = jnp.zeros((len(COUNT_NAMES),), jnp.int32)
    for i, p in enumerate(params["layers"]):
        h = _norm(x, p["attn_norm"], eps)
        q_nope, q_rope = _queries(p, h, positions, cfg)
        ckv, kr = _latents(p, h, positions, cfg)
        with jax.named_scope("mla_attn"):
            kv = _mm(ckv, p["wukv"]).reshape(t, cfg.num_attention_heads, -1)  # [k_nope | v] per head
            k_nope, v = kv[..., : cfg.qk_nope_head_dim], kv[..., cfg.qk_nope_head_dim :]
            mixed = _causal_attention(q_nope, q_rope, k_nope, kr, v, cfg, dtype)
            out = _mm(mixed.reshape(t, -1), p["wo"])
            state["ckv"][i] = jax.lax.dynamic_update_slice(state["ckv"][i], ckv[None], (slot, 0, 0))
            state["kr"][i] = jax.lax.dynamic_update_slice(state["kr"][i], kr[None], (slot, 0, 0))
        x = x + out
        out, n = _moe(p, _norm(x, p["ffn_norm"], eps), valid, cfg)
        x = x + out
        counts = counts + n
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=False)
    return state, _mm(_norm(last, params["final_norm"], eps), params["lm_head"]), counts


def decode_logits(params: Dict[str, Any], state: Dict[str, Any], active: jax.Array,
                  cfg: Mistral4Config) -> Tuple[Dict[str, Any], jax.Array, jax.Array]:
    """One token for every slot, attention in the absorbed form over the
    latent cache: feeds ``state["last"]`` at ``state["pos"]``. Rows outside
    ``active`` write nothing and choose no expert. Returns (the state with the
    cache extended but ``pos``/``last`` as they were, logits (slots, vocab), the
    counts (4,))."""
    dtype, eps = params["embed"].dtype, cfg.rms_norm_eps
    pos = state["pos"]
    slots, max_len = pos.shape[0], state["ckv"][0].shape[1]
    rows = jnp.arange(slots)
    write_at = jnp.where(active, pos, max_len)  # past the end: dropped
    seen = jnp.arange(max_len)[None, :] <= pos[:, None]
    x = params["embed"][state["last"]].astype(jnp.float32)
    state = dict(state, ckv=list(state["ckv"]), kr=list(state["kr"]))
    counts = jnp.zeros((len(COUNT_NAMES),), jnp.int32)
    for i, p in enumerate(params["layers"]):
        h = _norm(x, p["attn_norm"], eps)
        q_nope, q_rope = _queries(p, h, pos, cfg)
        ckv, kr = _latents(p, h, pos, cfg)
        with jax.named_scope("mla_attn"):
            latents = state["ckv"][i] = state["ckv"][i].at[rows, write_at].set(ckv, mode="drop")
            keys = state["kr"][i] = state["kr"][i].at[rows, write_at].set(kr, mode="drop")
            wuk, wuv = _up(p, cfg)
            folded = _einsum("bhd,chd->bhc", q_nope, wuk, dtype)  # Wuk into the query
            scores = (_einsum("bhc,bsc->bhs", folded, latents, dtype)
                      + _einsum("bhr,bsr->bhs", q_rope, keys, dtype)) * cfg.softmax_scale
            probs = jax.nn.softmax(jnp.where(seen[:, None, :], scores, -jnp.inf), axis=-1)
            mixed = _einsum("bhs,bsc->bhc", probs, latents, dtype)
            out = _mm(_einsum("bhc,chd->bhd", mixed, wuv, dtype).reshape(slots, -1), p["wo"])  # Wuv after the mix
        x = x + out
        out, n = _moe(p, _norm(x, p["ffn_norm"], eps), active, cfg)
        x = x + out
        counts = counts + n
    return state, _mm(_norm(x, params["final_norm"], eps), params["lm_head"]), counts


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


class Mistral4Decoder(SlotDecoder):
    """The ``mistral4`` decoder as the generation service drives it (``models/slot_decoder.py``)."""

    count_names = COUNT_NAMES
    lm_prefill, lm_decode = staticmethod(lm_prefill), staticmethod(lm_decode)
    init_params, init_state = staticmethod(init_params), staticmethod(init_state)
