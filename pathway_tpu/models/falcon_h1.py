"""The ``falcon_h1`` decoder (Falcon-H1-34B-Instruct's family) on the device: two programs over slots.

Every block runs **two token mixers on one normed input, side by side**: a
Mamba-2 state-space mixer and grouped-query attention, their outputs scaled and
added; a SwiGLU follows. ``models/falcon_h1_reference.py`` has the equations,
the muP multipliers and what the config is silent on; this file is the same
mathematics as two jitted programs that keep every request's state on the
device, in *slots* (``models/slot_decoder.py``, ``models/generation_service.py``):

- ``lm_prefill``: one prompt, padded on the right to a bucket, into one slot.
  The state-space mixer runs in Mamba-2's **chunked form** (SSD, arXiv:2405.21060,
  its minimal listing; ``ssd_scan``): products inside chunks of
  ``mamba_chunk_size`` tokens and a short recurrence over the chunks' states, so
  that the prefill stays product-bound where the token recurrence would rewrite
  the whole state a token. A place at or past the prompt's length gets ``dt = 0``:
  its decay is 1 and its input 0, so the state after the bucket *is* the state
  after the last real token, and it is written to the slot as it stands.
  Attention as ``models/lfm2.py``'s prefill (XLA products over the bucket).
- ``lm_decode``: one step over all slots, one greedy token a slot: the
  convolution over the slot's tail and the new input, **the recurrence itself**
  on the slot's state, attention over the cache. A slot that holds no request
  writes no state. The recurrence is one Pallas kernel a block (``ssm_step``)
  that visits the live slots only and reads and writes each of their states
  once, in place; the rest of the step is written as LFM2's is (all slots,
  ``where(active)``). The one count both programs return (``COUNT_NAMES``: the
  (slot, layer) states a call read and rewrote) is the live slots a block in a
  step and the blocks in a prefill.

**Three kinds of state live side by side in a slot**, for every layer: ``k``/``v``
``(slots, max_len, kv heads, head size)``, which grow with the context; ``ssm``
``(slots, heads, d_head, state)`` float32, the recurrent state, constant in the
context and rewritten whole at every token; ``tail`` ``(slots, mamba_d_conv - 1,
d_ssm + 2 groups x state)``, the convolution's last inputs; beside ``pos`` and
``last``. Nothing is zeroed when a slot is freed: a prefill starts from a zero
state, not from the slot's old one, and overwrites ``ssm`` and ``tail`` whole.

Precision as the other decoders: weights, and activations wherever they are a
product's operand, have the dtype of ``params["embed"]`` (bfloat16 as served);
products accumulate in float32; the residual stream, RMSNorm, ``dt``, the decays
and their cumulative sums, the state-space state, RoPE, softmax and the logits
are float32; keys, values and the convolution tail have the weights' dtype.
Layers are unrolled. ``_mm``, ``_norm`` and ``_rope`` are ``models/lfm2.py``'s,
``_einsum`` ``models/mistral4.py``'s.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.models.lfm2 import _mm, _norm, _rope
from pathway_tpu.models.mistral4 import _einsum
from pathway_tpu.models.slot_decoder import SlotDecoder, random_params

# what a call counts beside its tokens: the (slot, layer) state-space states it read and rewrote
COUNT_NAMES = ("state_rows",)

# what a ``config.json`` may say that these programs do not compute
_NOT_COMPUTED = {"mamba_norm_before_gate": True, "mamba_rms_norm": False, "mamba_conv_bias": False,
                 "mamba_proj_bias": True, "projectors_bias": True, "attention_bias": True, "mlp_bias": True,
                 "tie_word_embeddings": True}


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The published ``config.json`` keys that shape the model, at Falcon-H1-34B-Instruct's values."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369, 0.011160714285714284)

    def __post_init__(self) -> None:
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(f"mamba_d_ssm {self.mamba_d_ssm} is not {self.mamba_n_heads} heads of {self.mamba_d_head}")
        if self.mamba_n_heads % self.mamba_n_groups or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads do not divide into their groups")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has five entries (z, x, B, C, dt) and mlp_multipliers two (gate, down)")

    @property
    def conv_dim(self) -> int:
        """The places the convolution runs over: x, B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "FalconH1Config":
        """From a ``config.json`` as published; keys this model does not read
        are left aside, and one that asks for what it does not compute is refused."""
        asked = [k for k, v in _NOT_COMPUTED.items() if config.get(k, not v) == v]
        asked += [k for k in ("attn_layer_indices", "rope_scaling") if config.get(k) is not None]
        if asked:
            raise ValueError(f"falcon_h1: not computed here: {asked}")
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in config.items() if k in names}
        for key in ("ssm_multipliers", "mlp_multipliers"):
            if key in known:
                known[key] = tuple(known[key])
        return cls(**known)


def param_shapes(cfg: FalconH1Config, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """The parameter tree as shapes: matrices in ``dtype``; norms, the
    convolution and the state-space mixer's vectors float32."""
    h, f, d, heads = cfg.hidden_size, cfg.intermediate_size, cfg.mamba_d_ssm, cfg.mamba_n_heads
    nq, nkv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim

    def mat(*shape: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, dtype)

    def vec(*shape: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    layer = {
        "input_norm": vec(h), "pre_ff_norm": vec(h),
        "in_proj": mat(h, d + cfg.conv_dim + heads), "conv_w": vec(cfg.conv_dim, cfg.mamba_d_conv),
        "conv_b": vec(cfg.conv_dim), "A_log": vec(heads), "D": vec(heads), "dt_bias": vec(heads),
        "ssm_norm": vec(d), "out_proj": mat(d, h),
        "wq": mat(h, nq), "wk": mat(h, nkv), "wv": mat(h, nkv), "wo": mat(nq, h),
        "w1": mat(h, f), "w3": mat(h, f), "w2": mat(f, h),
    }
    return {"embed": mat(cfg.vocab_size, h), "final_norm": vec(h), "lm_head": mat(h, cfg.vocab_size),
            "layers": [dict(layer) for _ in range(cfg.num_hidden_layers)]}


def init_params(cfg: FalconH1Config, seed: int = 0, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Random parameters (``slot_decoder.random_params``): matrices normal at
    ``1/sqrt(fan in)``, the table at 0.02, norms around 1, the state-space
    mixer's vectors as Mamba-2 initialises them. What a run serves when no
    parameter tree is given."""
    return random_params(param_shapes(cfg, dtype), seed)


def init_state(cfg: FalconH1Config, slots: int, max_len: int, dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Empty slots: for every layer keys and values, the state-space state
    (float32) and the convolution tail; ``pos`` and ``last``."""
    n = cfg.num_hidden_layers
    kv = (slots, max_len, cfg.num_key_value_heads, cfg.head_dim)
    return {
        "k": [jnp.zeros(kv, dtype) for _ in range(n)],
        "v": [jnp.zeros(kv, dtype) for _ in range(n)],
        "ssm": [jnp.zeros((slots, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state), jnp.float32)
                for _ in range(n)],
        "tail": [jnp.zeros((slots, cfg.mamba_d_conv - 1, cfg.conv_dim), dtype) for _ in range(n)],
        "pos": jnp.zeros((slots,), jnp.int32),
        "last": jnp.zeros((slots,), jnp.int32),
    }


def _mup(cfg: FalconH1Config) -> jax.Array:
    """``ssm_multipliers`` over the places of ``in_proj``'s output: z, x, B, C, dt."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    sizes = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return jnp.concatenate([jnp.full((n,), m, jnp.float32) for n, m in zip(sizes, cfg.ssm_multipliers)])


def _ssm_inputs(p: Dict[str, jax.Array], u: jax.Array, cfg: FalconH1Config):
    """``in_proj`` of ``u`` (rows, hidden) scaled by ``mup`` and split: the gate
    ``z`` (float32), the convolution's input ``xbc`` (the weights' dtype, as the
    tail keeps it) and ``dt`` before its bias (float32)."""
    d = cfg.mamba_d_ssm
    zxbcdt = _mm(u, p["in_proj"]) * _mup(cfg)
    return zxbcdt[:, :d], zxbcdt[:, d : d + cfg.conv_dim].astype(p["in_proj"].dtype), zxbcdt[:, d + cfg.conv_dim :]


def _split_xbc(xbc: jax.Array, cfg: FalconH1Config):
    """The convolution's output (rows, conv_dim) as ``x`` (rows, groups, heads a
    group, d_head) and ``B``, ``C`` (rows, groups, state): head ``j`` is of group
    ``j // (heads / groups)``."""
    rows, d, g, n = xbc.shape[0], cfg.mamba_d_ssm, cfg.mamba_n_groups, cfg.mamba_d_state
    x = xbc[:, :d].reshape(rows, g, cfg.mamba_n_heads // g, cfg.mamba_d_head)
    return x, xbc[:, d : d + g * n].reshape(rows, g, n), xbc[:, d + g * n :].reshape(rows, g, n)


def _by_group(v: jax.Array, cfg: FalconH1Config) -> jax.Array:
    """A per-head vector (..., heads) as (..., groups, heads a group)."""
    return v.reshape(v.shape[:-1] + (cfg.mamba_n_groups, cfg.mamba_n_heads // cfg.mamba_n_groups))


def _ssm_out(p: Dict[str, jax.Array], y: jax.Array, z: jax.Array, cfg: FalconH1Config) -> jax.Array:
    """``y`` (rows, d_ssm) gated by ``z``, RMSNorm per group, ``out_proj``."""
    rows, g = y.shape[0], cfg.mamba_n_groups
    y = (y * jax.nn.silu(z)).reshape(rows, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.rms_norm_eps)
    return _mm(y.reshape(rows, -1) * p["ssm_norm"], p["out_proj"])


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, chunk: int,
             dtype: Any) -> Tuple[jax.Array, jax.Array]:
    """The state-space recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (outer)
    B_t``, ``y_t = S_t C_t`` from a zero state, in chunks of ``chunk`` tokens.
    ``x`` (T, groups, heads a group, d_head), ``dt`` (T, groups, heads a group),
    ``a`` (groups, heads a group), ``b``, ``c`` (T, groups, state). Returns ``y``
    like ``x`` and the state after the last token (groups, heads a group,
    d_head, state), both float32. A token with ``dt = 0`` leaves the state as
    it was. The log-decays and their sums are float32; the four products take
    their operands in ``dtype``."""
    t = x.shape[0]
    pad = -t % chunk
    if pad:  # places with dt = 0 up to a whole number of chunks
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)) for v in (x, dt, b, c))
    x, dt, b, c = (v.reshape((-1, chunk) + v.shape[1:]) for v in (x, dt, b, c))  # (chunks, chunk, ...)
    with jax.named_scope("ssd_scan"):
        cum = jnp.cumsum(dt * a, axis=1)  # (chunks, chunk, groups, heads a group): log-decay since the chunk's start
        dtx = x * dt[..., None]
        # (1) inside a chunk: Y = (L * (C B^T)) (dt x), L = exp(segsum) lower-triangular
        by_head = jnp.moveaxis(cum, 1, -1)  # (chunks, groups, heads a group, chunk): the token axes last and large
        seg = by_head[..., :, None] - by_head[..., None, :]  # [..., l, s]: the log-decay from after s to l
        decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)), seg, -jnp.inf))
        cb = _einsum("clgn,csgn->cgls", c, b, dtype)
        y = _einsum("cgrls,csgrp->clgrp", decay * cb[:, :, None], dtx, dtype)
        # (2) a chunk's own state: sum_s exp(cum_end - cum_s) B_s (outer) dt_s x_s
        to_end = jnp.exp(cum[:, -1:] - cum)
        own = _einsum("csgn,csgrp->cgrpn", b, dtx * to_end[..., None], dtype)

        # (3) the recurrence over the chunks: the state before each, and after the last
        def step(state, inputs):
            chunk_decay, chunk_state = inputs
            return chunk_decay[..., None, None] * state + chunk_state, state

        last, before = jax.lax.scan(step, jnp.zeros(own.shape[1:], jnp.float32), (jnp.exp(cum[:, -1]), own))
        # (4) what the state before the chunk gives its tokens
        y = y + _einsum("clgn,cgrpn->clgrp", c, before, dtype) * jnp.exp(cum)[..., None]
    return y.reshape((-1,) + y.shape[2:])[:t], last


def ssm_step(state: jax.Array, decay: jax.Array, dtx: jax.Array, b: jax.Array, c: jax.Array,
             active: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence for the live slots, as one Pallas kernel:
    ``S' = decay S + dtx (outer) B``, ``y = S' C``, all float32. ``state``
    (slots, heads, d_head, state) is rewritten in place (the caller donates it);
    ``decay`` (slots, heads, 1), ``dtx`` (slots, heads, d_head), ``b`` and ``c``
    (slots, heads, state) per head. Returns (the state, ``y`` (slots, heads,
    d_head), 0 in a slot that is not live).

    The grid walks the slots with the live ones first (their order and count
    scalar-prefetched), a whole slot's state a place (blocks of 8 heads
    measured slower on the chip, PR 36): it is fetched once,
    advanced and read against ``C`` from the same copy in VMEM, and written
    back once. Every place past the live count maps to the last live slot, so
    the pipeline neither fetches nor writes back there and the body runs
    nothing: a slot that holds no request is never touched. With no live slot
    the first place copies slot ``order[0]`` through unchanged, so every block
    the pipeline writes back is one the body wrote. Off the TPU it runs in the
    Pallas interpreter."""
    slots, heads, d_head, n = state.shape
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    n_live = jnp.sum(active, dtype=jnp.int32).reshape(1)

    def kernel(order_ref, n_live_ref, decay_ref, dtx_ref, b_ref, c_ref, state_ref, out_ref, y_ref):
        s = pl.program_id(0)

        @pl.when(s < n_live_ref[0])
        def _advance():
            new = decay_ref[0][:, :, None] * state_ref[0] + dtx_ref[0][:, :, None] * b_ref[0][:, None, :]
            out_ref[0] = new
            y_ref[0] = jnp.sum(new * c_ref[0][:, None, :], axis=-1)

        @pl.when((s == 0) & (n_live_ref[0] == 0))
        def _copy_through():
            out_ref[...] = state_ref[...]

    def spec(*shape):  # one slot, whole
        return pl.BlockSpec((1, heads) + shape,
                            lambda s, order, n_live: (order[jnp.minimum(s, jnp.maximum(n_live[0], 1) - 1)], 0)
                            + (0,) * len(shape))

    # the state's block in and out, each double-buffered (16 MiB at 32 x 128 x 256), and room for the rest
    block_bytes = heads * d_head * n * 4
    new, y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[spec(1), spec(d_head), spec(n), spec(n), spec(d_head, n)],
            out_specs=[spec(d_head, n), spec(d_head)],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((slots, heads, d_head), jnp.float32)],
        input_output_aliases={6: 0},  # the state: operand 6 counting the two prefetched
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=4 * block_bytes + (4 << 20)),
        interpret=jax.default_backend() != "tpu",
        name="ssm_step",
    )(order, n_live, decay, dtx, b, c, state)
    return new, jnp.where(active[:, None, None], y, 0.0)


def _mlp(p: Dict[str, jax.Array], u: jax.Array, cfg: FalconH1Config) -> jax.Array:
    with jax.named_scope("mlp_op"):
        gate_multiplier, down_multiplier = cfg.mlp_multipliers
        mid = _mm(u, p["w3"]) * jax.nn.silu(_mm(u, p["w1"]) * gate_multiplier)
        return _mm(mid, p["w2"]) * down_multiplier


def _qkv(p: Dict[str, jax.Array], u: jax.Array, positions: jax.Array, cfg: FalconH1Config):
    """Queries, keys (scaled by ``key_multiplier``; both after RoPE) and values
    of ``u`` (rows, hidden) at ``positions``, in the weights' dtype."""
    n, hd, dtype = u.shape[0], cfg.head_dim, p["wq"].dtype
    q = _mm(u, p["wq"]).reshape(n, cfg.num_attention_heads, hd)
    k = (_mm(u, p["wk"]) * cfg.key_multiplier).reshape(n, cfg.num_key_value_heads, hd)
    v = _mm(u, p["wv"]).reshape(n, cfg.num_key_value_heads, hd)
    q, k = _rope(q, positions, cfg.rope_theta), _rope(k, positions, cfg.rope_theta)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def prefill_logits(params: Dict[str, Any], state: Dict[str, Any], ids: jax.Array, length: jax.Array,
                   slot: jax.Array, cfg: FalconH1Config) -> Tuple[Dict[str, Any], jax.Array, jax.Array]:
    """One prompt into one slot. ``ids`` (bucket,) holds ``length`` tokens and
    padding after them. Returns (the state, the logits of the prompt's last
    token (vocab,), the counts (1,))."""
    t, dtype, eps = ids.shape[0], params["embed"].dtype, cfg.rms_norm_eps
    positions = jnp.arange(t)
    valid = positions < length
    x = params["embed"][ids].astype(jnp.float32) * cfg.embedding_multiplier
    group, taps = cfg.num_attention_heads // cfg.num_key_value_heads, cfg.mamba_d_conv
    causal = jnp.tril(jnp.ones((t, t), bool))
    state = dict(state, **{name: list(state[name]) for name in ("k", "v", "ssm", "tail")})
    counts = jnp.zeros((len(COUNT_NAMES),), jnp.int32)
    for i, p in enumerate(params["layers"]):
        h = _norm(x, p["input_norm"], eps)
        with jax.named_scope("ssm_op"):
            z, xbc, dt = _ssm_inputs(p, cfg.ssm_in_multiplier * h, cfg)
            padded = jnp.concatenate([jnp.zeros((taps - 1, cfg.conv_dim), dtype), xbc], axis=0)
            conv = sum(p["conv_w"][:, j] * padded[j : j + t].astype(jnp.float32) for j in range(taps)) + p["conv_b"]
            xs, b, c = _split_xbc(jax.nn.silu(conv), cfg)
            # dt = 0 at and past the prompt's length: the decay is 1 and the input 0 there
            dt = jnp.where(valid[:, None], jax.nn.softplus(dt + p["dt_bias"]), 0.0)
            y, last = ssd_scan(xs, _by_group(dt, cfg), _by_group(-jnp.exp(p["A_log"]), cfg), b, c,
                               cfg.mamba_chunk_size, dtype)
            y = y + _by_group(p["D"], cfg)[..., None] * xs
            mixed = cfg.ssm_out_multiplier * _ssm_out(p, y.reshape(t, -1), z, cfg)
            # the inputs at length - (taps - 1) .. length - 1: zeros where the prompt is shorter
            tail = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, axis=0)
            state["tail"][i] = jax.lax.dynamic_update_slice_in_dim(state["tail"][i], tail[None], slot, axis=0)
            state["ssm"][i] = jax.lax.dynamic_update_slice_in_dim(
                state["ssm"][i], last.reshape((1,) + state["ssm"][i].shape[1:]), slot, axis=0)
            counts = counts + 1
        with jax.named_scope("attn_op"):
            q, k, v = _qkv(p, cfg.attention_in_multiplier * h, positions, cfg)
            qg = q.reshape(t, cfg.num_key_value_heads, group, cfg.head_dim)
            scores = _einsum("qkgd,skd->kgqs", qg, k, dtype) / jnp.sqrt(jnp.float32(cfg.head_dim))
            probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
            out = _mm(_einsum("kgqs,skd->qkgd", probs, v, dtype).reshape(t, -1), p["wo"])
            state["k"][i] = jax.lax.dynamic_update_slice(state["k"][i], k[None], (slot, 0, 0, 0))
            state["v"][i] = jax.lax.dynamic_update_slice(state["v"][i], v[None], (slot, 0, 0, 0))
        x = x + mixed + cfg.attention_out_multiplier * out
        x = x + _mlp(p, _norm(x, p["pre_ff_norm"], eps), cfg)
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=False)
    logits = _mm(_norm(last, params["final_norm"], eps), params["lm_head"]) * cfg.lm_head_multiplier
    return state, logits, counts


def decode_logits(params: Dict[str, Any], state: Dict[str, Any], active: jax.Array,
                  cfg: FalconH1Config) -> Tuple[Dict[str, Any], jax.Array, jax.Array]:
    """One token for every slot: feeds ``state["last"]`` at ``state["pos"]``.
    Rows outside ``active`` write nothing. Returns (the state with keys, values,
    tails and state-space states advanced but ``pos``/``last`` as they were,
    logits (slots, vocab), the counts (1,))."""
    dtype, eps = params["embed"].dtype, cfg.rms_norm_eps
    pos = state["pos"]
    slots, max_len = pos.shape[0], state["k"][0].shape[1]
    rows = jnp.arange(slots)
    write_at = jnp.where(active, pos, max_len)  # past the end: dropped
    seen = jnp.arange(max_len)[None, :] <= pos[:, None]
    x = params["embed"][state["last"]].astype(jnp.float32) * cfg.embedding_multiplier
    group, heads = cfg.num_attention_heads // cfg.num_key_value_heads, cfg.mamba_n_heads
    live = jnp.sum(active, dtype=jnp.int32)

    def per_head(v):  # (slots, groups, state) as (slots, heads, state): a head's group's row
        return jnp.repeat(v, heads // cfg.mamba_n_groups, axis=1)

    state = dict(state, **{name: list(state[name]) for name in ("k", "v", "ssm", "tail")})
    counts = jnp.zeros((len(COUNT_NAMES),), jnp.int32)
    for i, p in enumerate(params["layers"]):
        h = _norm(x, p["input_norm"], eps)
        with jax.named_scope("ssm_op"):
            z, xbc, dt = _ssm_inputs(p, cfg.ssm_in_multiplier * h, cfg)
            tail = state["tail"][i]
            window = jnp.concatenate([tail, xbc[:, None]], axis=1)
            conv = jnp.einsum("bjc,cj->bc", window.astype(jnp.float32), p["conv_w"],
                              precision=jax.lax.Precision.HIGHEST) + p["conv_b"]
            xs, b, c = _split_xbc(jax.nn.silu(conv), cfg)
            dt = _by_group(jax.nn.softplus(dt + p["dt_bias"]), cfg)  # (slots, groups, heads a group)
            decay = jnp.exp(dt * _by_group(-jnp.exp(p["A_log"]), cfg)).reshape(slots, heads, 1)
            dtx = (dt[..., None] * xs).reshape(slots, heads, cfg.mamba_d_head)
            # only the live slots' states are read and rewritten, once each: the count says so
            state["ssm"][i], y = ssm_step(state["ssm"][i], decay, dtx, per_head(b), per_head(c), active)
            y = y.reshape(xs.shape) + _by_group(p["D"], cfg)[..., None] * xs
            mixed = cfg.ssm_out_multiplier * _ssm_out(p, y.reshape(slots, -1), z, cfg)
            state["tail"][i] = jnp.where(active[:, None, None], window[:, 1:], tail)
            counts = counts + live
        with jax.named_scope("attn_op"):
            q, k, v = _qkv(p, cfg.attention_in_multiplier * h, pos, cfg)
            keys = state["k"][i] = state["k"][i].at[rows, write_at].set(k, mode="drop")
            values = state["v"][i] = state["v"][i].at[rows, write_at].set(v, mode="drop")
            qg = q.reshape(slots, cfg.num_key_value_heads, group, cfg.head_dim)
            scores = _einsum("bkgd,bskd->bkgs", qg, keys, dtype) / jnp.sqrt(jnp.float32(cfg.head_dim))
            probs = jax.nn.softmax(jnp.where(seen[:, None, None, :], scores, -jnp.inf), axis=-1)
            out = _mm(_einsum("bkgs,bskd->bkgd", probs, values, dtype).reshape(slots, -1), p["wo"])
        x = x + mixed + cfg.attention_out_multiplier * out
        x = x + _mlp(p, _norm(x, p["pre_ff_norm"], eps), cfg)
    logits = _mm(_norm(x, params["final_norm"], eps), params["lm_head"]) * cfg.lm_head_multiplier
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


class FalconH1Decoder(SlotDecoder):
    """The ``falcon_h1`` decoder as the generation service drives it (``models/slot_decoder.py``)."""

    count_names = COUNT_NAMES
    lm_prefill, lm_decode = staticmethod(lm_prefill), staticmethod(lm_decode)
    init_params, init_state = staticmethod(init_params), staticmethod(init_state)
