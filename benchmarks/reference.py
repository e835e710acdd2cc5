"""The plain reference: tokenizer, MiniLM forward and exact cosine top-k.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``; it imports nothing of the program and
takes nothing the program has made. Its weights and the resident rows are the
run's inputs (``weights.py``), its documents and queries the run's texts.

``precision="fp8"`` is the control: the same forward, or the same scoring, with
every matrix product's operands rounded to float8 (e4m3), the nearest precision
below the bfloat16 the configuration states for the encoder's products and for
the index's scoring passes.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Tuple

import numpy as np
import xxhash

import jax
import jax.numpy as jnp

CLS, SEP, PAD = 101, 102, 0


def tokenize(texts: List[str], vocab_size: int, max_length: int) -> np.ndarray:
    """Word-hash ids as the configuration states them: lower-cased whitespace
    words, at most ``max_length - 2`` of them, each ``2000 + xxh32(word) mod
    (vocab_size - 3000)``, between [CLS] and [SEP]; 0 pads."""
    rows = []
    for t in texts:
        words = str(t).lower().split()[: max_length - 2]
        rows.append([CLS] + [2000 + xxhash.xxh32_intdigest(w) % (vocab_size - 3000) for w in words] + [SEP])
    ids = np.zeros((len(rows), max(len(r) for r in rows)), dtype=np.int32)
    for i, r in enumerate(rows):
        ids[i, : len(r)] = r
    return ids


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def lowered(x: jax.Array, precision: str) -> jax.Array:
    """``x`` as an operand of a matrix product in ``precision``."""
    x = x.astype(jnp.float32)
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "int8":  # symmetric, one scale per tensor
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
        return jnp.round(x / scale) * scale
    assert precision == "f32", precision
    return x


@functools.partial(jax.jit, static_argnames=("heads", "eps", "precision"))
def _forward(w: Dict[str, jax.Array], ids: jax.Array, *, heads: int, eps: float, precision: str):
    op = functools.partial(lowered, precision=precision)  # an operand of a matrix product

    with jax.default_matmul_precision("highest"):
        mask = ids != PAD
        n, s = ids.shape
        x = (w["word_emb"][ids].astype(jnp.float32) + w["pos_emb"][:s][None].astype(jnp.float32)
             + w["type_emb"][0][None, None].astype(jnp.float32))
        x = _layer_norm(x, w["emb_ln_g"], w["emb_ln_b"], eps)
        hd = x.shape[-1] // heads
        for l in range(w["wq"].shape[0]):
            def proj(wn, bn):
                return (op(x) @ op(w[wn][l]) + w[bn][l]).reshape(n, s, heads, hd)
            q, k, v = proj("wq", "bq"), proj("wk", "bk"), proj("wv", "bv")
            scores = jnp.einsum("nqhd,nkhd->nhqk", op(q), op(k)) / np.sqrt(hd)
            scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("nhqk,nkhd->nqhd", op(probs), op(v)).reshape(n, s, heads * hd)
            x = _layer_norm(x + op(ctx) @ op(w["wo"][l]) + w["bo"][l], w["ln1_g"][l], w["ln1_b"][l], eps)
            ff = jax.nn.gelu(op(x) @ op(w["w1"][l]) + w["b1"][l], approximate=False)
            x = _layer_norm(x + op(ff) @ op(w["w2"][l]) + w["b2"][l], w["ln2_g"][l], w["ln2_b"][l], eps)
        m = mask[:, :, None].astype(jnp.float32)
        pooled = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1e-9)
        return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def embed_texts(w: Dict[str, jax.Array], texts: List[str], model: Dict[str, Any],
                precision: str = "f32", block: int = 256) -> jax.Array:
    """(n, hidden) float32 unit vectors, in blocks of ``block`` texts, every block
    padded to the call's longest text rounded up to 16 tokens (pads are masked),
    so that one call compiles one program."""
    ids_all = tokenize(texts, model["vocab_size"], model["max_length"])
    width = -(-ids_all.shape[1] // 16) * 16
    parts = []
    for lo in range(0, len(texts), block):
        ids = np.zeros((block, width), dtype=np.int32)
        ids[:, 0] = CLS  # filler rows: one token, so no row is all padding
        chunk = ids_all[lo : lo + block]
        ids[: len(chunk), : chunk.shape[1]] = chunk
        vecs = _forward(w, jnp.asarray(ids), heads=model["num_attention_heads"],
                        eps=model["layer_norm_eps"], precision=precision)
        parts.append(vecs[: len(chunk)])
    return jnp.concatenate(parts)


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _block_topk(queries, rows, k, precision):
    with jax.default_matmul_precision("highest"):
        qn = queries / jnp.linalg.norm(queries, axis=1, keepdims=True)
        rn = rows / jnp.maximum(jnp.linalg.norm(rows, axis=1, keepdims=True), 1e-30)
        return jax.lax.top_k(lowered(qn, precision) @ lowered(rn, precision).T, k)


def exact_topk(queries: jax.Array, blocks: List[Callable[[], Tuple[jax.Array, int]]],
               k: int, precision: str = "f32") -> Tuple[np.ndarray, np.ndarray]:
    """Exact cosine top-k of every query over all the rows, block by block.
    ``blocks`` yields ``(rows, first_id)`` pairs; ids count rows over all blocks.
    ``precision="fp8"`` is the index's control: rows and queries rounded to
    float8 before the scoring product."""
    best_s = np.full((queries.shape[0], 0), -np.inf, dtype=np.float32)
    best_i = np.zeros((queries.shape[0], 0), dtype=np.int64)
    for make in blocks:
        rows, first = make()
        s, i = jax.device_get(_block_topk(queries, rows, min(k, rows.shape[0]), precision))
        best_s = np.concatenate([best_s, s], axis=1)
        best_i = np.concatenate([best_i, i.astype(np.int64) + first], axis=1)
        keep = np.argsort(-best_s, axis=1, kind="stable")[:, :k]
        best_s = np.take_along_axis(best_s, keep, axis=1)
        best_i = np.take_along_axis(best_i, keep, axis=1)
    return best_s, best_i


def cosine_to(queries: jax.Array, rows: jax.Array) -> np.ndarray:
    """(n_queries, n_rows) exact cosines (the scores of given rows)."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(queries @ rows.T)
