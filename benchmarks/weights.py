"""The encoder's weights and the resident rows, made on the device from ``--seed``.

Both are inputs of a run, made by the benchmark and handed to the program and to
the plain reference alike; neither takes anything the other has made. Weights
come out of ONE jitted call in the types they are served in (matrices and
embedding tables bfloat16, biases and layer norms float32), under the plain
reference's flat names with a leading layer axis.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key for any whole-number seed, past 2**31 too, and one of a few streams."""
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, int(seed) >> 31), stream)


@functools.partial(jax.jit, static_argnames=("vocab", "positions", "types", "hidden", "ffn",
                                              "layers", "std", "word_std"))
def _make(key, *, vocab, positions, types, hidden, ffn, layers, std, word_std):
    ks = iter(jax.random.split(key, 32))

    def mat(shape, s=std):
        return (s * jax.random.normal(next(ks), shape, jnp.float32)).astype(jnp.bfloat16)

    def vec(shape):
        return std * jax.random.normal(next(ks), shape, jnp.float32)

    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    return {
        "word_emb": mat((vocab, hidden), word_std), "pos_emb": mat((positions, hidden)),
        "type_emb": mat((types, hidden)),
        "emb_ln_g": ones((hidden,)), "emb_ln_b": vec((hidden,)),
        "wq": mat((layers, hidden, hidden)), "wk": mat((layers, hidden, hidden)),
        "wv": mat((layers, hidden, hidden)), "wo": mat((layers, hidden, hidden)),
        "bq": vec((layers, hidden)), "bk": vec((layers, hidden)), "bv": vec((layers, hidden)),
        "bo": vec((layers, hidden)),
        "ln1_g": ones((layers, hidden)), "ln1_b": vec((layers, hidden)),
        "w1": mat((layers, hidden, ffn)), "b1": vec((layers, ffn)),
        "w2": mat((layers, ffn, hidden)), "b2": vec((layers, hidden)),
        "ln2_g": ones((layers, hidden)), "ln2_b": vec((layers, hidden)),
    }


def make_weights(seed: int, model: Dict[str, Any], init: Dict[str, Any]):
    """(the reference's flat weights, the same arrays as the program's tree):
    one jitted call from the seed, so one program to compile and to cache."""
    heads = int(model["num_attention_heads"])

    @jax.jit
    def both(key):
        flat = _make(
            key, vocab=model["vocab_size"], positions=model["max_position_embeddings"],
            types=model["type_vocab_size"], hidden=model["hidden_size"],
            ffn=model["intermediate_size"], layers=model["num_hidden_layers"],
            std=float(init["std"]), word_std=float(init["word_embedding_std"]),
        )
        return flat, to_flax_tree(flat, heads)

    return both(seed_key(seed, 0))


def to_flax_tree(w: Dict[str, jax.Array], heads: int) -> Dict[str, Any]:
    """The same arrays under the names and shapes ``flax.linen`` gives the
    program's ``SentenceEncoder`` (heads split out of the projection matrices)."""
    h, nh = w["wq"].shape[-1], heads
    hd = h // nh
    p: Dict[str, Any] = {
        "word_embeddings": {"embedding": w["word_emb"]},
        "position_embeddings": {"embedding": w["pos_emb"]},
        "token_type_embeddings": {"embedding": w["type_emb"]},
        "embeddings_norm": {"scale": w["emb_ln_g"], "bias": w["emb_ln_b"]},
    }
    for i in range(w["wq"].shape[0]):
        attn = {
            name: {"kernel": w["w" + c][i].reshape(h, nh, hd), "bias": w["b" + c][i].reshape(nh, hd)}
            for name, c in (("query", "q"), ("key", "k"), ("value", "v"))
        }
        attn["out"] = {"kernel": w["wo"][i].reshape(nh, hd, h), "bias": w["bo"][i]}
        p[f"layer_{i}"] = {
            "attention": attn,
            "attention_norm": {"scale": w["ln1_g"][i], "bias": w["ln1_b"][i]},
            "intermediate": {"kernel": w["w1"][i], "bias": w["b1"][i]},
            "output": {"kernel": w["w2"][i], "bias": w["b2"][i]},
            "output_norm": {"scale": w["ln2_g"][i], "bias": w["ln2_b"][i]},
        }
    return {"params": p}


@functools.partial(jax.jit, static_argnames=("rows", "dim", "stride", "spread"))
def _rows(key, block, first_group, parents, *, rows, dim, stride, spread):
    k_dir, k_mix = jax.random.split(jax.random.fold_in(key, block))
    x = jax.random.normal(k_dir, (rows, dim), jnp.float32)
    unit = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    # every ``stride``-th row is a neighbour of one live document: that document's
    # embedding plus ``spread`` times the row's own random direction
    groups = first_group + jnp.arange(rows // stride)
    mix = spread[0] + (spread[1] - spread[0]) * jax.random.uniform(k_mix, (rows // stride, 1))
    near = parents[groups % parents.shape[0]] + mix * unit[::stride]
    return unit.at[::stride].set(near / jnp.linalg.norm(near, axis=1, keepdims=True))


def resident_block(seed: int, block: int, rows: int, dim: int, parents: jax.Array,
                   near: Dict[str, Any]) -> jax.Array:
    """Block ``block`` of the resident rows: ``rows`` float32 unit vectors. Most
    are random directions, which no query comes near. Every ``near["stride"]``-th
    is a neighbour of a live document (``parents``: the plain reference's
    embeddings of the live documents, taken in turn), so that the exact top-k of
    a query holds resident rows from every block beside live documents, and a
    search that leaves rows out answers differently."""
    stride = int(near["stride"])
    assert rows % stride == 0, (rows, stride)
    return _rows(seed_key(seed, 1), block, block * (rows // stride), parents, rows=rows, dim=dim,
                 stride=stride, spread=tuple(float(x) for x in near["spread"]))
