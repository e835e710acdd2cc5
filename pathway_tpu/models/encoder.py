"""TPU-native sentence encoder (MiniLM/BERT family) in Flax.

This re-hosts the reference's torch-backed ``SentenceTransformerEmbedder``
(``xpacks/llm/embedders.py:270-328``, ``model.encode`` at ``:315``) as a jit'd JAX module:
token ids in, mean-pooled L2-normalized sentence embeddings out, bfloat16 matmuls on the MXU.
Weights convert from a local HuggingFace checkpoint when available (zero-egress environments
fall back to deterministic random init — fine for benchmarks measuring throughput and for
tests using mock embedders).

Architecture = all-MiniLM-L6-v2 defaults: 6 layers, hidden 384, 12 heads, FFN 1536,
vocab 30522, max_len 512.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from flax import linen as nn

from pathway_tpu.engine import tracing
from pathway_tpu.internals.shapes import next_pow2


def quant_encode_enabled() -> bool:
    """``PATHWAY_IVF_QUANT_ENCODE``: quantized query-tower encode mode.
    ``auto`` (default) follows ``PATHWAY_IVF_QUANT`` — the encoder rounds its
    embeddings onto the per-row symmetric int8 lattice exactly when the index
    scores in int8, so query vectors arrive pre-scaled for the int8 scorer
    and its re-quantization is code-stable (zero additional rounding).
    ``on``/``off`` force the mode independently of the index."""
    mode = os.environ.get("PATHWAY_IVF_QUANT_ENCODE", "auto").strip().lower()
    if mode in ("on", "1", "true", "yes", "int8"):
        return True
    if mode in ("off", "0", "false", "no"):
        return False
    from pathway_tpu.ops.knn_quant import quant_mode

    return quant_mode() == "int8"


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    vocab_size: int = 30522
    hidden_size: int = 384
    num_layers: int = 6
    num_heads: int = 12
    intermediate_size: int = 1536
    max_position: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16  # activations/matmuls on the MXU; params stay f32


class TransformerLayer(nn.Module):
    config: EncoderConfig

    @nn.compact
    def __call__(self, hidden: jax.Array, mask: jax.Array) -> jax.Array:
        cfg = self.config
        attention_out = nn.MultiHeadDotProductAttention(
            num_heads=cfg.num_heads,
            dtype=cfg.dtype,
            param_dtype=jnp.float32,
            name="attention",
        )(hidden, hidden, mask=mask)
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="attention_norm")(
            hidden + attention_out
        )
        ff = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype, name="intermediate")(hidden)
        ff = nn.gelu(ff, approximate=False)
        ff = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="output")(ff)
        return nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="output_norm")(hidden + ff)


class SentenceEncoder(nn.Module):
    """BERT-style encoder with mean pooling + L2 normalization."""

    config: EncoderConfig = EncoderConfig()

    @nn.compact
    def __call__(self, input_ids: jax.Array, attention_mask: jax.Array) -> jax.Array:
        cfg = self.config
        positions = jnp.arange(input_ids.shape[1])[None, :]
        embeddings = (
            nn.Embed(cfg.vocab_size, cfg.hidden_size, name="word_embeddings")(input_ids)
            + nn.Embed(cfg.max_position, cfg.hidden_size, name="position_embeddings")(positions)
            + nn.Embed(cfg.type_vocab_size, cfg.hidden_size, name="token_type_embeddings")(
                jnp.zeros_like(input_ids)
            )
        )
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="embeddings_norm")(embeddings)
        hidden = hidden.astype(cfg.dtype)
        attn_mask = attention_mask[:, None, None, :].astype(bool)
        for i in range(cfg.num_layers):
            hidden = TransformerLayer(cfg, name=f"layer_{i}")(hidden, attn_mask)
        hidden = hidden.astype(jnp.float32)
        # mean pooling over valid tokens, then L2 normalize (sentence-transformers recipe)
        mask_f = attention_mask[:, :, None].astype(jnp.float32)
        pooled = jnp.sum(hidden * mask_f, axis=1) / jnp.maximum(
            jnp.sum(mask_f, axis=1), 1e-9
        )
        return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


class HashTokenizer:
    """Deterministic fallback tokenizer for zero-egress environments: word-hash into the
    vocab. NOT wordpiece — embeddings differ from the HF checkpoint, but throughput-identical
    (same shapes/FLOPs), which is what the benchmark measures.

    Vectorized: ids assemble through numpy scatter over a flat id array, and the
    word→id hash is memoized (``_word_ids``) so steady-state batches pay zero
    xxhash calls for repeated vocabulary — the per-word python loop + hash call
    per token was the host-side bottleneck in zero-egress benches. Output is
    trimmed to the batch's longest row (like the HF tokenizer) rather than
    padded to ``max_length``, so short batches stop paying 128-token pad FLOPs
    downstream."""

    _WORD_CACHE_MAX = 1 << 20  # unbounded ingest vocab must not grow the memo forever

    def __init__(self, vocab_size: int = 30522, max_length: int = 128):
        assert vocab_size > 3000, "hash ids live in [2000, vocab_size-1000)"
        self.vocab_size = vocab_size
        self.max_length = max_length
        self._word_ids: dict[str, int] = {}

    def _id_of(self, word: str) -> int:
        import xxhash

        return 2000 + (xxhash.xxh32_intdigest(word) % (self.vocab_size - 3000))

    def __call__(self, texts: list[str]) -> Tuple[np.ndarray, np.ndarray]:
        n = len(texts)
        limit = self.max_length - 2
        words_per = [str(t).lower().split()[:limit] for t in texts]
        cache = self._word_ids
        missing = {w for ws in words_per for w in ws if w not in cache}
        if missing:
            if len(cache) + len(missing) > self._WORD_CACHE_MAX:
                # overflow reset: re-hash EVERY word of the current batch, not
                # just `missing` — the clear just evicted the batch's cached ones
                cache.clear()
                missing = {w for ws in words_per for w in ws}
            for w in missing:
                cache[w] = self._id_of(w)
        lens = np.fromiter((len(ws) for ws in words_per), dtype=np.int64, count=n)
        width = int(lens.max()) + 2 if n else 2
        cols = np.arange(width)
        mask = (cols[None, :] < (lens + 2)[:, None]).astype(np.int32)
        ids = np.zeros((n, width), dtype=np.int32)
        if n:
            ids[:, 0] = 101  # [CLS]
            total = int(lens.sum())
            flat = np.fromiter(
                (cache[w] for ws in words_per for w in ws), dtype=np.int32, count=total
            )
            inner = cols[None, 1:] < (lens + 1)[:, None]
            ids[:, 1:][inner] = flat  # row-major boolean scatter keeps word order
            ids[np.arange(n), lens + 1] = 102  # [SEP]
        return ids, mask


def _hf_offline() -> None:
    # zero-egress environment: never let transformers hit the network (it retries for ~80s)
    import os

    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")


def _load_hf_tokenizer(model_name: str) -> Any:
    try:
        _hf_offline()
        from transformers import AutoTokenizer

        return AutoTokenizer.from_pretrained(model_name, local_files_only=True)
    except Exception:
        return None


def convert_hf_weights(model_name: str, config: EncoderConfig) -> Optional[Dict]:
    """Convert a locally cached HF BERT checkpoint to this module's param tree."""
    try:
        _hf_offline()
        import torch
        from transformers import AutoModel

        hf = AutoModel.from_pretrained(model_name, local_files_only=True)
    except Exception:
        return None
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    p: Dict[str, Any] = {}
    p["word_embeddings"] = {"embedding": sd["embeddings.word_embeddings.weight"]}
    p["position_embeddings"] = {"embedding": sd["embeddings.position_embeddings.weight"]}
    p["token_type_embeddings"] = {"embedding": sd["embeddings.token_type_embeddings.weight"]}
    p["embeddings_norm"] = {
        "scale": sd["embeddings.LayerNorm.weight"],
        "bias": sd["embeddings.LayerNorm.bias"],
    }
    h, nh = config.hidden_size, config.num_heads
    hd = h // nh
    for i in range(config.num_layers):
        pre = f"encoder.layer.{i}."
        attn = {}
        for name, hf_name in (("query", "query"), ("key", "key"), ("value", "value")):
            w = sd[pre + f"attention.self.{hf_name}.weight"]  # (h, h) torch layout
            b = sd[pre + f"attention.self.{hf_name}.bias"]
            attn[name] = {
                "kernel": w.T.reshape(h, nh, hd),
                "bias": b.reshape(nh, hd),
            }
        wo = sd[pre + "attention.output.dense.weight"]
        attn["out"] = {
            "kernel": wo.T.reshape(nh, hd, h),
            "bias": sd[pre + "attention.output.dense.bias"],
        }
        p[f"layer_{i}"] = {
            "attention": attn,
            "attention_norm": {
                "scale": sd[pre + "attention.output.LayerNorm.weight"],
                "bias": sd[pre + "attention.output.LayerNorm.bias"],
            },
            "intermediate": {
                "kernel": sd[pre + "intermediate.dense.weight"].T,
                "bias": sd[pre + "intermediate.dense.bias"],
            },
            "output": {
                "kernel": sd[pre + "output.dense.weight"].T,
                "bias": sd[pre + "output.dense.bias"],
            },
            "output_norm": {
                "scale": sd[pre + "output.LayerNorm.weight"],
                "bias": sd[pre + "output.LayerNorm.bias"],
            },
        }
    return {"params": jax.tree.map(jnp.asarray, p)}


class JaxSentenceEncoder:
    """Batched text → embedding pipeline: tokenize on host, encode jit'd on TPU.

    Pads batch length to power-of-two buckets so XLA compiles a handful of shapes.
    """

    def __init__(
        self,
        model_name: str = "sentence-transformers/all-MiniLM-L6-v2",
        config: EncoderConfig | None = None,
        max_length: int = 128,
        seed: int = 0,
        transfer_dtype: str = "float16",
        weights_dtype: str = "bfloat16",
    ):
        """``transfer_dtype``: wire format of returned embeddings. The default
        ``float16`` halves host<->device bytes; its ~5e-4 quantization sits
        BELOW the bfloat16 compute noise the forward pass already carries, so
        retrieval quality is unchanged. Pass ``float32`` to ship the pooled
        output unquantized.

        ``weights_dtype``: resident dtype of the matmul weights. The default
        ``bfloat16`` pre-casts ONCE at load — halving the HBM weight traffic per
        step and deleting the per-call f32->bf16 cast the mixed-precision module
        would otherwise do — standard inference precision for this model family
        (the forward pass already computes in bf16 either way). LayerNorm/bias
        params stay f32 via the module's ``param_dtype``. Pass ``float32`` to
        keep full-precision residency."""
        self.config = config or EncoderConfig()
        self.model = SentenceEncoder(self.config)
        self.max_length = max_length
        hf_tok = _load_hf_tokenizer(model_name)
        # what actually loaded: without a local HF checkpoint the encoder is
        # random-init + HashTokenizer (same shapes, same FLOPs, no semantics),
        # which callers that report results must say (chip_smoke.py does)
        self.tokenizer_source = "hf" if hf_tok is not None else "hash"
        if hf_tok is not None:
            self._tokenize = lambda texts: self._hf_tokenize(hf_tok, texts)
            self._tokenizer_lowercases = bool(getattr(hf_tok, "do_lower_case", False))
            # whitespace-run collapse is id-preserving ONLY for BERT-family
            # basic tokenization (splits on any whitespace); byte-level BPE
            # (RoBERTa-style) encodes the runs, so the canonical form must
            # stay identity there or exact-mode cache hits stop being bitwise
            self._tokenizer_ws_invariant = (
                hasattr(hf_tok, "do_lower_case") or "Bert" in type(hf_tok).__name__
            )
        else:
            self._tokenize = HashTokenizer(self.config.vocab_size, max_length)
            self._tokenizer_lowercases = True  # HashTokenizer lower()s every word
            self._tokenizer_ws_invariant = True  # str.split() collapses runs
        params = convert_hf_weights(model_name, self.config)
        self.weights_source = "hf" if params is not None else "random-init"
        if params is None:
            ids = jnp.zeros((1, 8), dtype=jnp.int32)
            params = self.model.init(jax.random.PRNGKey(seed), ids, jnp.ones_like(ids))
        if weights_dtype == "bfloat16":
            # kernels/embeddings to bf16; norms and biases keep f32 for stability
            def _cast(path: tuple, leaf: Any) -> Any:
                name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
                if name in ("kernel", "embedding") and leaf.dtype == jnp.float32:
                    return leaf.astype(jnp.bfloat16)
                return leaf

            params = jax.tree_util.tree_map_with_path(_cast, params)
        self.params = params
        self.transfer_dtype = jnp.float16 if transfer_dtype == "float16" else jnp.float32
        # transfer-lean kernel: the attention mask derives on-device from the pad
        # id (BERT-family [PAD]=0; no real token is id 0), and the normalized
        # embeddings ship in transfer_dtype
        out_dtype = self.transfer_dtype
        # quantized query tower (PATHWAY_IVF_QUANT_ENCODE): fold a per-row
        # symmetric int8 lattice round into the jitted forward — s = max|v|/127,
        # v -> round(v/s)*s in f32 BEFORE the wire cast. The row max is itself
        # a lattice point, so the int8 scorer's re-quantization reproduces the
        # codes exactly (|k| <= 127 keeps even the f16 wire perturbation under
        # half a code step); geometry served from cache must key on this mode
        self.quant_encode = quant_encode_enabled()
        self.quant_tag = "quant:int8" if self.quant_encode else ""

        # a named function: the device trace shows the program as
        # ``jit_encoder_forward`` (a lambda shows as ``jit__lambda_``)
        def encoder_forward(params: Any, ids: jax.Array) -> jax.Array:
            with jax.named_scope("encoder_forward"):
                out = self.model.apply(params, ids, (ids != 0).astype(jnp.int32))
            if self.quant_encode:
                out = out.astype(jnp.float32)
                s = jnp.maximum(
                    jnp.max(jnp.abs(out), axis=1, keepdims=True), 1e-30
                ) / 127.0
                out = jnp.round(out / s) * s
            return out.astype(out_dtype)

        self._encode_ids = jax.jit(encoder_forward)
        # (real, padded) tokens each calling thread has sent to the device
        self._dispatched = threading.local()

    def _hf_tokenize(self, tok: Any, texts: list[str]) -> Tuple[np.ndarray, np.ndarray]:
        out = tok(
            [str(t) for t in texts],
            padding=True,
            truncation=True,
            max_length=self.max_length,
            return_tensors="np",
        )
        return out["input_ids"].astype(np.int32), out["attention_mask"].astype(np.int32)

    @property
    def dim(self) -> int:
        return self.config.hidden_size

    def canonicalize(self, text: str) -> str:
        """Tokenizer-equivalence canonical form: two texts with equal
        canonical forms tokenize to IDENTICAL ids, hence bitwise-identical
        embeddings. Whitespace runs collapse only when the active tokenizer is
        whitespace-invariant (BERT-family basic tokenization / the hash
        fallback) and case folds only when it is uncased; for any other
        tokenizer family the canonical form is the identity — no equivalence
        is claimed that the tokenizer does not actually provide. The semantic
        query cache's exact mode keys on this, which is what makes an
        exact-mode hit bitwise-honest."""
        s = str(text)
        if not self._tokenizer_ws_invariant:
            return s
        s = " ".join(s.split())
        return s.lower() if self._tokenizer_lowercases else s

    def encode_device(self, texts: list[str]) -> Any:
        """The PADDED forward of ``texts`` as a device array, enqueued and not
        waited for: ``(batch bucket, dim)`` in the transfer dtype, row ``i``
        the embedding of ``texts[i]``, the rows past ``len(texts)`` the
        bucket's zero padding. Nothing is sliced on the device (a slice is a
        program of its own, keyed by ``len(texts)``): callers fetch the bucket
        once and cut it on the host (:func:`fetch_rows`)."""
        if not texts:
            return np.zeros((0, self.config.hidden_size), dtype=np.float32)
        with tracing.trace_span("tokenize", attrs={"rows": len(texts)}):
            ids, mask = self._tokenize(texts)
        return self._dispatch(ids, mask)

    def _dispatch(self, ids: np.ndarray, mask: np.ndarray) -> Any:
        """Pad a tokenized batch to pow2 (seq, batch) buckets and dispatch the
        jit'd forward WITHOUT blocking (JAX async dispatch: the returned array
        is a future; only reading it syncs). Rows beyond ``ids.shape[0]`` are
        zero padding."""
        seq = _next_pow2(ids.shape[1])
        batch = _next_pow2(ids.shape[0])
        ids_p = np.zeros((batch, seq), dtype=np.int32)
        ids_p[: ids.shape[0], : ids.shape[1]] = ids * mask  # padding -> id 0
        real, padded = self.dispatched_tokens()
        self._dispatched.counts = (real + int(mask.sum()), padded + batch * seq)
        # the host array goes in as it is: the call transfers it (a
        # ``jnp.asarray`` first is a second trip through the runtime)
        return self._encode_ids(self.params, ids_p)

    def dispatched_tokens(self) -> Tuple[int, int]:
        """(real tokens, padded tokens) the CALLING thread has sent to the
        device so far: tokens under the attention mask, and batch bucket x
        sequence bucket. Per thread, so that a caller reads the delta around
        its own dispatches whatever other threads encode meanwhile (the
        encoder service counts its ticks' tokens this way)."""
        return getattr(self._dispatched, "counts", (0, 0))

    def encode(self, texts: list[str]) -> np.ndarray:
        if not texts:
            return np.zeros((0, self.config.hidden_size), dtype=np.float32)
        return fetch_rows(self.encode_device(texts), len(texts))

    def encode_pipelined(
        self, texts: list[str], sub_batch: int = 128
    ) -> Tuple[np.ndarray, Dict[str, float]]:
        """Overlapped length-sorted encode: host-tokenize sub-batch k+1 while the
        device computes k.

        The batch sorts by a cheap whitespace length proxy and splits into
        ``sub_batch``-row sub-batches, each padded only to ITS longest row's
        pow2 bucket — short rows stop paying the global longest row's pad
        FLOPs. Dispatches are JAX-async: the loop never blocks on a forward, so
        tokenization of sub-batch k+1 runs while the device works on k (double
        buffering without explicit streams); the single sync point is the final
        fetch. Per-row results equal :meth:`encode`'s within float32 rounding,
        not bit for bit: masked attention/pooling make a row's value independent
        of its pad width, but each (batch, seq) bucket is its own XLA program,
        and two programs may order a reduction differently.

        Returns ``(embeddings (n, dim) float32 in input order, stats)`` where
        stats carries ``padded_tokens``/``real_tokens`` (the pad-waste ratio),
        ``tokenize_s`` and ``sub_batches``."""
        n = len(texts)
        dim = self.config.hidden_size
        stats: Dict[str, float] = {
            "padded_tokens": 0.0, "real_tokens": 0.0, "tokenize_s": 0.0,
            "sub_batches": 0.0,
        }
        out = np.empty((n, dim), dtype=np.float32)
        if n == 0:
            return out, stats
        import time as _time

        order = sorted(range(n), key=lambda i: len(str(texts[i]).split()))
        inflight = []  # (device future, original indices) — fetched after all dispatches
        for start in range(0, n, max(1, sub_batch)):
            idx = order[start : start + max(1, sub_batch)]
            t0 = _time.perf_counter()
            ids, mask = self._tokenize([texts[i] for i in idx])
            stats["tokenize_s"] += _time.perf_counter() - t0
            dev = self._dispatch(ids, mask)
            stats["padded_tokens"] += float(dev.shape[0] * _next_pow2(ids.shape[1]))
            stats["real_tokens"] += float(mask.sum())
            stats["sub_batches"] += 1
            inflight.append((dev, idx))
        for dev, idx in inflight:
            out[idx] = np.asarray(dev[: len(idx)], dtype=np.float32)
        return out, stats


def fetch_rows(out: Any, n: int) -> np.ndarray:
    """The first ``n`` rows of a (padded) forward as ONE host float32 array:
    one fetch of the whole bucket (6 KB at 8 x 384 float16), the slice and the
    cast in numpy. float16 to float32 is exact, so the rows are bit for bit
    what a cast on the device gave. Blocks until the device has the forward."""
    return np.asarray(out)[:n].astype(np.float32)


def _next_pow2(n: int) -> int:
    """Device shape bucket (floor 8) — the shared pow2 rule from
    ``internals/shapes.py``; kept as a named helper because the bench's FLOP
    accounting imports it to mirror the exact shapes executed."""
    return next_pow2(n, floor=8)
