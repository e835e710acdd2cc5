"""Pipelined embedding runtime shared by the ingest and query paths.

Stages in front of ``JaxSentenceEncoder``, each measured through
``engine/telemetry.py`` stage counters:

1. **Content-hash embed cache** (:class:`EmbedCache`): an LRU keyed on
   (model, xxhash-of-text) consulted BEFORE the encoder on both paths, so
   re-ingested/duplicate chunks and repeated queries skip the forward pass
   entirely. The cache is orthogonal to the engine's memoize-on-retraction
   contract for non-deterministic UDFs: retraction rows are replayed from the
   evaluator's per-key memo and never reach this layer — the cache only
   deduplicates *forward* work across distinct rows/commits with equal text.
2. **Semantic query cache** (query path only;
   :class:`~pathway_tpu.models.encoder_service.SemanticQueryCache`): above the
   content hash — exact mode keys on the tokenizer's canonical form so
   whitespace/case variants of a served query hit without a forward pass, and
   stay bitwise-honest by construction; cosine mode is opt-in.
3. **Overlapped length-sorted ingest** (``JaxSentenceEncoder.encode_pipelined``):
   commit batches split into length-sorted sub-batches, host tokenization of
   sub-batch k+1 overlapping the device's forward of k via JAX async dispatch.
4. **Query serving**: misses are submitted to the persistent
   continuously-batched
   :class:`~pathway_tpu.models.encoder_service.EncoderService`, which is also
   the admission point (row cap, ``overloaded`` probe, typed shed with an
   honest Retry-After, the ``embed.shed`` counter).

Counters (``telemetry.stage_snapshot("embed.")``): cache hits/misses/evictions,
semantic hits/misses, tokenize/encode timings, padded vs real token counts,
``embed.shed``, ``embed.svc.*`` service stages.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from pathway_tpu.engine import telemetry
from pathway_tpu.engine import tracing
from pathway_tpu.models.encoder_service import (  # noqa: F401 (EmbedOverloadError re-exported)
    EmbedOverloadError,
    EncoderService,
    SemanticQueryCache,
    default_canonicalize,
)


class EmbedCache:
    """Thread-safe LRU of text → embedding keyed by (model, content hash).

    Keys are 128-bit xxh3 digests of the text salted with the model name —
    content-addressed, so identical chunks across files/commits share one
    entry. Values are read-only float32 host rows. ``max_entries=0`` disables
    the cache (get always misses, put is a no-op) without branching at call
    sites."""

    def __init__(self, max_entries: int = 50_000, model: str = ""):
        self.max_entries = int(max_entries)
        self._salt = model.encode()
        self._data: "OrderedDict[bytes, np.ndarray]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def _key(self, text: str) -> bytes:
        import xxhash

        return xxhash.xxh3_128_digest(self._salt + b"\x00" + str(text).encode())

    def get(self, text: str) -> Optional[np.ndarray]:
        # per-row counters stay on the cache's own lock; the telemetry stage
        # counters (process-global lock) are fed one batch-level add per commit
        # by EmbedPipeline — a 1024-row ingest must not take the global lock
        # 1024 times
        if self.max_entries <= 0:
            return None
        key = self._key(text)
        with self._lock:
            vec = self._data.get(key)
            if vec is None:
                self.misses += 1
                return None
            self._data.move_to_end(key)
            self.hits += 1
            return vec

    def put(self, text: str, vec: np.ndarray) -> None:
        if self.max_entries <= 0:
            return
        row = np.ascontiguousarray(vec, dtype=np.float32)
        row.setflags(write=False)  # shared across rows/commits: must never mutate
        key = self._key(text)
        with self._lock:
            self._data[key] = row
            self._data.move_to_end(key)
            while len(self._data) > self.max_entries:
                self._data.popitem(last=False)
                self.evictions += 1
                telemetry.stage_add("embed.cache_evictions")  # rare: batch-level in practice

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "cache_hits": self.hits,
                "cache_misses": self.misses,
                "cache_evictions": self.evictions,
                "cache_size": len(self._data),
            }


class EmbedPipeline:
    """The embed runtime shared by ingest (``encode_batch``) and query
    (``embed_query_rows``) paths: caches → service/overlapped encode → fill.

    ``sub_batch``: length-sorted ingest sub-batch rows; ``cache_size``: LRU
    entries (0 disables both caches); ``max_queue_rows``: the encoder
    service's admission cap (None = ``PATHWAY_EMBED_MAX_QUEUE_ROWS``, 0
    disables); ``semantic_mode``/``semantic_threshold``: the semantic query
    cache's ``exact``/``cosine``/``off`` and cosine threshold."""

    def __init__(
        self,
        encoder: Any,
        *,
        model: str = "",
        sub_batch: int = 128,
        cache_size: int = 50_000,
        max_queue_rows: "int | None" = None,
        semantic_mode: str = "exact",
        semantic_threshold: float = 0.95,
    ):
        self.encoder = encoder
        self.sub_batch = int(sub_batch)
        # the encoder's quantized-tower mode joins the content-hash salt AND
        # the semantic keys: embeddings cached under one geometry can never
        # answer a query encoded under the other (a mode flip misses, it
        # does not serve stale lattice points)
        quant_tag = getattr(encoder, "quant_tag", "") or ""
        self.cache = EmbedCache(
            cache_size, model=f"{model}|{quant_tag}" if quant_tag else model
        )
        self._pad_padded = 0.0
        self._pad_real = 0.0
        if max_queue_rows is None:
            # the service's admission cap (rows pending for the encoder): the
            # REST plane probes it pre-admission and sheds with 429 +
            # Retry-After; 0 disables. Second line of defense behind the
            # per-route max_pending request cap — rows, not requests, are what
            # the encoder actually queues.
            max_queue_rows = int(
                os.environ.get("PATHWAY_EMBED_MAX_QUEUE_ROWS", "4096")
            )
        self.service = EncoderService(
            encoder, max_queue_rows=max_queue_rows, after_batch=self._fill_caches
        )
        # semantic query cache (query path ONLY — ingest and retraction rows
        # never consult it): exact mode keys on the tokenizer's canonical form
        # so hits stay bitwise-honest; cosine is opt-in; disabled entirely when
        # the content cache is disabled (cache_size=0 means "no caching")
        self.semantic_cache = SemanticQueryCache(
            mode=semantic_mode if cache_size > 0 else "off",
            threshold=semantic_threshold,
            canonicalize=getattr(encoder, "canonicalize", None) or default_canonicalize,
            key_tag=quant_tag,
        )

    # -- ingest path ---------------------------------------------------------

    def encode_batch(self, texts: List[str]) -> np.ndarray:
        """Host float32 (n, dim) embeddings for a commit batch: cache hits skip
        the forward; misses ride the overlapped length-sorted sub-batch path."""
        n = len(texts)
        dim = self.encoder.dim
        out = np.empty((n, dim), dtype=np.float32)
        miss_idx: List[int] = []
        with telemetry.stage_timer("embed.cache_lookup"):
            for i, t in enumerate(texts):
                hit = self.cache.get(t)
                if hit is None:
                    miss_idx.append(i)
                else:
                    out[i] = hit
        self._stage_cache_counts(n - len(miss_idx), len(miss_idx))
        if miss_idx:
            with telemetry.stage_timer("embed.ingest_encode"):
                vecs, stats = self.encoder.encode_pipelined(
                    [str(texts[i]) for i in miss_idx], sub_batch=self.sub_batch
                )
            telemetry.stage_add("embed.tokenize_s", stats["tokenize_s"])
            telemetry.stage_add("embed.padded_tokens", stats["padded_tokens"])
            telemetry.stage_add("embed.real_tokens", stats["real_tokens"])
            self._pad_padded += stats["padded_tokens"]
            self._pad_real += stats["real_tokens"]
            for j, i in enumerate(miss_idx):
                out[i] = vecs[j]
                self.cache.put(texts[i], vecs[j])
        return out

    # -- query path ----------------------------------------------------------

    def embed_query_rows(self, texts: List[str]) -> List[Any]:
        """Per-row embedding values for the serving path: read-only host
        float32 rows. Cache hits (content hash first, then the semantic query
        cache) return the cached row; misses ride the encoder service's
        continuous batch and return views of the one host array their tick
        fetched. The whole call is the commit thread's
        ``embed_wait`` span: until the service hands rows back."""
        with tracing.trace_span("embed_wait", attrs={"rows": len(texts)}):
            return self._embed_query_rows(texts)

    def _embed_query_rows(self, texts: List[str]) -> List[Any]:
        rows: List[Any] = [None] * len(texts)
        miss_idx: List[int] = []
        sem_hits = 0
        for i, t in enumerate(texts):
            hit = self.cache.get(t)
            if hit is None:
                hit = self.semantic_cache.get(str(t))
                if hit is not None:
                    sem_hits += 1
                    # promote: future lookups of THIS raw text hit the cheaper
                    # content-hash layer directly
                    self.cache.put(t, hit)
            else:
                # promote the other way: a content hit (possibly filled by the
                # INGEST path for identical chunk text) seeds the semantic
                # layer so canonical variants of this query hit too (no-op
                # once the key exists — steady-state hits stay a single read)
                self.semantic_cache.seed(str(t), hit)
            if hit is None:
                miss_idx.append(i)
            else:
                rows[i] = hit
        self._stage_cache_counts(len(texts) - len(miss_idx), len(miss_idx))
        if sem_hits:
            telemetry.stage_add("embed.svc.semantic_hits", sem_hits)
        if miss_idx and self.semantic_cache.max_entries > 0:
            telemetry.stage_add("embed.svc.semantic_misses", len(miss_idx))
        if miss_idx:
            # enforce_cap=False: REST admission already probed the cap; raising
            # here would kill the engine commit instead of shedding one request
            got = self.service.submit(
                [str(texts[i]) for i in miss_idx], enforce_cap=False
            )
            for i, v in zip(miss_idx, got):
                rows[i] = v
        return rows

    def _fill_caches(self, texts: List[str], rows: Sequence[Any]) -> None:
        """Runs on the service's worker AFTER responders are released:
        fills the content-hash AND semantic caches from the host rows the
        batch already fetched, without adding to any query's latency."""
        if self.cache.max_entries <= 0 or not texts:
            return
        with tracing.trace_span("cache_fill", attrs={"rows": len(texts)}):
            for t, v in zip(texts, rows):
                self.cache.put(t, v)
                self.semantic_cache.put(t, v)

    def _stage_cache_counts(self, hits: int, misses: int) -> None:
        """ONE batch-level telemetry add per counter per commit (the telemetry
        module's stated granularity) instead of a global-lock hit per row."""
        if self.cache.max_entries <= 0:
            return  # cache disabled: keep telemetry consistent with stats()
        if hits:
            telemetry.stage_add("embed.cache_hits", hits)
        if misses:
            telemetry.stage_add("embed.cache_misses", misses)

    # -- reporting -----------------------------------------------------------

    def pad_waste_ratio(self) -> float:
        """Fraction of encoded tokens that were padding (ingest path)."""
        if self._pad_padded <= 0:
            return 0.0
        return 1.0 - self._pad_real / self._pad_padded

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        out.update(self.cache.stats())
        out.update(self.semantic_cache.stats())
        out.update(self.service.stats())
        out["pad_waste_ratio"] = round(self.pad_waste_ratio(), 4)
        return out
