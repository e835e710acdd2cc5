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
4. **Query serving** — by default the persistent continuously-batched
   :class:`~pathway_tpu.models.encoder_service.EncoderService`
   (``PATHWAY_ENCSVC=off`` reverts to the PR-4 deadline path). The
   :class:`QueryCoalescer` stays as the ADMISSION SHIM in front of it: the
   ``max_queue_rows`` cap, ``overloaded`` pre-admission probe, typed shed with
   honest Retry-After, and the ``embed.shed`` counter keep their PR-6
   contract; only the batching mechanics moved into the service (a solo query
   no longer waits for a deadline window).

Counters (``telemetry.stage_snapshot("embed.")``): cache hits/misses/evictions,
semantic hits/misses, coalesce/service requests/batches/rows, dedup_rows,
tokenize/encode timings, padded vs real token counts, ``embed.svc.*`` service
stages.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from pathway_tpu.engine import telemetry
from pathway_tpu.engine import tracing
from pathway_tpu.models.encoder import fetch_rows


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


class EmbedOverloadError(RuntimeError):
    """The embed admission queue is full; the caller should shed load. Raised
    by direct ``QueryCoalescer.embed`` callers only — the REST plane consults
    the same cap BEFORE admission (``overloaded`` probe wired through
    ``rest_connector``) and sheds with HTTP 429 + ``Retry-After`` there, so an
    admitted request never dies inside an engine commit."""

    def __init__(self, message: str, *, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


class _Request:
    __slots__ = ("texts", "arrived", "event", "rows", "error")

    def __init__(self, texts: List[str]):
        self.texts = texts
        self.arrived = time.monotonic()
        self.event = threading.Event()
        self.rows: Optional[List[Any]] = None
        self.error: Optional[BaseException] = None


class QueryCoalescer:
    """Deadline-based micro-batcher merging concurrent embed requests into one
    encoder dispatch.

    The first request to arrive at an empty queue anchors a batch window of
    ``max_wait_ms``; requests arriving inside the window (or while the encoder
    is busy with the previous batch) join the same dispatch, capped at
    ``max_batch`` rows. A request is therefore dispatched no later than
    ``max_wait_ms`` after submission (deadline contract) and immediately once
    ``max_batch`` rows are waiting. Duplicate texts within a batch encode once
    (content dedup) — every request still receives its own rows, in order.

    ``encode_rows(texts) -> sequence of per-row values`` runs on the worker
    thread; the coalescer never inspects the row values (the pipeline hands
    it the rows of one host array). An optional ``after_batch(texts, rows)``
    hook runs AFTER responders are released (cache fill without adding to
    request latency).

    **Service shim mode** (``service=`` set, the default through
    ``EmbedPipeline`` since the encoder-service PR): the deadline worker is
    bypassed — :meth:`embed` enforces the admission cap / shed contract here
    (unchanged REST semantics: ``overloaded`` probed pre-admission, typed
    :class:`EmbedOverloadError` with honest Retry-After, ``embed.shed``
    counter) and then submits into the
    :class:`~pathway_tpu.models.encoder_service.EncoderService`'s ragged
    queue, whose continuous-batching tick replaces the ``max_wait_ms``
    window."""

    def __init__(
        self,
        encode_rows: Callable[[List[str]], Sequence[Any]],
        *,
        max_wait_ms: float = 2.0,
        max_batch: int = 256,
        max_queue_rows: int = 0,
        after_batch: Callable[[List[str], Sequence[Any]], None] | None = None,
        service: Any = None,
    ):
        self._encode_rows = encode_rows
        self.max_wait_ms = float(max_wait_ms)
        self.max_batch = max(1, int(max_batch))
        # admission cap: rows allowed to WAIT for the encoder (0 = unbounded).
        # Past it, embed() sheds with EmbedOverloadError instead of queueing —
        # an overloaded encoder otherwise grows the queue without bound and
        # every client's deadline contract silently dies
        self.max_queue_rows = max(0, int(max_queue_rows))
        self._after_batch = after_batch
        self._service = service
        # hard bound on one request's total wait (0 = no bound; the wait is
        # still abortable — see _await). Covers a wedged encoder device: the
        # fence deadline must never sit behind an unbounded embed wait.
        self.wait_timeout_s = float(
            os.environ.get("PATHWAY_EMBED_WAIT_TIMEOUT_S", "0") or 0
        )
        self._queue: "deque[_Request]" = deque()
        self._queued_rows = 0
        self._encode_ewma_s = 0.0  # smoothed per-batch encode time (Retry-After)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._worker: threading.Thread | None = None
        self._closed = False
        # counters (also mirrored into telemetry stage counters)
        self.requests = 0
        self.batches = 0
        self.coalesced_rows = 0
        self.dedup_rows = 0
        self.max_batch_rows = 0
        self.shed_requests = 0

    def _rows_pending(self) -> int:
        """Rows admitted against the cap but not yet answered — the shim
        delegates to the service's queue (waiting + in-flight), the legacy
        path counts its own queue. Lock-free read either way."""
        if self._service is not None:
            return int(self._service.queue_depth_rows())
        return self._queued_rows

    def overloaded(self, extra_rows: int = 0) -> bool:
        """Admission probe: would admitting ``extra_rows`` more rows exceed
        ``max_queue_rows``? Lock-free read — a soft cap with bounded overshoot,
        same contract as the REST ``max_pending`` check. Each probe also feeds
        the brownout ladder (``engine/brownout.py``) one occupancy sample, so
        the serving plane's degradation rungs engage from the same signal the
        shed decision uses."""
        if not self.max_queue_rows:
            return False
        pending = self._rows_pending()
        from pathway_tpu.engine.brownout import get_brownout

        get_brownout().observe_occupancy(pending / self.max_queue_rows)
        return pending + extra_rows >= self.max_queue_rows

    def retry_after_s(self, extra_rows: int = 0) -> float:
        """Honest Retry-After estimate: batches needed to drain the current
        queue x (batch window + smoothed encode time), floored at 1 s. In shim
        mode the window term drops (the service has no deadline wait) and the
        smoothed encode time comes from the service's ticks."""
        rows = self._rows_pending() + extra_rows
        if self._service is not None:
            batches = max(1.0, rows / self._service.max_in_flight)
            per_batch = self._service.encode_ewma_s() or 0.05
        else:
            batches = max(1.0, rows / self.max_batch)
            per_batch = self.max_wait_ms / 1000.0 + (self._encode_ewma_s or 0.05)
        return max(1.0, batches * per_batch)

    # -- submission ----------------------------------------------------------

    def embed(self, texts: List[str], *, enforce_cap: bool = True) -> List[Any]:
        """Blocking: returns one row value per input text, in order.
        Raises :class:`EmbedOverloadError` when ``max_queue_rows`` is set and
        admitting these rows would exceed it. The engine serving path passes
        ``enforce_cap=False``: its requests were already admitted against the
        same cap at the REST boundary (``overloaded`` probe), and raising
        mid-commit would tear down the run instead of shedding one request."""
        if not texts:
            return []
        # the coalescer admission wait is a traced hop: a child of whatever
        # span the calling thread holds (the commit span on the engine serving
        # path), covering admission + the batching/encode wait
        with tracing.trace_span(
            "coalesce", f"coalesce {len(texts)}", attrs={"rows": len(texts)}
        ):
            return self._embed_traced(texts, enforce_cap=enforce_cap)

    def _embed_traced(self, texts: List[str], *, enforce_cap: bool = True) -> List[Any]:
        if self._service is not None:
            return self._embed_via_service(list(texts), enforce_cap)
        req = _Request(list(texts))
        with self._cond:
            if self._closed:
                raise RuntimeError("QueryCoalescer is closed")
            if (
                enforce_cap
                and self.max_queue_rows
                and self._queued_rows + len(texts) > self.max_queue_rows
            ):
                self.shed_requests += 1
                telemetry.stage_add("embed.shed")
                raise EmbedOverloadError(
                    f"embed queue full ({self._queued_rows} rows waiting, cap "
                    f"{self.max_queue_rows})",
                    retry_after_s=self.retry_after_s(len(texts)),
                )
            self._queue.append(req)
            self._queued_rows += len(texts)
            self.requests += 1
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._run, name="pathway:embed-coalescer", daemon=True
                )
                self._worker.start()
            self._cond.notify_all()
        self._await(req)
        if req.error is not None:
            raise req.error
        assert req.rows is not None
        return req.rows

    def _embed_via_service(self, texts: List[str], enforce_cap: bool) -> List[Any]:
        """Shim path: admission accounting + shed here (the PR-6 contract the
        REST plane depends on), batching in the service."""
        with self._cond:
            if self._closed:
                raise RuntimeError("QueryCoalescer is closed")
            if (
                enforce_cap
                and self.max_queue_rows
                and self._rows_pending() + len(texts) > self.max_queue_rows
            ):
                self.shed_requests += 1
                telemetry.stage_add("embed.shed")
                raise EmbedOverloadError(
                    f"embed queue full ({self._rows_pending()} rows pending, "
                    f"cap {self.max_queue_rows})",
                    retry_after_s=self.retry_after_s(len(texts)),
                )
            self.requests += 1
        return self._service.submit(texts, enforce_cap=False)

    def _await(self, req: _Request) -> None:
        """Abortable wait for a submitted request (the PWA102 contract: every
        runtime wait must wake periodically so teardown and the fence deadline
        can abort it — the previous untimed ``event.wait()`` wedged the engine
        thread forever when the coalescer died with the request still queued).
        The worker drains the queue on close, so the typed abort only fires
        when the request is still queued and no worker remains to take it;
        ``PATHWAY_EMBED_WAIT_TIMEOUT_S`` (0 = unbounded) additionally bounds
        the total wait against a wedged encoder device."""
        deadline = (
            time.monotonic() + self.wait_timeout_s if self.wait_timeout_s > 0 else None
        )
        while not req.event.wait(timeout=0.25):
            with self._cond:
                if req.event.is_set():
                    break
                worker = self._worker
                if (
                    self._closed
                    and req in self._queue
                    and (worker is None or not worker.is_alive())
                ):
                    self._queue.remove(req)
                    self._queued_rows -= len(req.texts)
                    req.error = RuntimeError(
                        "QueryCoalescer closed before this request was "
                        "dispatched (no worker left to drain the queue)"
                    )
                    req.event.set()
                    break
            if deadline is not None and time.monotonic() > deadline:
                with self._cond:
                    if req in self._queue:
                        self._queue.remove(req)
                        self._queued_rows -= len(req.texts)
                raise TimeoutError(
                    f"embed request not answered within "
                    f"{self.wait_timeout_s:.0f}s "
                    "(PATHWAY_EMBED_WAIT_TIMEOUT_S) — encoder wedged?"
                )

    def close(self) -> None:
        """Idempotent. A live worker drains the queue before exiting (every
        already-admitted request is still answered); requests stranded with no
        worker fail typed from :meth:`_await` instead of hanging."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    # -- worker --------------------------------------------------------------

    def _gather(self) -> List[_Request]:
        """Wait for work, honor the batch window, take up to max_batch rows."""
        with self._cond:
            while not self._queue:
                if self._closed:
                    return []
                self._cond.wait(timeout=0.5)
            # the window anchors at the OLDEST queued request's arrival — time
            # it already spent waiting behind a busy encoder counts against the
            # deadline, so a request is dispatched no later than max_wait_ms
            # after submission (plus the in-flight batch, which is unavoidable).
            # Under brownout the window SHRINKS (engine/brownout.py): batching
            # efficiency is traded for latency while the queue is saturated.
            from pathway_tpu.engine.brownout import get_brownout

            window_ms = self.max_wait_ms * get_brownout().coalesce_window_scale()
            deadline = self._queue[0].arrived + window_ms / 1000.0
            while sum(len(r.texts) for r in self._queue) < self.max_batch:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self._closed:
                    break
                self._cond.wait(timeout=remaining)
            take: List[_Request] = []
            rows = 0
            while self._queue and (
                not take or rows + len(self._queue[0].texts) <= self.max_batch
            ):
                req = self._queue.popleft()
                take.append(req)
                rows += len(req.texts)
            self._queued_rows -= rows
            return take

    def _run(self) -> None:
        while True:
            batch = self._gather()
            if not batch:
                if self._closed:
                    return
                continue
            texts = [t for r in batch for t in r.texts]
            # content dedup inside the coalesced batch: N clients asking the
            # same question pay one forward row
            first_of: Dict[str, int] = {}
            unique: List[str] = []
            slot_of = []
            for t in texts:
                j = first_of.setdefault(t, len(unique))
                if j == len(unique):
                    unique.append(t)
                slot_of.append(j)
            try:
                _t_enc = time.monotonic()
                with telemetry.stage_timer("embed.coalesce_encode"):
                    out = self._encode_rows(unique)
                # smoothed encode time feeds the Retry-After estimate
                self._encode_ewma_s = (
                    0.8 * self._encode_ewma_s + 0.2 * (time.monotonic() - _t_enc)
                    if self._encode_ewma_s
                    else time.monotonic() - _t_enc
                )
                rows = [out[j] for j in slot_of]
            except BaseException as exc:  # propagate to every waiter in the batch
                for r in batch:
                    r.error = exc
                    r.event.set()
                continue
            self.batches += 1
            self.coalesced_rows += len(texts)
            self.dedup_rows += len(texts) - len(unique)
            self.max_batch_rows = max(self.max_batch_rows, len(texts))
            telemetry.stage_add("embed.coalesce_batches")
            telemetry.stage_add("embed.coalesce_rows", len(texts))
            if len(texts) > len(unique):
                telemetry.stage_add("embed.coalesce_dedup_rows", len(texts) - len(unique))
            pos = 0
            for r in batch:
                r.rows = rows[pos : pos + len(r.texts)]
                pos += len(r.texts)
                r.event.set()
            if self._after_batch is not None:
                try:
                    self._after_batch(unique, out)
                except Exception:
                    pass  # cache fill is best-effort; responders already released

    def stats(self) -> Dict[str, int]:
        return {
            "coalesce_requests": self.requests,
            "coalesce_batches": self.batches,
            "coalesce_rows": self.coalesced_rows,
            "coalesce_dedup_rows": self.dedup_rows,
            "coalesce_max_batch_rows": self.max_batch_rows,
            "coalesce_shed_requests": self.shed_requests,
        }


class EmbedPipeline:
    """The embed runtime shared by ingest (``encode_batch``) and query
    (``embed_query_rows``) paths: caches → service/overlapped encode → fill.

    Knobs: ``max_wait_ms``/``max_batch`` (legacy coalescer window),
    ``sub_batch`` (length-sorted ingest sub-batch rows), ``cache_size`` (LRU
    entries; 0 disables), ``service_mode`` (None = ``PATHWAY_ENCSVC`` env,
    default on), ``semantic_mode``/``semantic_size``/``semantic_threshold``
    (None = ``PATHWAY_ENCSVC_SEMANTIC*`` env; exact/4096/0.95),
    ``tick_ms``/``max_in_flight``/``prewarm`` forwarded to the
    :class:`~pathway_tpu.models.encoder_service.EncoderService`."""

    def __init__(
        self,
        encoder: Any,
        *,
        model: str = "",
        max_wait_ms: float = 2.0,
        max_batch: int = 256,
        sub_batch: int = 128,
        cache_size: int = 50_000,
        max_queue_rows: "int | None" = None,
        service_mode: "bool | None" = None,
        semantic_mode: "str | None" = None,
        semantic_size: "int | None" = None,
        semantic_threshold: "float | None" = None,
        tick_ms: "float | None" = None,
        max_in_flight: "int | None" = None,
        prewarm: "bool | None" = None,
    ):
        from pathway_tpu.models.encoder_service import (
            EncoderService,
            SemanticQueryCache,
            _env_flag,
            _env_float,
            _env_int,
            default_canonicalize,
        )

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
            # coalescer admission cap (rows waiting for the encoder): the REST
            # plane probes it pre-admission and sheds with 429 + Retry-After;
            # 0 disables. Second line of defense behind the per-route
            # max_pending request cap — rows, not requests, are what the
            # encoder actually queues.
            max_queue_rows = int(
                os.environ.get("PATHWAY_EMBED_MAX_QUEUE_ROWS", "4096")
            )
        if service_mode is None:
            service_mode = _env_flag("PATHWAY_ENCSVC", True)
        self.service = (
            EncoderService(
                encoder,
                tick_ms=tick_ms,
                max_in_flight=max_in_flight,
                prewarm=prewarm,
                after_batch=self._fill_caches,
            )
            if service_mode
            else None
        )
        # semantic query cache (query path ONLY — ingest and retraction rows
        # never consult it): exact mode keys on the tokenizer's canonical form
        # so hits stay bitwise-honest; cosine is opt-in; disabled entirely when
        # the content cache is disabled (cache_size=0 means "no caching")
        if semantic_mode is None:
            semantic_mode = os.environ.get("PATHWAY_ENCSVC_SEMANTIC", "exact") or "exact"
        if semantic_mode not in ("exact", "cosine", "off"):
            semantic_mode = "exact"
        if cache_size <= 0:
            semantic_mode = "off"
        if semantic_size is None:
            semantic_size = _env_int("PATHWAY_ENCSVC_SEMANTIC_SIZE", 4096)
        if semantic_threshold is None:
            semantic_threshold = _env_float("PATHWAY_ENCSVC_SEMANTIC_THRESHOLD", 0.95)
        self.semantic_cache = SemanticQueryCache(
            semantic_size,
            mode=semantic_mode,
            threshold=semantic_threshold,
            canonicalize=getattr(encoder, "canonicalize", None) or default_canonicalize,
            key_tag=quant_tag,
        )
        self.coalescer = QueryCoalescer(
            self._encode_device_rows,
            max_wait_ms=max_wait_ms,
            max_batch=max_batch,
            max_queue_rows=max_queue_rows,
            after_batch=self._fill_caches,
            service=self.service,
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
        continuous batch (or the legacy coalescer) and return views of the one
        host array their tick fetched. The whole call is the commit thread's
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
            got = self.coalescer.embed(
                [str(texts[i]) for i in miss_idx], enforce_cap=False
            )
            for i, v in zip(miss_idx, got):
                rows[i] = v
        return rows

    def _encode_device_rows(self, texts: List[str]) -> np.ndarray:
        """The legacy coalescer's batch: one padded forward, fetched once and
        cut on the host, as the service's tick does."""
        dev = self.encoder.encode_device(texts)
        with tracing.trace_span("encode.device_wait"):
            rows = fetch_rows(dev, len(texts))
        rows.setflags(write=False)  # waiters and the caches share these rows
        return rows

    def _fill_caches(self, texts: List[str], rows: Sequence[Any]) -> None:
        """Runs on the service/coalescer worker AFTER responders are released:
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
        out.update(self.coalescer.stats())
        out.update(self.semantic_cache.stats())
        if self.service is not None:
            out.update(self.service.stats())
        out["pad_waste_ratio"] = round(self.pad_waste_ratio(), 4)
        return out
