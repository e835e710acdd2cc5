"""Persistent on-device encoder service: continuous batching + warm jit caches.

The one way a query's text reaches the encoder
(:meth:`~pathway_tpu.models.embed_pipeline.EmbedPipeline.embed_query_rows` ->
:meth:`EncoderService.submit` -> tick), and its own admission point. A
*continuously-batched* encoder worker, the ragged-serving shape of the Ragged
Paged Attention recipe (PAPERS.md) applied to the query tower:

1. **Ragged admission queue.** Requests (solo or a commit's batch) append to a
   FIFO of variable-length text lists and wake the worker immediately — no
   deadline wait. Whatever is queued when the worker comes around is packed
   length-sorted into the next in-flight batch, capped at ``max_in_flight``
   rows; requests arriving while the device is busy ride the *next* tick, so
   concurrency still amortizes into one dispatch without any solo request ever
   waiting for a window to close.
2. **One admission point.** ``max_queue_rows`` caps the rows admitted but not
   yet answered (waiting + in flight). :meth:`EncoderService.overloaded` is the
   REST plane's lock-free pre-admission probe (429 + ``Retry-After`` from
   :meth:`EncoderService.retry_after_s`, and the brownout ladder's occupancy
   sample); :meth:`EncoderService.submit` sheds direct callers with a typed
   :class:`EmbedOverloadError` and counts ``embed.shed``.
3. **Always-warm pow2-bucketed forward.** The jitted forward only ever sees
   power-of-two (batch, seq) buckets (``JaxSentenceEncoder._dispatch``), so the
   whole reachable shape set is finite and enumerable. A background pre-warm
   thread compiles every bucket at service start (the Compiler-First caching
   argument: compiled state stays resident across requests) and records the
   wall cost as ``embed.svc.prewarm_s`` — compilation is reported at startup,
   never silently billed to the first query.
4. **Semantic query cache** (:class:`SemanticQueryCache`) sits ABOVE the
   content-hash cache in :class:`~pathway_tpu.models.embed_pipeline.EmbedPipeline`:
   exact mode (default) keys on the tokenizer's canonical form
   (``JaxSentenceEncoder.canonicalize``: whitespace collapse + case fold for
   uncased tokenizers), so a hit returns an embedding *bitwise-identical* to
   what the forward would produce — "  What is  RAG?" hits the entry stored
   for "what is rag?". Cosine mode (opt-in, ``threshold``) additionally
   answers near-matches via a cheap hashed bag-of-words proxy; it trades
   bitwise honesty for hit rate and is OFF by default.

Lifecycle: the worker thread spawns lazily on first :meth:`submit`, drains the
queue on :func:`stop_all_workers` (wired into ``GraphRunner.finish`` so
``pw.run`` teardown never leaks a device-owning thread) and respawns on the
next submit; :meth:`close` is the permanent variant. Every wait is timed and
abortable (the PWA102 contract) and the module lives in ``RUNTIME_MODULES`` so
PWA101-104 police its locks; the admission/tick/shutdown protocol is modeled
in ``internals/protocol_models.encsvc_model`` and explored under
``internals/sched.py`` (no deadlock, no dropped request, slots always
released) — the model was written and checked BEFORE this implementation, per
the PR-9 discipline.

Settings: the constants below (one value in use each); constructor arguments
where tests substitute a value; ``PATHWAY_ENCSVC_PREWARM`` (``1``/``0``, the
test harness turns the startup compile matrix off) and
``PATHWAY_EMBED_WAIT_TIMEOUT_S`` (a safety bound on one submission's wait)
from the environment.

Telemetry (PR-5 plane): ``embed.svc.*`` stage counters (prewarm_s,
prewarm_compiles, ticks, rows, batches, dedup_rows, encode_s,
semantic_hits/misses), ``embed.shed``, and three log-bucketed histograms on
``/metrics``: ``pathway_encsvc_queue_depth_rows``,
``pathway_encsvc_tick_occupancy`` (packed rows / max_in_flight),
``pathway_encsvc_tick_seconds``.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pathway_tpu.engine import telemetry
from pathway_tpu.engine import tracing as _tracing
from pathway_tpu.internals.config import env_float
from pathway_tpu.models.device_worker import DeviceWorker, stop_all_workers  # noqa: F401 (re-exported)
from pathway_tpu.models.encoder import fetch_rows


#: idle poll bound of the worker loop, in ms. Wakeups are notify-driven: the
#: tick only bounds how long a lost wakeup could park the worker
TICK_MS = 50.0
#: rows packed into one tick
MAX_IN_FLIGHT = 256
#: largest batch bucket compiled at start-up
PREWARM_MAX_BATCH = 64


class EmbedOverloadError(RuntimeError):
    """The encoder service's admission queue is full; the caller should shed
    load. Raised by :meth:`EncoderService.submit` for direct callers only —
    the REST plane consults the same cap BEFORE admission
    (:meth:`EncoderService.overloaded`, wired through ``rest_connector``) and
    sheds with HTTP 429 + ``Retry-After`` there, so an admitted request never
    dies inside an engine commit."""

    def __init__(self, message: str, *, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


def default_canonicalize(text: str) -> str:
    """Fallback canonical form when the encoder exposes none: collapse
    whitespace runs and case-fold — the equivalence every uncased BERT-family
    tokenizer already applies before wordpiece."""
    return " ".join(str(text).split()).lower()


class SemanticQueryCache:
    """Normalized-text query cache above the content-hash ``EmbedCache``.

    **exact** mode (default): key = ``canonicalize(text)``. Because the
    canonical form is exactly the equivalence the tokenizer applies anyway,
    two texts with the same key tokenize to identical ids and therefore
    identical (bitwise) embeddings — an exact-mode hit is as honest as
    re-running the forward. **cosine** mode (opt-in): on an exact-key miss, a
    hashed bag-of-words proxy vector of the query is cosine-compared against
    the cached proxies; a best match >= ``threshold`` answers with the cached
    embedding. Cosine hits are approximations — results are no longer
    bitwise-identical to a fresh encode, which is why the mode is off by
    default. **off**: get always misses, put is a no-op.

    Query-path ONLY by contract: the ingest path (``encode_batch``) and engine
    retraction rows never consult this layer — retractions replay from the
    evaluator's per-key memo (the ``deterministic=False`` contract) and
    re-ingested chunks ride the content-hash cache, so a semantic entry can
    never leak into document embeddings or retraction replay
    (regression-tested in ``tests/test_encoder_service.py``)."""

    #: proxy dimensionality for cosine mode — cheap to build and compare
    PROXY_DIM = 128

    def __init__(
        self,
        max_entries: int = 4096,
        *,
        mode: str = "exact",
        threshold: float = 0.95,
        canonicalize: Callable[[str], str] | None = None,
        key_tag: str = "",
    ):
        if mode not in ("exact", "cosine", "off"):
            raise ValueError(f"semantic cache mode must be exact|cosine|off, got {mode!r}")
        self.mode = mode
        self.max_entries = int(max_entries) if mode != "off" else 0
        self.threshold = float(threshold)
        base_canon = canonicalize or default_canonicalize
        if key_tag:
            # geometry-mode tag (e.g. the encoder's quantized-tower mode)
            # folded into every key: a mode flip can never serve embeddings
            # encoded under the other geometry — stale entries simply miss
            self._canon = lambda text: f"{key_tag}\x00{base_canon(text)}"
        else:
            self._canon = base_canon
        self._lock = threading.Lock()
        self._data: "OrderedDict[str, np.ndarray]" = OrderedDict()
        self._proxies: "OrderedDict[str, np.ndarray]" = OrderedDict()
        self.exact_hits = 0
        self.semantic_hits = 0
        self.misses = 0
        self.evictions = 0

    def _proxy(self, canon: str) -> np.ndarray:
        import xxhash

        vec = np.zeros(self.PROXY_DIM, dtype=np.float32)
        for word in canon.split():
            vec[xxhash.xxh32_intdigest(word) % self.PROXY_DIM] += 1.0
        norm = float(np.linalg.norm(vec))
        return vec / norm if norm > 0 else vec

    def get(self, text: str) -> Optional[np.ndarray]:
        if self.max_entries <= 0:
            return None
        key = self._canon(text)
        proxy = self._proxy(key) if self.mode == "cosine" else None
        with self._lock:
            vec = self._data.get(key)
            if vec is not None:
                self._data.move_to_end(key)
                self.exact_hits += 1
                return vec
            if proxy is not None and self._proxies:
                keys = list(self._proxies)
                mat = np.stack([self._proxies[k] for k in keys])
                sims = mat @ proxy
                best = int(np.argmax(sims))
                if float(sims[best]) >= self.threshold:
                    self.semantic_hits += 1
                    self._data.move_to_end(keys[best])
                    self._proxies.move_to_end(keys[best])
                    return self._data[keys[best]]
            self.misses += 1
            return None

    def put(self, text: str, vec: np.ndarray) -> None:
        if self.max_entries <= 0:
            return
        key = self._canon(text)
        row = np.ascontiguousarray(vec, dtype=np.float32)
        row.setflags(write=False)  # shared across queries: must never mutate
        proxy = self._proxy(key) if self.mode == "cosine" else None
        with self._lock:
            self._data[key] = row
            self._data.move_to_end(key)
            if proxy is not None:
                self._proxies[key] = proxy
                self._proxies.move_to_end(key)
            while len(self._data) > self.max_entries:
                old, _ = self._data.popitem(last=False)
                self._proxies.pop(old, None)
                self.evictions += 1

    def seed(self, text: str, vec: np.ndarray) -> None:
        """Idempotent :meth:`put` for the serving hot path: skips the lock,
        the row copy, and the LRU churn when the canonical key is already
        cached (the common case — every repeated content-cache hit re-seeds).
        The unlocked membership pre-check is benign: a racing double put is
        idempotent."""
        if self.max_entries <= 0:
            return
        if self._canon(text) in self._data:
            return
        self.put(text, vec)

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self._proxies.clear()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "semantic_mode": self.mode,
                "semantic_exact_hits": self.exact_hits,
                "semantic_cosine_hits": self.semantic_hits,
                "semantic_misses": self.misses,
                "semantic_evictions": self.evictions,
                "semantic_size": len(self._data),
            }


class _Submission:
    __slots__ = ("texts", "arrived", "event", "rows", "error")

    def __init__(self, texts: List[str]):
        self.texts = texts
        self.arrived = time.monotonic()
        self.event = threading.Event()
        self.rows: Optional[List[Any]] = None
        self.error: Optional[BaseException] = None


class EncoderService(DeviceWorker):
    """Persistent continuous-batching worker in front of one encoder.

    ``submit(texts)`` blocks until the worker answers with one row value per
    text: read-only host float32 rows, views of the one array the tick fetched
    from ``encoder.encode_device``'s padded forward. The worker packs
    everything queued at each tick — up to ``max_in_flight`` rows,
    length-sorted, duplicates encoded once — into one bucketed dispatch, so a
    solo request is dispatched the moment the worker is free (no deadline
    window) and a burst coalesces into one tick.

    The service is its own admission point: ``max_queue_rows`` (0 =
    unbounded) caps the rows admitted but not yet answered. ``overloaded`` /
    ``retry_after_s`` are the REST plane's pre-admission probes (429 +
    ``Retry-After``); ``submit`` sheds a direct caller with a typed
    :class:`EmbedOverloadError`."""

    _thread_name = "pathway:encsvc-worker"

    def __init__(
        self,
        encoder: Any,
        *,
        tick_ms: float = TICK_MS,
        max_in_flight: int = MAX_IN_FLIGHT,
        sub_batch: int = 64,
        max_queue_rows: int = 0,
        prewarm: bool | None = None,
        prewarm_max_batch: int = PREWARM_MAX_BATCH,
        after_batch: Callable[[List[str], Sequence[Any]], None] | None = None,
    ):
        super().__init__()
        self.encoder = encoder
        # the tick is the IDLE poll bound, not a batching delay: admission
        # notifies the worker, so a solo request never waits for it — it only
        # bounds how long a (hypothetical) lost wakeup could park the loop,
        # which is also what makes the idle wait abortable (PWA102)
        self.tick_s = max(0.001, float(tick_ms) / 1000.0)
        self.max_in_flight = max(1, int(max_in_flight))
        self.sub_batch = max(1, int(sub_batch))
        # admission cap: rows allowed to be pending (waiting + in flight).
        # Past it submit() sheds instead of queueing — an overloaded encoder
        # otherwise grows the queue without bound
        self.max_queue_rows = max(0, int(max_queue_rows))
        self._after_batch = after_batch
        # hard bound on one submission's total wait (0 = no bound; the wait is
        # still abortable, see _await). Covers a wedged device: the fence
        # deadline must never sit behind an unbounded embed wait
        self.wait_timeout_s = env_float("PATHWAY_EMBED_WAIT_TIMEOUT_S", 0.0)
        self._queued_rows = 0
        self._inflight_rows = 0
        self._encode_ewma_s = 0.0  # smoothed tick encode time (Retry-After)
        # counters (mirrored batch-level into the telemetry stage counters)
        self.requests = 0
        self.ticks = 0
        self.total_rows = 0
        self.batches = 0
        self.dedup_rows = 0
        # tokens under the attention mask, and batch bucket x sequence bucket,
        # of what the ticks sent to the device (the fill is their ratio)
        self.real_tokens = 0
        self.padded_tokens = 0
        self.max_tick_rows = 0
        self.shed_requests = 0
        # pre-warm state (abort via its own event: stop_worker must be able to
        # cancel a compile matrix even when no worker thread ever spawned, and
        # the worker's exit path resetting _stop_requested must not un-cancel)
        self._warm = threading.Event()
        self._prewarm_abort = threading.Event()
        self._prewarm_thread: threading.Thread | None = None
        self.prewarm_s = 0.0
        self.prewarm_compiles = 0
        if prewarm is None:
            prewarm = os.environ.get("PATHWAY_ENCSVC_PREWARM", "1").lower() not in (
                "0", "false", "no", "off",
            )
        self.prewarm_max_batch = max(8, int(prewarm_max_batch))
        if prewarm and self._prewarm_shapes():
            self._prewarm_thread = threading.Thread(
                target=self._prewarm_run, name="pathway:encsvc-prewarm", daemon=True
            )
            self._prewarm_thread.start()
        else:
            self._warm.set()

    # -- pre-warm ------------------------------------------------------------

    def _prewarm_shapes(self) -> List[Tuple[int, int]]:
        """Every pow2 (batch, seq) bucket the bucketed dispatch can reach,
        bounded by ``prewarm_max_batch`` x the encoder's ``max_length``. Empty
        when the encoder is not the jitted JAX module (mock encoders)."""
        if not hasattr(self.encoder, "_encode_ids") or not hasattr(self.encoder, "params"):
            return []
        from pathway_tpu.internals.shapes import next_pow2

        max_batch = next_pow2(
            min(self.max_in_flight, self.prewarm_max_batch), floor=8
        )
        max_seq = next_pow2(int(getattr(self.encoder, "max_length", 128)), floor=8)
        shapes = []
        b = 8
        while b <= max_batch:
            s = 8
            while s <= max_seq:
                shapes.append((b, s))
                s *= 2
            b *= 2
        return shapes

    def _prewarm_run(self) -> None:
        """Compile every reachable bucket off the request path; wall time and
        compile count land on ``embed.svc.prewarm_*`` so startup cost is
        reported instead of billed to the first query."""
        t0 = time.perf_counter()
        compiles = 0
        try:
            for batch, seq in self._prewarm_shapes():
                if self._prewarm_abort.is_set() or self._closed:
                    break  # remaining buckets compile lazily on first use
                # host ids, as a tick passes them: the same call signature
                ids = np.zeros((batch, seq), dtype=np.int32)
                out = self.encoder._encode_ids(self.encoder.params, ids)
                out.block_until_ready()
                compiles += 1
        except Exception:
            # pre-warm is best-effort (a failed compile resurfaces on use), but
            # not silent: prewarm_compiles then falls short of the bucket count
            logging.getLogger(__name__).exception(
                "encoder pre-warm stopped after %d of %d buckets",
                compiles, len(self._prewarm_shapes()),
            )
        finally:
            elapsed = time.perf_counter() - t0
            with self._cond:
                self.prewarm_s += elapsed
                self.prewarm_compiles += compiles
            telemetry.stage_add_many(
                {
                    "embed.svc.prewarm_s": elapsed,
                    "embed.svc.prewarm_compiles": float(compiles),
                }
            )
            self._warm.set()

    def wait_warm(self, timeout_s: float = 300.0) -> bool:
        """Block until the pre-warm pass finished (True) or ``timeout_s``
        elapsed (False). The benchmark calls this before its window, so
        compilation is excluded from request latency by construction."""
        return self._warm.wait(timeout=timeout_s)

    @property
    def warm(self) -> bool:
        return self._warm.is_set()

    # -- admission ------------------------------------------------------------

    def queue_depth_rows(self) -> int:
        """Rows admitted but not yet answered (waiting + in-flight). Lock-free
        read — a soft probe with bounded staleness."""
        return self._queued_rows + self._inflight_rows

    def overloaded(self, extra_rows: int = 0) -> bool:
        """Admission probe: would admitting ``extra_rows`` more rows exceed
        ``max_queue_rows``? Lock-free read — a soft cap with bounded overshoot,
        same contract as the REST ``max_pending`` check. Each probe also feeds
        the brownout ladder (``engine/brownout.py``) one occupancy sample, so
        the serving plane's degradation rungs engage from the same signal the
        shed decision uses."""
        if not self.max_queue_rows:
            return False
        pending = self.queue_depth_rows()
        from pathway_tpu.engine.brownout import get_brownout

        get_brownout().observe_occupancy(pending / self.max_queue_rows)
        return pending + extra_rows >= self.max_queue_rows

    def retry_after_s(self, extra_rows: int = 0) -> float:
        """Honest Retry-After estimate: ticks needed to drain the pending rows
        x smoothed tick encode time, floored at 1 s."""
        ticks = max(1.0, (self.queue_depth_rows() + extra_rows) / self.max_in_flight)
        return max(1.0, ticks * (self._encode_ewma_s or 0.05))

    # -- submission ----------------------------------------------------------

    def submit(self, texts: List[str], *, enforce_cap: bool = True) -> List[Any]:
        """Blocking: one row value per input text, in order. Raises
        :class:`EmbedOverloadError` when ``max_queue_rows`` is set and
        admitting these rows would exceed it. The engine serving path passes
        ``enforce_cap=False``: its requests were already admitted against the
        same cap at the REST boundary (``overloaded`` probe), and raising
        mid-commit would tear down the run instead of shedding one request."""
        if not texts:
            return []
        sub = _Submission(list(texts))
        with self._cond:
            if self._closed:
                raise RuntimeError("EncoderService is closed")
            pending = self._queued_rows + self._inflight_rows
            if (
                enforce_cap
                and self.max_queue_rows
                and pending + len(texts) > self.max_queue_rows
            ):
                self.shed_requests += 1
                telemetry.stage_add("embed.shed")
                raise EmbedOverloadError(
                    f"encoder service queue full ({pending} rows pending, "
                    f"cap {self.max_queue_rows})",
                    retry_after_s=self.retry_after_s(len(texts)),
                )
            self._queue.append(sub)
            self._queued_rows += len(texts)
            self.requests += 1
            self._ensure_worker_locked()
            self._cond.notify_all()
        self._await(sub)
        if sub.error is not None:
            raise sub.error
        assert sub.rows is not None
        return sub.rows

    def _await(self, sub: _Submission) -> None:
        """Abortable timed wait (PWA102): wakes every 0.25 s to observe
        teardown. A submission stranded with no worker (a stop/close raced the
        append) is self-healed by respawning the worker — unless the service
        is permanently closed, which fails it typed; an optional
        ``PATHWAY_EMBED_WAIT_TIMEOUT_S`` bounds the total wait against a
        wedged device."""
        deadline = (
            time.monotonic() + self.wait_timeout_s if self.wait_timeout_s > 0 else None
        )
        while not sub.event.wait(timeout=0.25):
            with self._cond:
                if sub.event.is_set():
                    break
                worker = self._worker
                worker_dead = worker is None or not worker.is_alive()
                if worker_dead and sub in self._queue:
                    if self._closed:
                        self._queue.remove(sub)
                        self._queued_rows -= len(sub.texts)
                        sub.error = RuntimeError(
                            "EncoderService closed before this submission was "
                            "dispatched (no worker left to drain the queue)"
                        )
                        sub.event.set()
                        break
                    self._ensure_worker_locked()
                    self._cond.notify_all()
            if deadline is not None and time.monotonic() > deadline:
                with self._cond:
                    if sub in self._queue:
                        self._queue.remove(sub)
                        self._queued_rows -= len(sub.texts)
                raise TimeoutError(
                    f"encoder service did not answer within "
                    f"{self.wait_timeout_s:.0f}s "
                    "(PATHWAY_EMBED_WAIT_TIMEOUT_S) — device wedged?"
                )

    # -- worker --------------------------------------------------------------

    def _gather(self) -> Tuple[List[_Submission], int]:
        """Take everything queued, up to ``max_in_flight`` rows (always at
        least one submission). Returns the take and the queue depth observed
        at wake — continuous batching: no deadline window, whatever is waiting
        when the worker is free rides this tick."""
        with self._cond:
            while not self._queue:
                if self._closed or self._stop_requested:
                    return [], 0
                self._cond.wait(timeout=self.tick_s)
            depth = self._queued_rows
            take: List[_Submission] = []
            rows = 0
            while self._queue and (
                not take or rows + len(self._queue[0].texts) <= self.max_in_flight
            ):
                sub = self._queue.popleft()
                take.append(sub)
                rows += len(sub.texts)
            self._queued_rows -= rows
            self._inflight_rows += rows
            return take, depth

    def _release_inflight(self, rows: int) -> None:
        with self._cond:
            self._inflight_rows -= rows
            self._cond.notify_all()

    def _encode_packed(self, texts: List[str]) -> Tuple[np.ndarray, int]:
        """Length-sorted packing of one tick's unique texts: small ticks are a
        single bucketed dispatch; large ticks split into ``sub_batch``-row
        length-sorted sub-batches (each padded only to ITS longest row's pow2
        bucket, dispatched async) so a ragged burst doesn't pay the longest
        row's padding on every short query. Every dispatch is enqueued before
        the first fetch; each padded forward is fetched once and cut on the
        host. Returns (read-only host float32 ``(len(texts), dim)``,
        dispatches)."""
        n = len(texts)
        order = list(range(n))
        if n > self.sub_batch:
            order.sort(key=lambda i: len(str(texts[i]).split()))
        with _tracing.trace_span("encode.dispatch", attrs={"rows": n}):
            inflight = []
            for start in range(0, n, self.sub_batch):
                idx = order[start : start + self.sub_batch]
                inflight.append((self.encoder.encode_device([texts[i] for i in idx]), idx))
        # the tick's one wait for the chip
        with _tracing.trace_span("encode.device_wait"):
            packed = np.concatenate([fetch_rows(dev, len(idx)) for dev, idx in inflight])
        rows = np.empty_like(packed)
        rows[order] = packed  # back from length order to the texts' order
        # waiters, the engine's memo and the caches share these rows
        rows.setflags(write=False)
        return rows, len(inflight)

    def _run(self) -> None:
        from pathway_tpu.engine.profile import histogram

        depth_hist = histogram("pathway_encsvc_queue_depth_rows")
        occ_hist = histogram("pathway_encsvc_tick_occupancy")
        tick_hist = histogram("pathway_encsvc_tick_seconds")
        # tokens this thread has sent to the device so far, read around each
        # tick (an encoder that does not count them leaves both at 0)
        sent = getattr(self.encoder, "dispatched_tokens", lambda: (0, 0))
        while True:
            batch, depth = self._gather()
            if not batch:
                with self._cond:
                    if self._exit_if_stopping_locked():
                        return
                continue
            t_tick = time.perf_counter()
            texts = [t for sub in batch for t in sub.texts]
            n_rows = len(texts)
            # content dedup inside the tick: N clients asking the same
            # question pay one forward row
            first_of: Dict[str, int] = {}
            unique: List[str] = []
            slot_of: List[int] = []
            for t in texts:
                j = first_of.setdefault(t, len(unique))
                if j == len(unique):
                    unique.append(t)
                slot_of.append(j)
            # a coalesced batch links its N parent query spans: drain the
            # contexts REST handlers registered under this tick's texts. The
            # tick span samples whenever ANY linked query's trace is sampled
            # (the batch is shared work — every sampled parent needs it)
            tracer = _tracing.get_tracer()
            trace_links = tuple(tracer.take_query_links(unique))
            real0, padded0 = sent()
            # the tick's span: encode, hand every waiter its rows, count,
            # fill the cache (None while nothing records)
            with tracer.trace_span(
                "encode",
                f"encode tick {len(unique)}",
                links=trace_links,
                attrs={"rows": n_rows, "unique": len(unique)},
            ) as enc_span:
                if enc_span is not None and any(l.sampled for l in trace_links):
                    enc_span.sampled = True
                try:
                    t_enc = time.monotonic()
                    with telemetry.stage_timer("embed.svc.encode"):
                        out, dispatches = self._encode_packed(unique)
                    enc_s = time.monotonic() - t_enc
                    self._encode_ewma_s = (
                        0.8 * self._encode_ewma_s + 0.2 * enc_s
                        if self._encode_ewma_s
                        else enc_s
                    )
                    rows = [out[j] for j in slot_of]
                    if enc_span is not None:
                        enc_span.attrs["dispatches"] = dispatches
                except BaseException as exc:  # propagate to every waiter in the tick
                    if enc_span is not None:
                        enc_span.attrs["error"] = type(exc).__name__
                    self._release_inflight(n_rows)
                    for sub in batch:
                        sub.error = exc
                        sub.event.set()
                    continue
                real1, padded1 = sent()
                with self._cond:
                    self.ticks += 1
                    self.total_rows += n_rows
                    self.batches += dispatches
                    self.dedup_rows += n_rows - len(unique)
                    self.real_tokens += real1 - real0
                    self.padded_tokens += padded1 - padded0
                    self.max_tick_rows = max(self.max_tick_rows, n_rows)
                    self._inflight_rows -= n_rows
                    self._cond.notify_all()
                pos = 0
                for sub in batch:
                    sub.rows = rows[pos : pos + len(sub.texts)]
                    pos += len(sub.texts)
                    sub.event.set()
                # telemetry AFTER responders are released: stage counters and
                # histograms are off the request latency path
                telemetry.stage_add_many(
                    {
                        "embed.svc.ticks": 1.0,
                        "embed.svc.rows": float(n_rows),
                        "embed.svc.batches": float(dispatches),
                        "embed.svc.dedup_rows": float(n_rows - len(unique)),
                    }
                )
                depth_hist.observe(float(depth))
                occ_hist.observe(n_rows / self.max_in_flight)
                tick_hist.observe(time.perf_counter() - t_tick)
                if self._after_batch is not None:
                    try:
                        self._after_batch(unique, out)
                    except Exception:
                        pass  # cache fill is best-effort; responders already released

    # -- lifecycle -----------------------------------------------------------

    def stop_worker(self, timeout_s: float = 10.0) -> None:
        """Drain the queue and stop the worker (``DeviceWorker.stop_worker``),
        and abort a running pre-warm (it cancels between bucket compiles; the
        join may still ride out ONE in-flight compile)."""
        self._prewarm_abort.set()
        super().stop_worker(timeout_s=timeout_s)
        prewarm = self._prewarm_thread
        if prewarm is not None and prewarm is not threading.current_thread():
            prewarm.join(timeout=timeout_s)

    # -- reporting -----------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._cond:
            return {
                "svc_requests": self.requests,
                "svc_ticks": self.ticks,
                "svc_rows": self.total_rows,
                "svc_batches": self.batches,
                "svc_dedup_rows": self.dedup_rows,
                "svc_real_tokens": self.real_tokens,
                "svc_padded_tokens": self.padded_tokens,
                "svc_max_tick_rows": self.max_tick_rows,
                "svc_avg_tick_rows": round(self.total_rows / max(self.ticks, 1), 2),
                "svc_occupancy": round(
                    self.total_rows / max(self.ticks * self.max_in_flight, 1), 4
                ),
                "svc_queue_rows": self._queued_rows + self._inflight_rows,
                "svc_shed_requests": self.shed_requests,
                "svc_prewarm_s": round(self.prewarm_s, 3),
                "svc_prewarm_compiles": self.prewarm_compiles,
                "svc_warm": self._warm.is_set(),
            }
