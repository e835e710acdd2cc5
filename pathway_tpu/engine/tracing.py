"""Distributed tracing plane: causal spans across REST, encoder, mesh, replicas.

The PR-5 metrics plane answers "how much / how slow" per rank; this module
answers "why was THIS query slow". Every hop of a request — REST admission,
the encoder tick that batched it, the commit that served it, the
exchange barrier it waited behind, the replica that answered — records a
:class:`Span` carrying (trace_id, span_id, parent_id, rank, kind, wall +
monotonic stamps, attrs, links), and the per-rank rings merge offline into one
causally-ordered tree with a critical path.

Design points, in the order they matter:

- **Head sampling with deterministic consistency.** The sampling decision is a
  pure function of the trace id (``_head_sampled``): every rank and component
  derives the SAME decision without exchanging a bit, which is what keeps a
  commit's spans consistent across ranks (the commit trace id itself is a pure
  function of ``(epoch, commit)`` — lockstep commit numbers are the cross-rank
  trace key, no wire change required). An explicit ``X-Pathway-Trace`` flag
  overrides the hash for that trace (callers can force-sample a request).
- **Slow promotion.** Unsampled traces buffer in a bounded pending map; when a
  trace's ROOT span finishes over ``PATHWAY_TRACE_SLOW_MS`` the whole local
  buffer promotes into the ring (``trace.promoted``), otherwise it drops when
  the root closes. Promotion is per-rank local by construction — a slow commit
  is slow on every rank that waited behind its barrier, so in practice all
  ranks promote the same trace.
- **Recording is opt-in, two ways.** A span is recorded when the env gate
  below is on, or while a ``jax.profiler`` session is on (observed through
  ``TraceAnnotation.is_enabled()``: no switch of our own). Outside both,
  ``trace_span``/``start`` return ``None`` after that one check and build
  nothing. In a session every trace counts as sampled and every span goes to
  the ring (sized to hold a whole traced span of serving), and every
  *synchronous* span (``trace_span``) also opens a
  ``jax.profiler.TraceAnnotation`` named ``pw.<kind>`` carrying its numeric
  attributes, so that it lands on the ``/host:CPU`` plane of the same
  ``.xplane.pb`` as the device planes: an idle gap on the device can then be
  named by what the host was doing (``cli trace <profile dir>``). A span whose
  life crosses an ``await`` or a thread (``start``/``finish``: the ``rest``
  span, ``replica_serve``) is never an annotation: annotations on one thread
  must nest, and requests interleave on the event-loop thread.
- **Few hot-path spans.** ``GraphRunner`` does NOT wrap every operator in a
  span; per-operator / fused-region child spans are synthesized from the
  already-collected :class:`~pathway_tpu.engine.profile.CommitProfile` ops at
  commit end, and only for sampled/promoted commits. Live spans sit only at
  the layer boundaries of the serving path (``admit``, ``embed_wait``,
  ``search`` and its parts, the encoder tick's parts, ``reply``); each costs
  one ``recording()`` check when nothing records.
- **A wait for the device is a kind of its own.** Every place on the serving
  path where the host blocks on the device is a span whose kind ends in
  ``.device_wait``: device idle under it reads "host waiting for the chip",
  idle under anything else reads "chip waiting for the host".
- **Crash-safe flush.** The ring flushes to ``trace-rank-N.jsonl`` on finish
  AND alongside every flight-recorder dump (crash, fence, SIGTERM, chaos
  kill) via :func:`pathway_tpu.engine.profile.register_trace_hooks` — a
  killed rank still leaves a partial trace. The lock is an RLock for the same
  reason the flight recorder's is: dumps run from signal handlers that may
  have interrupted a holder on the same thread.

The ring/flush lifecycle and the trace-context handoff across a membership
transition are model-checked (``internals/protocol_models.trace_ring_model``):
no span orphaned by an epoch bump, flush-on-crash never deadlocks the dying
rank, sampling decision consistent across a trace.

Env knobs: ``PATHWAY_TRACE=on`` turns span recording on (default off; the
header echo works either way; a ``jax.profiler`` session turns it on too);
``PATHWAY_TRACE_SAMPLE`` is the head-sampling probability (default 0.01);
``PATHWAY_TRACE_SLOW_MS`` always-samples roots slower than this (default 250);
``PATHWAY_TRACE_RING`` sizes the span ring (default 65536);
``PATHWAY_TRACE_DIR`` overrides the flush directory (default: the flight
recorder's dump dir).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import hashlib
import json
import os
import threading
import time
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from pathway_tpu.engine import telemetry

#: REST trace-propagation header (in AND out on every route). Value format:
#: ``<trace_id 16hex>-<span_id 16hex>-<flags 2hex>`` (flags bit 0 = sampled),
#: a deliberately W3C-traceparent-shaped shape without the version field.
TRACE_HEADER = "X-Pathway-Trace"

_ID_HEX = 16  # 64-bit ids, rendered as 16 hex chars

# the ring holds a whole traced span of serving: a 4 s profiler session at 200
# requests/s ends about 4 spans a request, 12 a commit and its encoder tick,
# and up to 48 synthesized operator rows a commit, some 30,000 in all
_RING_SPANS = 65536
# what trace_span hands out while nothing records: yields None, builds nothing
_NO_SPAN = contextlib.nullcontext()

# pending (unsampled, promotion-eligible) buffer bounds: per-trace and total
_MAX_PENDING_TRACES = 64
_MAX_PENDING_SPANS = 128
# bounded link registries (query-text -> ctx, admitted-query ctx feed)
_MAX_LINK_KEYS = 256
_MAX_LINKS_PER_KEY = 32


def _new_id() -> str:
    return os.urandom(_ID_HEX // 2).hex()


def _derived_id(seed: str) -> str:
    return hashlib.sha1(seed.encode("utf-8")).hexdigest()[:_ID_HEX]


class TraceContext:
    """The propagating identity of a span: enough to parent a child anywhere
    (another thread, another rank, another process) and to keep the sampling
    decision consistent along the way."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str, sampled: bool) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"TraceContext({self.trace_id}, {self.span_id}, sampled={self.sampled})"


class Span:
    """One timed unit of work. ``ts`` is wall-clock (cross-rank merge, after
    clock-offset correction), ``ts_mono`` is monotonic (intra-rank ordering
    immune to wall-clock steps); both stamp at START, ``duration_s`` closes."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "rank", "epoch", "kind", "name",
        "ts", "ts_mono", "duration_s", "attrs", "links", "sampled", "root",
    )

    def __init__(
        self,
        *,
        trace_id: str,
        span_id: str,
        parent_id: Optional[str],
        rank: int,
        epoch: int,
        kind: str,
        name: str,
        sampled: bool,
        root: bool,
        links: Tuple[TraceContext, ...] = (),
        attrs: Optional[Dict[str, Any]] = None,
    ) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.rank = rank
        self.epoch = epoch
        self.kind = kind
        self.name = name
        self.ts = time.time()
        self.ts_mono = time.monotonic()
        self.duration_s = 0.0
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.links: List[Dict[str, str]] = [
            {"trace_id": l.trace_id, "span_id": l.span_id} for l in links
        ]
        self.sampled = sampled
        self.root = root

    def context(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id, self.sampled)

    def add_link(self, ctx: TraceContext) -> None:
        self.links.append({"trace_id": ctx.trace_id, "span_id": ctx.span_id})

    def to_dict(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "rank": self.rank,
            "epoch": self.epoch,
            "kind": self.kind,
            "name": self.name,
            "ts": self.ts,
            "ts_mono": self.ts_mono,
            "duration_s": self.duration_s,
            "attrs": self.attrs,
            "links": self.links,
        }


# -- context propagation helpers ---------------------------------------------


def parse_trace_header(value: Optional[str]) -> Optional[TraceContext]:
    """Parse an ``X-Pathway-Trace`` value; tolerant — malformed input is
    treated as absent (a bad client header must not 500 the route)."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) < 2:
        return None
    trace_id, span_id = parts[0].lower(), parts[1].lower()
    if len(trace_id) != _ID_HEX or len(span_id) != _ID_HEX:
        return None
    try:
        int(trace_id, 16)
        int(span_id, 16)
    except ValueError:
        return None
    if len(parts) >= 3 and parts[2] in ("00", "01"):
        sampled = parts[2] == "01"  # explicit flag overrides the hash
    else:
        sampled = _head_sampled(trace_id)
    return TraceContext(trace_id, span_id, sampled)


def format_trace_header(ctx: TraceContext) -> str:
    return f"{ctx.trace_id}-{ctx.span_id}-{'01' if ctx.sampled else '00'}"


def _head_sampled(trace_id: str) -> bool:
    """THE sampling decision: a pure function of the trace id, so every rank
    and component agrees without exchanging a bit."""
    rate = get_tracer().sample_rate
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    return (int(trace_id[:8], 16) / float(1 << 32)) < rate


def new_trace_context(sampled: Optional[bool] = None) -> TraceContext:
    trace_id = _new_id()
    return TraceContext(
        trace_id,
        _new_id(),
        _head_sampled(trace_id) if sampled is None else sampled,
    )


def commit_trace_context(epoch: int, commit: int, rank: int = 0) -> TraceContext:
    """Deterministic identity for commit ``commit`` of mesh epoch ``epoch``:
    every rank derives the same trace id (lockstep commit numbers are the
    cross-rank key — nothing rides the wire) and its own span id, so all
    ranks' commit spans are siblings in one trace."""
    trace_id = _derived_id(f"commit:{epoch}:{commit}")
    span_id = _derived_id(f"{trace_id}:rank:{rank}")
    return TraceContext(trace_id, span_id, _head_sampled(trace_id))


_current_span: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "pathway_trace_span", default=None
)


def current_context() -> Optional[TraceContext]:
    span = _current_span.get()
    return span.context() if span is not None else None


class _AdoptedSpan:
    """Stands in the context-local slot for a span that is open elsewhere (a
    request's ``rest`` span on the event loop's thread)."""

    __slots__ = ("_ctx",)

    def __init__(self, ctx: TraceContext):
        self._ctx = ctx

    def context(self) -> TraceContext:
        return self._ctx


@contextlib.contextmanager
def adopt_context(ctx: Optional[TraceContext]) -> Iterator[None]:
    """Spans opened inside become children of ``ctx``: for work that carries a
    request on after the commit that took its row has ended (the loop-back
    connector's invocations), where no span is open around it."""
    if ctx is None:
        yield
        return
    token = _current_span.set(_AdoptedSpan(ctx))
    try:
        yield
    finally:
        _current_span.reset(token)


# -- the tracer ---------------------------------------------------------------


class Tracer:
    """Bounded per-rank span ring + pending (promotion-eligible) buffers +
    link registries. One RLock: flush may run from a signal handler that
    interrupted a holder on the same thread (see module docstring)."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self.enabled = True
        self.sample_rate = 0.01
        self.slow_ms = 250.0
        self.rank = 0
        self.epoch = 0
        self._default_dir: Optional[str] = None
        self._ring: "collections.deque[Span]" = collections.deque(
            maxlen=_RING_SPANS
        )
        # trace_id -> finished-but-unsampled spans awaiting the root's verdict
        self._pending: "collections.OrderedDict[str, List[Span]]" = (
            collections.OrderedDict()
        )
        # query-text key -> contexts of REST spans waiting on that text
        # (drained by the encoder tick that batches the text)
        self._query_links: "collections.OrderedDict[str, List[TraceContext]]" = (
            collections.OrderedDict()
        )
        # row key -> (context, push instant) of REST queries pushed into the
        # engine and not yet taken by a commit (drained by the commit that
        # finds the key among its input rows)
        self._commit_links: "collections.OrderedDict[bytes, Tuple[TraceContext, float]]" = (
            collections.OrderedDict()
        )
        self._offsets: Dict[int, float] = {}
        self.flushes = 0
        self.refresh()

    # -- configuration --------------------------------------------------------

    def refresh(self) -> None:
        """Re-read the env knobs (tests flip them between runs)."""
        env = os.environ
        # opt-in master gate (README: default off) — unset must mean OFF, or
        # every engine in the process pays span bookkeeping nobody asked for
        enabled = env.get("PATHWAY_TRACE", "").lower() in (
            "1", "true", "yes", "on",
        )
        rate = 0.01
        try:
            rate = float(env.get("PATHWAY_TRACE_SAMPLE", "0.01"))
        except ValueError:
            pass
        slow_ms = 250.0
        try:
            slow_ms = float(env.get("PATHWAY_TRACE_SLOW_MS", "250"))
        except ValueError:
            pass
        ring = _RING_SPANS
        try:
            ring = max(64, int(env.get("PATHWAY_TRACE_RING", "")))
        except ValueError:
            pass
        with self._lock:
            self.enabled = enabled
            self.sample_rate = min(1.0, max(0.0, rate))
            self.slow_ms = max(0.0, slow_ms)
            if self._ring.maxlen != ring:
                self._ring = collections.deque(self._ring, maxlen=ring)

    def configure(
        self, *, rank: Optional[int] = None, default_dir: Optional[str] = None
    ) -> None:
        with self._lock:
            if rank is not None:
                self.rank = rank
            if default_dir is not None:
                self._default_dir = default_dir
        self.refresh()

    def set_epoch(self, epoch: int) -> None:
        """Membership transition: spans opened after this stamp the new epoch.
        Pending buffers survive the bump — a span recorded under the old epoch
        is never orphaned by the transition (model invariant)."""
        with self._lock:
            self.epoch = epoch

    def set_clock_offsets(self, offsets: Dict[int, float]) -> None:
        """Heartbeat-estimated ``peer_wall - local_wall`` seconds per peer
        (the merger aligns rank files with these; see ``cluster.py``)."""
        with self._lock:
            self._offsets = dict(offsets)

    def recording(self) -> bool:
        """Whether a span opened now is kept: the env gate is on, or a
        ``jax.profiler`` session is (observed, not configured)."""
        return self.enabled or TraceAnnotation.is_enabled()

    # -- span lifecycle -------------------------------------------------------

    def start(
        self,
        kind: str,
        name: Optional[str] = None,
        *,
        ctx: Optional[TraceContext] = None,
        self_ctx: Optional[TraceContext] = None,
        links: Tuple[TraceContext, ...] = (),
        attrs: Optional[Dict[str, Any]] = None,
    ) -> Optional[Span]:
        """Open a span. ``ctx`` parents it explicitly (falls back to the
        context-local current span); ``self_ctx`` instead assigns the span's
        OWN identity (deterministic commit spans). Returns None when nothing
        records — callers must tolerate that. A span opened while a profiler
        session is on counts as sampled. This is the API for a span whose life
        crosses an ``await`` or a thread (close it with :meth:`finish`); it
        never opens a profiler annotation."""
        session = TraceAnnotation.is_enabled()
        if not (self.enabled or session):
            return None
        parent = ctx if ctx is not None else current_context()
        if self_ctx is not None:
            span = Span(
                trace_id=self_ctx.trace_id,
                span_id=self_ctx.span_id,
                parent_id=parent.span_id if parent is not None else None,
                rank=self.rank,
                epoch=self.epoch,
                kind=kind,
                name=name or kind,
                sampled=self_ctx.sampled,
                root=parent is None,
                links=links,
                attrs=attrs,
            )
        elif parent is not None:
            span = Span(
                trace_id=parent.trace_id,
                span_id=_new_id(),
                parent_id=parent.span_id,
                rank=self.rank,
                epoch=self.epoch,
                kind=kind,
                name=name or kind,
                sampled=parent.sampled,
                root=False,
                links=links,
                attrs=attrs,
            )
        else:
            root_ctx = new_trace_context()
            span = Span(
                trace_id=root_ctx.trace_id,
                span_id=root_ctx.span_id,
                parent_id=None,
                rank=self.rank,
                epoch=self.epoch,
                kind=kind,
                name=name or kind,
                sampled=root_ctx.sampled,
                root=True,
                links=links,
                attrs=attrs,
            )
        if session:
            span.sampled = True
        return span

    def finish(self, span: Span) -> None:
        """Close a span and route it: sampled -> ring; unsampled -> pending
        until its trace's root closes (slow root promotes the buffer, fast
        root drops it)."""
        if span.duration_s == 0.0:
            span.duration_s = max(0.0, time.monotonic() - span.ts_mono)
        slow = span.duration_s * 1000.0 >= self.slow_ms
        with self._lock:
            if span.sampled:
                self._ring.append(span)
                telemetry.stage_add("trace.span")
                return
            if span.root and slow:
                # always-sample slow roots: promote the whole local buffer
                span.sampled = True
                promoted = self._pending.pop(span.trace_id, [])
                for buffered in promoted:
                    buffered.sampled = True
                    self._ring.append(buffered)
                self._ring.append(span)
                telemetry.stage_add_many({
                    "trace.span": float(len(promoted) + 1),
                    "trace.promoted": 1.0,
                })
                return
            if span.root:
                dropped = self._pending.pop(span.trace_id, None)
                if dropped:
                    telemetry.stage_add("trace.dropped", float(len(dropped)))
                return
            bucket = self._pending.get(span.trace_id)
            if bucket is None:
                while len(self._pending) >= _MAX_PENDING_TRACES:
                    _, evicted = self._pending.popitem(last=False)
                    telemetry.stage_add("trace.dropped", float(len(evicted)))
                bucket = self._pending[span.trace_id] = []
            if len(bucket) < _MAX_PENDING_SPANS:
                bucket.append(span)

    def trace_span(
        self,
        kind: str,
        name: Optional[str] = None,
        *,
        ctx: Optional[TraceContext] = None,
        self_ctx: Optional[TraceContext] = None,
        links: Tuple[TraceContext, ...] = (),
        attrs: Optional[Dict[str, Any]] = None,
    ) -> "contextlib.AbstractContextManager[Optional[Span]]":
        """The one span-recording API for SYNCHRONOUS work: opened and closed
        on one thread with no ``await`` between (PWA205 lints literal ``kind``
        args against ``telemetry.TRACE_SPAN_KINDS``). Yields the open span (or
        None when nothing records) and installs it as the context-local
        parent. While a profiler session is on the span is also a
        ``TraceAnnotation`` named ``pw.<kind>`` on the calling thread's line
        of the ``/host:CPU`` plane, with its numeric attributes as stats."""
        if not self.recording():
            return _NO_SPAN
        return self._open_span(kind, name, ctx, self_ctx, links, attrs)

    @contextlib.contextmanager
    def _open_span(
        self,
        kind: str,
        name: Optional[str],
        ctx: Optional[TraceContext],
        self_ctx: Optional[TraceContext],
        links: Tuple[TraceContext, ...],
        attrs: Optional[Dict[str, Any]],
    ) -> Iterator[Optional[Span]]:
        span = self.start(
            kind, name, ctx=ctx, self_ctx=self_ctx, links=links, attrs=attrs
        )
        if span is None:  # the session ended between the two checks
            yield None
            return
        annotation: Any = _NO_SPAN
        if TraceAnnotation.is_enabled():
            annotation = TraceAnnotation(
                "pw." + kind,
                **{
                    k: v for k, v in span.attrs.items()
                    if isinstance(v, (int, float))
                },
            )
        token = _current_span.set(span)
        try:
            with annotation:
                yield span
        finally:
            _current_span.reset(token)
            self.finish(span)

    def record_span(
        self,
        kind: str,
        name: str,
        *,
        parent: TraceContext,
        ts: float,
        ts_mono: float,
        duration_s: float,
        attrs: Optional[Dict[str, Any]] = None,
        links: Tuple[TraceContext, ...] = (),
    ) -> None:
        """Synthesize an already-finished child span (operator / fused-region
        rows lifted from a CommitProfile at commit end — nothing on the
        operator hot path; the wait of a query row before its commit). Only
        call for sampled/promoted parents."""
        if not self.recording():
            return
        span = Span(
            trace_id=parent.trace_id,
            span_id=_new_id(),
            parent_id=parent.span_id,
            rank=self.rank,
            epoch=self.epoch,
            kind=kind,
            name=name,
            sampled=True,
            root=False,
            links=links,
            attrs=attrs,
        )
        span.ts = ts
        span.ts_mono = ts_mono
        span.duration_s = duration_s
        with self._lock:
            self._ring.append(span)
            telemetry.stage_add("trace.span")

    # -- link registries ------------------------------------------------------

    def register_query_link(self, key: str, ctx: TraceContext) -> None:
        """A REST query span waiting on ``key`` (the query text): the encoder
        tick that batches the text drains these into its span's links."""
        if not self.recording():
            return
        with self._lock:
            bucket = self._query_links.get(key)
            if bucket is None:
                while len(self._query_links) >= _MAX_LINK_KEYS:
                    self._query_links.popitem(last=False)
                bucket = self._query_links[key] = []
            if len(bucket) < _MAX_LINKS_PER_KEY:
                bucket.append(ctx)

    def take_query_links(self, keys: List[str]) -> List[TraceContext]:
        if not self.recording():
            return []
        out: List[TraceContext] = []
        with self._lock:
            for key in keys:
                out.extend(self._query_links.pop(key, ()))
        return out

    def register_commit_link(self, key: bytes, ctx: TraceContext) -> None:
        """A REST query about to be pushed into the engine under row key
        ``key``: the commit that takes that row links the query and records
        its ``queue`` span from this instant (call right before the push)."""
        if not self.recording():
            return
        with self._lock:
            while len(self._commit_links) >= _MAX_LINK_KEYS:
                self._commit_links.popitem(last=False)
            self._commit_links[key] = (ctx, time.monotonic())

    def commit_link_context(self, key: bytes) -> Optional[TraceContext]:
        """The context registered under row key ``key`` and not taken yet: what
        a sink inside the commit that takes the row reads, to carry the
        request on past that commit (the commit takes its links at its end)."""
        with self._lock:
            link = self._commit_links.get(key)
        return link[0] if link is not None else None

    def take_commit_links(
        self, keys: Iterable[bytes]
    ) -> List[Tuple[TraceContext, float]]:
        """(context, push instant) of every registered query whose row key is
        among ``keys`` (a commit's input rows; not read while nothing is
        registered)."""
        out: List[Tuple[TraceContext, float]] = []
        with self._lock:
            if not self._commit_links:
                return out
            for key in keys:
                link = self._commit_links.pop(key, None)
                if link is not None:
                    out.append(link)
        return out

    # -- flush / dump ---------------------------------------------------------

    def recent_spans(self, limit: int = 128) -> List[Dict[str, Any]]:
        """Snapshot of the newest ring spans (flight-dump embedding): safe to
        call from a signal handler — the RLock is reentrant and the snapshot
        is read-only."""
        with self._lock:
            spans = list(self._ring)[-limit:]
        return [s.to_dict() for s in spans]

    def _resolve_dir(self) -> Optional[str]:
        return os.environ.get("PATHWAY_TRACE_DIR") or self._default_dir

    def flush_path(self, directory: Optional[str] = None) -> Optional[str]:
        directory = directory or self._resolve_dir()
        if directory is None:
            return None
        return os.path.join(directory, f"trace-rank-{self.rank}.jsonl")

    def flush(
        self, directory: Optional[str] = None, reason: str = "flush"
    ) -> Optional[str]:
        """Write the ring to ``trace-rank-N.jsonl`` (atomic rename; first
        record is ``_meta`` with the clock offsets the merger aligns by).
        Never raises — a failing flush must not mask the failure being
        recorded."""
        if not self.enabled:
            return None
        path = self.flush_path(directory)
        if path is None:
            return None
        with self._lock:
            spans = [s.to_dict() for s in self._ring]
            meta = {
                "_meta": {
                    "rank": self.rank,
                    "epoch": self.epoch,
                    "reason": reason,
                    "ts": time.time(),
                    "ts_mono": time.monotonic(),
                    "clock_offsets": {str(k): v for k, v in self._offsets.items()},
                }
            }
        try:
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(json.dumps(meta))
                f.write("\n")
                for span in spans:
                    f.write(json.dumps(span))
                    f.write("\n")
            os.replace(tmp, path)
            with self._lock:
                self.flushes += 1
            telemetry.stage_add("trace.flush")
            return path
        except (OSError, TypeError, ValueError):
            return None

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._pending.clear()
            self._query_links.clear()
            self._commit_links.clear()
            self._offsets = {}
            self.flushes = 0
        self.refresh()


_tracer: Optional[Tracer] = None
_tracer_lock = threading.Lock()


def get_tracer() -> Tracer:
    """Process-wide tracer (lazily built from the env)."""
    global _tracer
    inst = _tracer  # noqa: PWA103 (double-checked locking: unlocked fast-path read; the only WRITE is under _tracer_lock below)
    if inst is None:
        with _tracer_lock:
            inst = _tracer
            if inst is None:
                inst = _tracer = Tracer()
                _register_flight_hooks(inst)
    return inst


def trace_span(
    kind: str,
    name: Optional[str] = None,
    *,
    ctx: Optional[TraceContext] = None,
    self_ctx: Optional[TraceContext] = None,
    links: Tuple[TraceContext, ...] = (),
    attrs: Optional[Dict[str, Any]] = None,
) -> "contextlib.AbstractContextManager[Optional[Span]]":
    """Module-level convenience over :meth:`Tracer.trace_span`."""
    return get_tracer().trace_span(
        kind, name, ctx=ctx, self_ctx=self_ctx, links=links, attrs=attrs
    )


def reset_tracing() -> None:
    """Test/bench hook: clear the ring, buffers, and registries (the tracer
    keeps its rank/dir config, re-reads the env knobs)."""
    inst = _tracer  # noqa: PWA103 (read-only peek at the singleton; writes stay under _tracer_lock in get_tracer)
    if inst is not None:
        inst.reset()


def _register_flight_hooks(tracer: Tracer) -> None:
    """Ride the flight recorder's dump paths: every crash/fence/chaos dump
    embeds the newest spans in its payload AND flushes the jsonl next to it,
    so a killed rank still yields a partial trace."""
    from pathway_tpu.engine import profile

    def _spans() -> Dict[str, Any]:
        return {"rank": tracer.rank, "spans": tracer.recent_spans()}

    def _flush(directory: Optional[str], reason: str) -> None:
        tracer.flush(directory, reason=reason)

    profile.register_trace_hooks(_spans, _flush)


# -- merging + critical path --------------------------------------------------


def load_trace_file(path: str) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """Read one ``trace-rank-N.jsonl``: ``(meta, spans)``; tolerant of torn
    tails (a rank killed mid-write loses at most its last line)."""
    meta: Dict[str, Any] = {}
    spans: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
            except ValueError:
                continue  # torn tail
            if "_meta" in record:
                meta = record["_meta"]
            else:
                spans.append(record)
    return meta, spans


def load_flight_spans(path: str) -> List[Dict[str, Any]]:
    """Spans embedded in a flight dump (``flight-rank-N.json``) — the partial
    trace a chaos-killed rank left behind."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return []
    trace = payload.get("trace") or {}
    spans = trace.get("spans") or []
    return [s for s in spans if isinstance(s, dict) and "span_id" in s]


def merge_trace_files(
    paths: List[str], flight_paths: Optional[List[str]] = None
) -> Dict[str, Any]:
    """Join per-rank trace files (plus flight-dump partials) into one span
    set, wall clocks aligned to rank 0's frame via the heartbeat-estimated
    offsets each rank recorded in its ``_meta``."""
    metas: Dict[int, Dict[str, Any]] = {}
    spans: List[Dict[str, Any]] = []
    seen: set = set()
    for path in paths:
        try:
            meta, file_spans = load_trace_file(path)
        except OSError:
            continue
        rank = int(meta.get("rank", -1))
        if rank >= 0:
            metas[rank] = meta
        for span in file_spans:
            key = (span.get("span_id"), span.get("rank"))
            if key not in seen:
                seen.add(key)
                spans.append(span)
    for path in flight_paths or []:
        for span in load_flight_spans(path):
            key = (span.get("span_id"), span.get("rank"))
            if key not in seen:
                seen.add(key)
                spans.append(span)
    # offsets[r] estimates rank-r wall minus rank-0 wall: prefer rank 0's own
    # measurement of peer r; fall back to rank r's measurement of peer 0
    offsets: Dict[int, float] = {0: 0.0}
    zero_meta = metas.get(0, {})
    zero_offsets = zero_meta.get("clock_offsets", {})
    for rank, meta in metas.items():
        if rank == 0:
            continue
        if str(rank) in zero_offsets:
            offsets[rank] = float(zero_offsets[str(rank)])
        elif "0" in meta.get("clock_offsets", {}):
            offsets[rank] = -float(meta["clock_offsets"]["0"])
        else:
            offsets[rank] = 0.0
    for span in spans:
        span["ts_adj"] = float(span.get("ts", 0.0)) - offsets.get(
            int(span.get("rank", 0)), 0.0
        )
    spans.sort(key=lambda s: s["ts_adj"])
    return {"spans": spans, "offsets": offsets, "ranks": sorted(metas)}


def _trace_tree(
    spans: List[Dict[str, Any]], trace_id: str
) -> Tuple[List[Dict[str, Any]], Dict[str, List[Dict[str, Any]]]]:
    """(roots, children-by-parent) for one trace, children in causal order."""
    mine = [s for s in spans if s.get("trace_id") == trace_id]
    ids = {s["span_id"] for s in mine}
    children: Dict[str, List[Dict[str, Any]]] = {}
    roots: List[Dict[str, Any]] = []
    for span in mine:
        parent = span.get("parent_id")
        if parent and parent in ids:
            children.setdefault(parent, []).append(span)
        else:
            roots.append(span)
    for bucket in children.values():
        bucket.sort(key=lambda s: s.get("ts_adj", s.get("ts", 0.0)))
    roots.sort(key=lambda s: s.get("ts_adj", s.get("ts", 0.0)))
    return roots, children


def critical_path(
    merged: Dict[str, Any], trace_id: Optional[str] = None
) -> Optional[Dict[str, Any]]:
    """The trace's critical path: from the slowest root, follow the
    largest-duration child to a leaf. Returns ``{"trace_id", "root", "path",
    "line"}`` — ``line`` is the post-mortem one-liner ("commit 4812: 78% in
    rank 1 groupby; barrier held 41 ms by rank 3")."""
    spans = merged.get("spans", [])
    if trace_id is None:
        best: Optional[Dict[str, Any]] = None
        for span in spans:
            if span.get("parent_id") is None and (
                best is None or span["duration_s"] > best["duration_s"]
            ):
                best = span
        if best is None:
            return None
        trace_id = best["trace_id"]
    roots, children = _trace_tree(spans, trace_id)
    if not roots:
        return None
    root = max(roots, key=lambda s: s.get("duration_s", 0.0))
    path = [root]
    node = root
    while True:
        kids = children.get(node["span_id"], [])
        if not kids:
            break
        node = max(kids, key=lambda s: s.get("duration_s", 0.0))
        path.append(node)
    leaf = path[-1]
    root_dur = max(root.get("duration_s", 0.0), 1e-9)
    pct = 100.0 * leaf.get("duration_s", 0.0) / root_dur
    line = (
        f"{root['name']}: {pct:.0f}% in rank {leaf.get('rank', '?')} "
        f"{leaf['name']}"
    )
    slowest_barrier: Optional[Dict[str, Any]] = None
    for span in spans:
        if span.get("trace_id") != trace_id or span.get("kind") != "barrier":
            continue
        wait = float(span.get("attrs", {}).get("straggler_wait_s", 0.0))
        if wait > 0.0 and (
            slowest_barrier is None
            or wait > float(slowest_barrier["attrs"]["straggler_wait_s"])
        ):
            slowest_barrier = span
    if slowest_barrier is not None:
        attrs = slowest_barrier["attrs"]
        line += (
            f"; barrier held {float(attrs['straggler_wait_s']) * 1000.0:.0f} ms "
            f"by rank {attrs.get('straggler_rank', '?')}"
        )
    return {"trace_id": trace_id, "root": root, "path": path, "line": line}


def format_trace_tree(merged: Dict[str, Any], trace_id: str) -> List[str]:
    """Indented causally-ordered rendering of one trace (``cli trace``)."""
    spans = merged.get("spans", [])
    roots, children = _trace_tree(spans, trace_id)
    lines: List[str] = []

    def _walk(span: Dict[str, Any], depth: int) -> None:
        link_note = ""
        if span.get("links"):
            link_note = f" links={len(span['links'])}"
        lines.append(
            f"{'  ' * depth}{span['kind']} {span['name']} "
            f"[rank {span.get('rank', '?')}] "
            f"{span.get('duration_s', 0.0) * 1000.0:.2f} ms{link_note}"
        )
        for child in children.get(span["span_id"], []):
            _walk(child, depth + 1)

    for root in roots:
        _walk(root, 0)
    return lines


def critical_path_line(directory: str) -> Optional[str]:
    """Convenience for the supervisor's post-mortem: merge whatever trace
    files (and flight-dump partials) the dir holds and return the critical
    path one-liner, or None when there is nothing to say."""
    import glob as _glob

    paths = sorted(_glob.glob(os.path.join(directory, "trace-rank-*.jsonl")))
    flights = sorted(_glob.glob(os.path.join(directory, "flight-rank-*.json")))
    if not paths and not flights:
        return None
    merged = merge_trace_files(paths, flights)
    if not merged["spans"]:
        return None
    result = critical_path(merged)
    return result["line"] if result else None


# -- device idle by host span (a jax.profiler trace) ---------------------------

#: one profiler event: (plane, line, name, start_ns, duration_ns)
ProfileEvent = Tuple[str, str, str, int, int]

_ANNOTATION_PREFIX = "pw."
_HOST_PLANE = "/host:CPU"
_DEVICE_PLANE_PREFIX = "/device:"


def find_profile(directory: str) -> Optional[str]:
    """The newest ``.xplane.pb`` a ``jax.profiler`` session left under
    ``directory`` (its ``plugins/profile/<time>/``), or None."""
    import glob as _glob

    paths = sorted(
        _glob.glob(
            os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    return paths[-1] if paths else None


def load_profile_events(path: str) -> List[ProfileEvent]:
    """The events ``idle_by_span`` reads from one ``.xplane.pb``: every event
    of the device planes, and this module's annotations (``pw.<kind>``) of the
    host plane. Nothing but ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData

    events: List[ProfileEvent] = []
    for plane in ProfileData.from_file(path).planes:
        host = plane.name == _HOST_PLANE
        if not host and not plane.name.startswith(_DEVICE_PLANE_PREFIX):
            continue
        for i, line in enumerate(plane.lines):
            # host lines are threads, and Python threads share one line name
            line_name = f"{line.name}#{i}" if host else line.name
            for ev in line.events:
                if host and not ev.name.startswith(_ANNOTATION_PREFIX):
                    continue
                events.append(
                    (plane.name, line_name, ev.name, int(ev.start_ns),
                     int(ev.duration_ns))
                )
    return events


def _innermost_segments(
    annotations: List[Tuple[int, int, str]]
) -> List[Tuple[int, int, str]]:
    """Cut the timeline where the innermost open annotation changes:
    ``(start, end, name)`` pieces, disjoint and sorted. Across threads the
    innermost is the annotation opened last (what the host turned to most
    recently); where none is open there is no piece."""
    import heapq

    points = sorted({t for a in annotations for t in a[:2]})
    by_start = sorted(annotations)
    open_heap: List[Tuple[int, int, str]] = []  # (-start, end, name)
    segments: List[Tuple[int, int, str]] = []
    nxt = 0
    for lo, hi in zip(points, points[1:]):
        while nxt < len(by_start) and by_start[nxt][0] <= lo:
            start, end, name = by_start[nxt]
            heapq.heappush(open_heap, (-start, end, name))
            nxt += 1
        while open_heap and open_heap[0][1] <= lo:
            heapq.heappop(open_heap)
        if open_heap:
            name = open_heap[0][2]
            if segments and segments[-1][2] == name and segments[-1][1] == lo:
                segments[-1] = (segments[-1][0], hi, name)
            else:
                segments.append((lo, hi, name))
    return segments


def idle_by_span(events: List[ProfileEvent]) -> Dict[str, Any]:
    """Where the device's idle time fell, by what the host was doing.

    Per device plane, busy is the union of its ``XLA Ops`` events (``XLA
    Modules`` where a plane has no op line) and an idle gap is the time
    between two busy intervals. Every instant of a gap goes to the innermost
    ``pw.<kind>`` annotation open on the host at that instant (``none`` where
    no annotation was open). Returns ``{"idle_s", "planes", "kinds": {kind:
    {"idle_s", "open_s"}}}``: idle seconds under each kind, averaged over the
    device planes, beside the seconds that kind was the innermost one. A kind
    ending in ``.device_wait`` is the host waiting for the chip; idle under
    any other kind, or under none, is the chip waiting for the host."""
    annotations = [
        (start, start + dur, name[len(_ANNOTATION_PREFIX):])
        for plane, _line, name, start, dur in events
        if plane == _HOST_PLANE and name.startswith(_ANNOTATION_PREFIX)
        and dur > 0
    ]
    segments = _innermost_segments(annotations)
    kinds: Dict[str, Dict[str, float]] = {}
    for lo, hi, name in segments:
        row = kinds.setdefault(name, {"idle_s": 0.0, "open_s": 0.0})
        row["open_s"] += (hi - lo) / 1e9
    planes = sorted(
        {e[0] for e in events if e[0].startswith(_DEVICE_PLANE_PREFIX)}
    )
    idle_total = 0.0
    for plane in planes:
        mine = [e for e in events if e[0] == plane]
        busy = [e for e in mine if e[1] == "XLA Ops"] or [
            e for e in mine if e[1] == "XLA Modules"
        ]
        covered: List[List[int]] = []
        for start, end in sorted((e[3], e[3] + e[4]) for e in busy):
            if covered and start <= covered[-1][1]:
                covered[-1][1] = max(covered[-1][1], end)
            else:
                covered.append([start, end])
        seg = 0
        for (_, gap_lo), (gap_hi, _) in zip(covered, covered[1:]):
            idle_total += (gap_hi - gap_lo) / 1e9
            while seg < len(segments) and segments[seg][1] <= gap_lo:
                seg += 1
            at, i = gap_lo, seg
            while at < gap_hi:
                if i < len(segments) and segments[i][0] <= at:
                    upto, name = min(segments[i][1], gap_hi), segments[i][2]
                    i += 1
                else:
                    upto = min(segments[i][0], gap_hi) if i < len(segments) else gap_hi
                    name = "none"
                row = kinds.setdefault(name, {"idle_s": 0.0, "open_s": 0.0})
                row["idle_s"] += (upto - at) / 1e9
                at = upto
    n = max(len(planes), 1)
    for row in kinds.values():
        row["idle_s"] /= n
    return {"idle_s": idle_total / n, "planes": len(planes), "kinds": kinds}


def format_idle_by_span(result: Dict[str, Any]) -> List[str]:
    """The table ``cli trace`` prints for a profiler directory."""
    idle = result["idle_s"]
    lines = [
        f"device idle between operations: {idle:.4f} s over "
        f"{result['planes']} device plane(s), by the innermost pw.<kind> open "
        "on the host",
        f"{'kind':<26}{'idle_s':>10}{'of idle':>9}{'open_s':>10}{'idle while open':>17}",
    ]
    named = 0.0
    for kind, row in sorted(
        result["kinds"].items(), key=lambda kv: -kv[1]["idle_s"]
    ):
        share = 100.0 * row["idle_s"] / idle if idle else 0.0
        within = (
            f"{100.0 * row['idle_s'] / row['open_s']:.1f} %"
            if row["open_s"] else "-"
        )
        if kind != "none":
            named += row["idle_s"]
        lines.append(
            f"{kind:<26}{row['idle_s']:>10.4f}{share:>8.1f}%"
            f"{row['open_s']:>10.4f}{within:>17}"
        )
    waits = sum(
        row["idle_s"] for kind, row in result["kinds"].items()
        if kind.endswith(".device_wait")
    )
    if idle:
        lines.append(
            f"named: {100.0 * named / idle:.1f} % of the idle seconds; host "
            f"waiting for the chip (*.device_wait): {100.0 * waits / idle:.1f} %, "
            f"chip waiting for the host: {100.0 * (idle - waits) / idle:.1f} %"
        )
    return lines
