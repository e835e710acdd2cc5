"""Read-replica serving fleet: bounded-staleness followers with
kill-invisible failover.

ROADMAP item 4: the cluster so far scales INGEST (elastic ranks, autoscaler)
but every retrieval query still lands on the primary's serving plane. This
module adds a fleet of read-only replicas that scale QUERY capacity
independently of ingest:

- **cold start** — a replica bootstraps from the latest read-back-verified
  bootstrap export in the replica feed (``persistence/replica_feed.py``):
  bounded row fragments stream through
  :meth:`~pathway_tpu.ops.knn.BruteForceKnnIndex.install_descriptor_rows`,
  so peak bootstrap memory stays flat however large the corpus. A torn
  bootstrap (checksum mismatch on any fragment) is a TYPED refusal
  (``ReplicaBootstrapError``) — the replica reports ``refused`` and stays out
  of rotation; it never serves a half-installed index;
- **follow** — after bootstrap the replica tails the feed's per-commit row
  frames, applying each exactly once (a frame at or below the applied commit
  id is skipped — the double-apply guard ``replica_follow_model`` proves);
- **bounded staleness** — every query may carry ``max_staleness_s``; a
  replica that cannot satisfy the bound sheds with HTTP 429 and an honest
  integer ``Retry-After`` (``engine/brownout.py:retry_after_int``, the one
  formatter every shed path shares) estimated from its poll cadence and
  pending-frame backlog;
- **kill-invisible failover** — the router walks the fleet round-robin and
  falls back to the primary; a SIGKILL'd replica surfaces as a connect error
  the router absorbs, never as a client-visible 5xx;
- **independent autoscaling** — the fleet grows/shrinks on query load through
  the same damped pure controller the ingest autoscaler uses
  (``AutoscalePolicy.replica_from_env()``), without touching ingest ranks.

Replica results are BITWISE-equal to the primary's at the same commit id
(``tests/test_replica.py``): fragments install through the same
``add_many`` path the primary ingested through, and quantized stores
regenerate codes bit-identically per the ``quant_state`` contract.

Each replica is a separate PROCESS (``python -m pathway_tpu.parallel.replica``)
supervised by :class:`ReplicaFleet` — the supervisor embeds a fleet next to
its ingest ranks (``Supervisor(replicas=N)`` / ``PATHWAY_REPLICAS``), writes
replica post-mortems with the same attribution discipline as rank
post-mortems (exit cause, last applied commit, staleness at death), and
preserves replica flight dumps past supervise-dir cleanup.

Env knobs (the fleet's own namespace — full table in README.md):

======================================  =======  ===========================
``PATHWAY_REPLICAS``                    0        fleet size at spawn
``PATHWAY_REPLICA_FEED``                —        feed root directory
``PATHWAY_REPLICA_PORT``                0        serving port (0 = OS picks)
``PATHWAY_REPLICA_POLL_S``              0.05     frame-tail poll period
``PATHWAY_REPLICA_FRAGMENT_ROWS``       4096     bootstrap fragment rows
``PATHWAY_REPLICA_MAX_RESTARTS``        10       per-fleet relaunch budget
``PATHWAY_REPLICA_AUTOSCALE``           off      ``on`` scales the fleet
``PATHWAY_REPLICA_AUTOSCALE_MIN``       1        fleet floor
``PATHWAY_REPLICA_AUTOSCALE_MAX``       4        fleet ceiling
``PATHWAY_REPLICA_AUTOSCALE_QPS``       200      target queries/s per replica
======================================  =======  ===========================
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple

from pathway_tpu.internals.config import env_float as _env_float
from pathway_tpu.persistence.replica_feed import (
    ReplicaBootstrapError,
    ReplicaFeed,
    ReplicaFeedError,
)

_STATUS_PREFIX = "replica-"
_STATUS_SUFFIX = ".status.json"

#: replica flight dumps live in a subdirectory of the supervise dir so a
#: replica's ``flight-rank-K.json`` can never collide with ingest rank K's
FLIGHT_SUBDIR = "replicas"


def replica_status_path(supervise_dir: str, replica_id: int) -> str:
    return os.path.join(
        supervise_dir, f"{_STATUS_PREFIX}{replica_id}{_STATUS_SUFFIX}"
    )


def write_replica_status(
    supervise_dir: str, replica_id: int, payload: Dict[str, Any]
) -> None:
    """Atomically publish one replica's liveness record (same rename
    discipline as the rank status files — a reader never sees a torn JSON)."""
    path = replica_status_path(supervise_dir, replica_id)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def read_replica_statuses(
    supervise_dir: str, n: int
) -> Dict[int, Dict[str, Any]]:
    out: Dict[int, Dict[str, Any]] = {}
    for rid in range(n):
        try:
            with open(replica_status_path(supervise_dir, rid)) as f:
                out[rid] = json.load(f)
        except (OSError, ValueError):
            continue
    return out


# -- typed serving errors ------------------------------------------------------


class ReplicaStaleError(RuntimeError):
    """The replica cannot satisfy the query's ``max_staleness_s`` bound.
    Carries the honest retry estimate the shed response advertises."""

    def __init__(self, staleness_s: float, retry_after_s: float):
        self.staleness_s = float(staleness_s)
        self.retry_after_s = float(retry_after_s)
        super().__init__(
            f"replica is {staleness_s:.3f}s stale, beyond the query's bound — "
            f"retry in ~{retry_after_s:.2f}s or relax max_staleness_s"
        )


class ReplicaNotServingError(RuntimeError):
    """The replica is not in rotation (still bootstrapping, or its bootstrap
    was refused). The router treats this as failover, never a client 5xx."""

    def __init__(self, state: str, cause: "Optional[BaseException]" = None):
        self.state = state
        self.cause = cause
        detail = f": {cause}" if cause is not None else ""
        super().__init__(f"replica is not serving (state={state}){detail}")


class ReplicaUnavailableError(RuntimeError):
    """Every candidate (fleet AND primary fallback) was exhausted. Only
    raised when the router has no primary — with one configured, this error
    is unreachable by construction."""


def _stage_add(name: str, value: float = 1.0) -> None:
    try:
        from pathway_tpu.engine import telemetry

        telemetry.stage_add(name, value)
    except Exception:
        pass


def _flight_event(kind: str, **details: Any) -> None:
    try:
        from pathway_tpu.engine.profile import get_flight_recorder

        get_flight_recorder().record_event(kind, **details)
    except Exception:
        pass


# -- the follower --------------------------------------------------------------


class ReplicaFollower:
    """Read-only index follower: bootstrap from the feed, tail its frames.

    The ``index_factory`` receives the bootstrap HEADER (dim, metric, quant
    sidecars, filter data) and returns a fresh index implementing the
    descriptor-install contract (``install_descriptor_header`` /
    ``install_descriptor_rows`` / ``search_many``). Thread-safe: one RLock
    covers apply and search, so a query never reads a half-applied frame."""

    def __init__(
        self,
        feed: ReplicaFeed,
        index_factory: "Callable[[Dict[str, Any]], Any]",
        *,
        replica_id: int = 0,
        poll_s: "float | None" = None,
        clock: "Callable[[], float]" = time.monotonic,
    ):
        self.feed = feed
        self.replica_id = int(replica_id)
        self.poll_s = (
            float(poll_s)
            if poll_s is not None
            else _env_float("PATHWAY_REPLICA_POLL_S", 0.05)
        )
        self._clock = clock
        self._index_factory = index_factory
        self._lock = threading.RLock()
        self.index: Any = None
        self.state = "init"  # init|bootstrapping|following|refused|stopped
        self.applied_commit = -1
        self.refusal: "Optional[BaseException]" = None
        # clock() of the last poll that left the replica caught up with the
        # feed tip — staleness is measured from here
        self._fresh_as_of: "Optional[float]" = None
        self.served = 0
        self.shed = 0
        # formatted trace context of the last applied frame's originating
        # commit — replica_serve spans link back through this rider
        self._trace_rider: "Optional[str]" = None

    # -- lifecycle -------------------------------------------------------------

    def bootstrap(self) -> int:
        """Cold-start from the latest read-back-verified bootstrap export.
        Raises :class:`ReplicaFeedError` when no bootstrap exists yet, and
        :class:`ReplicaBootstrapError` (after marking the replica
        ``refused``) on a torn export — a typed refusal, not a crash."""
        with self._lock:
            self.state = "bootstrapping"
        holder: Dict[str, Any] = {}

        def install_header(header: Dict[str, Any]) -> None:
            index = self._index_factory(header)
            index.install_descriptor_header(header)
            holder["index"] = index

        def install_fragment(keys: List[Any], vectors: Any) -> None:
            holder["index"].install_descriptor_rows(keys, vectors)

        try:
            commit = self.feed.load_bootstrap(
                replica_id=self.replica_id,
                install_header=install_header,
                install_fragment=install_fragment,
            )
        except ReplicaBootstrapError as exc:
            with self._lock:
                self.state = "refused"
                self.refusal = exc
            _stage_add("replica.bootstrap_refused")
            _flight_event(
                "replica_refused", replica=self.replica_id, error=str(exc)[:240]
            )
            raise
        with self._lock:
            self.index = holder["index"]
            self.applied_commit = commit
            self.state = "following"
            self._fresh_as_of = self._clock()
        _stage_add("replica.bootstraps")
        _flight_event(
            "replica_bootstrap", replica=self.replica_id, commit=commit
        )
        return commit

    def poll_frames(self) -> int:
        """Apply every feed frame past the applied commit, in commit order.
        Returns the number applied. The chaos harness can stretch this poll
        (``replica_lag``) or SIGKILL mid-apply (``replica_kill``)."""
        with self._lock:
            if self.state != "following":
                return 0
            applied_floor = self.applied_commit
        try:
            from pathway_tpu.internals.chaos import get_chaos

            chaos = get_chaos()
        except Exception:
            chaos = None
        if chaos is not None:
            lag = chaos.replica_lag_s(self.replica_id)
            if lag > 0:
                time.sleep(lag)
        applied = 0
        for commit, path in self.feed.frames_after(applied_floor):
            payload = self.feed.read_frame(path)
            apply_t0 = time.perf_counter()
            with self._lock:
                if payload["commit"] <= self.applied_commit:
                    # double-apply guard: a frame re-listed across polls (or
                    # re-read after a racing prune+re-export) is a no-op —
                    # replica_follow_model proves replays break bitwise parity
                    _stage_add("replica.frames_skipped")
                    continue
                self._apply_locked(payload)
                self.applied_commit = int(payload["commit"])
                self._trace_rider = payload.get("trace") or self._trace_rider
            applied += 1
            _stage_add("replica.frames_applied")
            _stage_add("replica.rows_applied", len(payload.get("keys") or ()))
            self._trace_apply(payload, time.perf_counter() - apply_t0)
            if chaos is not None:
                chaos.maybe_replica_kill(self.replica_id, int(payload["commit"]))
        with self._lock:
            self._fresh_as_of = self._clock()
        _stage_add("replica.polls")
        try:
            from pathway_tpu.engine.profile import histogram

            histogram("pathway_replica_staleness_seconds").observe(
                self.staleness_s()
            )
        except Exception:
            pass
        return applied

    def _apply_locked(self, payload: Dict[str, Any]) -> None:
        # removals first: a key both removed and re-upserted in one commit
        # must land at the upsert's vector (add_many upserts via remove+add)
        for key in payload.get("removals") or ():
            self.index.remove(key)  # noqa: PWA103 (caller holds self._lock — the _locked suffix)
        keys = list(payload.get("keys") or ())
        if keys:
            self.index.install_descriptor_rows(keys, payload["vectors"])  # noqa: PWA103 (caller holds self._lock)
        filter_data = payload.get("filter_data") or {}
        if filter_data:
            # AFTER the upsert — add_many pops filter entries for re-added keys
            self.index.filter_data.update(filter_data)  # noqa: PWA103 (caller holds self._lock)

    def _trace_apply(self, payload: Dict[str, Any], elapsed: float) -> None:
        """Emit a ``replica_apply`` span as a child of the originating
        commit's trace (the rider the primary attached to the feed frame).
        Backdated to cover the apply — spans never sit on the hot path."""
        rider = payload.get("trace")
        if not rider:
            return
        try:
            from pathway_tpu.engine.tracing import (
                get_tracer,
                parse_trace_header,
            )

            tracer = get_tracer()
            if not tracer.recording():
                return
            parent = parse_trace_header(str(rider))
            if parent is None:
                return
            span = tracer.start(
                "replica_apply",
                f"apply commit {int(payload['commit'])}",
                ctx=parent,
                attrs={
                    "replica": self.replica_id,
                    "commit": int(payload["commit"]),
                    "rows": len(payload.get("keys") or ()),
                },
            )
            if span is not None:
                span.ts -= elapsed
                span.ts_mono -= elapsed
                span.duration_s = max(elapsed, 1e-9)
                tracer.finish(span)
        except Exception:
            pass

    def applied_trace_rider(self) -> "Optional[str]":
        """Formatted trace context of the last applied feed frame's
        originating commit (None before any traced frame applies)."""
        with self._lock:
            return self._trace_rider

    # -- serving ---------------------------------------------------------------

    def staleness_s(self) -> float:
        """Seconds since this replica last confirmed it was caught up with
        the feed tip. Infinity before the first successful bootstrap."""
        with self._lock:
            fresh = self._fresh_as_of
        if fresh is None:
            return float("inf")
        return max(0.0, self._clock() - fresh)

    def pending_frames(self) -> int:
        with self._lock:
            floor = self.applied_commit
        try:
            return len(self.feed.frames_after(floor))
        except ReplicaFeedError:
            return 0

    def retry_estimate_s(self) -> float:
        """Honest shed estimate: one poll per pending frame plus the poll
        now in flight — how long until this replica is plausibly fresh."""
        return self.poll_s * (self.pending_frames() + 1)

    def search_many(
        self,
        vectors: List[Any],
        limits: List[int],
        *,
        max_staleness_s: "float | None" = None,
        filter_exprs: "List[Any] | None" = None,
    ) -> "Tuple[int, List[List[tuple]]]":
        """Answer a query batch at this replica's applied commit. Raises
        :class:`ReplicaNotServingError` out of rotation and
        :class:`ReplicaStaleError` when the staleness bound cannot be met."""
        with self._lock:
            if self.state != "following":
                _stage_add("replica.refused_query")
                raise ReplicaNotServingError(self.state, self.refusal)
            staleness = self.staleness_s()
            if max_staleness_s is not None and staleness > float(max_staleness_s):
                self.shed += 1
                _stage_add("replica.shed_stale")
                raise ReplicaStaleError(staleness, self.retry_estimate_s())
            results = self.index.search_many(vectors, limits, filter_exprs)
            commit = self.applied_commit
            self.served += 1
        _stage_add("replica.serve")
        return commit, results

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            staleness = self.staleness_s()
            return {
                "kind": "replica",
                "replica": self.replica_id,
                "state": self.state,
                "applied_commit": self.applied_commit,
                "staleness_s": (
                    None if staleness == float("inf") else round(staleness, 4)
                ),
                "served_total": self.served,
                "shed_total": self.shed,
                "refusal": (
                    None if self.refusal is None else str(self.refusal)[:240]
                ),
            }


def default_index_factory(header: Dict[str, Any]) -> Any:
    """Build the replica's index from the bootstrap header: a plain dense
    index, or the tiered/quantized store when the header carries quant
    sidecars (the install path verifies mode parity either way)."""
    from pathway_tpu.ops.knn import BruteForceKnnIndex

    dim = int(header.get("dim") or 0)
    if dim <= 0:
        raise ReplicaBootstrapError(
            "bootstrap header carries no dim — the export predates the "
            "replica-feed contract; re-export with a current primary"
        )
    metric = str(header.get("metric") or "l2sq")
    quant = header.get("quant") or {}
    if str(quant.get("mode", "off")) != "off":
        # quantized geometry rides the tiered IVF store; the header install
        # verifies mode parity (PATHWAY_IVF_QUANT must match the primary)
        from pathway_tpu.ops.knn import IvfKnnIndex

        return IvfKnnIndex(dim, metric=metric, tiered=True)
    return BruteForceKnnIndex(dim, metric=metric)


# -- the serving endpoint ------------------------------------------------------


class ReplicaServer:
    """Per-replica HTTP surface: ``POST /v1/retrieve`` (query batch with an
    optional ``max_staleness_s`` bound), ``GET /healthz`` (JSON liveness with
    the applied commit and staleness), ``GET /metrics``/``/status``
    (OpenMetrics — replica gauges + the shared process metrics plane, so the
    same strict-grammar tests cover worker and replica expositions)."""

    def __init__(self, follower: ReplicaFollower, port: int = 0):
        self.follower = follower
        follower_ref = follower
        server_ref = self

        class Handler(BaseHTTPRequestHandler):
            def _send(
                self, code: int, body: bytes, content_type: str,
                headers: "Dict[str, str] | None" = None,
            ) -> None:
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _send_json(
                self, code: int, payload: Dict[str, Any],
                headers: "Dict[str, str] | None" = None,
            ) -> None:
                self._send(
                    code,
                    json.dumps(payload, sort_keys=True).encode(),
                    "application/json",
                    headers,
                )

            def do_GET(self) -> None:  # noqa: N802 (http.server API)
                if self.path == "/healthz":
                    payload = follower_ref.snapshot()
                    payload["alive"] = True
                    payload["port"] = server_ref.port
                    self._send_json(200, payload)
                    return
                if self.path in ("/status", "/metrics"):
                    body = server_ref.to_openmetrics().encode()
                    self._send(200, body, "application/openmetrics-text")
                    return
                self.send_response(404)
                self.end_headers()

            def do_POST(self) -> None:  # noqa: N802 (http.server API)
                if self.path != "/v1/retrieve":
                    self.send_response(404)
                    self.end_headers()
                    return
                try:
                    length = int(self.headers.get("Content-Length") or 0)
                    req = json.loads(self.rfile.read(length) or b"{}")
                    vectors = req["vectors"]
                    k = int(req.get("k", 3))
                    max_staleness = req.get("max_staleness_s")
                    filters = req.get("filters")
                except (KeyError, ValueError, TypeError) as exc:
                    self._send_json(400, {"error": f"bad request: {exc}"})
                    return
                from pathway_tpu.engine.brownout import retry_after_int

                # replica_serve span: child of the query's incoming trace
                # (X-Pathway-Trace), linked back to the originating commit's
                # trace via the rider the last applied feed frame carried
                serve_headers: Dict[str, str] = {}
                serve_span = None
                serve_t0 = time.perf_counter()
                try:
                    from pathway_tpu.engine import tracing as _tracing

                    tracer = _tracing.get_tracer()
                    if tracer.recording():
                        parent = _tracing.parse_trace_header(
                            self.headers.get(_tracing.TRACE_HEADER) or ""
                        )
                        links = []
                        rider = follower_ref.applied_trace_rider()
                        if rider:
                            link_ctx = _tracing.parse_trace_header(rider)
                            if link_ctx is not None:
                                links.append(link_ctx)
                        serve_span = tracer.start(
                            "replica_serve",
                            "POST /v1/retrieve",
                            ctx=parent,
                            links=tuple(links),
                            attrs={"replica": follower_ref.replica_id},
                        )
                        if serve_span is not None:
                            serve_headers[_tracing.TRACE_HEADER] = (
                                _tracing.format_trace_header(
                                    serve_span.context()
                                )
                            )
                except Exception:
                    serve_span = None

                def _finish_span(
                    status: int, commit: "Optional[int]" = None
                ) -> None:
                    if serve_span is None:
                        return
                    try:
                        from pathway_tpu.engine.tracing import get_tracer

                        serve_span.attrs["status"] = status
                        if commit is not None:
                            serve_span.attrs["commit"] = commit
                        serve_span.duration_s = max(
                            time.perf_counter() - serve_t0, 1e-9
                        )
                        get_tracer().finish(serve_span)
                    except Exception:
                        pass

                try:
                    commit, results = follower_ref.search_many(
                        vectors,
                        [k] * len(vectors),
                        max_staleness_s=max_staleness,
                        filter_exprs=filters,
                    )
                except ReplicaStaleError as exc:
                    _finish_span(429)
                    serve_headers.update(
                        {"Retry-After": retry_after_int(exc.retry_after_s)}
                    )
                    self._send_json(
                        429,
                        {
                            "error": "stale",
                            "staleness_s": round(exc.staleness_s, 4),
                        },
                        headers=serve_headers,
                    )
                    return
                except ReplicaNotServingError as exc:
                    # out of rotation — the router fails over; a 503 here is
                    # router-facing, never client-facing
                    _finish_span(503)
                    self._send_json(
                        503,
                        {"error": "not_serving", "state": exc.state},
                        headers=serve_headers,
                    )
                    return
                _finish_span(200, commit)
                self._send_json(
                    200,
                    {
                        "commit": commit,
                        "results": [
                            [[key, score] for key, score in row]
                            for row in results
                        ],
                    },
                    headers=serve_headers,
                )

            def log_message(self, *args: Any) -> None:
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", int(port)), Handler)
        self.port = self.httpd.server_address[1]
        self.thread = threading.Thread(
            target=self.httpd.serve_forever,
            daemon=True,
            name=f"pathway:replica-{follower.replica_id}-http",
        )
        self.thread.start()

    def to_openmetrics(self) -> str:
        from pathway_tpu.engine.http_server import metrics_plane_lines

        snap = self.follower.snapshot()
        staleness = snap["staleness_s"]
        lines = [
            "# HELP pathway_replica_applied_commit Last commit id applied by this replica",
            "# TYPE pathway_replica_applied_commit gauge",
            f"pathway_replica_applied_commit {snap['applied_commit']}",
            "# HELP pathway_replica_staleness_current_seconds Seconds since this replica last matched the feed tip",
            "# TYPE pathway_replica_staleness_current_seconds gauge",
            "pathway_replica_staleness_current_seconds "
            + ("+Inf" if staleness is None else repr(float(staleness))),
            "# HELP pathway_replica_served A counter of query batches served by this replica",
            "# TYPE pathway_replica_served counter",
            f"pathway_replica_served_total {snap['served_total']}",
            "# HELP pathway_replica_shed A counter of query batches shed for staleness",
            "# TYPE pathway_replica_shed counter",
            f"pathway_replica_shed_total {snap['shed_total']}",
        ]
        lines.extend(metrics_plane_lines())
        lines.append("# EOF")
        return "\n".join(lines) + "\n"

    def close(self) -> None:
        httpd, self.httpd = self.httpd, None
        if httpd is None:
            return
        httpd.shutdown()
        httpd.server_close()


# -- the router ----------------------------------------------------------------


class ReplicaRouter:
    """Client-side fleet router: round-robin over replica endpoints with a
    primary fallback. A dead/refusing replica is absorbed (cooldown + next
    candidate), a stale replica's 429 tries the rest of the fleet before the
    primary — the client NEVER sees a 5xx from a killed replica.

    ``primary`` is a callable ``(vectors, k, filters) -> (commit, results)``
    (typically a closure over the primary's index) — always fresh, so with a
    primary configured every query is answerable."""

    def __init__(
        self,
        endpoints: List[str],
        primary: "Optional[Callable[..., Tuple[int, List[List[tuple]]]]]" = None,
        *,
        timeout_s: float = 5.0,
        unhealthy_cooldown_s: float = 1.0,
        clock: "Callable[[], float]" = time.monotonic,
    ):
        self.endpoints = list(endpoints)
        self.primary = primary
        self.timeout_s = float(timeout_s)
        self.unhealthy_cooldown_s = float(unhealthy_cooldown_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._rr = 0
        self._unhealthy_until: Dict[str, float] = {}
        self.stats = {
            "served": 0, "replica_served": 0, "primary_served": 0,
            "failovers": 0, "sheds_seen": 0,
        }

    def _candidates(self) -> List[str]:
        now = self._clock()
        with self._lock:
            start = self._rr
            self._rr = (self._rr + 1) % max(1, len(self.endpoints))
            ordered = (
                self.endpoints[start:] + self.endpoints[:start]
            )
            return [
                ep
                for ep in ordered
                if self._unhealthy_until.get(ep, 0.0) <= now
            ]

    def _mark_unhealthy(self, endpoint: str) -> None:
        with self._lock:
            self._unhealthy_until[endpoint] = (
                self._clock() + self.unhealthy_cooldown_s
            )

    def retrieve(
        self,
        vectors: List[Any],
        k: int,
        *,
        max_staleness_s: "float | None" = None,
        filters: "List[Any] | None" = None,
    ) -> "Tuple[Optional[int], List[List[tuple]]]":
        """Serve one query batch from the fleet, failing over silently."""
        import urllib.error
        import urllib.request

        started = self._clock()
        body = json.dumps(
            {
                "vectors": [
                    [float(x) for x in vec] for vec in vectors
                ],
                "k": int(k),
                "max_staleness_s": max_staleness_s,
                "filters": filters,
            }
        ).encode()
        tried = 0
        min_retry: "Optional[float]" = None
        for endpoint in self._candidates():
            tried += 1
            try:
                req = urllib.request.Request(
                    f"{endpoint}/v1/retrieve",
                    data=body,
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=self.timeout_s) as resp:
                    payload = json.loads(resp.read())
            except urllib.error.HTTPError as exc:
                if exc.code == 429:
                    # an honest shed: another replica (or the primary) may be
                    # fresher — keep the smallest advertised backoff in case
                    # nothing else can answer
                    with self._lock:
                        self.stats["sheds_seen"] += 1
                    _stage_add("replica.router.shed_seen")
                    try:
                        retry = float(exc.headers.get("Retry-After") or 1)
                    except (TypeError, ValueError):
                        retry = 1.0
                    min_retry = (
                        retry if min_retry is None else min(min_retry, retry)
                    )
                else:
                    # 503 not_serving / unexpected status: out of rotation
                    self._mark_unhealthy(endpoint)
                    _stage_add("replica.router.unhealthy")
                continue
            except (OSError, ValueError) as exc:
                # connect refused / reset / timeout / torn body — the
                # kill-invisible path: absorb and move on
                self._mark_unhealthy(endpoint)
                _stage_add("replica.router.unhealthy")
                _flight_event(
                    "replica_failover",
                    endpoint=endpoint,
                    error=str(exc)[:120],
                )
                continue
            results = [
                [(key, float(score)) for key, score in row]
                for row in payload["results"]
            ]
            self._note_served(started, tried, kind="replica")
            return int(payload["commit"]), results
        if self.primary is not None:
            commit, results = self.primary(vectors, k, filters)
            self._note_served(started, tried + 1, kind="primary")
            return commit, results
        if min_retry is not None:
            raise ReplicaStaleError(float("nan"), min_retry)
        raise ReplicaUnavailableError(
            f"all {len(self.endpoints)} replica endpoint(s) are unreachable "
            "and no primary fallback is configured"
        )

    def _note_served(self, started: float, tried: int, *, kind: str) -> None:
        with self._lock:
            self.stats["served"] += 1
            self.stats[f"{kind}_served"] += 1
            failed_over = tried > 1 or kind == "primary"
            if failed_over:
                self.stats["failovers"] += 1
        _stage_add("replica.router.served")
        _stage_add(f"replica.router.{kind}_served")
        if failed_over:
            _stage_add("replica.router.failover")
            elapsed = max(0.0, self._clock() - started)
            try:
                from pathway_tpu.engine.profile import histogram

                histogram("pathway_replica_failover_seconds").observe(elapsed)
            except Exception:
                pass


# -- the fleet (supervisor side) -----------------------------------------------


class ReplicaFleet:
    """Launch and watch N replica processes next to the ingest ranks.

    Replica deaths do NOT consume the ingest restart budget — a replica is
    stateless below its feed, so a relaunch is cheap and bounded by its own
    ``PATHWAY_REPLICA_MAX_RESTARTS``. Post-mortem attribution (exit cause,
    last applied commit, staleness at death) and flight-dump preservation
    mirror the rank discipline in ``parallel/supervisor.py``."""

    def __init__(
        self,
        *,
        feed_root: str,
        supervise_dir: str,
        run_id: str,
        n: int = 1,
        base_env: "Optional[Dict[str, str]]" = None,
        autoscale: "bool | None" = None,
    ):
        self.feed_root = feed_root
        self.supervise_dir = supervise_dir
        self.run_id = run_id
        self.target_n = int(n)
        self.base_env = dict(base_env) if base_env is not None else dict(os.environ)
        self.procs: Dict[int, "subprocess.Popen[bytes]"] = {}
        self.restarts = 0
        self.max_restarts = int(
            _env_float("PATHWAY_REPLICA_MAX_RESTARTS", 10)
        )
        self.post_mortems: List[str] = []
        self._last_status: Dict[int, Dict[str, Any]] = {}
        self._controller: Any = None
        self._signal_carry: "Optional[tuple]" = None
        self._last_sample_at: "Optional[float]" = None
        if autoscale is None:
            from pathway_tpu.parallel.autoscaler import replica_autoscale_enabled

            autoscale = replica_autoscale_enabled()
        if autoscale:
            from pathway_tpu.parallel.autoscaler import (
                AutoscaleController,
                AutoscalePolicy,
            )

            policy = AutoscalePolicy.replica_from_env()
            self.target_n = max(policy.min_workers, min(policy.max_workers, self.target_n))
            self._controller = AutoscaleController(policy, self.target_n)

    # -- process plumbing ------------------------------------------------------

    def _child_env(self, replica_id: int) -> Dict[str, str]:
        env = dict(self.base_env)
        env["PATHWAY_REPLICA_ID"] = str(replica_id)
        env["PATHWAY_REPLICA_FEED"] = self.feed_root
        env["PATHWAY_REPLICA_PORT"] = env.get("PATHWAY_REPLICA_PORT", "0")
        env["PATHWAY_SUPERVISE_DIR"] = self.supervise_dir
        env["PATHWAY_RUN_ID"] = self.run_id
        env["PATHWAY_FLIGHT_RECORDER_DIR"] = os.path.join(
            self.supervise_dir, FLIGHT_SUBDIR
        )
        # replicas are serving-plane processes: never let them inherit the
        # ingest ranks' process identity or re-enter the spawn machinery
        for noise in ("PATHWAY_PROCESS_ID", "PATHWAY_RESTART_COUNT"):
            env.pop(noise, None)
        return env

    def _launch(self, replica_id: int) -> None:
        os.makedirs(
            os.path.join(self.supervise_dir, FLIGHT_SUBDIR), exist_ok=True
        )
        self.procs[replica_id] = subprocess.Popen(
            [sys.executable, "-m", "pathway_tpu.parallel.replica"],
            env=self._child_env(replica_id),
        )
        _stage_add("replica.fleet.launch")

    def start(self) -> None:
        for rid in range(self.target_n):
            if rid not in self.procs:
                self._launch(rid)

    def statuses(self) -> Dict[int, Dict[str, Any]]:
        live = read_replica_statuses(self.supervise_dir, self.target_n)
        self._last_status.update(live)
        return live

    def endpoints(self) -> List[str]:
        """Base URLs of every replica that has advertised a port."""
        out = []
        for rid in sorted(self.procs):
            status = self._last_status.get(rid) or {}
            port = status.get("port")
            if port:
                out.append(f"http://127.0.0.1:{int(port)}")
        return out

    def wait_serving(
        self, n: "int | None" = None, deadline_s: float = 240.0
    ) -> List[str]:
        """Block until ``n`` replicas report ``following`` (default: the
        whole fleet); returns their endpoints. Raises TimeoutError past the
        deadline — spawn-convergence tests budget 240 s."""
        want = self.target_n if n is None else int(n)
        deadline = time.monotonic() + float(deadline_s)
        while True:
            live = self.statuses()
            serving = [
                rid
                for rid, st in live.items()
                if st.get("state") == "following" and st.get("port")
            ]
            if len(serving) >= want:
                return self.endpoints()
            if time.monotonic() > deadline:
                states = {rid: st.get("state") for rid, st in live.items()}
                raise TimeoutError(
                    f"replica fleet did not converge: {len(serving)}/{want} "
                    f"serving after {deadline_s:.0f}s (states={states})"
                )
            self.watch_once()
            time.sleep(0.05)

    # -- death handling --------------------------------------------------------

    def _preserve_flight_dump(self, replica_id: int) -> "Optional[str]":
        import shutil
        import tempfile

        src = os.path.join(
            self.supervise_dir, FLIGHT_SUBDIR, f"flight-rank-{replica_id}.json"
        )
        if not os.path.exists(src):
            return None
        dst = os.path.join(
            tempfile.gettempdir(),
            f"pathway-flight-{self.run_id}-replica-{replica_id}.json",
        )
        try:
            shutil.copyfile(src, dst)
            return dst
        except OSError:
            return None

    def _attribute_death(self, replica_id: int, code: int) -> str:
        from pathway_tpu.parallel.supervisor import describe_exit

        status = self._last_status.get(replica_id) or {}
        staleness = status.get("staleness_s")
        dump = self._preserve_flight_dump(replica_id)
        line = (
            f"replica {replica_id}: {describe_exit(code)}; "
            f"last applied commit "
            f"{status.get('applied_commit', 'unknown')}; "
            f"staleness at death "
            f"{'unknown' if staleness is None else f'{staleness:.3f}s'}"
            + (f"; flight dump preserved at {dump}" if dump else "")
        )
        self.post_mortems.append(line)
        return line

    def watch_once(self) -> List[str]:
        """One watch tick: reap dead replicas, attribute, relaunch within
        the fleet's own budget. Returns new post-mortem lines (the
        supervisor prints them — a replica death is an EVENT, not a cluster
        failure)."""
        lines: List[str] = []
        self.statuses()
        for rid, proc in list(self.procs.items()):
            code = proc.poll()
            if code is None:
                continue
            lines.append(self._attribute_death(rid, code))
            _flight_event(
                "replica_failover", replica=rid, exit_code=code, relaunch=True
            )
            del self.procs[rid]
            try:
                os.unlink(replica_status_path(self.supervise_dir, rid))
            except OSError:
                pass
            if rid < self.target_n:
                if self.restarts < self.max_restarts:
                    self.restarts += 1
                    _stage_add("replica.fleet.relaunch")
                    self._launch(rid)
                else:
                    lines.append(
                        f"replica {rid}: relaunch budget exhausted "
                        f"({self.max_restarts}) — fleet degrades to "
                        f"{len(self.procs)} replica(s); the router's primary "
                        "fallback keeps serving"
                    )
        return lines

    # -- autoscaling -----------------------------------------------------------

    def autoscale_tick(self, now: "float | None" = None) -> "Optional[int]":
        """Drive the fleet's damped controller from the replicas' served/shed
        counters. Fleet transitions are immediate (launch/terminate a
        process) so issue and completion collapse into one tick."""
        if self._controller is None:
            return None
        if now is None:
            now = time.monotonic()
        policy = self._controller.policy
        if (
            self._last_sample_at is not None
            and now - self._last_sample_at < policy.sample_period_s
        ):
            return None
        self._last_sample_at = now
        signals, self._signal_carry = _fleet_signals(
            self.statuses(), self._signal_carry, now, self.target_n
        )
        target = self._controller.sample(now, signals)
        if target is None:
            return None
        self._controller.on_issued(target, now)
        self.scale_to(target)
        self._controller.on_complete(target, now)
        _stage_add("replica.fleet.scale")
        _flight_event("replica_failover", fleet_scaled_to=target)
        return target

    def scale_to(self, target: int) -> None:
        target = max(0, int(target))
        old = self.target_n
        self.target_n = target
        for rid in range(old, target):  # grow
            if rid not in self.procs:
                self._launch(rid)
        for rid in range(target, old):  # shrink: highest ids drain first
            proc = self.procs.pop(rid, None)
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            try:
                os.unlink(replica_status_path(self.supervise_dir, rid))
            except OSError:
                pass

    def autoscaler_line(self) -> "Optional[str]":
        if self._controller is None:
            return None
        last = self._controller.last_decision()
        return (
            f"replica autoscaler: n={self._controller.current_n}, "
            f"state={self._controller.state}"
            + (f"; last decision: {last.kind} -> {last.target_n} ({last.reason})" if last else "")
        )

    def stop(self) -> None:
        """Terminate the fleet, preserving flight dumps first (the supervise
        dir is about to be rmtree'd)."""
        for rid, proc in list(self.procs.items()):
            if proc.poll() is None:
                proc.terminate()
        deadline = time.monotonic() + 10.0
        for rid, proc in list(self.procs.items()):
            try:
                proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            self._preserve_flight_dump(rid)
        self.procs.clear()


def _fleet_signals(
    statuses: Dict[int, Dict[str, Any]],
    prev: "Optional[tuple]",
    now: float,
    current_n: int,
) -> "tuple":
    """Fold replica status files into one AutoscaleSignals sample: query
    rate as ``ingest_rate`` (the controller is policy-agnostic — a rate
    against per-unit capacity), staleness sheds as ``shed_rate``."""
    from pathway_tpu.parallel.autoscaler import AutoscaleSignals

    served = 0.0
    shed = 0.0
    stable = True
    for rid in range(current_n):
        status = statuses.get(rid)
        if status is None:
            stable = False
            continue
        # a refused replica is PRESENT but out of rotation: it must not
        # freeze the controller (stable) nor add capacity (it serves nothing)
        served += float(status.get("served_total") or 0.0)
        shed += float(status.get("shed_total") or 0.0)
    carry = (now, served, shed)
    if prev is None:
        return AutoscaleSignals(stable=stable, current_n=current_n), carry
    prev_now, prev_served, prev_shed = prev
    dt = max(1e-6, now - prev_now)
    return (
        AutoscaleSignals(
            ingest_rate=max(0.0, served - prev_served) / dt,
            shed_rate=max(0.0, shed - prev_shed) / dt,
            stable=stable,
            current_n=current_n,
        ),
        carry,
    )


# -- the replica child process -------------------------------------------------


def main() -> int:
    """Entry point of one replica process (``python -m
    pathway_tpu.parallel.replica``): bootstrap, follow, serve, publish."""
    replica_id = int(_env_float("PATHWAY_REPLICA_ID", 0))
    feed_root = os.environ.get("PATHWAY_REPLICA_FEED")
    if not feed_root:
        print(
            "replica: PATHWAY_REPLICA_FEED is required (the feed root the "
            "primary exports bootstraps and frames into)",
            file=sys.stderr,
        )
        return 2
    from pathway_tpu.parallel.mesh import require_cpu_platform

    require_cpu_platform(f"replica {replica_id}")
    port = int(_env_float("PATHWAY_REPLICA_PORT", 0))
    supervise_dir = os.environ.get("PATHWAY_SUPERVISE_DIR")
    bootstrap_deadline = _env_float("PATHWAY_REPLICA_BOOTSTRAP_DEADLINE_S", 240.0)

    try:
        from pathway_tpu.engine.profile import get_flight_recorder

        get_flight_recorder().configure(rank=replica_id, default_dir=None)
    except Exception:
        pass

    stop = threading.Event()

    def _on_term(signum: int, frame: Any) -> None:
        stop.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    follower = ReplicaFollower(
        ReplicaFeed(feed_root), default_index_factory, replica_id=replica_id
    )
    server = ReplicaServer(follower, port=port)

    def publish() -> None:
        if supervise_dir is None:
            return
        payload = follower.snapshot()
        payload["port"] = server.port
        payload["pid"] = os.getpid()
        payload["time"] = time.time()
        write_replica_status(supervise_dir, replica_id, payload)

    try:
        publish()
        # wait for the primary's first bootstrap export, then cold-start;
        # a TORN export is a typed refusal — stay up, out of rotation, so
        # the operator sees "refused" instead of a crash loop
        deadline = time.monotonic() + bootstrap_deadline
        while not stop.is_set():
            if follower.feed.latest_bootstrap() is None:
                # nothing exported yet: keep waiting (the primary may still
                # be warming up) — only a TORN export is a refusal
                if time.monotonic() > deadline:
                    print(
                        f"replica {replica_id}: no bootstrap export appeared "
                        f"within {bootstrap_deadline:.0f}s — refusing",
                        file=sys.stderr,
                    )
                    follower.state = "refused"
                    publish()
                    break
                stop.wait(min(0.2, follower.poll_s * 2))
                continue
            try:
                follower.bootstrap()
            except ReplicaBootstrapError:
                pass  # typed refusal: stay up, out of rotation
            publish()
            break
        publish()
        while not stop.is_set():
            if follower.state == "following":
                follower.poll_frames()
            publish()
            stop.wait(follower.poll_s)
    finally:
        try:
            follower.state = "stopped"
            publish()
        except Exception:
            pass
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
