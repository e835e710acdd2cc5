"""REST ingestion server.

Parity: reference ``io/http/_server.py`` (``PathwayWebserver:329``, ``rest_connector:624``):
an aiohttp server turns each HTTP request into a row of a streaming table; a response writer
subscribes to a result table and resolves the pending HTTP future for the query's key.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence

from pathway_tpu.engine import tracing
from pathway_tpu.engine.datasource import StreamingDataSource
from pathway_tpu.engine.profile import histogram as _histogram
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import parse_graph as pg
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.json import Json
from pathway_tpu.internals.keys import Pointer, pointer_from
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.table import Table


class EndpointDocumentation:
    """Per-endpoint settings for the OpenAPI v3 document (reference
    ``io/http/_server.py:126``)."""

    DEFAULT_RESPONSES = {
        "200": {"description": "OK"},
        "400": {
            "description": "The request is incorrect. Please check if it complies "
            "with the endpoint's input schema"
        },
    }

    def __init__(
        self,
        *,
        summary: str | None = None,
        description: str | None = None,
        tags: Sequence[str] | None = None,
        method_types: Sequence[str] | None = None,
    ):
        self.summary = summary
        self.description = description
        self.tags = list(tags) if tags else None
        self.method_types = (
            {m.upper() for m in method_types} if method_types is not None else None
        )

    def generate_docs(self, method: str, schema: Any) -> dict | None:
        method = method.upper()
        if self.method_types is not None and method not in self.method_types:
            return None
        entry: dict = {"responses": dict(self.DEFAULT_RESPONSES)}
        if self.summary:
            entry["summary"] = self.summary
        if self.description:
            entry["description"] = self.description
        if self.tags:
            entry["tags"] = self.tags
        properties, required = _openapi_schema_fields(schema)
        if method == "GET":
            entry["parameters"] = [
                {
                    "name": name,
                    "in": "query",
                    "required": name in required,
                    "schema": spec,
                }
                for name, spec in properties.items()
            ]
        else:
            entry["requestBody"] = {
                "content": {
                    "application/json": {
                        "schema": {
                            "type": "object",
                            "properties": properties,
                            "required": sorted(required),
                        }
                    }
                },
                "required": True,
            }
        return entry


def _openapi_schema_fields(schema: Any) -> tuple[dict, set]:
    from pathway_tpu.internals import dtype as dt

    type_map = {
        dt.INT: {"type": "integer"},
        dt.FLOAT: {"type": "number"},
        dt.BOOL: {"type": "boolean"},
        dt.STR: {"type": "string"},
        dt.JSON: {"type": "object"},
        dt.BYTES: {"type": "string", "format": "binary"},
    }
    properties: dict = {}
    required: set = set()
    for name, col in schema.columns().items():
        base = col.dtype.strip_optional()
        properties[name] = dict(type_map.get(base, {"type": "string"}))
        has_default = getattr(col, "has_default", False)
        if has_default() if callable(has_default) else has_default:
            if col.default_value is not None and col.default_value is not ...:
                properties[name]["default"] = col.default_value
        elif col.dtype == base:  # non-optional, no default
            required.add(name)
    return properties, required


class PathwayWebserver:
    """One aiohttp server shared by any number of rest_connector endpoints.

    When ``openapi_docs_path`` is set (default ``/_schema``), the server exposes the
    auto-generated OpenAPI v3 document for every registered endpoint (reference
    ``EndpointDocumentation`` docgen)."""

    def __init__(
        self,
        host: str = "0.0.0.0",
        port: int = 8080,
        with_cors: bool = False,
        openapi_docs_path: str | None = "/_schema",
    ):
        self.host = host
        self.port = port
        self.with_cors = with_cors
        self.openapi_docs_path = openapi_docs_path
        self._routes: Dict[tuple, Any] = {}
        self._docs: Dict[tuple, tuple] = {}  # (method, route) -> (schema, docs)
        self._started = threading.Event()
        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None
        self._runner = None

    def _register_docs(
        self,
        route: str,
        methods: Sequence[str],
        schema: Any,
        documentation: "EndpointDocumentation | None" = None,
    ) -> None:
        # documentation is declared at connector-construction time (before any
        # engine run), so the OpenAPI document is complete without serving
        for method in methods:
            self._docs[(method.upper(), route)] = (
                schema,
                documentation or EndpointDocumentation(),
            )

    def _register(self, route: str, methods: Sequence[str], handler: Any) -> None:
        if (
            self.openapi_docs_path is not None
            and route == self.openapi_docs_path
            and any(m.upper() == "GET" for m in methods)
        ):
            raise ValueError(
                f"route {route!r} collides with the OpenAPI docs endpoint; pass "
                "openapi_docs_path=None (or another path) to PathwayWebserver"
            )
        for method in methods:
            self._routes[(method.upper(), route)] = handler
        if self.openapi_docs_path is not None:
            self._routes.setdefault(("GET", self.openapi_docs_path), self._serve_openapi)
        self._ensure_running()

    async def _serve_openapi(self, request: Any) -> Any:
        import aiohttp.web as web
        import json as _json

        return web.Response(
            text=_json.dumps(self.openapi_description()),
            content_type="application/json",
        )

    def openapi_description(self) -> dict:
        """The OpenAPI v3 document covering every documented endpoint."""
        paths: dict = {}
        for (method, route), (schema, docs) in sorted(self._docs.items()):
            entry = docs.generate_docs(method, schema)
            if entry is None:
                continue
            paths.setdefault(route, {})[method.lower()] = entry
        return {
            "openapi": "3.0.3",
            "info": {"title": "Pathway-TPU API", "version": "1.0.0"},
            "servers": [{"url": f"http://{self.host}:{self.port}"}],
            "paths": paths,
        }

    def _ensure_running(self) -> None:
        if self._thread is not None:
            return

        def serve() -> None:
            import aiohttp.web as web

            async def main() -> None:
                app = web.Application()

                async def dispatch(request: web.Request) -> web.Response:
                    handler = self._routes.get((request.method, request.path))
                    if handler is None:
                        response: web.Response = web.Response(
                            status=404, text="no such endpoint"
                        )
                    else:
                        response = await handler(request)
                    if tracing.TRACE_HEADER not in response.headers:
                        # the trace context echoes on EVERY route — including
                        # 404s and routes that did not open a span — so
                        # clients can always correlate a response
                        ctx = tracing.parse_trace_header(
                            request.headers.get(tracing.TRACE_HEADER)
                        ) or tracing.new_trace_context()
                        response.headers[tracing.TRACE_HEADER] = (
                            tracing.format_trace_header(ctx)
                        )
                    return response

                app.router.add_route("*", "/{tail:.*}", dispatch)
                runner = web.AppRunner(app)
                await runner.setup()
                self._runner = runner
                site = web.TCPSite(runner, self.host, self.port)
                await site.start()
                self._started.set()
                while True:
                    await asyncio.sleep(3600)

            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)
            try:
                self._loop.run_until_complete(main())
            except Exception:
                self._started.set()
                raise

        self._thread = threading.Thread(target=serve, daemon=True, name="pathway:webserver")
        self._thread.start()
        self._started.wait(timeout=10)


class RestServerSubject:
    def __init__(
        self,
        webserver: PathwayWebserver,
        route: str,
        methods: Sequence[str],
        schema: sch.SchemaMetaclass,
        delete_completed_queries: bool,
        request_validator: Any = None,
        documentation: "EndpointDocumentation | None" = None,
        max_pending: int = 0,
        shed_stage: str = "rest.shed",
        retry_after: Callable[[], float] | None = None,
        overload_probe: Callable[[], bool] | None = None,
    ):
        self.webserver = webserver
        self.route = route
        self.methods = methods
        self.schema = schema
        self.delete_completed_queries = delete_completed_queries
        self.request_validator = request_validator
        self.documentation = documentation
        self.futures: Dict[bytes, "asyncio.Future"] = {}
        # admission control: requests already pushed into the engine and not
        # yet answered. Past ``max_pending`` (0 = unbounded) new requests are
        # shed with 429 + Retry-After instead of queueing without bound —
        # first slice of the REST-plane backpressure story
        self.max_pending = max(0, int(max_pending))
        self.shed_stage = shed_stage
        self._retry_after = retry_after
        # secondary admission probe (e.g. the encoder service's row cap):
        # sheds on downstream queue depth, not just this route's request count
        self._overload_probe = overload_probe
        self.shed_requests = 0
        # per-client shed attribution (X-Pathway-Client header): a noisy
        # neighbor's flood shows up HERE, not smeared over everyone. Only the
        # handler's event-loop thread mutates it. BOUNDED: the header is
        # attacker-controlled, so only the first _MAX_SHED_CLIENTS distinct
        # ids get their own counter — later ids fold into "other" (an id
        # rotation attack must not grow the stage-counter dict or /metrics
        # cardinality without bound)
        self.shed_by_client: Dict[str, int] = {}
        self._counter = 0
        self._lock = threading.Lock()

    def run(self, source: StreamingDataSource) -> None:
        async def handler(request: Any) -> Any:
            # the route's "rest" span: parented by the client's X-Pathway-Trace
            # context (or a fresh root), covering admission -> engine commit ->
            # future resolution, and echoed back with OUR span id so the
            # client can look the request up in the merged trace. It lives
            # across awaits on the event-loop thread, where requests
            # interleave, so it is opened with start/finish and is never a
            # profiler annotation; its synchronous children (admit, reply) are
            parent_ctx = tracing.parse_trace_header(
                request.headers.get(tracing.TRACE_HEADER)
            )
            tracer = tracing.get_tracer()
            span = tracer.start(
                "rest",
                f"{request.method} {self.route}",
                ctx=parent_ctx,
                attrs={"route": self.route},
            )
            try:
                body = (
                    await request.text()
                    if request.method in ("POST", "PUT", "PATCH")
                    else None
                )
                response = await _handle(request, body, span)
                if span is not None:
                    span.attrs["status"] = response.status
                echo_ctx = (
                    span.context()
                    if span is not None
                    else (parent_ctx or tracing.new_trace_context())
                )
                response.headers[tracing.TRACE_HEADER] = (
                    tracing.format_trace_header(echo_ctx)
                )
            finally:
                if span is not None:
                    tracer.finish(span)
            return response

        async def _handle(request: Any, body: "str | None", span: Any) -> Any:
            span_ctx = span.context() if span is not None else None
            with tracing.trace_span("admit", ctx=span_ctx):
                admitted = _admit(request, body, span_ctx)
            if not isinstance(admitted, tuple):
                return admitted  # refused: the response says why
            import aiohttp.web as web

            kb, key, row, future, t0 = admitted
            try:
                result = await future
                with tracing.trace_span("reply", ctx=span_ctx):
                    # the serving-path latency histogram (/metrics exports it
                    # next to commit duration): push -> engine commit ->
                    # future resolution
                    _histogram("pathway_rest_latency_seconds").observe(
                        time.perf_counter() - t0
                    )
                    if isinstance(result, Json):
                        result = result.value
                    response = web.json_response(result)
            finally:
                # a cancelled handler (client disconnect/timeout) must release
                # its admission slot and retract its query row — under the
                # max_pending check a leaked slot is a permanent 429 wedge,
                # not just a memory leak
                self.futures.pop(kb, None)
                if self.delete_completed_queries:
                    source.push(row, key=key, diff=-1)
            return response

        def _admit(request: Any, body: "str | None", span_ctx: Any) -> Any:
            """Parse, validate, admission check, push: everything between the
            request's body and the row's entry into the engine, with no await.
            Returns the refusal's response, or what the wait for the reply
            needs."""
            import aiohttp.web as web

            if body is not None:
                try:
                    payload = json.loads(body)
                except json.JSONDecodeError:
                    payload = {}
            else:
                payload = dict(request.query)
            if self.request_validator is not None:
                try:
                    self.request_validator(payload)
                except Exception as e:
                    return web.Response(status=400, text=str(e))
            from pathway_tpu.engine.brownout import get_brownout, retry_after_int

            brownout = get_brownout()
            # quiesce window: a membership transition has the commit loop
            # paused — an admitted request would HANG until the cluster
            # resumes at C+1, so shed with the expected remaining pause as an
            # honest Retry-After instead (chaos-tested)
            quiesce_s = brownout.quiesce_retry_after()
            if quiesce_s is not None:
                from pathway_tpu.engine import telemetry

                telemetry.stage_add("rest.quiesce_shed")
                return web.Response(
                    status=429,
                    headers={"Retry-After": retry_after_int(quiesce_s)},
                    text=(
                        "resharding in progress (cluster quiesced at a commit "
                        "boundary); retry after the indicated delay"
                    ),
                )
            probe_hit = False
            if self._overload_probe is not None:
                try:
                    probe_hit = bool(self._overload_probe())
                except Exception:
                    probe_hit = False
            # brownout rung 1/2: the admission cap TIGHTENS before the
            # autoscaler spends a reshard pause — cheap degradation first
            effective_pending = self.max_pending
            brownout_level = 0
            if self.max_pending:
                scale = brownout.admission_scale()
                if scale < 1.0:
                    brownout_level = brownout.level()
                    effective_pending = max(1, int(self.max_pending * scale))
            if probe_hit or (
                effective_pending and len(self.futures) >= effective_pending
            ):
                # shed BEFORE pushing into the engine: an admitted request
                # costs an engine commit + an embed slot; a shed one costs
                # only this response
                self.shed_requests += 1
                from pathway_tpu.engine import telemetry

                telemetry.stage_add(self.shed_stage)
                client = _client_id(request)
                if client is not None:
                    if (
                        client not in self.shed_by_client
                        and len(self.shed_by_client) >= _MAX_SHED_CLIENTS
                    ):
                        client = "other"
                    self.shed_by_client[client] = (
                        self.shed_by_client.get(client, 0) + 1
                    )
                    telemetry.stage_add(f"{self.shed_stage}.client.{client}")
                retry_s = 1.0
                if self._retry_after is not None:
                    try:
                        retry_s = float(self._retry_after())
                    except Exception:
                        pass
                reason = (
                    "downstream embed queue full"
                    if probe_hit
                    else (
                        f"{len(self.futures)} requests in flight "
                        f"(cap {effective_pending}"
                        + (
                            f", tightened by brownout rung {brownout_level}"
                            if brownout_level
                            else ""
                        )
                        + ")"
                    )
                )
                return web.Response(
                    status=429,
                    headers={"Retry-After": retry_after_int(retry_s)},
                    text=(
                        f"overloaded: {reason}; retry after the indicated delay"
                    ),
                )
            with self._lock:
                self._counter += 1
                qid = self._counter
            key = pointer_from(qid, self.route, "rest")
            from pathway_tpu.internals.keys import pointers_to_keys

            kb = pointers_to_keys([key]).tobytes()
            loop = asyncio.get_running_loop()
            future: asyncio.Future = loop.create_future()
            self.futures[kb] = future
            row = {}
            for name, col in self.schema.columns().items():
                v = payload.get(name, col.default_value if col.has_default else None)
                if col.dtype.strip_optional() == dt.JSON and v is not None and not isinstance(v, Json):
                    v = Json(v)
                row[name] = v
            if span_ctx is not None:
                # causal handoff into the engine: the commit that takes this
                # row links the query and records its queue wait from here
                # (take_commit_links in GraphRunner.step, keyed by row key),
                # and the encoder tick that batches the query text links it
                # too (take_query_links keyed by text) — a coalesced batch
                # ends up linking all N parent query spans
                tracer = tracing.get_tracer()
                for field in ("query", "text", "prompt"):
                    text = row.get(field)
                    if isinstance(text, str) and text:
                        tracer.register_query_link(text, span_ctx)
                        break
                tracer.register_commit_link(kb, span_ctx)
            t0 = time.perf_counter()
            source.push(row, key=key, diff=1)
            return kb, key, row, future, t0

        self.webserver._register(self.route, self.methods, handler)
        # block forever: the server lives until the process exits
        threading.Event().wait()

    def resolve(self, key: Pointer, result: Any) -> None:
        from pathway_tpu.internals.keys import pointers_to_keys

        kb = pointers_to_keys([key]).tobytes()
        future = self.futures.get(kb)
        if future is not None and self.webserver._loop is not None:
            self.webserver._loop.call_soon_threadsafe(
                lambda: future.set_result(result) if not future.done() else None
            )


def rest_connector(
    host: str | None = None,
    port: int | None = None,
    *,
    webserver: PathwayWebserver | None = None,
    route: str = "/",
    schema: sch.SchemaMetaclass | None = None,
    methods: Sequence[str] = ("POST",),
    # serving path: a 1 ms commit tick makes per-request latency wake+commit.
    # Bursts still batch naturally — while one commit processes, arriving
    # requests queue and drain together in the next batch — so the tick only
    # throttles tiny-commit storms, it is not the batching mechanism (see
    # StreamingDataSource.next_batch).
    autocommit_duration_ms: int | None = 1,
    keep_queries: bool | None = None,
    delete_completed_queries: bool = False,
    request_validator: Any = None,
    documentation: "EndpointDocumentation | None" = None,
    max_pending: int = 0,
    shed_stage: str = "rest.shed",
    retry_after: "Callable[[], float] | None" = None,
    overload_probe: "Callable[[], bool] | None" = None,
) -> tuple[Table, Any]:
    """Expose an HTTP endpoint as a streaming table; returns (queries, response_writer).
    ``max_pending`` caps in-flight requests on the route (0 = unbounded): past
    it — or while the optional ``overload_probe`` callable reports a saturated
    downstream queue — requests are shed with 429 + ``Retry-After`` (estimated
    by the optional ``retry_after`` callable) and counted on stage counter
    ``shed_stage``."""
    if webserver is None:
        webserver = PathwayWebserver(host=host or "0.0.0.0", port=port or 8080)
    if schema is None:
        schema = sch.schema_from_types(query=str)
    subject = RestServerSubject(
        webserver, route, methods, schema, delete_completed_queries, request_validator,
        documentation=documentation, max_pending=max_pending, shed_stage=shed_stage,
        retry_after=retry_after, overload_probe=overload_probe,
    )
    webserver._register_docs(route, methods, schema, documentation)

    class _Runner:
        def run(self, source: StreamingDataSource) -> None:
            subject.run(source)

    source = StreamingDataSource(subject=_Runner(), autocommit_ms=autocommit_duration_ms)
    node = G.add_node(pg.InputNode(source=source, streaming=True, name=f"rest:{route}"))
    queries = Table(node, schema, name="rest_queries")

    def response_writer(result_table: Table, result_column: str = "result") -> None:
        def on_change(key: Pointer, row: dict, time: int, is_addition: bool) -> None:
            if is_addition:
                subject.resolve(key, _jsonable(row.get(result_column)))

        from pathway_tpu.io._subscribe import subscribe

        subscribe(result_table, on_change)

    return queries, response_writer


# distinct client ids tracked per route before attribution folds into "other"
_MAX_SHED_CLIENTS = 32


def _client_id(request: Any) -> "str | None":
    """Sanitized ``X-Pathway-Client`` header value for shed attribution
    (stage-counter-safe: alnum/dash/underscore, bounded length)."""
    try:
        raw = request.headers.get("X-Pathway-Client")
    except Exception:
        return None
    if not raw:
        return None
    cleaned = "".join(c for c in str(raw)[:32] if c.isalnum() or c in "-_")
    return cleaned or None


def _jsonable(v: Any) -> Any:
    from pathway_tpu.internals.json import jsonable_value

    return jsonable_value(v)
