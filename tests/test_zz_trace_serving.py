"""Distributed tracing against a LIVE REST route (always-on tier-1): the
``X-Pathway-Trace`` header echoes on every response, the route's span parents
to the caller's context, and a coalesced encoder tick links the N query spans
whose texts it batched (the fan-in edge ``cli trace`` renders).

Lives at the end of the suite's alphabetical order on purpose — these tests
start a real ``pw.run`` engine behind a REST connector, and streaming REST
sources run forever (daemon threads); see ``test_zz_brownout_serving.py``.
"""

from __future__ import annotations

import json
import socket
import threading
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import tracing
from pathway_tpu.engine.tracing import (
    TRACE_HEADER,
    get_tracer,
    parse_trace_header,
    reset_tracing,
)
from pathway_tpu.internals.parse_graph import G

pytestmark = pytest.mark.trace

_PORT = 18803


@pytest.fixture(autouse=True)
def _always_sample(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "1.0")
    reset_tracing()
    yield
    # env is still patched "on" here — reset alone would leave the global
    # tracer live for whatever outlives this module (daemon engine threads)
    reset_tracing()
    get_tracer().enabled = False


_started = threading.Event()


def _ensure_server():
    """One echo engine for the whole module (REST sources stream forever)."""
    if _started.is_set():
        return
    from pathway_tpu.io.http import PathwayWebserver, rest_connector

    G.clear()
    ws = PathwayWebserver(host="127.0.0.1", port=_PORT)

    class Q(pw.Schema):
        text: str

    queries, writer = rest_connector(
        webserver=ws, route="/v1/retrieve", schema=Q,
        max_pending=64, delete_completed_queries=True,
        autocommit_duration_ms=25,
    )
    writer(queries.select(result=pw.this.text))
    threading.Thread(
        target=lambda: pw.run(monitoring_level=pw.MonitoringLevel.NONE),
        daemon=True,
    ).start()
    deadline = time.monotonic() + 20
    while True:
        try:
            socket.create_connection(("127.0.0.1", _PORT), timeout=1).close()
            _started.set()
            return
        except OSError:
            assert time.monotonic() < deadline, "REST server never came up"
            time.sleep(0.2)


def _post(text: str, *, trace: "str | None" = None, timeout: float = 30.0):
    """POST one query; returns (status, response_headers)."""
    import urllib.error
    import urllib.request

    headers = {"Content-Type": "application/json"}
    if trace is not None:
        headers[TRACE_HEADER] = trace
    req = urllib.request.Request(
        f"http://127.0.0.1:{_PORT}/v1/retrieve",
        data=json.dumps({"text": text}).encode(),
        headers=headers,
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            r.read()
            return r.status, dict(r.headers)
    except urllib.error.HTTPError as exc:
        exc.read()
        return exc.code, dict(exc.headers)


def test_zz_rest_echoes_trace_header_and_parents_the_route_span():
    _ensure_server()
    sent_trace, sent_span = "ab" * 8, "12" * 8
    status, headers = _post(
        "trace echo probe", trace=f"{sent_trace}-{sent_span}-01"
    )
    assert status == 200
    echoed = parse_trace_header(headers.get(TRACE_HEADER))
    assert echoed is not None, headers
    # same trace id, NEW span id (the route's own span), sampled flag kept
    assert echoed.trace_id == sent_trace
    assert echoed.span_id != sent_span
    assert echoed.sampled is True
    spans = [
        s for s in get_tracer().recent_spans(limit=4096)
        if s["trace_id"] == sent_trace
    ]
    assert spans, "route span never reached the ring"
    rest = next(s for s in spans if s["kind"] == "rest")
    assert rest["parent_id"] == sent_span  # child of the CALLER's span
    assert rest["span_id"] == echoed.span_id
    assert rest["attrs"]["route"] == "/v1/retrieve"
    assert rest["attrs"]["status"] == 200


def test_zz_headerless_request_still_gets_a_trace_id():
    _ensure_server()
    status, headers = _post("no inbound header")
    assert status == 200
    minted = parse_trace_header(headers.get(TRACE_HEADER))
    assert minted is not None, headers
    assert minted.sampled is True  # PATHWAY_TRACE_SAMPLE=1.0 head decision


def test_zz_coalesced_encode_tick_links_the_batched_query_spans():
    """Two REST queries register their span contexts under their texts; the
    encoder tick that batches those texts drains the registry and emits ONE
    ``encode`` span linking BOTH parents — the coalesced fan-in edge."""
    from pathway_tpu.models.encoder_service import EncoderService

    _ensure_server()
    text_a, text_b = "coalesce probe alpha", "coalesce probe beta"
    status_a, headers_a = _post(text_a, trace="aa" * 8 + "-" + "01" * 8 + "-01")
    status_b, headers_b = _post(text_b, trace="bb" * 8 + "-" + "02" * 8 + "-01")
    assert status_a == 200 and status_b == 200
    parent_a = parse_trace_header(headers_a[TRACE_HEADER])
    parent_b = parse_trace_header(headers_b[TRACE_HEADER])

    class _HashEncoder:
        dim = 8

        def encode_device(self, texts):
            rows = [
                np.frombuffer(
                    str(t).encode().ljust(8, b"\0")[:8], dtype=np.uint8
                ).astype(np.float32)
                for t in texts
            ]
            return np.stack(rows)

    svc = EncoderService(_HashEncoder(), prewarm=False)
    try:
        out = svc.submit([text_a, text_b])
        assert len(out) == 2
    finally:
        svc.close()
    encodes = [
        s for s in get_tracer().recent_spans(limit=4096)
        if s["kind"] == "encode"
    ]
    assert encodes, "encode tick span never reached the ring"
    linked = {
        link["span_id"] for span in encodes for link in span["links"]
    }
    # the tick links the ROUTE spans the queries got (their echoed span ids)
    assert parent_a.span_id in linked and parent_b.span_id in linked
    span = next(
        s for s in encodes
        if {l["span_id"] for l in s["links"]}
        >= {parent_a.span_id, parent_b.span_id}
    )
    assert span["attrs"]["unique"] == 2
    # the tick's parts: the enqueue, then its one wait for the forward
    parts = [
        s["kind"] for s in get_tracer().recent_spans(limit=4096)
        if s["parent_id"] == span["span_id"]
    ]
    assert parts == ["encode.dispatch", "encode.device_wait"]  # no cache, no fill


def test_zz_trace_current_context_does_not_leak_between_requests():
    # the route wrapper resets the contextvar: after serving, no ambient span
    assert tracing.current_context() is None
