"""The serving path's stages on the profiler's clock, against a LIVE
``VectorStoreServer`` (threaded REST server, a tiny encoder, the CPU): while a
``jax.profiler`` session is on, with ``PATHWAY_TRACE`` unset, one
``/v1/retrieve`` leaves its synchronous spans as ``pw.<kind>`` annotations on
the ``/host:CPU`` plane of the session's ``.xplane.pb`` and the whole span set
of the request in the tracer's ring.

Lives at the end of the suite's alphabetical order on purpose: REST sources
stream forever (daemon threads); see ``test_zz_trace_serving.py``.
"""

from __future__ import annotations

import json
import socket
import time
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.engine import tracing

pytestmark = pytest.mark.trace

ROUTE = "/v1/retrieve"

#: the live server of the ``traced`` fixture, for the tests that talk to it again
_LIVE: dict = {}


def _post(port: int, route: str, payload: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One server, a few warm requests, then ONE request inside a profiler
    session: (profile directory, the ring's spans after the session)."""
    import jax

    from pathway_tpu.internals import parse_graph as pg
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.stdlib.indexing import nearest_neighbors as nn
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    mp = pytest.MonkeyPatch()
    mp.delenv("PATHWAY_TRACE", raising=False)
    tracing.reset_tracing()
    assert not tracing.get_tracer().recording()
    pg.G.clear()
    embedder = SentenceTransformerEmbedder(encoder_config=EncoderConfig(
        vocab_size=30522, hidden_size=32, num_layers=1, num_heads=2,
        intermediate_size=64,
    ))
    docs = [f"document {i} on subject {i % 5} and matter {i % 3}" for i in range(32)]
    table = pw.debug.table_from_rows(
        pw.schema_builder({"data": str, "_metadata": str}),
        [(text, json.dumps({"path": f"doc{i}"})) for i, text in enumerate(docs)],
    )
    server = VectorStoreServer(table, embedder=embedder, index_factory=nn.BruteForceKnnFactory(
        embedder=embedder, metric=nn.BruteForceKnnMetricKind.COS, reserved_space=64,
    ))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server.run_server(host="127.0.0.1", port=port, threaded=True)
    deadline = time.monotonic() + 120
    while True:
        try:
            if int(_post(port, "/v1/statistics", {}).get("file_count", 0)) == len(docs):
                break
        except OSError:
            pass
        assert time.monotonic() < deadline, "the corpus was never indexed"
        time.sleep(0.2)
    for i in range(3):  # compile the query path's programs outside the session
        _post(port, ROUTE, {"query": f"subject {i} warm", "k": 3})
    assert tracing.get_tracer().recent_spans() == []  # nothing records yet

    directory = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        reply = _post(port, ROUTE, {"query": "subject 4 matter 1", "k": 3})
        time.sleep(0.2)  # the tick's cache fill runs after the waiters left
    finally:
        jax.profiler.stop_trace()
    assert len(reply) == 3
    spans = tracing.get_tracer().recent_spans(limit=1 << 20)
    _LIVE.update(port=port, embedder=embedder)
    yield directory, spans
    _LIVE.clear()
    tracing.reset_tracing()
    mp.undo()


def test_zz_session_puts_the_sync_spans_on_the_host_plane(traced):
    directory, _ = traced
    events = tracing.load_profile_events(tracing.find_profile(directory))
    assert events and all(e[0] == "/host:CPU" for e in events)  # the CPU has no device plane
    names = {e[2] for e in events}
    assert names >= {"pw.admit", "pw.commit", "pw.embed_wait", "pw.search", "pw.search.prepare",
                     "pw.search.device_wait", "pw.search.assemble", "pw.encode",
                     "pw.encode.dispatch", "pw.tokenize", "pw.encode.device_wait",
                     "pw.cache_fill", "pw.reply"}
    # the cache fill reuses the tick's one fetch: it waits for no device
    assert "pw.cache_fill.device_wait" not in names
    # the asynchronous rest span lives across awaits on the event-loop thread,
    # where requests interleave: never an annotation
    assert "pw.rest" not in names
    by_line: dict = {}
    for _, line, name, start, dur in events:
        by_line.setdefault(line, []).append((start, start + dur, name))
    for line, spans in by_line.items():
        stack: list = []
        for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
            while stack and stack[-1][1] <= start:
                stack.pop()
            # what is still open must contain the newcomer: nesting, no straddle
            assert not stack or end <= stack[-1][1], (line, stack[-1], (start, end, name))
            stack.append((start, end, name))
    search = next(e for e in events if e[2] == "pw.search")
    inside = [e for e in events if e[1] == search[1] and search[3] <= e[3]
              and e[3] + e[4] <= search[3] + search[4] and e is not search]
    assert {e[2] for e in inside} == {"pw.search.prepare", "pw.search.device_wait", "pw.search.assemble"}


def test_zz_ring_holds_one_requests_stages_and_they_add_up(traced):
    _, spans = traced
    [rest] = [s for s in spans if s["kind"] == "rest" and s["attrs"].get("route") == ROUTE]
    assert rest["attrs"]["status"] == 200
    mine = {s["kind"]: s for s in spans if s["parent_id"] == rest["span_id"]}
    assert set(mine) == {"admit", "queue", "reply"}
    assert all(s["trace_id"] == rest["trace_id"] for s in mine.values())
    # by its link, not its number: engines other test files left running in
    # this process count their own commits
    [commit] = [s for s in spans if s["kind"] == "commit"
                and {"trace_id": rest["trace_id"], "span_id": rest["span_id"]} in s["links"]]
    assert commit["attrs"]["queries"] == 1
    assert commit["attrs"]["commit"] == mine["queue"]["attrs"]["commit"]
    under_commit = {s["kind"]: s for s in spans if s["trace_id"] == commit["trace_id"]}
    assert {"embed_wait", "search", "search.prepare", "search.device_wait",
            "search.assemble"} <= set(under_commit)
    assert under_commit["search"]["attrs"]["queries"] == 1
    assert under_commit["search.device_wait"]["parent_id"] == under_commit["search"]["span_id"]
    [encode] = [s for s in spans if s["kind"] == "encode"]
    assert {"trace_id": rest["trace_id"], "span_id": rest["span_id"]} in encode["links"]
    end = lambda s: s["ts_mono"] + s["duration_s"]
    tick = {s["kind"]: s for s in spans if s["trace_id"] == encode["trace_id"]}
    # encode > encode.dispatch > tokenize, encode.device_wait (the tick's one
    # fetch of the padded forward), cache_fill (host only: no child)
    assert set(tick) == {"encode", "encode.dispatch", "tokenize", "encode.device_wait", "cache_fill"}
    assert tick["tokenize"]["parent_id"] == tick["encode.dispatch"]["span_id"]
    for kind in ("encode.dispatch", "encode.device_wait", "cache_fill"):
        assert tick[kind]["parent_id"] == encode["span_id"], kind
    assert end(tick["encode.dispatch"]) <= tick["encode.device_wait"]["ts_mono"] + 1e-6

    # admitted, waited, committed, replied, in that order and without overlap
    # up to the commit; the queue ends where its commit starts
    assert rest["ts_mono"] <= mine["admit"]["ts_mono"] <= end(mine["admit"]) <= end(mine["queue"]) + 1e-4
    assert end(mine["queue"]) == pytest.approx(commit["ts_mono"], abs=1e-6)
    assert commit["ts_mono"] < mine["reply"]["ts_mono"] <= end(mine["reply"]) <= end(rest)
    covered, at = 0.0, rest["ts_mono"]
    for s in sorted((mine["admit"], mine["queue"], commit, mine["reply"]), key=lambda s: s["ts_mono"]):
        a, b = max(s["ts_mono"], at), min(end(s), end(rest))
        if b > a:
            covered, at = covered + b - a, b
    unattributed = 1.0 - covered / rest["duration_s"]
    assert 0.0 <= unattributed < 0.5, unattributed
    staged = sum(mine[k]["duration_s"] for k in ("admit", "queue", "reply")) + commit["duration_s"]
    # the stages sum to the rest span up to the unattributed share (and the
    # part of the commit that ran on after the reply had left)
    assert staged >= covered - 1e-9
    assert covered == pytest.approx(rest["duration_s"] * (1.0 - unattributed))


def test_zz_cli_trace_reads_a_profiler_directory(traced):
    from click.testing import CliRunner

    from pathway_tpu.cli import cli

    directory, _ = traced
    result = CliRunner().invoke(cli, ["trace", directory])
    assert result.exit_code == 0, result.output
    assert "by the innermost pw.<kind>" in result.output
    assert "search.device_wait" in result.output and "0 device plane(s)" in result.output


def test_zz_retrieve_sheds_429_when_the_services_rows_are_over_the_cap(traced):
    """``VectorStoreServer`` hands ``rest_connector`` the encoder service's own
    ``overloaded`` and ``retry_after_s``: with the service's pending rows at
    ``PATHWAY_EMBED_MAX_QUEUE_ROWS`` a ``/v1/retrieve`` is refused before it
    costs a commit, 429 with an integer ``Retry-After``, counted once on
    ``embed.shed`` by the REST plane (the service refused nothing itself)."""
    import urllib.error

    from pathway_tpu.engine import telemetry

    port, svc = _LIVE["port"], _LIVE["embedder"].pipeline.service
    assert svc.max_queue_rows == 4096  # the env's default, handed down by EmbedPipeline
    shed_before = telemetry.stage_snapshot("embed.").get("embed.shed", 0.0)
    svc._queued_rows = svc.max_queue_rows  # a full queue, without racing the worker
    try:
        with pytest.raises(urllib.error.HTTPError) as refused:
            _post(port, ROUTE, {"query": "subject 2 refused", "k": 3})
    finally:
        svc._queued_rows = 0
    assert refused.value.code == 429
    assert int(refused.value.headers["Retry-After"]) >= 1
    assert telemetry.stage_snapshot("embed.").get("embed.shed", 0.0) == shed_before + 1
    assert svc.shed_requests == 0
    # the queue drained: the route answers again
    assert len(_post(port, ROUTE, {"query": "subject 2 admitted", "k": 3})) == 3
