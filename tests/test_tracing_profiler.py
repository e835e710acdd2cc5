"""Recording on the profiler's clock (``engine/tracing.py``): the gate (the
env switch or a ``jax.profiler`` session, observed), the ring's size, the
encoder service's token counters, the device programs' stable names, and the
reader that splits a device's idle time over the host's ``pw.<kind>`` spans.
"""

from __future__ import annotations

import numpy as np
import pytest

from pathway_tpu.engine import telemetry, tracing
from pathway_tpu.engine.tracing import Tracer, new_trace_context

pytestmark = pytest.mark.trace


@pytest.fixture
def sampled(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "1.0")
    monkeypatch.delenv("PATHWAY_TRACE_RING", raising=False)
    telemetry.stage_reset("trace.")
    # the sampling rate is read from the process-wide tracer, which stays
    # DISABLED so that engines other test files left running write no spans
    shared = tracing.get_tracer()
    shared.refresh()
    shared.enabled = False
    yield Tracer()
    shared.reset()
    shared.enabled = False


def test_nothing_records_outside_the_env_gate_and_a_session(monkeypatch):
    import jax

    monkeypatch.delenv("PATHWAY_TRACE", raising=False)
    telemetry.stage_reset("trace.")
    assert not jax.profiler.TraceAnnotation.is_enabled()
    inst = Tracer()
    assert not inst.recording()
    # one check, then the shared do-nothing context: no Span, no generator
    assert inst.trace_span("commit", attrs={"commit": 1}) is inst.trace_span("search")
    with inst.trace_span("search", attrs={"queries": 3}) as span:
        assert span is None
        assert tracing.current_context() is None
    assert inst.start("rest", "POST /v1/retrieve") is None
    inst.record_span("queue", "queue", parent=new_trace_context(True), ts=0.0,
                     ts_mono=0.0, duration_s=1.0)
    inst.register_commit_link(b"key", new_trace_context(True))
    assert inst.take_commit_links([b"key"]) == []
    assert inst.recent_spans() == []
    assert telemetry.stage_snapshot("trace.").get("trace.span", 0.0) == 0.0


def test_ring_keeps_a_traced_span_of_200_requests_a_second(sampled):
    """4 s at 200 requests/s, one commit per two requests and as many commits
    of retractions, each commit with 31 synthesized operator rows (the
    VectorStoreServer graph's count) beside its live children: every
    request's ``rest`` span is still readable afterwards."""
    tracer = sampled
    rests = []
    for commit in range(400):
        for _ in range(2):
            rest = tracer.start("rest", "POST /v1/retrieve")
            with tracer.trace_span("admit", ctx=rest.context()):
                pass
            tracer.record_span("queue", "queue", parent=rest.context(), ts=rest.ts,
                               ts_mono=rest.ts_mono, duration_s=1e-3)
            with tracer.trace_span("reply", ctx=rest.context()):
                pass
            tracer.finish(rest)
            rests.append(rest.span_id)
        for n in (2 * commit, 2 * commit + 1):  # the queries' commit, the retractions'
            ctx = tracing.commit_trace_context(0, n)
            with tracer.trace_span("commit", self_ctx=ctx) as span:
                for kind in ("embed_wait", "search", "search.prepare",
                             "search.prepare", "search.device_wait", "search.assemble",
                             "encode", "encode.dispatch", "tokenize", "encode.device_wait",
                             "cache_fill"):
                    with tracer.trace_span(kind):
                        pass
            for _ in range(31):
                tracer.record_span("operator", "rowwise", parent=span.context(), ts=span.ts,
                                   ts_mono=span.ts_mono, duration_s=1e-4)
    kept = {s["span_id"] for s in tracer.recent_spans(limit=1 << 20)}
    assert len(kept) > 30_000  # the old ring of 4,096 spans held a seventh of a second
    assert set(rests) <= kept


def test_a_commit_that_moved_no_row_synthesizes_no_operator_span(sampled, monkeypatch):
    from pathway_tpu.engine.profile import CommitProfile
    from pathway_tpu.engine.runner import GraphRunner

    tracer = sampled
    runner = GraphRunner.__new__(GraphRunner)
    ops = [(1, "rowwise", "rowwise", 1e-5, 0, 0, False)]
    for rows, expected in ((0, 0), (2, 1)):
        runner._last_commit_profile = CommitProfile(
            commit=7, rank=0, duration_s=1e-3, input_rows=rows, output_rows=0, neu=False, ops=ops)
        with tracer.trace_span("commit", self_ctx=tracing.commit_trace_context(0, 7 + rows)) as span:
            pass
        runner._trace_commit_ops(tracer, span)
        assert sum(s["kind"] == "operator" for s in tracer.recent_spans()) == expected


@pytest.fixture(scope="module")
def tiny_encoder():
    from pathway_tpu.models.encoder import EncoderConfig, JaxSentenceEncoder

    return JaxSentenceEncoder(config=EncoderConfig(
        vocab_size=30522, hidden_size=32, num_layers=1, num_heads=2, intermediate_size=64))


def test_service_counts_the_tokens_of_a_tick(tiny_encoder):
    from pathway_tpu.models.encoder_service import EncoderService

    assert tiny_encoder.tokenizer_source == "hash"  # one token per word, [CLS] and [SEP]
    svc = EncoderService(tiny_encoder, prewarm=False)
    try:
        rows = svc.submit(["alpha beta gamma", "one two three four five"])
        assert len(rows) == 2
        stats = svc.stats()
        assert stats["svc_ticks"] == 1
        # 3 + 2 and 5 + 2 tokens under the mask; two rows pad to the 8-row
        # bucket and seven columns to the 8-column bucket
        assert (stats["svc_real_tokens"], stats["svc_padded_tokens"]) == (12, 64)
        svc.submit(["a " * 11])  # 13 columns: the 16-column bucket
        stats = svc.stats()
        assert (stats["svc_real_tokens"], stats["svc_padded_tokens"]) == (12 + 13, 64 + 8 * 16)
    finally:
        svc.close()
    # the counts are the calling thread's own: the service's ticks left this thread's at 0
    assert tiny_encoder.dispatched_tokens() == (0, 0)


def _lowered(program: str, tiny_encoder):
    import jax.numpy as jnp

    if program == "encoder":
        ids = jnp.zeros((8, 8), jnp.int32)
        return tiny_encoder._encode_ids.lower(tiny_encoder.params, ids)
    from pathway_tpu.ops.knn import _search_kernel

    data = jnp.zeros((64, 32), jnp.float32)
    return _search_kernel.lower(data, jnp.ones((64,), bool), jnp.ones((64,), jnp.float32),
                                jnp.zeros((8, 32), jnp.float32), k=4, metric="cos")


@pytest.mark.parametrize("program, module, scopes", [
    ("encoder", "jit_encoder_forward", ["encoder_forward"]),
    ("search", "jit__search_kernel", ["knn_score", "knn_top_k"]),
])
def test_device_programs_carry_stable_names(tiny_encoder, program, module, scopes):
    """The device trace names a program after its jitted function: the encoder's
    forward is a named function (a lambda shows as ``jit__lambda_``), and the
    benchmark's ``search_programs`` matches ``^jit__search_kernel$``."""
    lowered = _lowered(program, tiny_encoder)
    assert f"module @{module} " in lowered.as_text()
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert f"{scope}/" in text, scope


MS = 1_000_000
DEV, HOST = "/device:TPU:0", "/host:CPU"
HAND_MADE = [
    (DEV, "XLA Ops", "%fusion", 0 * MS, 10 * MS),
    (DEV, "XLA Ops", "%top_k", 20 * MS, 10 * MS),  # idle 10..20
    (DEV, "XLA Ops", "%fusion", 50 * MS, 5 * MS),  # idle 30..50
    (DEV, "XLA Ops", "%fusion", 100 * MS, 5 * MS),  # idle 55..100
    (DEV, "XLA Modules", "jit__search_kernel(1)", 0, 10 * MS),  # not read where ops are
    (HOST, "python#0", "pw.commit", 5 * MS, 40 * MS),
    (HOST, "python#0", "pw.search", 8 * MS, 10 * MS),
    (HOST, "python#0", "pw.search.device_wait", 12 * MS, 5 * MS),
    (HOST, "python#1", "pw.encode", 70 * MS, 10 * MS),
    (HOST, "python#1", "XlaLaunch", 0, 200 * MS),  # not ours: not an annotation
]


def test_device_idle_is_split_over_the_innermost_host_span():
    result = tracing.idle_by_span(HAND_MADE)
    assert result["planes"] == 1 and result["idle_s"] == pytest.approx(0.075)
    idle = {kind: round(row["idle_s"] * 1e3, 6) for kind, row in result["kinds"].items()}
    # 10..20: search 10..12 and 17..18, its device_wait 12..17, commit 18..20;
    # 30..50: commit to 45, then nothing open; 55..100: encode 70..80 of it
    assert idle == {"search": 3.0, "search.device_wait": 5.0, "commit": 17.0, "encode": 10.0,
                    "none": 40.0}
    assert result["kinds"]["commit"]["open_s"] == pytest.approx(0.030)  # 40 ms less the search's 10
    text = "\n".join(tracing.format_idle_by_span(result))
    assert "named: 46.7 % of the idle seconds" in text
    assert "host waiting for the chip (*.device_wait): 6.7 %" in text
    # across threads the innermost is the span opened last
    both = tracing.idle_by_span([
        (DEV, "XLA Ops", "a", 0, 1 * MS), (DEV, "XLA Ops", "b", 11 * MS, 1 * MS),
        (HOST, "python#0", "pw.embed_wait", 0, 20 * MS), (HOST, "python#1", "pw.tokenize", 4 * MS, 2 * MS),
    ])
    assert {k: round(v["idle_s"] * 1e3, 6) for k, v in both["kinds"].items()} == {
        "embed_wait": 8.0, "tokenize": 2.0}
    assert tracing.idle_by_span([])["idle_s"] == 0.0
