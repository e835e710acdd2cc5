"""EmbedPipeline tests: overlapped length-sorted encode, the content-hash
cache, the embedder's constructor surface, and their interaction with the
engine's memoize-on-retraction and fence-replay contracts (the encoder
service's own tests are in ``test_encoder_service.py``). All tier-1 (CPU, tiny
encoder config); the torture-scale variants live behind the ``slow`` marker.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals import expression as expr
from pathway_tpu.internals.keys import KEY_DTYPE, pointer_from
from pathway_tpu.internals.shapes import next_pow2
from pathway_tpu.models.embed_pipeline import EmbedCache, EmbedPipeline
from pathway_tpu.models.encoder import EncoderConfig, HashTokenizer, JaxSentenceEncoder

TINY = EncoderConfig(
    vocab_size=8192, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=128
)


@pytest.fixture(scope="module")
def tiny_encoder() -> JaxSentenceEncoder:
    # nonexistent model name -> deterministic random init + HashTokenizer
    return JaxSentenceEncoder("pw-test-tiny", config=TINY, max_length=64)


def _tiny_embedder(**kwargs):
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    return SentenceTransformerEmbedder(
        model="pw-test-tiny", encoder_config=TINY, **kwargs
    )


# -- shared pow2 util ---------------------------------------------------------


def test_next_pow2_shared_rule():
    assert [next_pow2(n) for n in (0, 1, 2, 3, 8, 9, 1000)] == [1, 1, 2, 4, 8, 16, 1024]
    assert [next_pow2(n, floor=8) for n in (0, 1, 8, 9)] == [8, 8, 8, 16]
    # every former duplicate delegates to the one rule
    from pathway_tpu.models.encoder import _next_pow2 as enc_pow2
    from pathway_tpu.ops.knn import next_pow2 as knn_pow2
    from pathway_tpu.ops.segment import _next_pow2 as seg_pow2

    for n in (1, 5, 8, 9, 127, 128, 129):
        assert knn_pow2(n) == next_pow2(n)
        assert seg_pow2(n) == next_pow2(n)
        assert enc_pow2(n) == next_pow2(n, floor=8)


# -- vectorized HashTokenizer -------------------------------------------------


def _reference_tokenize(texts, vocab_size=30522, max_length=128):
    """The pre-vectorization per-word loop, kept as the parity oracle."""
    import xxhash

    n = len(texts)
    ids = np.zeros((n, max_length), dtype=np.int32)
    mask = np.zeros((n, max_length), dtype=np.int32)
    for i, text in enumerate(texts):
        words = str(text).lower().split()[: max_length - 2]
        toks = [101] + [
            2000 + (xxhash.xxh32_intdigest(w) % (vocab_size - 3000)) for w in words
        ] + [102]
        ids[i, : len(toks)] = toks
        mask[i, : len(toks)] = 1
    return ids, mask


def test_hash_tokenizer_vectorized_parity():
    texts = ["Hello World", "", "a b c d e f g h", "ONE two THREE", "x " * 200]
    tok = HashTokenizer()
    ids, mask = tok(texts)
    ref_ids, ref_mask = _reference_tokenize(texts)
    width = ids.shape[1]
    assert width <= 128  # trimmed to the longest row, not padded to max_length
    assert np.array_equal(ids, ref_ids[:, :width])
    assert np.array_equal(mask, ref_mask[:, :width])
    assert ref_ids[:, width:].sum() == 0  # nothing real was trimmed away
    # second call rides the word->id memo and must agree with the first
    ids2, mask2 = tok(texts)
    assert np.array_equal(ids, ids2) and np.array_equal(mask, mask2)


def test_hash_tokenizer_word_cache_bound():
    tok = HashTokenizer()
    tok._WORD_CACHE_MAX = 8
    tok([f"w{i}" for i in range(6)])
    assert len(tok._word_ids) == 6
    tok([f"v{i}" for i in range(6)])  # would exceed the cap -> memo resets
    assert len(tok._word_ids) == 6
    # correctness survives the reset
    ids_a, _ = tok(["w0 v0"])
    ids_b, _ = _reference_tokenize(["w0 v0"])
    assert np.array_equal(ids_a, ids_b[:, : ids_a.shape[1]])
    # the batch that TRIGGERS the overflow may itself mix cached and new words:
    # the reset must re-hash the cached ones too, not KeyError on them
    tok2 = HashTokenizer()
    tok2._WORD_CACHE_MAX = 4
    tok2(["alpha beta"])  # cached: alpha, beta
    ids_mix, _ = tok2(["alpha beta gamma delta epsilon"])  # overflow mid-batch
    ref_mix, _ = _reference_tokenize(["alpha beta gamma delta epsilon"])
    assert np.array_equal(ids_mix, ref_mix[:, : ids_mix.shape[1]])


# -- encoder: single copy + sorted sub-batch equivalence ----------------------


def test_encode_single_copy_float32(tiny_encoder):
    out = tiny_encoder.encode(["hello world"])
    assert out.dtype == np.float32
    assert out.shape == (1, TINY.hidden_size)


def test_sorted_subbatch_matches_one_bucket_path(tiny_encoder):
    rng = np.random.default_rng(3)
    texts = [
        " ".join(f"word{rng.integers(0, 500)}" for _ in range(int(rng.integers(1, 40))))
        for _ in range(37)
    ]
    sync = tiny_encoder.encode(texts)
    piped, stats = tiny_encoder.encode_pipelined(texts, sub_batch=8)
    # Equal within float32 rounding, not bit for bit: masking makes a row's
    # value independent of its pad width, but the one-bucket path and each
    # sub-batch run DIFFERENT XLA programs (one per (batch, seq) bucket), and
    # two programs may order a reduction differently. The rows are unit-norm,
    # so 1e-5 is about a hundred float32 ulps of their largest component.
    np.testing.assert_allclose(piped, sync, rtol=0, atol=1e-5)
    assert stats["sub_batches"] == 5
    assert stats["real_tokens"] <= stats["padded_tokens"]
    # sorting must actually reduce padding vs the one-bucket sync path
    ids, mask = tiny_encoder._tokenize(texts)
    sync_padded = next_pow2(len(texts), floor=8) * next_pow2(ids.shape[1], floor=8)
    assert stats["padded_tokens"] < sync_padded


def test_encode_pipelined_empty(tiny_encoder):
    out, stats = tiny_encoder.encode_pipelined([], sub_batch=8)
    assert out.shape == (0, TINY.hidden_size)
    assert stats["sub_batches"] == 0


# -- content-hash cache -------------------------------------------------------


def test_embed_cache_hit_miss_eviction():
    cache = EmbedCache(max_entries=2, model="m")
    v1 = np.ones(4, dtype=np.float32)
    assert cache.get("a") is None
    cache.put("a", v1)
    hit = cache.get("a")
    assert np.array_equal(hit, v1)
    assert not hit.flags.writeable  # shared rows must be immutable
    cache.put("b", v1 * 2)
    cache.put("c", v1 * 3)  # evicts LRU ("a")
    assert cache.get("a") is None
    assert np.array_equal(cache.get("c"), v1 * 3)
    s = cache.stats()
    assert (s["cache_hits"], s["cache_evictions"], s["cache_size"]) == (2, 1, 2)
    assert s["cache_misses"] == 2


def test_embed_cache_model_salt_and_disabled():
    a = EmbedCache(max_entries=4, model="model-a")
    a.put("text", np.ones(2, dtype=np.float32))
    b = EmbedCache(max_entries=4, model="model-b")
    assert b.get("text") is None  # different model never shares entries
    off = EmbedCache(max_entries=0)
    off.put("text", np.ones(2, dtype=np.float32))
    assert off.get("text") is None and len(off) == 0


def test_pipeline_cache_reingest_skips_forward(tiny_encoder):
    pipe = EmbedPipeline(tiny_encoder, model="t", sub_batch=8, cache_size=128)
    texts = [f"doc number {i} about topic {i % 3}" for i in range(20)]
    first = pipe.encode_batch(texts)
    assert np.array_equal(first, tiny_encoder.encode(texts))
    calls = []
    orig = tiny_encoder.encode_pipelined
    tiny_encoder.encode_pipelined = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
    try:
        second = pipe.encode_batch(texts)
    finally:
        tiny_encoder.encode_pipelined = orig
    assert calls == []  # full cache hit: the encoder never ran
    assert np.array_equal(second, first)
    assert pipe.cache.stats()["cache_hits"] == len(texts)
    assert 0.0 <= pipe.pad_waste_ratio() < 1.0


# -- engine integration: memoize-on-retraction + fence replay -----------------


def test_query_memo_retraction_never_reinvokes_encoder():
    """device_expression is deterministic=False: the engine memoizes each query
    row's embedding and REPLAYS it on retraction — with the pipeline in front,
    the retraction must reach neither the encoder service nor the encoder."""
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.internals import parse_graph as pg

    emb = _tiny_embedder(embed_cache_size=0)  # cache off: isolate the memo path
    forwards = []
    orig = emb.encoder.encode_device
    emb.encoder.encode_device = lambda texts: (forwards.append(list(texts)), orig(texts))[1]

    pg.G.clear()
    t = pw.debug.table_from_rows(
        pw.schema_builder({"q": str}),
        [("what is a cat", 0, 1), ("what is a dog", 0, 1), ("what is a cat", 2, -1)],
        is_stream=True,
    )
    res = t.select(v=emb.device_expression(t.q))
    got = []
    pw.io.subscribe(
        res,
        on_batch=lambda keys, diffs, columns, time: got.extend(
            zip(columns["v"], diffs.tolist())
        ),
    )
    GraphRunner(pg.G._current).run(monitoring_level=pw.MonitoringLevel.NONE)
    # both inserts encoded exactly once (one tick), retraction replayed
    assert sum(len(b) for b in forwards) == 2
    ins_cat = [np.asarray(v) for v, d in got if d == 1]
    ret = [np.asarray(v) for v, d in got if d == -1]
    assert len(ins_cat) == 2 and len(ret) == 1
    assert any(np.array_equal(ret[0], v) for v in ins_cat)


@pytest.mark.chaos
def test_fence_replay_inflight_coalesced_queries_exactly_once():
    """Cluster-fence contract for in-flight coalesced queries (the PR 3 replay
    semantics): a fence aborts the commit AFTER the coalesced encode ran but
    before results committed; the engine resets evaluator state (fresh memo)
    and lockstep-replays the same rows. Each query must be re-answered EXACTLY
    once, each with its own row, and the content-hash cache must absorb the
    replay so the device forward does not run a second time."""
    from pathway_tpu.engine.expression_evaluator import evaluate

    emb = _tiny_embedder(embed_cache_size=64)
    forwards = []
    orig = emb.encoder.encode_device
    emb.encoder.encode_device = lambda texts: (forwards.append(list(texts)), orig(texts))[1]

    texts = np.array(
        [f"inflight query {i}" for i in range(4)] + ["inflight query 0"], dtype=object
    )
    e = emb.device_expression(expr.ColumnReference(None, "q"))
    keys = np.empty(len(texts), dtype=KEY_DTYPE)
    for i in range(len(texts)):
        p = pointer_from(f"row{i}")
        keys[i] = (p.hi, p.lo)
    diffs = np.ones(len(texts), dtype=np.int64)

    def run_commit(memo: dict) -> np.ndarray:
        return evaluate(
            e,
            len(texts),
            lambda ref: texts,
            keys=keys,
            diffs=diffs,
            memo=memo,
            memo_tokens={id(e): "nd0"},
        )

    memo_before_fence: dict = {}
    first = run_commit(memo_before_fence)
    n_forward_rows_first = sum(len(b) for b in forwards)
    assert n_forward_rows_first == 4  # 5 rows, 1 duplicate text deduped

    # the query-path cache fill runs on the service's worker AFTER responders
    # are released (off the serving latency path); the fence quiesce
    # (PATHWAY_FENCE_TIMEOUT_S, default 180 s) dwarfs it in production — wait
    # for it here so the replay assertion is deterministic under suite load
    deadline = time.monotonic() + 30.0
    while len(emb.pipeline.cache) < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(emb.pipeline.cache) == 4

    # FENCE: commit aborted, evaluator state reset -> replay with a FRESH memo
    memo_after_fence: dict = {}
    replay = run_commit(memo_after_fence)

    # replayed exactly once: one more evaluation, same per-row values
    assert len(replay) == len(first) == len(texts)
    for i in range(len(texts)):
        assert np.array_equal(np.asarray(first[i]), np.asarray(replay[i])), i
    # ...and the replay was absorbed by the content cache: no new forward rows
    assert sum(len(b) for b in forwards) == n_forward_rows_first
    # the replayed commit rebuilt its memo so a post-fence retraction replays
    store = memo_after_fence["nd0"]
    assert len(store) == len(texts)
    ret_diffs = -np.ones(len(texts), dtype=np.int64)
    before = sum(len(b) for b in forwards)
    retr = evaluate(
        e,
        len(texts),
        lambda ref: texts,
        keys=keys,
        diffs=ret_diffs,
        memo=memo_after_fence,
        memo_tokens={id(e): "nd0"},
    )
    assert sum(len(b) for b in forwards) == before  # retraction: no encoder work
    for i in range(len(texts)):
        assert np.array_equal(np.asarray(retr[i]), np.asarray(replay[i]))
    assert len(store) == 0  # memo entries popped on retraction


# -- embedder dimension short-circuit ----------------------------------------


def test_api_embedder_dimension_short_circuit():
    from pathway_tpu.xpacks.llm.embedders import (
        GeminiEmbedder,
        LiteLLMEmbedder,
        OpenAIEmbedder,
    )

    # known models: no client library, no network, no asyncio.run
    assert OpenAIEmbedder(model="text-embedding-3-small").get_embedding_dimension() == 1536
    assert OpenAIEmbedder(model="text-embedding-3-large").get_embedding_dimension() == 3072
    assert (
        OpenAIEmbedder(model="text-embedding-3-large", dimensions=256).get_embedding_dimension()
        == 256
    )
    assert GeminiEmbedder(model="models/embedding-001").get_embedding_dimension() == 768
    assert (
        LiteLLMEmbedder(model="openai/text-embedding-3-small").get_embedding_dimension()
        == 1536
    )


def test_unknown_embedder_still_probes():
    from pathway_tpu.xpacks.llm.embedders import BaseEmbedder

    class Custom(BaseEmbedder):
        def __init__(self):
            super().__init__()
            self.calls = 0

            def embed(text: str) -> list:
                self.calls += 1
                return [0.0] * 5

            self.func = embed

    c = Custom()
    assert c.get_embedding_dimension() == 5
    assert c.calls == 1


def test_sentence_transformer_dimension_no_encode(tiny_encoder):
    emb = _tiny_embedder()
    forwards = []
    orig = emb.encoder.encode_device
    emb.encoder.encode_device = lambda t: (forwards.append(t), orig(t))[1]
    assert emb.get_embedding_dimension() == TINY.hidden_size
    assert forwards == []


# -- document store integration ----------------------------------------------


def test_document_store_serves_pipeline_stats():
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        BruteForceKnnFactory,
        BruteForceKnnMetricKind,
    )
    from pathway_tpu.xpacks.llm.document_store import DocumentStore

    from .utils import capture_rows

    emb = _tiny_embedder(embed_cache_size=32)
    factory = BruteForceKnnFactory(
        dimensions=TINY.hidden_size, metric=BruteForceKnnMetricKind.COS, embedder=emb
    )
    docs = pw.debug.table_from_rows(
        pw.schema_builder({"data": bytes, "_metadata": pw.Json}),
        [
            (b"cats sit on mats", pw.Json({"path": "/a.txt"})),
            (b"dogs chase balls", pw.Json({"path": "/b.txt"})),
        ],
    )
    store = DocumentStore(docs, retriever_factory=factory)
    stats_q = pw.debug.table_from_rows(pw.schema_builder({"dummy": int}), [(1,)])
    rows = capture_rows(store.statistics_query(stats_q))
    payload = rows[0]["result"].value
    assert payload["file_count"] == 2
    emb_stats = payload["embedder"]
    for key in ("cache_hits", "cache_misses", "svc_ticks", "pad_waste_ratio"):
        assert key in emb_stats


def test_document_store_retrieve_with_pipeline_cache():
    """End-to-end retrieve through the pipelined embedder: correct hit, and a
    repeated identical query answered out of the content-hash cache."""
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        BruteForceKnnFactory,
        BruteForceKnnMetricKind,
    )
    from pathway_tpu.xpacks.llm.document_store import DocumentStore

    from .utils import capture_rows

    emb = _tiny_embedder(embed_cache_size=32)
    factory = BruteForceKnnFactory(
        dimensions=TINY.hidden_size, metric=BruteForceKnnMetricKind.COS, embedder=emb
    )
    docs = pw.debug.table_from_rows(
        pw.schema_builder({"data": bytes, "_metadata": pw.Json}),
        [
            (b"the cat sits on the mat", pw.Json({"path": "/cats.txt"})),
            (b"dogs chase the ball in the park", pw.Json({"path": "/dogs.txt"})),
        ],
    )
    store = DocumentStore(docs, retriever_factory=factory)
    q_schema = pw.schema_builder(
        {"query": str, "k": int, "metadata_filter": str, "filepath_globpattern": str}
    )
    queries = pw.debug.table_from_rows(
        q_schema, [("the cat sits on the mat", 1, None, None)]
    )
    rows = capture_rows(store.retrieve_query(queries))
    docs_out = rows[0]["result"].value
    assert docs_out[0]["metadata"]["path"] == "/cats.txt"
    hits_before = emb.pipeline.cache.stats()["cache_hits"]
    from pathway_tpu.internals import parse_graph as pg

    pg.G.clear()  # fresh run; the embedder object (and its cache) persists
    queries2 = pw.debug.table_from_rows(
        q_schema, [("the cat sits on the mat", 1, None, None)]
    )
    # the document table was rebuilt in the new graph, so ingest re-runs too —
    # the cache must serve BOTH the re-ingested chunks and the repeated query
    docs2 = pw.debug.table_from_rows(
        pw.schema_builder({"data": bytes, "_metadata": pw.Json}),
        [
            (b"the cat sits on the mat", pw.Json({"path": "/cats.txt"})),
            (b"dogs chase the ball in the park", pw.Json({"path": "/dogs.txt"})),
        ],
    )
    store2 = DocumentStore(docs2, retriever_factory=factory)
    rows2 = capture_rows(store2.retrieve_query(queries2))
    assert rows2[0]["result"].value[0]["metadata"]["path"] == "/cats.txt"
    assert emb.pipeline.cache.stats()["cache_hits"] > hits_before


# -- telemetry stage counters -------------------------------------------------


def test_stage_counters_accumulate_and_reset():
    from pathway_tpu.engine import telemetry

    telemetry.stage_reset("testns.")
    telemetry.stage_add("testns.count", 2)
    telemetry.stage_add("testns.count", 3)
    with telemetry.stage_timer("testns.work"):
        pass
    snap = telemetry.stage_snapshot("testns.")
    assert snap["testns.count"] == 5
    assert snap["testns.work_calls"] == 1
    assert snap["testns.work_s"] >= 0
    telemetry.stage_reset("testns.")
    assert telemetry.stage_snapshot("testns.") == {}


@pytest.mark.slow
def test_pipeline_torture_many_threads(tiny_encoder):
    """Soak: 64 threads hammering cache+service with overlapping text sets;
    every response must match the direct encode."""
    pipe = EmbedPipeline(tiny_encoder, model="t", cache_size=256)
    texts = [f"torture {i % 40}" for i in range(400)]
    expected = {t: tiny_encoder.encode([t])[0] for t in set(texts)}
    errors = []

    def client(ti: int) -> None:
        t = texts[ti]
        row = np.asarray(pipe.embed_query_rows([t])[0], dtype=np.float32)
        if not np.array_equal(row, expected[t]):
            errors.append(ti)

    import concurrent.futures

    with concurrent.futures.ThreadPoolExecutor(64) as pool:
        list(pool.map(client, range(len(texts))))
    assert errors == []


# -- the embedder's constructor surface ---------------------------------------


@pytest.mark.parametrize(
    "removed",
    [
        {"max_wait_ms": 2.0},
        {"max_coalesce_batch": 256},
        {"encoder_service": True},
        {"sub_batch": 128},
        {"semantic_cache": "exact"},
        {"semantic_cache_size": 4096},
        {"semantic_threshold": 0.95},
        {"encsvc_tick_ms": 50.0},
        {"encsvc_max_in_flight": 256},
        {"encsvc_prewarm": False},
    ],
    ids=lambda kw: next(iter(kw)),
)
def test_removed_embedder_keyword_raises_type_error(removed):
    """The deadline path's settings and the pass-throughs nobody passed are
    gone from the embedder: asking for one fails at construction and is not
    swallowed by ``**kwargs``."""
    with pytest.raises(TypeError, match=next(iter(removed))):
        _tiny_embedder(**removed)


def test_default_embedder_exposes_what_the_benchmark_reads():
    """``benchmarks/systems/*`` and ``benchmarks/metrics/encsvc_*`` read these
    off a ``SentenceTransformerEmbedder`` built with ``encoder_config`` alone:
    the service (never None), its warm state, the tick and token counters in
    ``pipeline.stats()``, and the span kinds of the query-embedding path."""
    from pathway_tpu.engine.telemetry import TRACE_SPAN_KINDS
    from pathway_tpu.models.encoder_service import EncoderService

    emb = _tiny_embedder()
    svc = emb.pipeline.service
    assert isinstance(svc, EncoderService)
    assert svc.wait_warm(timeout_s=60.0)
    assert svc.prewarm_compiles == 0 and svc.prewarm_s == 0.0  # conftest: pre-warm off
    assert isinstance(svc._prewarm_shapes(), list)
    emb.pipeline.embed_query_rows(["what the benchmark reads"])
    stats = emb.pipeline.stats()
    for name in ("svc_ticks", "svc_rows", "svc_real_tokens", "svc_padded_tokens"):
        assert isinstance(stats[name], int) and stats[name] > 0, (name, stats[name])
    assert stats["svc_real_tokens"] <= stats["svc_padded_tokens"]
    assert {
        "embed_wait", "encode", "encode.dispatch", "tokenize", "encode.device_wait",
        "cache_fill",
    } <= TRACE_SPAN_KINDS
    assert "coalesce" not in TRACE_SPAN_KINDS
    svc.close()
