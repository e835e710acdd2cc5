"""Encoder service tests: continuous batching, pre-warmed jit buckets, the
semantic query cache's honesty contract (exact mode bitwise;
retraction/re-ingest isolation), the one admission point (row cap, probe,
typed shed, Retry-After, ``embed.shed``) reached directly and through
``EmbedPipeline``, and the fence-replay exactly-once extension for
service-queued in-flight queries. All tier-1 (CPU, tiny encoder config)."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from pathway_tpu.internals import expression as expr
from pathway_tpu.internals.keys import KEY_DTYPE, pointer_from
from pathway_tpu.models.embed_pipeline import EmbedOverloadError, EmbedPipeline
from pathway_tpu.models.encoder import EncoderConfig, JaxSentenceEncoder
from pathway_tpu.models.encoder_service import (
    EncoderService,
    SemanticQueryCache,
    stop_all_workers,
)

pytestmark = pytest.mark.encsvc

TINY = EncoderConfig(
    vocab_size=8192, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=128
)


@pytest.fixture(scope="module")
def tiny_encoder() -> JaxSentenceEncoder:
    return JaxSentenceEncoder("pw-test-tiny", config=TINY, max_length=64)


def _tiny_embedder(**kwargs):
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    return SentenceTransformerEmbedder(
        model="pw-test-tiny", encoder_config=TINY, **kwargs
    )


def _hash_rows(texts):
    out = []
    for t in texts:
        h = np.frombuffer(str(t).encode().ljust(8, b"\0")[:8], dtype=np.uint8)
        out.append(h.astype(np.float32))
    return out


class _HashEncoder:
    """Instant deterministic encoder: row value encodes the text identity."""

    dim = 8

    def __init__(self):
        self.calls = []

    def encode_device(self, texts):
        self.calls.append(list(texts))
        return np.stack(_hash_rows(texts))


class _GatedHashEncoder(_HashEncoder):
    """Holds its first forward until ``release`` is set, so a burst piles up
    behind tick 1."""

    def __init__(self):
        super().__init__()
        self.release = threading.Event()
        self._held = False

    def encode_device(self, texts):
        if not self._held:
            self._held = True
            self.release.wait(timeout=10)
        return super().encode_device(texts)


ENTRIES = ("submit", "embed_query_rows")


def _service_and_entry(encoder, entry, **kwargs):
    """The service and the call that reaches it: ``submit`` directly, or
    ``EmbedPipeline.embed_query_rows`` with the caches off, which is how a
    commit reaches it."""
    if entry == "submit":
        svc = EncoderService(encoder, prewarm=False, **kwargs)
        return svc, svc.submit
    pipe = EmbedPipeline(encoder, model=entry, cache_size=0, **kwargs)
    return pipe.service, pipe.embed_query_rows


def _wait_depth(svc, rows, what):
    deadline = time.monotonic() + 5.0
    while svc.queue_depth_rows() != rows:
        assert time.monotonic() < deadline, what
        time.sleep(0.01)


# ---------------------------------------------------------------------------
# SemanticQueryCache
# ---------------------------------------------------------------------------


def test_semantic_cache_exact_mode_normalized_key():
    cache = SemanticQueryCache(8, mode="exact")
    vec = np.arange(4, dtype=np.float32)
    cache.put("what is rag?", vec)
    # whitespace runs and case fold onto the same canonical key
    hit = cache.get("  What   is  RAG? ")
    assert hit is not None and np.array_equal(hit, vec)
    assert not hit.flags.writeable
    assert cache.get("what is ivf?") is None
    s = cache.stats()
    assert s["semantic_exact_hits"] == 1 and s["semantic_misses"] == 1
    assert s["semantic_cosine_hits"] == 0  # exact mode never fuzzy-matches


def test_semantic_cache_lru_eviction_and_off_mode():
    cache = SemanticQueryCache(2, mode="exact")
    v = np.ones(2, dtype=np.float32)
    cache.put("a", v)
    cache.put("b", v * 2)
    cache.put("c", v * 3)  # evicts "a"
    assert cache.get("a") is None
    assert np.array_equal(cache.get("c"), v * 3)
    assert cache.stats()["semantic_evictions"] == 1
    off = SemanticQueryCache(8, mode="off")
    off.put("a", v)
    assert off.get("a") is None and len(off) == 0


def test_semantic_cache_cosine_mode_near_match():
    cache = SemanticQueryCache(8, mode="cosine", threshold=0.8)
    vec = np.arange(4, dtype=np.float32)
    cache.put("how do i restart a crashed worker rank", vec)
    # near-duplicate phrasing: high bag-of-words cosine, different exact key
    hit = cache.get("how do i restart a crashed worker")
    assert hit is not None and np.array_equal(hit, vec)
    assert cache.stats()["semantic_cosine_hits"] == 1
    # unrelated text stays a miss even in cosine mode
    assert cache.get("tumbling window aggregation semantics") is None


def test_semantic_cache_cosine_threshold_respected():
    strict = SemanticQueryCache(8, mode="cosine", threshold=0.999)
    strict.put("alpha beta gamma delta", np.ones(2, dtype=np.float32))
    assert strict.get("alpha beta gamma epsilon") is None  # below threshold
    assert strict.get("alpha  BETA gamma delta") is not None  # exact canonical key


# ---------------------------------------------------------------------------
# EncoderService: continuous batching
# ---------------------------------------------------------------------------


def test_service_solo_submit_no_deadline_wait():
    """A solo request dispatches the moment the worker is free — well under
    any deadline-window latency (the legacy path waited max_wait_ms)."""
    enc = _HashEncoder()
    svc = EncoderService(enc, tick_ms=5_000.0, prewarm=False)  # absurd tick
    t0 = time.perf_counter()
    out = svc.submit(["solo"])
    elapsed = time.perf_counter() - t0
    assert np.array_equal(out[0], _hash_rows(["solo"])[0])
    assert elapsed < 2.0, f"solo submit waited for a window: {elapsed:.3f}s"
    svc.close()


@pytest.mark.parametrize("entry", ENTRIES)
def test_service_concurrent_clients_coalesce_and_get_own_rows(entry):
    enc = _GatedHashEncoder()
    svc, embed = _service_and_entry(enc, entry)
    results: dict = {}

    def client(i: int) -> None:
        results[i] = embed([f"query {i}"])[0]

    threads = [threading.Thread(target=client, args=(i,)) for i in range(16)]
    threads[0].start()
    time.sleep(0.2)  # worker now held inside tick 1
    for t in threads[1:]:
        t.start()
    _wait_depth(svc, 16, "the burst never queued")
    enc.release.set()
    for t in threads:
        t.join(timeout=10)
    for i in range(16):  # every client got exactly ITS row
        assert np.array_equal(results[i], _hash_rows([f"query {i}"])[0]), i
    assert svc.ticks < svc.requests  # the pile-up coalesced into fewer ticks
    assert svc.max_tick_rows > 1
    assert svc.total_rows == 16
    assert sum(len(b) for b in enc.calls) + svc.dedup_rows == 16
    assert svc.queue_depth_rows() == 0  # slots always released
    svc.close()


@pytest.mark.parametrize("entry", ENTRIES)
def test_service_dedups_identical_texts_within_tick(entry):
    enc = _GatedHashEncoder()
    svc, embed = _service_and_entry(enc, entry)
    out: list = [None] * 8

    def client(i: int) -> None:
        out[i] = embed(["same question"])[0]

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    threads[0].start()
    time.sleep(0.2)
    for t in threads[1:]:
        t.start()
    _wait_depth(svc, 8, "the burst never queued")
    enc.release.set()
    for t in threads:
        t.join(timeout=10)
    expect = _hash_rows(["same question"])[0]
    assert all(np.array_equal(v, expect) for v in out)
    # the duplicate text encoded once per tick, not once per client
    assert sum(len(b) for b in enc.calls) == svc.ticks
    assert svc.dedup_rows == 8 - svc.ticks
    svc.close()


@pytest.mark.parametrize(
    "exc_type,waiters", [(RuntimeError, 1), (ValueError, 1), (RuntimeError, 3)]
)
def test_service_error_propagates_and_releases_slots(exc_type, waiters):
    """A failing tick hands its exception, typed, to every waiter it took."""

    class _FailingEncoder:
        dim = 4

        def encode_device(self, texts):
            raise exc_type("encoder exploded")

    svc = EncoderService(_FailingEncoder(), prewarm=False)
    errors = []

    def client(i: int) -> None:
        try:
            svc.submit([f"q{i}"])
        except exc_type as exc:
            errors.append(str(exc))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(waiters)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert errors == ["encoder exploded"] * waiters
    assert svc.queue_depth_rows() == 0  # the leak_inflight invariant, live
    # the worker survives a failing tick
    svc.encoder = _HashEncoder()
    assert np.array_equal(svc.submit(["later"])[0], _hash_rows(["later"])[0])
    svc.close()


def test_service_large_tick_splits_length_sorted():
    enc = _HashEncoder()
    svc = EncoderService(enc, sub_batch=4, prewarm=False)
    texts = [f"{'w ' * (i % 7 + 1)}q{i}" for i in range(10)]
    out = svc.submit(texts)
    for i, t in enumerate(texts):
        assert np.array_equal(out[i], _hash_rows([t])[0]), i
    # one submission of 10 rows with sub_batch=4 → 3 length-sorted dispatches
    assert len(enc.calls) == 3
    assert sorted(len(b) for b in enc.calls) == [2, 4, 4]
    lengths = [len(t.split()) for b in enc.calls for t in b]
    assert lengths == sorted(lengths), "packing was not length-sorted"
    svc.close()


# ---------------------------------------------------------------------------
# pre-warm: startup honesty
# ---------------------------------------------------------------------------


def test_prewarm_compiles_buckets_and_reports_wall_time(tiny_encoder):
    from pathway_tpu.engine import telemetry

    before = telemetry.stage_snapshot("embed.svc.").get("embed.svc.prewarm_s", 0.0)
    svc = EncoderService(
        tiny_encoder, prewarm=True, prewarm_max_batch=8, max_in_flight=8
    )
    assert svc.wait_warm(timeout_s=120.0), "pre-warm never finished"
    # batch bucket {8} x seq buckets {8,16,32,64} for max_length=64
    assert svc.prewarm_compiles == 4
    assert svc.prewarm_s > 0.0
    snap = telemetry.stage_snapshot("embed.svc.")
    assert snap.get("embed.svc.prewarm_s", 0.0) > before
    assert snap.get("embed.svc.prewarm_compiles", 0.0) >= 4
    stats = svc.stats()
    assert stats["svc_warm"] and stats["svc_prewarm_compiles"] == 4
    # warm path still answers correctly
    row = np.asarray(svc.submit(["warm bucket query"])[0], dtype=np.float32)
    assert np.array_equal(row, tiny_encoder.encode(["warm bucket query"])[0])
    svc.close()


def test_stop_worker_aborts_prewarm_even_without_worker(tiny_encoder):
    """pw.run teardown (stop_all_workers) must cancel an in-flight pre-warm
    compile matrix even when no query ever spawned a worker — the abort rides
    its own event, not the worker's _stop_requested flag."""
    svc = EncoderService(
        tiny_encoder, prewarm=True, prewarm_max_batch=256, max_in_flight=256
    )
    svc.stop_worker()
    pt = svc._prewarm_thread
    assert pt is None or not pt.is_alive(), "pre-warm thread survived stop_worker"
    assert svc._prewarm_abort.is_set()
    assert svc.warm  # nobody blocks on wait_warm after an abort
    svc.close()


def test_prewarm_skipped_for_non_jax_encoders():
    svc = EncoderService(_HashEncoder(), prewarm=True)
    assert svc.warm  # nothing to compile: warm immediately, no thread spun
    assert svc.prewarm_compiles == 0
    svc.close()


# ---------------------------------------------------------------------------
# pipeline integration: semantic cache honesty
# ---------------------------------------------------------------------------


def _wait_cache_fill(pipe: EmbedPipeline, n: int, timeout: float = 10.0) -> None:
    deadline = time.monotonic() + timeout
    while len(pipe.cache) < n and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(pipe.cache) >= n, "after-batch cache fill never ran"


def test_exact_mode_hit_is_bitwise_identical_to_direct_encode(tiny_encoder):
    pipe = EmbedPipeline(tiny_encoder, model="t", cache_size=64)
    pipe.embed_query_rows(["What is a Vector  Index?"])
    _wait_cache_fill(pipe, 1)
    variant = "  what IS a vector index?  "
    row = pipe.embed_query_rows([variant])[0]
    assert pipe.semantic_cache.stats()["semantic_exact_hits"] == 1
    direct = tiny_encoder.encode([variant])[0]
    assert np.array_equal(np.asarray(row, dtype=np.float32), direct), (
        "exact-mode semantic hit is not bitwise-identical to a fresh encode"
    )
    stop_all_workers()


def test_semantic_hit_skips_the_forward_entirely(tiny_encoder):
    pipe = EmbedPipeline(tiny_encoder, model="t2", cache_size=64)
    calls = []
    orig = tiny_encoder.encode_device
    tiny_encoder.encode_device = lambda t: (calls.append(list(t)), orig(t))[1]
    try:
        pipe.embed_query_rows(["semantic skip test"])
        _wait_cache_fill(pipe, 1)
        n_before = sum(len(b) for b in calls)
        pipe.embed_query_rows(["  SEMANTIC   skip   test "])
        assert sum(len(b) for b in calls) == n_before  # no new forward rows
    finally:
        tiny_encoder.encode_device = orig
    stop_all_workers()


def test_cosine_mode_is_opt_in_and_off_by_default(tiny_encoder):
    pipe = EmbedPipeline(tiny_encoder, model="t3", cache_size=64)
    assert pipe.semantic_cache.mode == "exact"
    pipe2 = EmbedPipeline(
        tiny_encoder, model="t4", cache_size=64,
        semantic_mode="cosine", semantic_threshold=0.8,
    )
    assert pipe2.semantic_cache.mode == "cosine"
    stop_all_workers()


def test_reingest_never_served_from_semantic_cache(tiny_encoder):
    """The ingest path (encode_batch) must not consult the semantic cache: a
    poisoned semantic entry for the same canonical text must never leak into
    document embeddings on re-ingest."""
    pipe = EmbedPipeline(tiny_encoder, model="t5", cache_size=64)
    text = "document chunk about cats"
    truth = pipe.encode_batch([text])[0]
    # plant a poisoned semantic entry under the same canonical key
    pipe.semantic_cache.put(text, np.full(TINY.hidden_size, 777.0, dtype=np.float32))
    pipe.cache.clear()  # force the content cache to miss on re-ingest
    again = pipe.encode_batch(["  DOCUMENT chunk about cats  "])[0]
    assert not np.array_equal(again, np.full(TINY.hidden_size, 777.0)), (
        "re-ingest was served from the semantic query cache"
    )
    reingest = pipe.encode_batch([text])[0]
    assert np.array_equal(reingest, truth)
    stop_all_workers()


def test_retractions_never_reach_semantic_cache():
    """device_expression is deterministic=False: retraction rows replay from
    the engine memo — neither the service, the content cache, nor the semantic
    cache may see them (a semantic near-match answering a retraction would
    break the bit-identical replay contract)."""
    import pathway_tpu as pw
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.internals import parse_graph as pg

    emb = _tiny_embedder(embed_cache_size=64)
    forwards = []
    orig = emb.encoder.encode_device
    emb.encoder.encode_device = lambda t: (forwards.append(list(t)), orig(t))[1]

    sem_gets = []
    orig_get = emb.pipeline.semantic_cache.get
    emb.pipeline.semantic_cache.get = lambda t: (sem_gets.append(t), orig_get(t))[1]

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
    # the two inserts consulted the caches; the retraction consulted NOTHING
    # (replayed from the evaluator memo): 2 lookups, 2 forward rows, no more
    assert len(sem_gets) == 2
    assert sum(len(b) for b in forwards) == 2
    ret = [np.asarray(v) for v, d in got if d == -1]
    ins = [np.asarray(v) for v, d in got if d == 1]
    assert len(ret) == 1 and any(np.array_equal(ret[0], v) for v in ins)


# ---------------------------------------------------------------------------
# the one admission point: row cap, probe, typed shed, Retry-After
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("entry", ENTRIES)
def test_service_sheds_past_the_row_cap_with_honest_retry_after(entry):
    """Past ``max_queue_rows`` pending rows (waiting + in flight) ``submit``
    sheds a direct caller with a typed EmbedOverloadError carrying an honest
    Retry-After and counts ``embed.shed``; the REST probe says the same; the
    engine path (admitted at the REST boundary) is never refused; and
    admission opens again once the queue drains. Same contract whether the
    service was built bare or by ``EmbedPipeline`` with its cap."""
    from pathway_tpu.engine import telemetry

    enc = _GatedHashEncoder()
    svc, embed = _service_and_entry(enc, entry, max_queue_rows=3)
    done: dict = {}

    def client(name, texts):
        done[name] = svc.submit(texts)

    # a: taken by the worker and held inside encode_device (in flight)
    ta = threading.Thread(target=client, args=("a", ["a"]))
    ta.start()
    _wait_depth(svc, 1, "worker never picked up row a")
    # b: fills the cap exactly (two rows waiting behind the held tick)
    tb = threading.Thread(target=client, args=("b", ["b1", "b2"]))
    tb.start()
    _wait_depth(svc, 3, "rows b never queued")

    assert svc.overloaded()
    shed_before = telemetry.stage_snapshot("embed.").get("embed.shed", 0.0)
    with pytest.raises(EmbedOverloadError) as exc_info:
        svc.submit(["c"])
    assert exc_info.value.retry_after_s >= 1.0
    assert svc.shed_requests == 1
    assert telemetry.stage_snapshot("embed.").get("embed.shed", 0.0) == shed_before + 1
    # the engine path (already admitted at the REST boundary) is not refused
    td = threading.Thread(
        target=lambda: done.update(
            d=embed(["d"]) if entry == "embed_query_rows"
            else svc.submit(["d"], enforce_cap=False)
        )
    )
    td.start()
    _wait_depth(svc, 4, "row d was not admitted past the cap")
    enc.release.set()
    for t in (ta, tb, td):
        t.join(timeout=10)
    assert np.array_equal(done["a"][0], _hash_rows(["a"])[0])
    assert np.array_equal(done["b"][1], _hash_rows(["b2"])[0])
    assert np.array_equal(done["d"][0], _hash_rows(["d"])[0])
    # the queue drained: admission opens again, no sticky overload state
    assert not svc.overloaded()
    assert np.array_equal(svc.submit(["e"])[0], _hash_rows(["e"])[0])
    assert svc.shed_requests == 1
    svc.close()


def test_service_retry_after_scales_with_queue_depth():
    """Retry-After must be an estimate, not a constant: a deeper queue names a
    later retry (ticks-to-drain x smoothed tick time, floored at 1 s)."""
    svc = EncoderService(_HashEncoder(), max_in_flight=2, prewarm=False)
    svc._encode_ewma_s = 2.0  # pretend the encoder runs 2 s ticks
    shallow = svc.retry_after_s(extra_rows=2)    # 1 tick to drain
    deep = svc.retry_after_s(extra_rows=20)      # 10 ticks to drain
    assert shallow >= 1.0
    assert deep > shallow * 5
    svc.close()


def test_service_overload_probe_and_engine_path_bypass():
    """``overloaded`` is the REST pre-admission probe for the row cap;
    ``submit(enforce_cap=False)`` (the engine serving path — its request was
    already admitted against the cap at the REST boundary) never raises even
    past the cap, so a race between admission and the commit cannot tear the
    run down."""
    svc = EncoderService(_HashEncoder(), max_queue_rows=2, prewarm=False)
    assert not svc.overloaded()
    svc._queued_rows = 2  # simulate a full queue without racing the worker
    assert svc.overloaded()
    assert svc.overloaded(extra_rows=1)
    svc._queued_rows = 0
    assert not svc.overloaded()
    assert svc.overloaded(extra_rows=2)
    svc._queued_rows = 5  # past the cap: enforce_cap=False must still admit
    got = svc.submit(["x", "y", "z"], enforce_cap=False)
    assert np.array_equal(got[2], _hash_rows(["z"])[0])
    assert svc.shed_requests == 0
    svc.close()

    unbounded = EncoderService(_HashEncoder(), prewarm=False)
    assert not unbounded.overloaded(extra_rows=10**9)  # cap 0 = disabled
    unbounded.close()


def test_shed_is_counted_once_at_the_one_admission_point():
    """``embed.shed`` and ``stats()["svc_shed_requests"]`` move together: the
    probe counts nothing, a refused ``submit`` counts once in both."""
    from pathway_tpu.engine import telemetry

    svc = EncoderService(_HashEncoder(), max_queue_rows=2, prewarm=False)
    svc._queued_rows = 2  # a full queue, without racing the worker
    before = telemetry.stage_snapshot("embed.").get("embed.shed", 0.0)
    for n in range(1, 4):
        assert svc.overloaded()
        with pytest.raises(EmbedOverloadError):
            svc.submit([f"refused {n}"])
        counted = telemetry.stage_snapshot("embed.").get("embed.shed", 0.0) - before
        assert counted == n == svc.stats()["svc_shed_requests"]
    svc._queued_rows = 0
    svc.close()


def test_overloaded_feeds_the_brownout_ladder_from_the_service_depth():
    """Each probe hands the ladder one occupancy sample, pending rows over
    the cap: a service at 90 % engages rung 2 with no other signal."""
    from pathway_tpu.engine.brownout import get_brownout

    svc = EncoderService(_HashEncoder(), max_queue_rows=10, prewarm=False)
    assert get_brownout().level() == 0
    svc._queued_rows, svc._inflight_rows = 2, 1  # 30 %
    assert not svc.overloaded()
    assert get_brownout().level() == 0
    svc._queued_rows, svc._inflight_rows = 5, 4  # 90 %: waiting + in flight
    assert not svc.overloaded()
    assert get_brownout().level() == 2
    assert svc.overloaded(extra_rows=1)
    svc._queued_rows = svc._inflight_rows = 0
    svc.close()

    unbounded = EncoderService(_HashEncoder(), prewarm=False)
    unbounded._queued_rows = 10**6
    assert not unbounded.overloaded()  # no cap: no occupancy to report
    unbounded._queued_rows = 0
    unbounded.close()


def test_embed_pipeline_wires_queue_cap_from_env(monkeypatch, tiny_encoder):
    """EmbedPipeline hands PATHWAY_EMBED_MAX_QUEUE_ROWS to its service, and
    an explicit argument wins over the env."""
    monkeypatch.setenv("PATHWAY_EMBED_MAX_QUEUE_ROWS", "17")
    pipe = EmbedPipeline(tiny_encoder, model="t")
    assert pipe.service.max_queue_rows == 17
    pipe.service.close()
    pipe2 = EmbedPipeline(tiny_encoder, model="t", max_queue_rows=0)
    assert pipe2.service.max_queue_rows == 0
    pipe2.service.close()


# ---------------------------------------------------------------------------
# fence replay: service-queued in-flight queries, exactly once
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_fence_replay_service_inflight_queries_exactly_once():
    """The PR-3 replay contract extended to the encoder service: a fence
    aborts the commit AFTER service-queued queries were encoded but before
    results committed; the replay with a fresh memo must answer every query
    exactly once with identical values, absorbed by the content cache — the
    service's forward must not run a second time, and the semantic cache must
    not have answered any retraction."""
    from pathway_tpu.engine.expression_evaluator import evaluate

    emb = _tiny_embedder(embed_cache_size=64)
    forwards = []
    orig = emb.encoder.encode_device
    emb.encoder.encode_device = lambda t: (forwards.append(list(t)), orig(t))[1]

    texts = np.array(
        [f"inflight svc query {i}" for i in range(4)] + ["inflight svc query 0"],
        dtype=object,
    )
    e = emb.device_expression(expr.ColumnReference(None, "q"))
    keys = np.empty(len(texts), dtype=KEY_DTYPE)
    for i in range(len(texts)):
        p = pointer_from(f"row{i}")
        keys[i] = (p.hi, p.lo)

    def run_commit(memo: dict, diffs: np.ndarray) -> np.ndarray:
        return evaluate(
            e,
            len(texts),
            lambda ref: texts,
            keys=keys,
            diffs=diffs,
            memo=memo,
            memo_tokens={id(e): "nd0"},
        )

    ins = np.ones(len(texts), dtype=np.int64)
    first = run_commit({}, ins)
    n_rows_first = sum(len(b) for b in forwards)
    assert n_rows_first == 4  # 5 rows, 1 duplicate deduped in the tick
    assert emb.pipeline.service.ticks >= 1

    _wait_cache_fill(emb.pipeline, 4, timeout=30.0)

    # FENCE: evaluator state reset → lockstep replay with a FRESH memo
    memo_after: dict = {}
    replay = run_commit(memo_after, ins)
    assert len(replay) == len(first) == len(texts)
    for i in range(len(texts)):
        assert np.array_equal(np.asarray(first[i]), np.asarray(replay[i])), i
    # absorbed by the content cache: the service ran no new forward rows
    assert sum(len(b) for b in forwards) == n_rows_first

    # post-fence retraction: engine memo replay, no cache/service involvement
    sem_before = emb.pipeline.semantic_cache.stats()
    retr = run_commit(memo_after, -np.ones(len(texts), dtype=np.int64))
    assert sum(len(b) for b in forwards) == n_rows_first
    sem_after = emb.pipeline.semantic_cache.stats()
    assert sem_after["semantic_exact_hits"] == sem_before["semantic_exact_hits"]
    assert sem_after["semantic_cosine_hits"] == sem_before["semantic_cosine_hits"]
    for i in range(len(texts)):
        assert np.array_equal(np.asarray(retr[i]), np.asarray(replay[i]))
    assert len(memo_after["nd0"]) == 0  # memo entries popped on retraction
