"""The query path between the encoder tick and the search: a query's embedding
crosses as a row of ONE host array per tick, padding and slicing happen in
numpy, and the only device programs are the encoder forward and the search
kernel, each keyed by its padded bucket and never by the batch size.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.models.embed_pipeline import EmbedPipeline
from pathway_tpu.models.encoder import EncoderConfig, JaxSentenceEncoder, fetch_rows
from pathway_tpu.ops.knn import BruteForceKnnIndex, kernel_cache_sizes

DIM, DOCS, K = 32, 200, 5
COMPILE_EVENTS = "/jax/core/compile/"  # trace, lowering, backend compile


class _Path:
    """A tiny encoder behind the pipeline's service, and a dense cosine index
    over a random corpus (random directions: the top-k gaps are wide)."""

    def __init__(self) -> None:
        self.encoder = JaxSentenceEncoder(config=EncoderConfig(
            vocab_size=30522, hidden_size=DIM, num_layers=1, num_heads=2,
            intermediate_size=64))
        self.pipe = EmbedPipeline(self.encoder, model="host-rows")
        corpus = np.random.default_rng(7).normal(size=(DOCS, DIM)).astype(np.float32)
        self.corpus = corpus / np.linalg.norm(corpus, axis=1, keepdims=True)
        self.index = BruteForceKnnIndex(DIM, metric="cos", initial_capacity=256)
        self.index.add_many(list(range(DOCS)), list(self.corpus))
        self.sent = 0

    def texts(self, count: int) -> list:
        """``count`` unique four-word texts: one sequence bucket, no cache hit."""
        self.sent += count
        return [f"query about subject number{self.sent - j}" for j in range(count)]

    def drive(self, size: int) -> None:
        rows = self.pipe.embed_query_rows(self.texts(size))
        got = self.index.search_many(rows, [K] * size, None)
        assert len(got) == size and all(len(g) == K for g in got)

    def wait_cache_fill(self) -> None:
        """The tick fills the caches after its waiters left, on its own thread."""
        deadline = time.monotonic() + 10.0
        while len(self.pipe.cache) < self.sent and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(self.pipe.cache) == self.sent


@pytest.fixture(scope="module")
def path():
    p = _Path()
    for bucket in (8, 16, 32, 64):  # one warm call per bucket
        p.drive(bucket)
    p.wait_cache_fill()
    yield p
    p.pipe.service.close()


@pytest.mark.parametrize(
    "sizes", [range(1, 2), range(2, 9), range(9, 17), range(17, 33), range(33, 41)],
    ids=["1", "2-8", "9-16", "17-32", "33-40"],
)
def test_query_path_compiles_nothing_per_batch_size(path, sizes):
    """After one warm call per bucket, no batch size compiles anything: not on
    the commit's thread (the stack, the pad, the result slices) and not on the
    service's (the forward's slice, per-row slices, the cache fill's stack)."""
    me = threading.get_ident()
    compiled = []

    def listener(event: str, _seconds: float, **_kw) -> None:
        here = threading.current_thread()
        if event.startswith(COMPILE_EVENTS) and (
            here.ident == me or here.name == "pathway:encsvc-worker"
        ):
            compiled.append((event[len(COMPILE_EVENTS):], here.name))

    kernels = kernel_cache_sizes()
    forwards = path.encoder._encode_ids._cache_size()
    ticks = path.pipe.stats()["svc_ticks"]
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        for size in sizes:
            path.drive(size)
        path.wait_cache_fill()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert path.pipe.stats()["svc_ticks"] == ticks + len(sizes)  # the service ran them
    assert compiled == []
    assert kernel_cache_sizes() == kernels
    assert path.encoder._encode_ids._cache_size() == forwards


def test_host_rows_answer_as_a_device_batch_and_as_plain_numpy(path):
    """One corpus, one batch of 3 (bucket 8): the tick's host rows are the
    float32 casts of the forward's float16 rows; ``search_many`` on them
    returns a plain numpy cosine top-k's keys and, bit for bit, what the kept
    device branch returns for a ``jax.Array`` batch of the same embeddings."""
    texts = path.texts(3)
    forward = np.asarray(path.encoder.encode_device(texts))
    assert forward.shape == (8, DIM) and forward.dtype == np.float16  # padded, as dispatched
    want = forward[:3].astype(np.float32)

    rows = path.pipe.embed_query_rows(texts)
    assert len(rows) == 3  # no padding row is handed out
    for row, emb in zip(rows, want):
        assert isinstance(row, np.ndarray) and row.dtype == np.float32
        assert not row.flags.writeable  # shared with the caches
        np.testing.assert_array_equal(row, emb)

    got = path.index.search_many(rows, [K] * 3, None)
    assert len(got) == 3  # no padding row is answered
    cos = (want / np.linalg.norm(want, axis=1, keepdims=True)) @ path.corpus.T
    for qi, result in enumerate(got):
        order = np.argsort(-cos[qi])[:K]
        assert [key for key, _ in result] == list(order)
        # float32 operands on the CPU: the kernel's scores are the plain ones
        # up to float32 rounding (the bf16 passes of a chip's default
        # precision are held by the benchmark's own limits)
        np.testing.assert_allclose([s for _, s in result], cos[qi, order], atol=1e-5)

    device_batch = jnp.asarray(forward[:3])  # float16, on the device
    assert path.index.search_many(device_batch, [K] * 3, None) == got
    # the store alone: a host batch and a device batch, padded each its way
    host = path.index.store.search_batch(want, K)
    dev = path.index.store.search_batch(jnp.asarray(want), K)
    for a, b in zip(host, dev):
        assert a.shape == (3, K)
        np.testing.assert_array_equal(a, b)


def test_a_tick_of_two_submissions_hands_each_waiter_its_own_rows(path, monkeypatch):
    svc = path.pipe.service
    first, second, held = path.texts(2), path.texts(1), path.texts(1)
    solo = {t: fetch_rows(path.encoder.encode_device([t]), 1)[0] for t in first + second}
    release = threading.Event()
    forward = path.encoder.encode_device

    def gated(texts):
        if texts == held:
            release.wait(timeout=10)  # hold one tick so that two submissions pile up
        return forward(texts)

    monkeypatch.setattr(path.encoder, "encode_device", gated)
    got: dict = {}
    threads = [threading.Thread(target=lambda t=t: got.__setitem__(tuple(t), svc.submit(t)))
               for t in (held, first, second)]
    ticks = svc.ticks
    threads[0].start()
    deadline = time.monotonic() + 5.0
    while svc._inflight_rows < 1 and time.monotonic() < deadline:
        time.sleep(0.005)
    for t in threads[1:]:
        t.start()
    while svc.queue_depth_rows() < 4 and time.monotonic() < deadline:
        time.sleep(0.005)
    release.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert svc.ticks == ticks + 2  # the held one, then both submissions in one
    for texts in (first, second):
        rows = got[tuple(texts)]
        assert len(rows) == len(texts)
        for text, row in zip(texts, rows):
            # its own text's embedding (float16 steps; other texts are far off)
            np.testing.assert_allclose(row, solo[text], atol=2e-3)
            others = [v for t, v in solo.items() if t != text]
            assert all(np.abs(row - v).max() > 2e-2 for v in others)
    # views of ONE host array for the tick
    bases = {id(row.base) for texts in (first, second) for row in got[tuple(texts)]}
    assert len(bases) == 1
