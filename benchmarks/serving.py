"""Set-up of the system under test: ``VectorStoreServer`` in a thread of this process.

Copied from ``chip_smoke.py:serve_phase`` (proven on the chip in PR 21), not
imported: the server is built through its normal constructor, the index through
its normal factory, and the engine-built index instance is kept through the
factory hook. What the benchmark adds is the run's inputs: the encoder's weights
from ``--seed`` (set on the program's encoder before anything is embedded), the
live documents as the doc table, and the resident rows installed through the
index's own bulk-install contract (``install_descriptor_rows``) before the live
documents arrive. A share of the resident rows are neighbours of the live
documents (of the plain reference's embeddings of them, made here in set-up),
so that a reply's top-k holds resident rows too.
"""

from __future__ import annotations

import asyncio
import json
import time
import urllib.request
from typing import Any, Callable, Dict, List

import numpy as np

import loadgen
import reference
import weights as weights_mod

READY_DEADLINE_S = 1100.0  # a cold first run compiles 20 encoder buckets


def index_factory(cfg: Dict[str, Any], embedder: Any) -> Any:
    """The program's index factory the configuration names, with its arguments."""
    from pathway_tpu.stdlib.indexing import nearest_neighbors as nn

    spec = cfg["index"]
    args = dict(spec["args"])
    args["metric"] = nn.BruteForceKnnMetricKind[args["metric"]]
    return getattr(nn, spec["factory"])(embedder=embedder, **args)


def post(port: int, route: str, payload: dict, timeout: float = 60.0) -> Any:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class System:
    """The running server and the handles the harness reads: the embedder (its
    counters), the index instance the engine built, and the run's inputs."""

    def __init__(self, cfg: Dict[str, Any], seed: int, port: int, docs: List[str],
                 log: Callable[[str], None]):
        import jax

        import pathway_tpu as pw
        from pathway_tpu.internals import parse_graph as pg
        from pathway_tpu.models.encoder import EncoderConfig
        from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
        from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

        self.cfg, self.seed, self.port, self.docs, self.log = cfg, seed, port, docs, log
        self.timings: Dict[str, float] = {}
        model = cfg["model"]
        t0 = time.monotonic()
        pg.G.clear()
        published = EncoderConfig()
        enc_cfg = EncoderConfig(
            vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
            num_layers=model["num_hidden_layers"], num_heads=model["num_attention_heads"],
            intermediate_size=model["intermediate_size"],
            max_position=model["max_position_embeddings"],
            type_vocab_size=model["type_vocab_size"], layer_norm_eps=model["layer_norm_eps"],
        )
        # the published widths are the program's own default: pass none then
        self.embedder = SentenceTransformerEmbedder(
            encoder_config=None if enc_cfg == published else enc_cfg
        )
        enc = self.embedder.encoder
        assert enc.weights_source == "random-init" and enc.tokenizer_source == "hash", (
            enc.weights_source, enc.tokenizer_source)
        assert enc.max_length == model["max_length"], enc.max_length
        log(f"encoder layers={enc.config.num_layers} hidden={enc.config.hidden_size} "
            f"heads={enc.config.num_heads} ffn={enc.config.intermediate_size} "
            f"vocab={enc.config.vocab_size}; weights: {enc.weights_source} (replaced by the "
            f"run's seeded ones); tokenizer: {enc.tokenizer_source}")
        self.timings["embedder_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        self.weights, tree = weights_mod.make_weights(seed, model, cfg["assumed"]["weights_init"])
        same = jax.tree.map(lambda a, b: a.shape == b.shape and a.dtype == b.dtype, tree, enc.params)
        assert all(jax.tree.leaves(same)), "seeded weights differ from the program's tree"
        enc.params = tree
        self.timings["weights_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        # the plain reference's embeddings of the live documents: the resident
        # rows' neighbours are drawn around them, and the comparison reads them again
        self.doc_vecs = reference.embed_texts(self.weights, docs, model)
        self.doc_vecs.block_until_ready()
        self.timings["reference_docs_s"] = time.monotonic() - t0

        doc_table = pw.debug.table_from_rows(
            pw.schema_builder({"data": str, "_metadata": str}),
            [(text, json.dumps({"path": f"doc{i}"})) for i, text in enumerate(docs)],
        )
        self.server = VectorStoreServer(
            doc_table, embedder=self.embedder, index_factory=index_factory(cfg, self.embedder)
        )
        inner = self.server.index.inner_index
        make_index = inner.make_instance_factory()
        self.built: List[Any] = []

        def make_and_fill() -> Any:
            index = make_index()
            self._install_resident(index)
            self.built.append(index)
            return index

        inner.make_instance_factory = lambda: make_and_fill
        self.thread = self.server.run_server(host="127.0.0.1", port=port, threaded=True)

    def _install_resident(self, index: Any) -> None:
        """``resident_rows`` unit vectors from the seed (``weights.resident_block``),
        drawn on the device block by block, fetched and handed to the index's
        bulk install; each block is flushed to the device before the next is staged."""
        import jax

        corpus = self.cfg["corpus"]
        n, block = int(corpus["resident_rows"]), int(corpus["install_block_rows"])
        t0 = time.monotonic()
        for b, lo in enumerate(range(0, n, block)):
            rows = np.asarray(self.resident_block(b))[: n - lo]
            # resident keys are plain numbers past the live documents': never a Pointer
            index.install_descriptor_rows(range(len(self.docs) + lo, len(self.docs) + lo + len(rows)), rows)
            index.store._flush()
        stats = jax.devices()[0].memory_stats() or {}
        self.timings["resident_install_s"] = time.monotonic() - t0
        self.log(f"resident rows installed: {n} in blocks of {block} "
                 f"(set-up: {self.timings['resident_install_s']:.1f} s); device bytes_in_use="
                 f"{stats.get('bytes_in_use')} peak_bytes_in_use={stats.get('peak_bytes_in_use')}")

    def resident_block(self, b: int) -> Any:
        corpus = self.cfg["corpus"]
        return weights_mod.resident_block(self.seed, b, int(corpus["install_block_rows"]),
                                          self.cfg["model"]["hidden_size"], self.doc_vecs, corpus["near"])

    @property
    def store(self) -> Any:
        assert len(self.built) == 1, f"expected one index instance, engine built {len(self.built)}"
        return self.built[0].store

    def wait_ready(self) -> None:
        """Until ``/v1/statistics`` counts every live document and the encoder's
        pre-warm has compiled every bucket; then the placement assertions."""
        import jax

        t0 = time.monotonic()
        deadline = t0 + READY_DEADLINE_S
        n_live = len(self.docs)
        while True:
            if not self.thread.is_alive():
                raise RuntimeError("the server thread died before the corpus was indexed")
            if time.monotonic() > deadline:
                raise TimeoutError(f"corpus not indexed within {READY_DEADLINE_S:.0f} s")
            try:
                stats = post(self.port, "/v1/statistics", {}, timeout=30.0)
            except OSError:
                stats = None  # not listening yet, or busy inside the ingest commit
            if stats is not None and int(stats.get("file_count", 0)) == n_live:
                break
            time.sleep(0.25)
        self.timings["ready_s"] = time.monotonic() - t0
        svc = self.embedder.pipeline.service
        assert svc is not None, "the encoder service is the default query path"
        assert svc.wait_warm(timeout_s=READY_DEADLINE_S), "encoder pre-warm did not finish"
        self.prewarm_buckets = len(svc._prewarm_shapes())
        assert svc.prewarm_compiles == self.prewarm_buckets, (
            f"pre-warm compiled {svc.prewarm_compiles} of {self.prewarm_buckets} buckets")
        self.timings["prewarm_s"] = float(svc.prewarm_s)
        store, corpus = self.store, self.cfg["corpus"]
        total = n_live + int(corpus["resident_rows"])
        assert len(store) == total, f"index holds {len(store)} of {total} rows"
        assert store.capacity == int(self.cfg["index"]["args"]["reserved_space"]), store.capacity
        platform = jax.devices()[0].platform
        assert all(d.platform == platform for d in store._data.devices()), store._data.devices()
        self.log(f"ready: file_count={n_live}, len(store)={len(store)}, capacity={store.capacity}, "
                 f"store data on {platform}; pre-warm {svc.prewarm_compiles}/{self.prewarm_buckets} "
                 f"buckets in {svc.prewarm_s:.1f} s (set-up: {self.timings['ready_s']:.1f} s to ready)")

    def warm_up(self, k: int, max_batch: int) -> None:
        """Compile what the window will run before it runs: the search program
        for every padded query bucket, then, for every batch size up to
        ``max_batch``, the small programs the serving path compiles per batch
        size (slice the encoder's output, stack the rows, pad to the bucket), by
        driving the program's own query path (``embed_query_rows`` into
        ``search_many``) with that many unique texts; last a few bursts over
        HTTP, so that REST, the commit and the reply have run too."""
        t0 = time.monotonic()
        dim = self.cfg["model"]["hidden_size"]
        for q in self.cfg["index"]["warm_query_buckets"]:
            self.store.search_batch(np.full((q, dim), 1.0 / np.sqrt(dim), np.float32), k)
        self.timings["search_compile_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        n = 0

        def texts(count: int) -> List[str]:
            nonlocal n
            n += count
            return [f"{self.docs[(n + j) % len(self.docs)].split(' ', 1)[1][:40]} warm{n + j}"
                    for j in range(count)]

        index = self.built[0]
        for size in range(1, max_batch + 1):
            rows = self.embedder.pipeline.embed_query_rows(texts(size))
            got = index.search_many(rows, [k] * size, None)
            assert len(got) == size and all(len(g) == k for g in got), (size, [len(g) for g in got])
        self.timings["warm_sizes_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        for burst in (1, 4, 16):
            reqs = [{"i": j, "phase": "warm", "due": 0.0, "k": k, "query": q}
                    for j, q in enumerate(texts(burst))]
            recs = asyncio.run(loadgen.drive(reqs, "127.0.0.1", self.port, "/v1/retrieve",
                                              time.monotonic(), 120.0))
            bad = [r for r in recs if r["status"] != 200]
            assert not bad, f"warm-up burst of {burst}: {bad[0]}"
        self.timings["warm_http_s"] = time.monotonic() - t0

    def counters(self) -> Dict[str, float]:
        """The program's own counts, read before and after the window."""
        from pathway_tpu.ops.knn import kernel_cache_sizes

        out = {f"kernel.{k}": float(v) for k, v in kernel_cache_sizes().items()}
        for name, value in self.embedder.pipeline.stats().items():
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                out[name] = float(value)
        return out
