"""The system under test ``vector_store``: ``VectorStoreServer`` in a thread of this
process, a reply that is "embed the query, then the cosine top-k over all rows",
and what belongs to that reply alone: how it is read, the plain reference it is
judged by (``reference.py`` over the inputs of ``weights.py``), the numbers
compared (``compare.compare``) and the controls. ``run.py`` finds this file by
the configuration's ``"system"`` key (this one where it has none) and asks for
``System``, ``parse_reply``, ``good``, ``judge``, ``CONTROLS``, ``COMPILE_COUNTERS``,
``metric_context``.

The set-up is copied from ``chip_smoke.py:serve_phase`` (proven on the chip in PR 21), not
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

import compare
import loadgen
import reference
import trace_reduce
import weights as weights_mod

READY_DEADLINE_S = 1100.0  # a cold first run compiles 20 encoder buckets
parse_reply = compare.parse_reply  # a ``/v1/retrieve`` body as (document, text, score) per rank

# what --calibrate puts in the program's place: (the encoder's precision, the
# scoring's precision, whether the resident rows are scanned)
CONTROLS = {
    "fp8_encoder": ("fp8", "f32", True),   # the control: the nearest precision below bfloat16
    "fp8_index": ("f32", "fp8", True),     # the same step down in the index's scoring passes
    "int8_encoder": ("int8", "f32", True),  # per-tensor int8, read beside the control
    "live_rows_only": ("f32", "f32", False),  # a guarantee broken: resident rows left out
}


# the counters whose growth is a program compiled: every search kernel's cache, the encoder's pre-warm
COMPILE_COUNTERS = ("kernel.", "svc_prewarm_compiles")


def good(answer: compare.Answer, traffic: Dict[str, Any]) -> bool:
    """A reply counts where it could be read and holds ``k`` entries."""
    return answer is not None and len(answer) == asked_k(traffic)


def asked_k(traffic: Dict[str, Any]) -> int:
    """The ``k`` every request of the traffic asks for: a fixed field of its body."""
    return int(traffic["request"]["fixed"]["k"])


def index_factory(cfg: Dict[str, Any], embedder: Any) -> Any:
    """The program's index factory the configuration names, with its arguments."""
    from pathway_tpu.stdlib.indexing import nearest_neighbors as nn

    args = dict(cfg["index"]["args"])
    args["metric"] = nn.BruteForceKnnMetricKind[args["metric"]]
    return getattr(nn, cfg["index"]["factory"])(embedder=embedder, **args)


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

    def warm_up(self, traffic: Dict[str, Any]) -> None:
        """Compile what the window will run before it runs: the search program
        for every padded query bucket (the encoder's buckets are the program's
        own pre-warm), then a few bursts over HTTP on the traffic's own route,
        so that REST, the commit and the reply have run too."""
        t0 = time.monotonic()
        k, dim = asked_k(traffic), self.cfg["model"]["hidden_size"]
        for q in self.cfg["index"]["warm_query_buckets"]:
            self.store.search_batch(np.full((q, dim), 1.0 / np.sqrt(dim), np.float32), k)
        self.timings["search_compile_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        request = traffic["request"]
        n = 0
        for burst in (1, 4, 16):
            reqs = [{"i": j, "phase": "warm", "due": 0.0, **request["fixed"],
                     "query": f"{self.docs[(n + j) % len(self.docs)].split(' ', 1)[1][:40]} warm{n + j}"}
                    for j in range(burst)]
            n += burst
            recs = asyncio.run(loadgen.drive(reqs, "127.0.0.1", self.port, request, time.monotonic(), 120.0))
            bad = [r for r in recs if r["status"] != 200]
            assert not bad, f"warm-up burst of {burst}: {bad[0]}"
        self.timings["warm_http_s"] = time.monotonic() - t0

    def counters(self) -> Dict[str, float]:
        """The program's own counts, read before and after the window: the
        search kernels' cache sizes and the embed pipeline's numbers."""
        from pathway_tpu.ops.knn import kernel_cache_sizes

        out = {f"kernel.{k}": float(v) for k, v in kernel_cache_sizes().items()}
        for name, value in self.embedder.pipeline.stats().items():
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                out[name] = float(value)
        return out


def reference_check(spec: Dict[str, Any], system: System, sample: List[dict], controls=()):
    """The plain reference over the sampled queries: its embeddings of the live
    documents (made in set-up, where the resident rows' neighbours are drawn
    around them) and of the queries, and its exact top-k over ALL rows (live and
    resident, the resident ones drawn again from the seed block by block). For
    each name in ``controls`` also that control's answers, in the program's place."""
    cfg, k = spec["config"], asked_k(spec["traffic"])
    model, corpus = cfg["model"], cfg["corpus"]
    queries = [r["query"] for r in sample]
    query_vecs = reference.embed_texts(system.weights, queries, model)
    n_res, block = int(corpus["resident_rows"]), int(corpus["install_block_rows"])

    def resident(b: int, lo: int):
        return lambda: (system.resident_block(b)[: n_res - lo], len(system.docs) + lo)

    def blocks(doc_vecs, with_resident: bool = True):
        rest = [resident(b, lo) for b, lo in enumerate(range(0, n_res, block))] if with_resident else []
        return [lambda: (doc_vecs, 0)] + rest

    ref_topk, ref_ids = reference.exact_topk(query_vecs, blocks(system.doc_vecs), k)
    out = {"ref_scores": reference.cosine_to(query_vecs, system.doc_vecs), "ref_topk": ref_topk,
           "ref_ids": ref_ids, "control_answers": {}}
    for name in controls:
        encoder, scoring, with_resident = CONTROLS[name]
        docs_low, queries_low = system.doc_vecs, query_vecs
        if encoder != "f32":
            docs_low = reference.embed_texts(system.weights, system.docs, model, encoder)
            queries_low = reference.embed_texts(system.weights, queries, model, encoder)
        scores, ids = reference.exact_topk(queries_low, blocks(docs_low, with_resident), k, scoring)
        out["control_answers"][name] = compare.answers_from(ids, scores, system.docs)
    return out


def judge(spec: Dict[str, Any], system: System, sample: List[dict], controls=()):
    """The numbers ``compare.compare`` reads off the sampled replies against
    the reference, and off each control's answers in their place: (the
    program's numbers, {control: its numbers}). ``run.py`` holds each to the
    cell's limits."""
    k = asked_k(spec["traffic"])
    ref = reference_check(spec, system, sample, controls)
    n_live, block = len(system.docs), int(spec["config"]["corpus"]["install_block_rows"])
    res_ids = ref["ref_ids"][ref["ref_ids"] >= n_live] - n_live
    system.log(f"reference top-{k} of {len(sample)} sampled queries: {res_ids.size} of {ref['ref_ids'].size} "
               f"entries are resident rows, {len(set(res_ids.tolist()))} distinct, from install blocks "
               f"{sorted(set((res_ids // block).tolist()))}")
    judged = lambda answers: compare.compare(answers, k, system.docs, ref["ref_scores"], ref["ref_topk"])
    return judged([r["answer"] for r in sample]), {n: judged(a) for n, a in ref["control_answers"].items()}


def search_time(ctx: Dict[str, Any]):
    """(device seconds, calls, queries per call) of the configuration's search
    programs inside the traced span; None where the trace shows none."""
    if ctx.get("trace") is None:
        return None
    seconds, calls = trace_reduce.program_seconds(ctx["trace"], ctx["spec"]["config"]["search_programs"])
    if calls <= 0 or seconds <= 0:
        return None
    span = ctx["trace_span"]
    served = sum(1 for r in ctx["gen"]["records"]
                 if r["done"] is not None and r["status"] == 200 and span["t0"] <= r["done"] < span["t1"])
    return seconds, calls, served / calls


def metric_context(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What this system's metric readers need beyond the common context: the
    rows a search scans, and the search programs' time in the trace."""
    return {"n_rows": int(cfg["corpus"]["total_rows"]), "search_time": search_time}
