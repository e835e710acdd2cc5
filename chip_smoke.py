"""chip_smoke.py — the quickest proof that the serving path still starts on the chip.

Drives the main path once, through the entry points a user calls, in ONE process
(server in a thread, client in the same process; a chip belongs to one process):

    VectorStoreServer(doc_table, embedder=SentenceTransformerEmbedder())
      .run_server(threaded=True) -> /v1/statistics -> /v1/inputs -> /v1/retrieve

with the default full-width ``EncoderConfig()`` (all-MiniLM-L6-v2: 6 layers, hidden
384, 12 heads, FFN 1536, vocab 30522) over a corpus generated from a seed; first
with the dense index, then with ``index_factory="ivf"`` (the Pallas page kernel,
compiled, against its XLA twin), then — when four devices are visible — with the
store sharded over a four-chip mesh.

It exits non-zero, and prints no result line, unless JAX's default platform is
``tpu``; it sets no ``JAX_PLATFORMS`` and no compile-cache directory. Nothing is
caught and reported as a key: any exception, any request that times out, any phase
that did not run ends the process with a traceback. The last line of stdout on
success is ``{"ok": true, "device": {...}}`` with the device as JAX reports it.
Seconds printed here are set-up (import, native build, compiles, ingest), labelled
as such; they are not a metric of the system.

    python chip_smoke.py
"""

from __future__ import annotations

import faulthandler
import json
import os
import sys
import time
import traceback
import urllib.request

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))

SEED = 21
N_DOCS = 3000
K = 5
READY_DEADLINE_S = 900.0  # import → prewarm → ingest of the whole corpus, cold
REQUEST_TIMEOUT_S = 180.0  # the first retrieve compiles the search kernel

# Same text, two paths: a document embedded at ingest (128-row sub-batches) and
# the same words embedded as a query (8-row service tick). The forward pass is
# bf16 with f32 accumulation and a float16 output cast (~5e-4), and XLA picks a
# different tiling per batch shape, so the two vectors agree to bf16 rounding,
# not bitwise: cosine within 1e-2 of 1.
SELF_COS_TOL = 1e-2

# Pallas page kernel vs its XLA twin on the same store and queries, both traced
# under jax.default_matmul_precision("highest"): the same f32 math in two
# programs, so what is left is f32 accumulation order (measured 4.5e-8 to
# 3.6e-7 on unit-norm rows, d = 32 to 384).
IVF_EXACT_TOL = 1e-5

# Two programs at DEFAULT matmul precision — what the server runs. On a TPU a
# default-precision f32 dot feeds the MXU bf16 passes of its operands, and
# Mosaic and XLA (and XLA with and without shard_map) split and order those
# passes differently: unit-norm scores then differ by 3e-4 to 1.5e-3 (measured),
# operand rounding rather than accumulation order. Ranks may swap only between
# candidates closer than this.
DEFAULT_PRECISION_TOL = 5e-3


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - T_START:6.1f}s] {msg}", flush=True)


def corpus() -> list:
    """N_DOCS distinct documents of 6-40 words from a 2000-word vocabulary, so
    ingest crosses several (batch, seq) buckets. Deterministic in SEED."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    vocab = [f"w{i:04d}" for i in range(2000)]
    docs = []
    for i in range(N_DOCS):
        n_words = int(rng.integers(6, 41))
        words = [vocab[j] for j in rng.integers(0, len(vocab), n_words)]
        docs.append((f"doc{i} " + " ".join(words), json.dumps({"path": f"doc{i}"})))
    return docs


def post(port: int, route: str, payload: dict, timeout: float = REQUEST_TIMEOUT_S):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def same_ranking(ids_a, scores_a, ids_b, scores_b, tol: float) -> float:
    """Two top-k answers agree: finite scores within ``tol`` rank by rank, and a
    different id at a rank only where it is a near-tie (the other answer holds
    that id too, or it sits within ``tol`` of the other answer's last score).
    Returns the largest score difference."""
    import numpy as np

    scores_a, scores_b = np.asarray(scores_a), np.asarray(scores_b)
    assert np.isfinite(scores_a).all() and np.isfinite(scores_b).all()
    worst = float(np.max(np.abs(scores_a - scores_b)))
    assert worst <= tol, f"scores differ by {worst} > {tol}"
    for r in range(len(ids_a)):
        for c in range(len(ids_a[r])):
            if ids_a[r][c] != ids_b[r][c]:
                assert (
                    ids_a[r][c] in list(ids_b[r])
                    or abs(scores_a[r][c] - scores_b[r][-1]) <= tol
                ), f"row {r} rank {c}: {ids_a[r]} {scores_a[r]} vs {ids_b[r]} {scores_b[r]}"
    return worst


def on_tpu(array) -> bool:
    return all(d.platform == "tpu" for d in array.devices())


def serve_phase(name: str, port: int, index_factory=None) -> dict:
    """Build the server through its normal constructor, run it threaded, wait
    until /v1/statistics reports the whole corpus, then query it. Returns the
    store the engine built and the answers (ids and distances per query)."""
    import jax
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.internals import parse_graph as pg
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    t_phase = time.perf_counter()
    pg.G.clear()
    docs = corpus()
    doc_table = pw.debug.table_from_rows(
        pw.schema_builder({"data": str, "_metadata": str}), docs
    )
    embedder = SentenceTransformerEmbedder()  # default EncoderConfig: full width
    enc = embedder.encoder
    cfg = enc.config
    log(
        f"{name}: encoder layers={cfg.num_layers} hidden={cfg.hidden_size} "
        f"heads={cfg.num_heads} ffn={cfg.intermediate_size} vocab={cfg.vocab_size}; "
        f"weights: {enc.weights_source}; tokenizer: {enc.tokenizer_source}"
    )
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            cfg.vocab_size) == (6, 384, 12, 1536, 30522), "not the published width"
    assert all(on_tpu(leaf) for leaf in jax.tree.leaves(enc.params)), (
        "encoder params are not on a TPU device"
    )

    server = VectorStoreServer(doc_table, embedder=embedder, index_factory=index_factory)
    # the engine builds the index instance inside pw.run; keep a handle on it so
    # its placement can be asserted afterwards
    inner = server.index.inner_index
    make_index = inner.make_instance_factory()
    built: list = []

    def make_and_keep():
        built.append(make_index())
        return built[-1]

    inner.make_instance_factory = lambda: make_and_keep
    thread = server.run_server(host="127.0.0.1", port=port, threaded=True)

    deadline = time.perf_counter() + READY_DEADLINE_S
    stats = None
    while True:
        if not thread.is_alive():
            raise RuntimeError(f"{name}: the server thread died before the corpus was indexed")
        if time.perf_counter() > deadline:
            raise TimeoutError(f"{name}: corpus not indexed within {READY_DEADLINE_S:.0f} s")
        try:
            stats = post(port, "/v1/statistics", {}, timeout=30.0)
        except OSError:
            stats = None  # not listening yet, or busy inside the ingest commit
        if stats is not None and int(stats.get("file_count", 0)) == N_DOCS:
            break
        time.sleep(0.5)
    ready_s = time.perf_counter() - t_phase
    log(f"{name}: /v1/statistics file_count={stats['file_count']} (set-up: {ready_s:.1f} s to ready)")

    svc = embedder.pipeline.service
    assert svc is not None, "the encoder service is the default query path"
    assert svc.wait_warm(timeout_s=READY_DEADLINE_S), "encoder pre-warm did not finish"
    n_buckets = len(svc._prewarm_shapes())
    assert svc.prewarm_compiles == n_buckets, (
        f"pre-warm compiled {svc.prewarm_compiles} of {n_buckets} buckets "
        "(a compile failed; see the log above)"
    )
    log(f"{name}: encoder pre-warm compiled {svc.prewarm_compiles}/{n_buckets} buckets "
        f"(set-up: {svc.prewarm_s:.1f} s)")

    inputs = post(port, "/v1/inputs", {})
    assert len(inputs) == N_DOCS, f"/v1/inputs returned {len(inputs)} of {N_DOCS}"

    assert len(built) == 1, f"expected one index instance, engine built {len(built)}"
    store = built[0].store
    assert len(store) == N_DOCS, f"index holds {len(store)} of {N_DOCS} documents"

    # 1: a document's own text (answered from the ingest path's content cache)
    # 2: the same words in another case (misses the cache: encoder service,
    #    device-resident query rows into the index) — both must come back first
    # 3+: unseen word mixes through the encoder service
    ids, dists = [], []
    own_a, own_b = docs[7][0], docs[1234][0]
    queries = [own_a, own_b.upper()] + [
        f"w{(37 * i) % 2000:04d} w{(91 * i + 5) % 2000:04d} w{(13 * i + 11) % 2000:04d} query {i}"
        for i in range(4)
    ]
    for qi, text in enumerate(queries):
        got = post(port, "/v1/retrieve", {"query": text, "k": K})
        assert len(got) == K, f"{name}: retrieve {qi} returned {len(got)} of {K} results"
        # every document's text starts with its own id ("doc7 w0677 ...")
        ids.append([r["text"].split()[0] for r in got])
        dists.append([float(r["dist"]) for r in got])
        assert all(np.isfinite(dists[-1])), f"{name}: non-finite distances {dists[-1]}"
        assert dists[-1] == sorted(dists[-1]), f"{name}: not ordered by distance {dists[-1]}"
    for qi, want in ((0, "doc7"), (1, "doc1234")):
        assert ids[qi][0] == want, f"{name}: self-query {qi} returned {ids[qi][0]} first, not {want}"
        # metric is cosine; dist = -cosine
        assert abs(-dists[qi][0] - 1.0) <= SELF_COS_TOL, (
            f"{name}: self-query {qi} cosine {-dists[qi][0]:.5f} not within {SELF_COS_TOL} of 1"
        )
    log(f"{name}: {len(queries)} /v1/retrieve answered; self-queries first with cosine "
        f"{-dists[0][0]:.5f} (cache path) and {-dists[1][0]:.5f} (encoder service path)")
    if not thread.is_alive():
        raise RuntimeError(f"{name}: the server thread died while serving")
    return {"store": store, "ids": ids, "dists": dists}


def check_ivf_kernel(store) -> None:
    """The IVF store the server just queried: prove the Pallas kernel is in the
    compiled program, and compare it with the XLA twin on the same store."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.ops.knn_ivf import IvfKnnStore, _ivf_query_fused

    assert type(store) is IvfKnnStore, f"expected the flat IVF store, got {type(store).__name__}"
    assert on_tpu(store._data), "IVF store data is not on a TPU device"
    assert jax.default_backend() == "tpu"  # what search_batch keys impl="pallas" on
    rng = np.random.default_rng(SEED + 1)
    slots = np.fromiter(store.slot_of.values(), dtype=np.int64)[:32]
    q = np.asarray(store._data[jnp.asarray(slots)].astype(jnp.float32))
    # a small nudge (norm ~0.04): random-init embeddings of different documents
    # already sit at cosine ~0.98 of each other, so each query stays nearest to
    # the document it came from by a margin far above the tolerance
    q = q + 0.002 * rng.normal(size=q.shape).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)

    assert store._prepare_search()
    store._ensure_packed()
    packed, pn, pm, rows, first_page, n_pages = store._packed
    hlo = _ivf_query_fused.lower(
        store._centroids, first_page, n_pages, packed, pn, pm, rows, jnp.asarray(q),
        k=8, n_probe=store.n_probe, max_pages=store._max_pages, metric=store.metric,
        impl="pallas",
    ).compile().as_text()
    assert "tpu_custom_call" in hlo, "impl='pallas' did not compile to a Mosaic kernel"

    with jax.default_matmul_precision("highest"):
        ps, pi = store._search_device(q, K, impl="pallas")
        xs, xi = store._search_device(q, K, impl="xla")
    exact = same_ranking(pi, ps, xi, xs, IVF_EXACT_TOL)
    # IVF is approximate (8 of 64+ clusters probed), so not every last one
    assert (pi[:, 0] == slots).mean() >= 0.9, "nudged documents no longer find themselves"
    ps, pi = store._search_device(q, K, impl="pallas")
    xs, xi = store._search_device(q, K, impl="xla")
    default = same_ranking(pi, ps, xi, xs, DEFAULT_PRECISION_TOL)
    log(f"ivf: impl='pallas' is a compiled Mosaic kernel; vs impl='xla' over {len(q)} queries: "
        f"max |score diff| {exact:.1e} at highest precision (tolerance {IVF_EXACT_TOL}), "
        f"{default:.1e} at default precision (tolerance {DEFAULT_PRECISION_TOL})")


def sharded_phase(port: int, one_chip: dict) -> None:
    import jax

    from pathway_tpu.parallel.knn_sharded import ShardedKNNStore
    from pathway_tpu.parallel.mesh import make_mesh, set_default_mesh

    # model_parallel=1: the default factorization would make data=1, model=4,
    # and nothing would be sharded
    set_default_mesh(make_mesh(4, model_parallel=1))
    try:
        out = serve_phase("sharded", port)
    finally:
        set_default_mesh(None)
    store = out["store"]
    assert type(store) is ShardedKNNStore, f"got {type(store).__name__}"
    devices = store._data.sharding.device_set
    assert len(devices) == 4 and all(d.platform == "tpu" for d in devices), devices
    compiled = sum(int(fn._cache_size()) for fn in store._search.values())
    assert compiled <= 2, f"{compiled} search programs compiled for {len(out['ids'])} queries"
    worst = same_ranking(
        out["ids"], out["dists"], one_chip["ids"], one_chip["dists"], DEFAULT_PRECISION_TOL
    )
    log(f"sharded: store over {len(devices)} distinct TPU devices, {compiled} search program(s) "
        f"for {len(out['ids'])} queries, answers equal the one-chip phase "
        f"(max |score diff| {worst:.1e}, tolerance {DEFAULT_PRECISION_TOL})")


def main() -> int:
    import importlib.metadata

    import jax
    import jaxlib

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"device: platform={device['platform']} device_kind={device['kind']!r} "
        f"count={device['count']}; python {sys.version.split()[0]} jax {jax.__version__} "
        f"jaxlib {jaxlib.__version__} libtpu {libtpu}")
    if device["platform"] != "tpu":
        print("chip_smoke.py: JAX found no TPU; this check does not run on "
              f"platform {device['platform']!r}", file=sys.stderr)
        return 1

    so_path = os.path.join(HERE, "pathway_tpu", "native", "_pathway_native.so")
    so_existed = os.path.exists(so_path)
    t0 = time.perf_counter()
    from pathway_tpu import native

    if native.get_lib() is None:
        raise RuntimeError(f"native library unavailable: {native.unavailable_reason()}")
    log(f"native: {'reused ' + so_path if so_existed else 'built in this run from csrc/pathway_native.cc'} "
        f"(set-up: import + build {time.perf_counter() - t0:.1f} s)")

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(HERE, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == cache_dir, (
        f"compile cache at {jax.config.jax_compilation_cache_dir!r}, expected {cache_dir!r}"
    )
    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    entries_before = cache_entries()
    log(f"compile cache: {cache_dir} ({entries_before} entries at start: "
        f"{'warm' if entries_before else 'cold'})")

    dense = serve_phase("dense", port=18801)
    from pathway_tpu.ops.knn import DenseKNNStore

    assert type(dense["store"]) is DenseKNNStore, type(dense["store"]).__name__
    assert on_tpu(dense["store"]._data), "dense store data is not on a TPU device"

    ivf = serve_phase("ivf", port=18802, index_factory="ivf")
    check_ivf_kernel(ivf["store"])

    if device["count"] >= 4:
        sharded_phase(18803, dense)
    else:
        log(f"sharded: not run ({device['count']} devices)")

    assert cache_entries() > 0, f"compile cache {cache_dir} is empty after the run"
    log(f"compile cache: {cache_entries()} entries at end")
    log(f"set-up seconds, whole run: {time.perf_counter() - T_START:.1f} "
        f"(compile cache {'warm' if entries_before else 'cold'} at start)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # the contract allows 1200 s: a hang dumps every thread's stack and exits 1
    faulthandler.dump_traceback_later(1150, exit=True)
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - print it and leave non-zero, below
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the REST servers run in threads with no stop call; leave without joining them
    os._exit(code)
