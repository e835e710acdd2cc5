"""The as-of-now collapsed index reply, assembled inside the index operator.

``DataIndex.query_as_of_now(..., collapse_rows=True)`` once lowered its reply
through flatten / ix / groupby / join_left after the external-index operator.
``ExternalIndexEvaluator`` now builds that row itself. These tests hold the
operator's reply against the chain it replaced, written out here from the same
operators, as update streams: the same rows, under the same keys, in the same
commits.
"""

from __future__ import annotations

import json
import socket
import time
import urllib.request

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import telemetry
from pathway_tpu.engine.runner import GraphRunner
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import expression as expr
from pathway_tpu.internals.json import Json
from pathway_tpu.internals.keys import Pointer
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.reducers import reducers
from pathway_tpu.internals.trace import EngineErrorWithTrace
from pathway_tpu.stdlib.indexing.data_index import _InstanceFactory
from pathway_tpu.stdlib.indexing.nearest_neighbors import (
    BruteForceKnnFactory,
    BruteForceKnnMetricKind,
)

from .utils import T, _norm


def _vec(text: str) -> np.ndarray:
    # the first letter picks the direction and the length its reach: texts that
    # share both have one vector, so their scores tie
    v = np.zeros(4, dtype=np.float32)
    v[ord(text[0]) % 3] = 1.0
    v[3] = len(text) / 10.0
    return v


_embed = pw.udf(_vec)


_RESIDENT = np.array([[1.0, 0.0, 0.0, 0.3], [0.0, 1.0, 0.0, 0.3]], dtype=np.float32)


def _index(docs, *, metadata: bool = False, resident: bool = False):
    factory = BruteForceKnnFactory(
        dimensions=4, metric=BruteForceKnnMetricKind.L2SQ, embedder=_embed
    )
    index = factory.build_index(
        docs.text, docs, metadata_column=docs.meta if metadata else None
    )
    if resident:
        # rows installed through the index's descriptor contract, keyed by plain
        # numbers: a match with no data row behind it
        inner = index.inner_index
        make = inner.make_instance_factory()

        def make_with_resident():
            instance = make()
            instance.install_descriptor_rows([100, 101], _RESIDENT)
            return instance

        inner.make_instance_factory = lambda: make_with_resident
    return index


def _chain(index, query_column, number_of_matches, metadata_filter=None):
    """The lowering ``DataIndex._query`` used for as-of-now collapsed queries
    before the operator assembled the reply."""
    queries = query_column.table
    query_table = queries.select(
        _pw_query=index.inner_index.preprocess_query(query_column),
        _pw_limit=number_of_matches,
        **({"_pw_qfilter": metadata_filter} if metadata_filter is not None else {}),
    )
    index_table = index._index_table
    reply = query_table._external_index_as_of_now(
        index_table,
        index_column=index_table._pw_vec,
        query_column=query_table._pw_query,
        index_factory=_InstanceFactory(index.inner_index.make_instance_factory()),
        res_type=dt.ANY,
        query_responses_limit_column=query_table._pw_limit,
        index_filter_data_column=(
            index_table._pw_meta if index.inner_index.metadata_column is not None else None
        ),
        query_filter_column=query_table._pw_qfilter if metadata_filter is not None else None,
        asof_now=True,
    )
    data_table = index.data_table
    flat = reply.flatten(reply._pw_index_reply, origin_id="_pw_query_id")
    matched = flat.select(
        _pw_query_id=flat._pw_query_id,
        _pw_match_ptr=flat._pw_index_reply[0],
        _pw_score=flat._pw_index_reply[1],
    )
    data_rows = data_table.ix(matched._pw_match_ptr)
    enriched = matched.select(
        matched._pw_query_id,
        matched._pw_score,
        **{name: data_rows[name] for name in data_table.column_names()},
    )
    grouped = enriched.groupby(enriched._pw_query_id).reduce(
        enriched._pw_query_id,
        _pw_index_reply_score=reducers.tuple(enriched._pw_score, sort_by=-enriched._pw_score),
        **{
            name: reducers.tuple(enriched[name], sort_by=-enriched._pw_score)
            for name in data_table.column_names()
        },
    )
    rekeyed = grouped.with_id(grouped._pw_query_id).without("_pw_query_id")
    joined = queries.join_left(rekeyed, queries.id == rekeyed.id, id=queries.id).select(
        *[queries[n] for n in queries.column_names()],
        _pw_index_reply_score=expr.coalesce(rekeyed._pw_index_reply_score, expr.make_tuple()),
        **{
            name: expr.coalesce(rekeyed[name], expr.make_tuple())
            for name in data_table.column_names()
        },
    )
    return joined


def _record(table) -> list:
    events: list = []

    def on_change(key, row, time, is_addition):
        cells = tuple((name, _norm(row[name])) for name in table.column_names())
        events.append((time, key, 1 if is_addition else -1, cells))

    pw.io.subscribe(table, on_change)
    return events


def _by_commit(events: list) -> list:
    commits: dict = {}
    for time_, key, diff, cells in events:
        commits.setdefault(time_, []).append((repr(key), diff, repr(cells)))
    return [(t, sorted(rows)) for t, rows in sorted(commits.items())]


def _run() -> None:
    runner = GraphRunner(G._current)
    runner.lint_exempt = True
    runner.run(monitoring_level=pw.MonitoringLevel.NONE)


_DOCS = """
  | text | n | __time__ | __diff__
1 | aaa  | 1 | 0        | 1
2 | abb  | 2 | 0        | 1
3 | bcc  | 3 | 0        | 1
4 | cdd  | 4 | 0        | 1
"""

CASES = {
    # a doc updated, one deleted and one added before the query arrives
    "data_changes_before_query": (
        _DOCS + """
2 | abb  | 2 | 2        | -1
2 | azz  | 7 | 2        | 1
4 | cdd  | 4 | 2        | -1
5 | aqq  | 8 | 2        | 1
""",
        """
  | q   | k | __time__ | __diff__
7 | axx | 2 | 4        | 1
8 | cyy | 3 | 4        | 1
""",
        {},
    ),
    # after the query: a new doc (never matched: as of now), an unmatched
    # doc updated and deleted, a matched doc deleted (its cells read ERROR)
    "data_changes_after_query": (
        _DOCS + """
5 | aqq  | 8 | 4        | 1
4 | cdd  | 4 | 4        | -1
4 | cdd  | 9 | 4        | 1
3 | bcc  | 3 | 6        | -1
1 | aaa  | 1 | 8        | -1
""",
        """
  | q   | k | __time__ | __diff__
7 | axx | 2 | 2        | 1
""",
        {},
    ),
    "query_inserted_then_retracted": (
        _DOCS,
        """
  | q   | k | __time__ | __diff__
7 | axx | 2 | 2        | 1
8 | bxx | 1 | 4        | 1
7 | axx | 2 | 4        | -1
8 | bxx | 1 | 6        | -1
""",
        {},
    ),
    "k_zero": (
        _DOCS,
        """
  | q   | k | __time__ | __diff__
7 | axx | 0 | 2        | 1
""",
        {},
    ),
    "k_above_corpus": (
        _DOCS,
        """
  | q   | k  | __time__ | __diff__
7 | axx | 10 | 2        | 1
""",
        {},
    ),
    "metadata_filter": (
        """
  | text | n | owner | __time__ | __diff__
1 | aaa  | 1 | alice | 0        | 1
2 | abb  | 2 | bob   | 0        | 1
3 | acc  | 3 | alice | 0        | 1
""",
        """
  | q   | k | flt              | __time__ | __diff__
7 | axx | 3 | owner == 'alice' | 2        | 1
8 | axx | 3 | owner == 'carol' | 2        | 1
""",
        {"metadata": True},
    ),
    "tied_scores": (
        """
  | text | n | __time__ | __diff__
1 | abc  | 1 | 0        | 1
2 | aaa  | 2 | 0        | 1
3 | axy  | 3 | 0        | 1
4 | bcd  | 4 | 0        | 1
5 | azz  | 5 | 0        | 1
""",
        """
  | q   | k | __time__ | __diff__
7 | aqq | 3 | 2        | 1
8 | aqq | 5 | 2        | 1
""",
        {},
    ),
    "matched_keys_without_data_rows": (
        _DOCS,
        """
  | q   | k | __time__ | __diff__
7 | axx | 4 | 2        | 1
8 | bxx | 3 | 2        | 1
7 | axx | 4 | 4        | -1
""",
        {"resident": True},
    ),
    "query_with_no_match": (
        """
  | text | n | __time__ | __diff__
1 | aaa  | 1 | 4        | 1
""",
        """
  | q   | k | __time__ | __diff__
7 | axx | 2 | 2        | 1
""",
        {},
    ),
    # ties: the updated match moves behind its equal scores, as the chain moved it
    "matched_row_updated_while_live": (
        """
  | text | n | __time__ | __diff__
1 | abc  | 1 | 0        | 1
2 | aaa  | 2 | 0        | 1
3 | axy  | 3 | 0        | 1
""",
        """
  | q   | k | __time__ | __diff__
7 | aqq | 3 | 2        | 1
8 | bqq | 1 | 2        | 1
7 | aqq | 3 | 8        | -1
""",
        {"updates": """
1 | abc  | 1 | 4        | -1
1 | abc  | 6 | 4        | 1
3 | axy  | 3 | 6        | -1
3 | axy  | 9 | 6        | 1
2 | aaa  | 2 | 6        | -1
2 | aaa  | 5 | 6        | 1
"""},
    ),
}


def _build(docs_md: str, queries_md: str, opts: dict):
    docs = T(docs_md + opts.get("updates", ""))
    if opts.get("metadata"):
        docs = docs.select(
            docs.text,
            docs.n,
            meta=pw.apply_with_type(lambda o: Json({"owner": o}), Json, docs.owner),
        )
    queries = T(queries_md)
    index = _index(docs, metadata=bool(opts.get("metadata")), resident=bool(opts.get("resident")))
    flt = queries.flt if opts.get("metadata") else None
    assembled = index.query_as_of_now(
        queries.q, number_of_matches=queries.k, collapse_rows=True, metadata_filter=flt
    )
    chain = _chain(index, queries.q, queries.k, flt)
    return assembled, chain


@pytest.mark.parametrize("case", sorted(CASES))
def test_assembled_reply_streams_as_the_chain(case):
    docs_md, queries_md, opts = CASES[case]
    assembled, chain = _build(docs_md, queries_md, opts)
    assert assembled.column_names() == chain.column_names()
    got, want = _record(assembled), _record(chain)
    _run()
    assert want, "the case produced no reply"
    assert _by_commit(got) == _by_commit(want)


def test_assembled_reply_values_and_order():
    """The rows themselves, not only their agreement with the chain: best score
    first, ties in index order, ``None`` cells for a resident match."""
    docs_md, queries_md, opts = CASES["matched_keys_without_data_rows"]
    assembled, _ = _build(docs_md, queries_md, opts)
    events = _record(assembled)
    _run()
    first = {key: dict(cells) for time_, key, diff, cells in events if diff > 0}
    rows = sorted(first.values(), key=lambda r: r["q"])
    assert rows[0]["q"] == "axx"
    # axx is (0, 1, 0, .3): resident row 101, aaa and abb tie at 0 in index order
    # (the resident rows were installed first); resident row 100 leads those at -2
    assert rows[0]["text"] == (None, "aaa", "abb", None)
    assert rows[0]["n"] == (None, 1, 2, None)
    assert rows[0]["_pw_index_reply_score"] == pytest.approx((0.0, 0.0, 0.0, -2.0))
    # bxx is (0, 0, 1, .3): bcc at 0, then both resident rows at -2
    assert rows[1]["text"] == ("bcc", None, None)


def test_deleted_match_then_retraction_replays_the_last_row():
    """A matched doc deleted while the reply is live reads ``ERROR`` (as the chain
    gave); the query's later retraction replays that row. The chain itself
    failed at this retraction ("retraction of absent key")."""
    docs = T(_DOCS + "1 | aaa | 1 | 4 | -1\n")
    queries = T(
        """
  | q   | k | __time__ | __diff__
7 | axx | 2 | 2        | 1
7 | axx | 2 | 6        | -1
"""
    )
    res = _index(docs).query_as_of_now(queries.q, number_of_matches=queries.k)
    events = _record(res)
    _run()
    texts = [(diff, dict(cells)["text"]) for _, _, diff, cells in events]
    from pathway_tpu.engine.columnar import ERROR

    assert texts == [
        (1, ("aaa", "abb")),
        (-1, ("aaa", "abb")),
        (1, ("abb", ERROR)),
        (-1, ("abb", ERROR)),
    ]


@pytest.mark.parametrize("path", ["assembled", "chain"])
def test_matched_pointer_missing_from_the_data_table_raises(path):
    """A matched ``Pointer`` with no data row is a ``KeyError``, as ``ix`` made it."""
    docs = T(_DOCS)
    queries = T(
        """
  | q   | k | __time__
7 | axx | 1 | 2
"""
    )
    index = _index(docs)
    inner = index.inner_index
    make = inner.make_instance_factory()

    def make_with_stray():
        instance = make()
        instance.install_descriptor_rows([Pointer(1, 2)], _RESIDENT[1:])
        return instance

    inner.make_instance_factory = lambda: make_with_stray
    if path == "assembled":
        res = index.query_as_of_now(queries.q, number_of_matches=queries.k)
    else:
        res = _chain(index, queries.q, queries.k)
    _record(res)
    with pytest.raises(EngineErrorWithTrace) as failure:
        _run()
    assert isinstance(failure.value.cause, KeyError)


def _kinds_added(build) -> set:
    """The node kinds a query call adds to the graph (cross-table references
    such as the chain's ix are no inputs, so a walk over inputs would miss them)."""
    n0 = len(G._current.nodes)
    build()
    return {node.kind for node in G._current.nodes[n0:]}


def test_only_the_as_of_now_collapsed_path_leaves_the_chain():
    docs = T(_DOCS)
    queries = T(
        """
q   | k
axx | 2
"""
    )
    index = _index(docs)
    chain_kinds = {"flatten", "ix", "groupby", "join"}
    asof = _kinds_added(lambda: index.query_as_of_now(queries.q, number_of_matches=queries.k))
    assert "external_index" in asof and not asof & chain_kinds
    live = _kinds_added(lambda: index.query(queries.q, number_of_matches=queries.k))
    assert live >= chain_kinds
    flat = _kinds_added(
        lambda: index.query_as_of_now(queries.q, number_of_matches=queries.k, collapse_rows=False)
    )
    assert flat >= {"flatten", "ix"}
    reply = index.query_as_of_now(queries.q, number_of_matches=queries.k)
    assert reply._universe is queries._universe


def test_reply_reemits_counter_moves_with_a_live_update():
    docs_md, queries_md, opts = CASES["matched_row_updated_while_live"]
    assembled, _ = _build(docs_md, queries_md, opts)
    _record(assembled)
    before = telemetry.stage_snapshot("index.")
    _run()
    after = telemetry.stage_snapshot("index.")
    moved = {
        name: after.get(name, 0.0) - before.get(name, 0.0)
        for name in ("index.replies_assembled", "index.reply_reemits")
    }
    # two queries answered; the update of abc re-emits both replies (it is bqq's
    # one match too), the two updates of the next commit aqq's once
    assert moved == {"index.replies_assembled": 2.0, "index.reply_reemits": 3.0}


def _post(port: int, route: str, payload: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def test_one_retrieve_counts_one_assembled_reply():
    """One ``/v1/retrieve`` moves ``index.replies_assembled`` by one; its
    retraction (pushed before the reply returns) does not, and both counters
    are on ``/metrics``."""
    from pathway_tpu.engine.http_server import ProberStats
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    from .mocks import FakeEmbedder
    from .utils import validate_openmetrics

    texts = ["the cat sits on the mat", "dogs chase the ball", "qubits compute"]
    docs = pw.debug.table_from_rows(
        pw.schema_builder({"data": str, "_metadata": str}),
        [(t, json.dumps({"path": f"doc{i}"})) for i, t in enumerate(texts)],
    )
    server = VectorStoreServer(docs, embedder=FakeEmbedder(dim=16))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server.run_server(host="127.0.0.1", port=port, threaded=True)
    deadline = time.monotonic() + 60
    while True:
        try:
            if int(_post(port, "/v1/statistics", {}).get("file_count", 0)) == len(texts):
                break
        except OSError:
            pass
        assert time.monotonic() < deadline, "the corpus was never indexed"
        time.sleep(0.2)
    before = telemetry.stage_snapshot("index.")
    reply = _post(port, "/v1/retrieve", {"query": "dogs chase the ball", "k": 2})
    assert [d["text"] for d in reply][:1] == ["dogs chase the ball"]
    # a later commit: the retraction pushed before the reply has been processed
    _post(port, "/v1/statistics", {})
    after = telemetry.stage_snapshot("index.")
    assert after.get("index.replies_assembled", 0) - before.get("index.replies_assembled", 0) == 1
    assert after.get("index.reply_reemits", 0) == before.get("index.reply_reemits", 0)
    text = ProberStats().to_openmetrics()
    validate_openmetrics(text)
    assert 'pathway_stage_total{stage="index.replies_assembled"}' in text


def test_a_joining_rank_answers_with_the_documents_texts():
    """Grow 1 -> 2: the fragment addressed to the new rank carries the payload
    beside the index, so the new rank's replies hold the texts, not ``None``."""
    from pathway_tpu.engine.columnar import Delta, objarray
    from pathway_tpu.engine.evaluators import ExternalIndexEvaluator
    from pathway_tpu.internals.keys import pointer_from, pointers_to_keys
    from pathway_tpu.parallel.membership import (
        build_fragments,
        compute_reshard_plan,
        import_fragments,
    )

    docs = pw.debug.table_from_rows(
        pw.schema_builder({"text": str, "n": int}), [("aaa", 1), ("abb", 2), ("bcc", 3)]
    )
    queries = pw.debug.table_from_rows(pw.schema_builder({"q": str, "k": int}), [("axx", 2)])
    res = _index(docs).query_as_of_now(queries.q, number_of_matches=queries.k)
    pw.io.subscribe(res, lambda *a, **k: None)

    def runner_of():
        runner = GraphRunner(G._current)
        runner.lint_exempt = True
        return runner

    old = runner_of()
    old.run(monitoring_level=pw.MonitoringLevel.NONE)
    for node in old._nodes:
        ev = old.evaluators[node.id]
        ev._cluster_policies = tuple(ev.cluster_input_policy(i) for i in range(len(node.inputs)))
    plan = compute_reshard_plan(old)
    assert plan.ok, plan.refusals
    [nid] = [n.id for n in old._nodes if n.kind == "external_index"]
    assert plan.policies[nid] == "replicate"
    frags, _ = build_fragments(old, plan, new_n=2, commit=1, generation=1)

    new = runner_of()
    new.setup(monitoring_level=pw.MonitoringLevel.NONE)
    import_fragments(new, [frags[1]])
    ev = new.evaluators[nid]
    assert isinstance(ev, ExternalIndexEvaluator)
    key = pointers_to_keys([pointer_from("a joining rank's query")])
    query = Delta(
        key, np.ones(1, dtype=np.int64),
        {"_pw_query": objarray([_vec("axx")]),
         "_pw_limit": np.array([2])},
    )
    index_side = Delta.empty(ev.node.inputs[0].column_names())
    out = ev.process([index_side, query])
    assert list(out.columns["text"]) == [("aaa", "abb")]
    assert list(out.columns["n"]) == [(1, 2)]
