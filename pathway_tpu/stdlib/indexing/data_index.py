"""DataIndex / InnerIndex — typed index querying over tables.

Parity: reference ``stdlib/indexing/data_index.py`` (``DataIndex:278``, ``InnerIndex:206``).
The query path compiles to the engine's as-of-now external-index operator
(``pathway_tpu/engine/evaluators.py::ExternalIndexEvaluator`` ↔ reference
``external_index.rs:38``); KNN search itself runs as a jit'd matmul+top_k on the TPU.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import expression as expr
from pathway_tpu.internals.reducers import reducers
from pathway_tpu.internals.table import Table


class InnerIndex:
    """Engine-facing index description: data column + factory for per-worker instances."""

    def __init__(
        self,
        data_column: expr.ColumnReference,
        metadata_column: expr.ColumnReference | None = None,
    ):
        self.data_column = data_column
        self.metadata_column = metadata_column

    def make_instance_factory(self) -> Any:
        raise NotImplementedError

    def preprocess_query(self, query_column: expr.ColumnReference) -> expr.ColumnExpression:
        """Hook: e.g. embed text queries before the index sees them."""
        return query_column

    def preprocess_data(self, data_column: expr.ColumnReference) -> expr.ColumnExpression:
        """Hook: e.g. embed indexed documents (text column → vector column)."""
        return data_column


class _InstanceFactory:
    def __init__(self, make: Callable[[], Any]):
        self._make = make

    def make_instance(self) -> Any:
        return self._make()


def _payload_name(name: str) -> str:
    return f"_pw_payload_{name}"


class DataIndex:
    """Index over ``data_table``; querying returns per-query matched rows.

    ``query_as_of_now`` gives as-of-now semantics (answers never retracted on index change;
    used by RAG serving); ``query`` re-answers queries when the index updates.

    Who builds the reply: for ``query_as_of_now(..., collapse_rows=True)`` the index
    operator itself (``ExternalIndexEvaluator``) assembles one row per query from the
    data columns it keeps beside the vectors, because such a reply is answered once
    and never revisited. ``query`` and ``collapse_rows=False`` lower the reply through
    flatten / ix (and, collapsed, groupby / join_left): a live query is re-answered
    whenever the index changes, and that chain's differential update tracks it.
    """

    def __init__(
        self,
        data_table: Table,
        inner_index: InnerIndex,
    ):
        self.data_table = data_table
        self.inner_index = inner_index
        # build the (possibly embedded) index-side table ONCE: every query surface shares
        # it, so the corpus crosses the TPU embedder a single time per document update;
        # the data columns ride along as the payload of assembled as-of-now replies
        self._index_table = data_table.select(
            _pw_vec=inner_index.preprocess_data(inner_index.data_column),
            **(
                {"_pw_meta": inner_index.metadata_column}
                if inner_index.metadata_column is not None
                else {}
            ),
            **{_payload_name(name): data_table[name] for name in data_table.column_names()},
        )

    def query_as_of_now(
        self,
        query_column: expr.ColumnReference,
        *,
        number_of_matches: Any = 3,
        collapse_rows: bool = True,
        metadata_filter: expr.ColumnExpression | None = None,
    ) -> Table:
        return self._query(
            query_column,
            number_of_matches=number_of_matches,
            collapse_rows=collapse_rows,
            metadata_filter=metadata_filter,
            as_of_now=True,
        )

    def query(
        self,
        query_column: expr.ColumnReference,
        *,
        number_of_matches: Any = 3,
        collapse_rows: bool = True,
        metadata_filter: expr.ColumnExpression | None = None,
    ) -> Table:
        return self._query(
            query_column,
            number_of_matches=number_of_matches,
            collapse_rows=collapse_rows,
            metadata_filter=metadata_filter,
            as_of_now=False,
        )

    def _query(
        self,
        query_column: expr.ColumnReference,
        *,
        number_of_matches: Any,
        collapse_rows: bool,
        metadata_filter: expr.ColumnExpression | None,
        as_of_now: bool,
    ) -> Table:
        queries = query_column.table
        processed_query = self.inner_index.preprocess_query(query_column)
        query_table = queries.select(
            _pw_query=processed_query,
            _pw_limit=number_of_matches,
            **(
                {"_pw_qfilter": metadata_filter}
                if metadata_filter is not None
                else {}
            ),
        )
        index_table = self._index_table
        assemble = as_of_now and collapse_rows
        data_names = self.data_table.column_names()
        reply = query_table._external_index_as_of_now(
            index_table,
            index_column=index_table._pw_vec,
            query_column=query_table._pw_query,
            index_factory=_InstanceFactory(self.inner_index.make_instance_factory()),
            res_type=dt.ANY,
            query_responses_limit_column=query_table._pw_limit,
            index_filter_data_column=(
                index_table._pw_meta if self.inner_index.metadata_column is not None else None
            ),
            query_filter_column=(
                query_table._pw_qfilter if metadata_filter is not None else None
            ),
            asof_now=as_of_now,
            index_payload_columns=(
                {name: index_table[_payload_name(name)] for name in data_names}
                if assemble
                else None
            ),
        )
        if assemble:
            # the operator's row is the collapsed reply, keyed by the query
            return reply.select(
                *[queries[n] for n in queries.column_names()],
                _pw_index_reply_score=reply._pw_index_reply_score,
                **{name: reply[name] for name in data_names},
            )
        # reply: per query key, tuple of (data_key, score)
        if not collapse_rows:
            flat = reply.flatten(reply._pw_index_reply, origin_id="_pw_query_id")
            matched = flat.select(
                _pw_query_id=flat._pw_query_id,
                _pw_match_ptr=flat._pw_index_reply[0],
                _pw_index_reply_score=flat._pw_index_reply[1],
            )
            data_cols = {
                name: self.data_table.ix(matched._pw_match_ptr)[name]
                for name in self.data_table.column_names()
            }
            return matched.select(
                matched._pw_query_id, matched._pw_index_reply_score, **data_cols
            )

        flat = reply.flatten(reply._pw_index_reply, origin_id="_pw_query_id")
        matched = flat.select(
            _pw_query_id=flat._pw_query_id,
            _pw_match_ptr=flat._pw_index_reply[0],
            _pw_score=flat._pw_index_reply[1],
        )
        data_rows = self.data_table.ix(matched._pw_match_ptr)
        enriched_cols = {
            name: data_rows[name] for name in self.data_table.column_names()
        }
        enriched = matched.select(
            matched._pw_query_id, matched._pw_score, **enriched_cols
        )
        grouped = enriched.groupby(enriched._pw_query_id).reduce(
            enriched._pw_query_id,
            _pw_index_reply_score=reducers.tuple(
                enriched._pw_score, sort_by=-enriched._pw_score
            ),
            **{
                name: reducers.tuple(enriched[name], sort_by=-enriched._pw_score)
                for name in self.data_table.column_names()
            },
        )
        rekeyed = grouped.with_id(grouped._pw_query_id).without("_pw_query_id")
        # left-join back (keyed by the query id) so zero-match queries still produce a row
        joined = queries.join_left(rekeyed, queries.id == rekeyed.id, id=queries.id).select(
            *[queries[n] for n in queries.column_names()],
            **{
                "_pw_index_reply_score": expr.coalesce(
                    rekeyed._pw_index_reply_score, expr.make_tuple()
                ),
            },
            **{
                name: expr.coalesce(rekeyed[name], expr.make_tuple())
                for name in self.data_table.column_names()
            },
        )
        return joined
