"""KNN inner indexes & factories.

Parity: reference ``stdlib/indexing/nearest_neighbors.py`` (``USearchKnn:65``,
``BruteForceKnn:170``, ``LshKnn:262``, factories ``:407-528``). TPU-native mechanism: exact
brute force is a jit'd MXU matmul + ``lax.top_k`` (``pathway_tpu/ops/knn.py``); USearchKnn
(HNSW ANN in the reference) is served by the same exact kernel — on TPU, exact search over
10M×384 vectors is a single fused matmul well inside the latency budget, so approximate
graph-walk indexes are unnecessary until far larger corpora.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Any, Callable, Optional

from pathway_tpu.internals import expression as expr
from pathway_tpu.internals.table import Table
from pathway_tpu.ops.knn import BruteForceKnnIndex, LshKnnIndex
from pathway_tpu.stdlib.indexing.data_index import DataIndex, InnerIndex
from pathway_tpu.stdlib.indexing.retrievers import AbstractRetrieverFactory


class BruteForceKnnMetricKind(enum.Enum):
    L2SQ = "l2sq"
    COS = "cos"
    IP = "ip"


class USearchMetricKind(enum.Enum):
    L2SQ = "l2sq"
    COS = "cos"
    IP = "ip"


def _metric_str(metric: Any) -> str:
    if isinstance(metric, enum.Enum):
        return str(metric.value)
    return str(metric)


class _KnnInnerIndex(InnerIndex):
    def __init__(
        self,
        data_column: expr.ColumnReference,
        metadata_column: expr.ColumnReference | None,
        dimensions: int,
        metric: Any,
        embedder: Any = None,
        make_index: Callable[[], Any] | None = None,
    ):
        super().__init__(data_column, metadata_column)
        self.dimensions = dimensions
        self.metric = _metric_str(metric)
        self.embedder = embedder
        self._make_index = make_index

    def make_instance_factory(self) -> Callable[[], Any]:
        return self._make_index

    # Indexes behind the serving path override this to True: their queries are
    # embedded through the embedder's query-path variant (caches, the encoder
    # service's continuous batch, one memoized encode per query row). Cells are
    # host float32 rows either way.
    _device_queries = False

    def preprocess_query(self, query_column: expr.ColumnReference) -> expr.ColumnExpression:
        if self.embedder is not None:
            device = getattr(self.embedder, "device_expression", None)
            if self._device_queries and device is not None:
                return device(query_column)
            return _apply_embedder(self.embedder, query_column)
        return query_column

    def preprocess_data(self, data_column: expr.ColumnReference) -> expr.ColumnExpression:
        if self.embedder is not None:
            return _apply_embedder(self.embedder, data_column)
        return data_column


def _apply_embedder(embedder: Any, column: Any) -> expr.ColumnExpression:
    from pathway_tpu.internals.udfs import UDF

    if isinstance(embedder, UDF) or callable(embedder):
        result = embedder(column)
        if isinstance(result, expr.ColumnExpression):
            return result
    raise TypeError("embedder must be a pw.UDF or callable producing an expression")


def _make_bf_index(dimensions: int, metric_s: str, reserved_space: int) -> Any:
    """Engine-facing index instance; a configured multi-shard mesh swaps in the
    row-sharded store with all-gather top-k merge (the reference's per-worker sharded
    index, ``external_index.rs`` + ``shard.rs``)."""
    from pathway_tpu.parallel.mesh import data_shards, get_default_mesh

    mesh = get_default_mesh()
    return BruteForceKnnIndex(
        dimensions,
        metric=metric_s,
        initial_capacity=max(16, reserved_space),
        mesh=mesh if data_shards(mesh) > 1 else None,
    )


class BruteForceKnn(_KnnInnerIndex):
    """Exact KNN on the TPU (reference ``BruteForceKnn:170`` over
    ``brute_force_knn_integration.rs``)."""

    _device_queries = True  # served: queries ride the encoder service

    def __init__(
        self,
        data_column: expr.ColumnReference,
        metadata_column: expr.ColumnReference | None = None,
        *,
        dimensions: int,
        reserved_space: int = 1024,
        auxiliary_space: int = 1024,
        metric: BruteForceKnnMetricKind = BruteForceKnnMetricKind.L2SQ,
        embedder: Any = None,
    ):
        metric_s = _metric_str(metric)
        super().__init__(
            data_column,
            metadata_column,
            dimensions,
            metric_s,
            embedder,
            make_index=lambda: _make_bf_index(dimensions, metric_s, reserved_space),
        )


class USearchKnn(_KnnInnerIndex):
    """API parity with the reference's HNSW index; served exactly on TPU (see module doc)."""

    _device_queries = True  # same dense-store kernel as BruteForceKnn

    def __init__(
        self,
        data_column: expr.ColumnReference,
        metadata_column: expr.ColumnReference | None = None,
        *,
        dimensions: int,
        reserved_space: int = 1024,
        metric: USearchMetricKind = USearchMetricKind.COS,
        connectivity: int = 16,
        expansion_add: int = 128,
        expansion_search: int = 64,
        embedder: Any = None,
    ):
        metric_s = _metric_str(metric)
        super().__init__(
            data_column,
            metadata_column,
            dimensions,
            metric_s,
            embedder,
            make_index=lambda: _make_bf_index(dimensions, metric_s, reserved_space),
        )


class LshKnn(_KnnInnerIndex):
    """Approximate KNN via random-projection LSH (reference ``LshKnn:262``)."""

    def __init__(
        self,
        data_column: expr.ColumnReference,
        metadata_column: expr.ColumnReference | None = None,
        *,
        dimensions: int,
        n_or: int = 8,
        n_and: int = 4,
        bucket_length: float = 4.0,
        distance_type: str = "euclidean",
        embedder: Any = None,
    ):
        metric = "cos" if distance_type == "cosine" else "l2sq"
        super().__init__(
            data_column,
            metadata_column,
            dimensions,
            metric,
            embedder,
            make_index=lambda: LshKnnIndex(
                dimensions,
                metric=metric,
                bucket_length=bucket_length,
                n_or=n_or,
                n_and=n_and,
            ),
        )


@dataclass
class _KnnFactoryBase(AbstractRetrieverFactory):
    dimensions: int | None = None
    reserved_space: int = 1024
    metric: Any = None
    embedder: Any = None

    index_cls: Any = None

    def build_inner_index(
        self,
        data_column: expr.ColumnReference,
        metadata_column: expr.ColumnReference | None = None,
    ) -> InnerIndex:
        dims = self.dimensions
        if dims is None and self.embedder is not None:
            dims = _probe_embedder_dims(self.embedder)
        assert dims is not None, "dimensions required (or an embedder to probe)"
        kwargs: dict = dict(dimensions=dims, embedder=self.embedder)
        if self.metric is not None:
            kwargs["metric"] = self.metric
        if self.index_cls in (BruteForceKnn, USearchKnn):
            kwargs["reserved_space"] = self.reserved_space
        return self.index_cls(data_column, metadata_column, **kwargs)

    def build_index(
        self,
        data_column: expr.ColumnReference,
        data_table: Table,
        metadata_column: expr.ColumnReference | None = None,
        **kwargs: Any,
    ) -> DataIndex:
        return DataIndex(data_table, self.build_inner_index(data_column, metadata_column))


def _probe_embedder_dims(embedder: Any) -> int:
    if hasattr(embedder, "get_embedding_dimension"):
        return int(embedder.get_embedding_dimension())
    if hasattr(embedder, "__wrapped__"):
        sample = embedder.__wrapped__("test")
        return len(sample)
    func = getattr(embedder, "func", None)
    if func is not None:
        import asyncio

        result = func("test")
        if asyncio.iscoroutine(result):
            result = asyncio.run(result)
        return len(result)
    raise ValueError("cannot determine embedder dimensionality")


class BruteForceKnnFactory(_KnnFactoryBase):
    def __init__(
        self,
        *,
        dimensions: int | None = None,
        reserved_space: int = 1024,
        auxiliary_space: int = 1024,
        metric: BruteForceKnnMetricKind = BruteForceKnnMetricKind.L2SQ,
        embedder: Any = None,
    ):
        super().__init__(dimensions, reserved_space, metric, embedder, BruteForceKnn)


class UsearchKnnFactory(_KnnFactoryBase):
    def __init__(
        self,
        *,
        dimensions: int | None = None,
        reserved_space: int = 1024,
        metric: USearchMetricKind = USearchMetricKind.COS,
        connectivity: int = 16,
        expansion_add: int = 128,
        expansion_search: int = 64,
        embedder: Any = None,
    ):
        super().__init__(dimensions, reserved_space, metric, embedder, USearchKnn)


USearchKnnFactory = UsearchKnnFactory


class LshKnnFactory(_KnnFactoryBase):
    def __init__(
        self,
        *,
        dimensions: int | None = None,
        n_or: int = 8,
        n_and: int = 4,
        bucket_length: float = 4.0,
        distance_type: str = "euclidean",
        embedder: Any = None,
    ):
        super().__init__(dimensions, 1024, None, embedder, LshKnn)
        self.n_or = n_or
        self.n_and = n_and
        self.bucket_length = bucket_length
        self.distance_type = distance_type

    def build_inner_index(
        self,
        data_column: expr.ColumnReference,
        metadata_column: expr.ColumnReference | None = None,
    ) -> InnerIndex:
        dims = self.dimensions or _probe_embedder_dims(self.embedder)
        return LshKnn(
            data_column,
            metadata_column,
            dimensions=dims,
            n_or=self.n_or,
            n_and=self.n_and,
            bucket_length=self.bucket_length,
            distance_type=self.distance_type,
            embedder=self.embedder,
        )


# -- document-index presets (reference ``:407-528`` + vector_document_index.py) ----


def default_brute_force_knn_document_index(
    data_column: expr.ColumnReference,
    data_table: Table,
    *,
    dimensions: int,
    embedder: Any = None,
    metadata_column: expr.ColumnReference | None = None,
    metric: BruteForceKnnMetricKind = BruteForceKnnMetricKind.COS,
) -> DataIndex:
    return DataIndex(
        data_table,
        BruteForceKnn(
            data_column,
            metadata_column,
            dimensions=dimensions,
            metric=metric,
            embedder=embedder,
        ),
    )


def default_usearch_knn_document_index(
    data_column: expr.ColumnReference,
    data_table: Table,
    *,
    dimensions: int,
    embedder: Any = None,
    metadata_column: expr.ColumnReference | None = None,
    metric: USearchMetricKind = USearchMetricKind.COS,
) -> DataIndex:
    return DataIndex(
        data_table,
        USearchKnn(
            data_column,
            metadata_column,
            dimensions=dimensions,
            metric=metric,
            embedder=embedder,
        ),
    )


def default_lsh_knn_document_index(
    data_column: expr.ColumnReference,
    data_table: Table,
    *,
    dimensions: int,
    embedder: Any = None,
    metadata_column: expr.ColumnReference | None = None,
) -> DataIndex:
    return DataIndex(
        data_table,
        LshKnn(data_column, metadata_column, dimensions=dimensions, embedder=embedder),
    )


def _make_ivf_index(
    dimensions: int, metric_s: str, reserved_space: int, n_clusters: int, n_probe: int
) -> Any:
    """Engine-facing IVF index instance; a configured multi-shard mesh swaps in
    the row-sharded IVF store (per-shard fused probe→gather→score kernel +
    top-k merge — the same merge contract as the dense sharded store)."""
    from pathway_tpu.ops.knn import IvfKnnIndex
    from pathway_tpu.parallel.mesh import data_shards, get_default_mesh

    mesh = get_default_mesh()
    return IvfKnnIndex(
        dimensions,
        metric=metric_s,
        initial_capacity=max(16, reserved_space),
        n_clusters=n_clusters,
        n_probe=n_probe,
        mesh=mesh if data_shards(mesh) > 1 else None,
    )


class IvfKnn(_KnnInnerIndex):
    """Approximate KNN via IVF-Flat on the TPU — the reference's ANN slot
    (``USearchKnn`` over HNSW, ``usearch_integration.rs:20``) filled with a
    coarse-quantizer design that maps to the MXU (``ops/knn_ivf.py``).
    ``n_probe`` trades recall for candidate volume; ``n_probe == n_clusters``
    degenerates to exact search."""

    _device_queries = True

    def __init__(
        self,
        data_column: expr.ColumnReference,
        metadata_column: expr.ColumnReference | None = None,
        *,
        dimensions: int,
        reserved_space: int = 1024,
        n_clusters: int = 64,
        n_probe: int = 8,
        metric: BruteForceKnnMetricKind = BruteForceKnnMetricKind.L2SQ,
        embedder: Any = None,
    ):
        metric_s = _metric_str(metric)
        super().__init__(
            data_column,
            metadata_column,
            dimensions,
            metric_s,
            embedder,
            make_index=lambda: _make_ivf_index(
                dimensions, metric_s, reserved_space, n_clusters, n_probe
            ),
        )


class IvfKnnFactory(_KnnFactoryBase):
    def __init__(
        self,
        *,
        dimensions: int | None = None,
        reserved_space: int = 1024,
        n_clusters: int = 64,
        n_probe: int = 8,
        metric: BruteForceKnnMetricKind = BruteForceKnnMetricKind.L2SQ,
        embedder: Any = None,
    ):
        super().__init__(dimensions, reserved_space, metric, embedder, IvfKnn)
        self.n_clusters = n_clusters
        self.n_probe = n_probe

    def build_inner_index(
        self,
        data_column: expr.ColumnReference,
        metadata_column: expr.ColumnReference | None = None,
    ) -> InnerIndex:
        dims = self.dimensions
        if dims is None and self.embedder is not None:
            dims = _probe_embedder_dims(self.embedder)
        assert dims is not None, "dimensions required (or an embedder to probe)"
        return IvfKnn(
            data_column,
            metadata_column,
            dimensions=dims,
            reserved_space=self.reserved_space,
            n_clusters=self.n_clusters,
            n_probe=self.n_probe,
            metric=self.metric,
            embedder=self.embedder,
        )
