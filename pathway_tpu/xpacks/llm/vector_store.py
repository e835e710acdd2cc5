"""VectorStoreServer — the reference's flagship RAG service.

Parity: reference ``xpacks/llm/vector_store.py:39`` (graph ``:227-310``, REST ``run_server:478``):
document sources → parse → split → TPU embedder → KNN index; REST endpoints
``/v1/retrieve``, ``/v1/statistics``, ``/v1/inputs``. Plus ``VectorStoreClient``.
"""

from __future__ import annotations

import json
import threading
from typing import Any, Callable, Iterable, List, Optional

import pathway_tpu as pw
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import expression as expr
from pathway_tpu.internals.json import Json
from pathway_tpu.internals.table import Table
from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory, BruteForceKnnMetricKind
from pathway_tpu.xpacks.llm.document_store import DocumentStore


class VectorStoreServer:
    """docs sources + embedder → served KNN index (reference ``vector_store.py:39``)."""

    def __init__(
        self,
        *docs: Table,
        embedder: Any,
        parser: Any = None,
        splitter: Any = None,
        doc_post_processors: list[Callable] | None = None,
        index_factory: Any = None,
    ):
        self.embedder = embedder
        if index_factory is None:
            index_factory = BruteForceKnnFactory(
                embedder=embedder, metric=BruteForceKnnMetricKind.COS
            )
        elif index_factory == "ivf":
            # sublinear serving at large corpora: the IVF-Flat index's fused
            # probe→gather→score kernel (ops/knn_ivf.py) end-to-end — embed →
            # probe centroids → stream candidate pages → top-k, one device
            # round-trip per query batch
            from pathway_tpu.stdlib.indexing.nearest_neighbors import IvfKnnFactory

            index_factory = IvfKnnFactory(
                embedder=embedder, metric=BruteForceKnnMetricKind.COS
            )
        self.docs = list(docs)
        self.store = DocumentStore(
            self.docs,
            retriever_factory=index_factory,
            parser=parser,
            splitter=splitter,
            doc_post_processors=doc_post_processors,
        )

    # reference schema names
    class QuerySchema(pw.Schema):
        query: str
        k: int = pw.column_definition(default_value=3, dtype=int)
        metadata_filter: str | None = pw.column_definition(default_value=None)
        filepath_globpattern: str | None = pw.column_definition(default_value=None)

    class StatisticsSchema(pw.Schema):
        pass

    class InputsQuerySchema(pw.Schema):
        metadata_filter: str | None = pw.column_definition(default_value=None)
        filepath_globpattern: str | None = pw.column_definition(default_value=None)

    def retrieve_query(self, queries: Table) -> Table:
        return self.store.retrieve_query(queries)

    def statistics_query(self, queries: Table) -> Table:
        return self.store.statistics_query(queries)

    def inputs_query(self, queries: Table) -> Table:
        return self.store.inputs_query(queries)

    @property
    def index(self) -> Any:
        return self.store.index

    def run_server(
        self,
        host: str = "0.0.0.0",
        port: int = 8000,
        *,
        threaded: bool = False,
        with_cache: bool = True,
        cache_backend: Any = None,
        terminate_on_error: bool = True,
    ) -> Any:
        """Serve /v1/retrieve, /v1/statistics, /v1/inputs (reference ``:478``)."""
        from pathway_tpu.io.http import PathwayWebserver, rest_connector

        webserver = PathwayWebserver(host=host, port=port)
        # retrieve is the embed-bound route: cap admitted-but-unanswered
        # queries so an embed stampede sheds (429 + Retry-After, counted as
        # pathway_stage_total{stage="embed.shed"}) instead of queueing without
        # bound in front of the encoder
        import os as _os

        max_pending = int(_os.environ.get("PATHWAY_EMBED_MAX_PENDING", "1024"))
        pipeline = getattr(
            getattr(self.store, "embedder", None) or self.embedder, "pipeline", None
        )
        service = getattr(pipeline, "service", None)
        retrieve_queries, retrieve_writer = rest_connector(
            webserver=webserver,
            route="/v1/retrieve",
            schema=self.QuerySchema,
            methods=("GET", "POST"),
            delete_completed_queries=True,
            max_pending=max_pending,
            shed_stage="embed.shed",
            retry_after=service.retry_after_s if service is not None else None,
            # second line of defense: the encoder service's row cap
            # (PATHWAY_EMBED_MAX_QUEUE_ROWS) probed pre-admission, so a slow
            # encoder sheds on pending ROWS even while fewer than max_pending
            # REQUESTS are in flight
            overload_probe=service.overloaded if service is not None else None,
        )
        retrieve_writer(self.retrieve_query(retrieve_queries))

        stats_queries, stats_writer = rest_connector(
            webserver=webserver,
            route="/v1/statistics",
            schema=self.StatisticsSchema,
            methods=("GET", "POST"),
            delete_completed_queries=True,
        )
        stats_writer(self.statistics_query(stats_queries))

        inputs_queries, inputs_writer = rest_connector(
            webserver=webserver,
            route="/v1/inputs",
            schema=self.InputsQuerySchema,
            methods=("GET", "POST"),
            delete_completed_queries=True,
        )
        inputs_writer(self.inputs_query(inputs_queries))

        def run() -> None:
            pw.run(
                monitoring_level=pw.MonitoringLevel.NONE,
                terminate_on_error=terminate_on_error,
            )

        if threaded:
            thread = threading.Thread(target=run, daemon=True, name="pathway:vector-server")
            thread.start()
            return thread
        run()
        return None


class VectorStoreClient:
    """HTTP client for VectorStoreServer (reference ``vector_store.py`` client)."""

    def __init__(
        self,
        host: str | None = None,
        port: int | None = None,
        url: str | None = None,
        timeout: int = 15,
        additional_headers: dict | None = None,
    ):
        self.url = url if url is not None else f"http://{host}:{port}"
        self.timeout = timeout
        self.headers = {"Content-Type": "application/json", **(additional_headers or {})}

    def query(
        self, query: str, k: int = 3, metadata_filter: str | None = None, filepath_globpattern: str | None = None
    ) -> list:
        import requests

        data = {"query": query, "k": k}
        if metadata_filter is not None:
            data["metadata_filter"] = metadata_filter
        if filepath_globpattern is not None:
            data["filepath_globpattern"] = filepath_globpattern
        response = requests.post(
            self.url + "/v1/retrieve", json=data, headers=self.headers, timeout=self.timeout
        )
        response.raise_for_status()
        return response.json()

    __call__ = query

    def get_vectorstore_statistics(self) -> dict:
        import requests

        response = requests.post(
            self.url + "/v1/statistics", json={}, headers=self.headers, timeout=self.timeout
        )
        response.raise_for_status()
        return response.json()

    def get_input_files(
        self, metadata_filter: str | None = None, filepath_globpattern: str | None = None
    ) -> list:
        import requests

        response = requests.post(
            self.url + "/v1/inputs",
            json={"metadata_filter": metadata_filter, "filepath_globpattern": filepath_globpattern},
            headers=self.headers,
            timeout=self.timeout,
        )
        response.raise_for_status()
        return response.json()
