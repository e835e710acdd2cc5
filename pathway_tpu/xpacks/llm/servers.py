"""REST servers for RAG apps (parity: reference ``xpacks/llm/servers.py:16-227``)."""

from __future__ import annotations

import threading
from typing import Any, Optional

import pathway_tpu as pw
from pathway_tpu.internals.table import Table


class BaseRestServer:
    """Builds rest_connector endpoints over a webserver (reference ``:16``)."""

    def __init__(self, host: str, port: int, **rest_kwargs: Any):
        from pathway_tpu.io.http import PathwayWebserver

        self.host = host
        self.port = port
        self.webserver = PathwayWebserver(host=host, port=port, **rest_kwargs)

    def serve(
        self,
        route: str,
        schema: type,
        handler: Any,
        *,
        methods: tuple = ("POST",),
        retry_strategy: Any = None,
        cache_strategy: Any = None,
        **additional_endpoint_kwargs: Any,
    ) -> None:
        import warnings

        from pathway_tpu.io.http import rest_connector

        if retry_strategy is not None or cache_strategy is not None:
            # reference applies these to the endpoint's response path; engine-level UDF
            # caching isn't wired yet — configure the strategies on the LLM /
            # embedder UDFs instead, which does work
            warnings.warn(
                "retry_strategy/cache_strategy on serve() are not applied yet; set them "
                "on the UDFs (e.g. OpenAIChat(retry_strategy=...)) instead",
                stacklevel=2,
            )
        queries, writer = rest_connector(
            webserver=self.webserver,
            route=route,
            schema=schema,
            methods=methods,
            delete_completed_queries=True,
            **additional_endpoint_kwargs,
        )
        writer(handler(queries))

    def run(
        self,
        *,
        threaded: bool = False,
        with_cache: bool = True,
        cache_backend: Any = None,
        terminate_on_error: bool = True,
        **kwargs: Any,
    ) -> Any:
        # with_cache/cache_backend configure UDF caching in the reference; here caching is
        # set per-UDF via cache_strategy (see internals/udfs), so they are accepted for
        # API parity but have no engine-level effect yet.
        def target() -> None:
            pw.run(monitoring_level=pw.MonitoringLevel.NONE, terminate_on_error=terminate_on_error)

        if threaded:
            thread = threading.Thread(target=target, daemon=True, name="pathway:rest-server")
            thread.start()
            return thread
        target()
        return None


class DocumentStoreServer(BaseRestServer):
    """Serves retrieve/statistics/inputs of a DocumentStore (reference ``:92``)."""

    def __init__(self, host: str, port: int, document_store: Any, **rest_kwargs: Any):
        super().__init__(host, port, **rest_kwargs)
        store = document_store.store if hasattr(document_store, "store") else document_store
        self.serve(
            "/v1/retrieve", store.RetrieveQuerySchema, store.retrieve_query, methods=("GET", "POST")
        )
        self.serve(
            "/v1/statistics",
            store.StatisticsQuerySchema,
            store.statistics_query,
            methods=("GET", "POST"),
        )
        self.serve(
            "/v1/inputs", store.InputsQuerySchema, store.inputs_query, methods=("GET", "POST")
        )


class QARestServer(BaseRestServer):
    """Serves answer/retrieve/statistics/list_documents of a QuestionAnswerer (``:140``)."""

    def __init__(self, host: str, port: int, rag_question_answerer: Any, **rest_kwargs: Any):
        super().__init__(host, port, **rest_kwargs)
        qa = rag_question_answerer
        self.serve("/v1/pw_ai_answer", qa.AnswerQuerySchema, qa.answer_query)
        self.serve("/v2/answer", qa.AnswerQuerySchema, qa.answer_query)
        self.serve("/v1/retrieve", qa.RetrieveQuerySchema, qa.retrieve, methods=("GET", "POST"))
        self.serve("/v2/list_documents", qa.InputsQuerySchema, qa.list_documents, methods=("GET", "POST"))
        self.serve("/v1/statistics", qa.StatisticsQuerySchema, qa.statistics, methods=("GET", "POST"))


class QASummaryRestServer(QARestServer):
    """Adds the summarize endpoint (reference ``:193``)."""

    def __init__(self, host: str, port: int, rag_question_answerer: Any, **rest_kwargs: Any):
        super().__init__(host, port, rag_question_answerer, **rest_kwargs)
        qa = rag_question_answerer
        self.serve("/v1/pw_ai_summary", qa.SummarizeQuerySchema, qa.summarize_query)
        self.serve("/v2/summarize", qa.SummarizeQuerySchema, qa.summarize_query)
