"""Embedders (parity: reference ``xpacks/llm/embedders.py:64-401``).

``SentenceTransformerEmbedder`` is the TPU-native flagship: the HF encoder re-hosted as a
jit'd Flax module (``pathway_tpu/models/encoder.py``) with column-batched dispatch — the whole
commit batch crosses host→device once. API-backed embedders (OpenAI/LiteLLM/Gemini) are async
UDFs with capacity/retry/cache, gated on their client libraries.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import numpy as np

from pathway_tpu.internals import expression as expr
from pathway_tpu.internals.udfs import (
    AsyncRetryStrategy,
    CacheStrategy,
    UDF,
    async_executor,
)


class BaseEmbedder(UDF):
    # Embedders that know their output width up front set this (constructor
    # table/kwarg) so graph build never pays a real encode of "." — for the
    # API-backed embedders that probe was a NETWORK call (and an asyncio.run)
    # per index construction.
    _dimension: int | None = None

    def get_embedding_dimension(self, **kwargs: Any) -> int:
        if self._dimension is not None and not kwargs:
            return int(self._dimension)
        result = self.func(".", **kwargs)  # type: ignore[misc]
        import asyncio

        if asyncio.iscoroutine(result):
            result = asyncio.run(result)
        return len(result)


# Output widths of the fixed-dimension API models (the reference docs' values):
# consulted at graph-build time so known models skip the probe encode entirely.
_KNOWN_EMBED_DIMS = {
    "text-embedding-3-small": 1536,
    "text-embedding-3-large": 3072,
    "text-embedding-ada-002": 1536,
    "models/embedding-001": 768,
    "models/text-embedding-004": 768,
}


def _known_dim(model: str | None) -> int | None:
    if model is None:
        return None
    # litellm routes as "provider/model": match on the tail as well
    return _KNOWN_EMBED_DIMS.get(model) or _KNOWN_EMBED_DIMS.get(
        model.rsplit("/", 1)[-1]
    )


class SentenceTransformerEmbedder(BaseEmbedder):
    """Local encoder on the TPU (reference ``:270`` — torch ``model.encode`` at ``:315``)."""

    def __init__(
        self,
        model: str = "sentence-transformers/all-MiniLM-L6-v2",
        *,
        call_kwargs: "dict | None" = None,
        device: str = "tpu",
        batch_size: int = 1024,
        embed_cache_size: int = 50_000,
        encoder_config: Any = None,
        **kwargs: Any,
    ):
        """``embed_cache_size``: content-hash LRU entries (0 disables the
        content and the semantic query cache); ``encoder_config``: override
        ``EncoderConfig`` (tests use a tiny architecture). Query texts go
        through the pipeline's caches into the persistent encoder service,
        whose settings are its own (``models/encoder_service.py``)."""
        super().__init__(**kwargs)
        from pathway_tpu.models.embed_pipeline import EmbedPipeline
        from pathway_tpu.models.encoder import JaxSentenceEncoder

        if device not in ("tpu", None):
            import warnings

            warnings.warn(
                f"device={device!r} ignored: the encoder runs on the default JAX backend "
                "(TPU when available)",
                stacklevel=2,
            )
        if call_kwargs:
            import warnings

            warnings.warn(
                f"call_kwargs {sorted(call_kwargs)} are torch SentenceTransformer options "
                "with no JAX equivalent; ignored",
                stacklevel=2,
            )
        self.encoder = JaxSentenceEncoder(model, config=encoder_config)
        self.batch_size = batch_size
        self.pipeline = EmbedPipeline(
            self.encoder, model=model, cache_size=embed_cache_size
        )

        def embed_one(text: str) -> np.ndarray:
            return self.pipeline.encode_batch([str(text)])[0]

        self.func = embed_one

    def __call__(self, *args: Any, **kwargs: Any) -> expr.ColumnExpression:
        pipeline = self.pipeline

        def embed_batch(texts: List[str]) -> List[np.ndarray]:
            vectors = pipeline.encode_batch(texts)
            return [vectors[i] for i in range(len(texts))]

        return expr.BatchApplyExpression(
            embed_batch,
            np.ndarray,
            False,
            True,
            args,
            kwargs,
            max_batch_size=self.batch_size,
        )

    def device_expression(self, *args: Any, **kwargs: Any) -> expr.ColumnExpression:
        """Query-path variant: embedding cells are read-only host float32 rows
        (views of the one array an encoder tick fetched), which the KNN search
        stacks, pads and ships to the device in one transfer.
        Runs through the pipeline's content-hash + semantic caches and submits
        misses into the persistent encoder service's continuous batch, so a
        solo query dispatches immediately into a pre-warmed jit bucket,
        concurrent retrieve queries share one encoder dispatch, and
        repeated/equivalent texts skip the forward entirely.

        Declared ``deterministic=False`` so the engine memoizes each query row's
        embedding and REPLAYS it on retraction (the rest connector's
        delete-completed-queries cleanup) instead of re-running the encoder — one
        encode per query, with the memo entry popped on retraction. The content
        cache sits BELOW that memo: it never answers retraction rows, it only
        dedups forward work across distinct rows with equal text."""
        pipeline = self.pipeline

        def embed_batch(texts: List[str]) -> List[Any]:
            return pipeline.embed_query_rows([str(t) for t in texts])

        return expr.BatchApplyExpression(
            embed_batch,
            np.ndarray,
            False,
            False,
            args,
            kwargs,
            max_batch_size=self.batch_size,
        )

    def pipeline_stats(self) -> dict:
        """Cache/encoder-service/pad-waste counters (surfaced by
        ``DocumentStore.statistics_query``)."""
        return self.pipeline.stats()

    def get_embedding_dimension(self, **kwargs: Any) -> int:
        return self.encoder.dim


class OpenAIEmbedder(BaseEmbedder):
    """OpenAI embeddings API (reference ``:85``)."""

    def __init__(
        self,
        capacity: int | None = None,
        model: str | None = "text-embedding-3-small",
        retry_strategy: AsyncRetryStrategy | None = None,
        cache_strategy: CacheStrategy | None = None,
        api_key: str | None = None,
        **openai_kwargs: Any,
    ):
        super().__init__(
            executor=async_executor(capacity=capacity),
            retry_strategy=retry_strategy,
            cache_strategy=cache_strategy,
        )
        self.model = model
        self.kwargs = dict(openai_kwargs)
        # graph build learns the dim WITHOUT a network call: an explicit
        # ``dimensions=`` request (v3 models) wins, else the model table
        if "dimensions" in self.kwargs:
            self._dimension = int(self.kwargs["dimensions"])
        else:
            self._dimension = _known_dim(model)
        self.api_key = api_key
        self._client: Any = None
        self._client_loop: Any = None

        async def embed(input: str, **kwargs: Any) -> list:
            import asyncio

            # cache per event loop: each commit batch runs under its own asyncio.run()
            loop = asyncio.get_running_loop()
            if self._client is None or self._client_loop is not loop:
                try:
                    import openai
                except ImportError as e:
                    raise ImportError("openai client library is not installed") from e
                from pathway_tpu.xpacks.llm._utils import close_async_client

                await close_async_client(self._client)
                self._client = openai.AsyncOpenAI(api_key=self.api_key)
                self._client_loop = loop
            response = await self._client.embeddings.create(
                input=[input or "."], model=kwargs.get("model", self.model), **self.kwargs
            )
            return response.data[0].embedding

        self.func = embed


class LiteLLMEmbedder(BaseEmbedder):
    """LiteLLM multi-provider embeddings (reference ``:180``)."""

    def __init__(
        self,
        capacity: int | None = None,
        model: str | None = None,
        retry_strategy: AsyncRetryStrategy | None = None,
        cache_strategy: CacheStrategy | None = None,
        **litellm_kwargs: Any,
    ):
        super().__init__(
            executor=async_executor(capacity=capacity),
            retry_strategy=retry_strategy,
            cache_strategy=cache_strategy,
        )
        self.model = model
        self.kwargs = dict(litellm_kwargs)
        if "dimensions" in self.kwargs:
            self._dimension = int(self.kwargs["dimensions"])
        else:
            self._dimension = _known_dim(model)

        async def embed(input: str, **kwargs: Any) -> list:
            try:
                import litellm
            except ImportError as e:
                raise ImportError("litellm is not installed") from e
            response = await litellm.aembedding(
                input=[input or "."], model=kwargs.get("model", self.model), **self.kwargs
            )
            return response.data[0]["embedding"]

        self.func = embed


class GeminiEmbedder(BaseEmbedder):
    """Google Gemini embeddings (reference ``:330``)."""

    def __init__(
        self,
        model: str | None = "models/embedding-001",
        capacity: int | None = None,
        retry_strategy: AsyncRetryStrategy | None = None,
        cache_strategy: CacheStrategy | None = None,
        **genai_kwargs: Any,
    ):
        super().__init__(
            executor=async_executor(capacity=capacity),
            retry_strategy=retry_strategy,
            cache_strategy=cache_strategy,
        )
        self.model = model
        self.kwargs = dict(genai_kwargs)
        if "output_dimensionality" in self.kwargs:
            self._dimension = int(self.kwargs["output_dimensionality"])
        else:
            self._dimension = _known_dim(model)

        async def embed(input: str, **kwargs: Any) -> list:
            try:
                import google.generativeai as genai
            except ImportError as e:
                raise ImportError("google-generativeai is not installed") from e
            response = genai.embed_content(
                content=input or ".", model=kwargs.get("model", self.model), **self.kwargs
            )
            return response["embedding"]

        self.func = embed
