"""LLM chat wrappers (parity: reference ``xpacks/llm/llms.py:27-654``).

``OpenAIChat`` (``:84``), ``LiteLLMChat`` (``:313``), ``HFPipelineChat`` (``:441``),
``CohereChat`` (``:544``) — async UDFs with capacity/retry/cache; clients gated at call time.
``DeviceChat`` is the one that calls nothing out: a slot decoder on this process's device
behind a generation service (``models/generation_service.py``), and the one whose call
outlives the commit that made it (``fully_async_executor``); ``Lfm2Chat`` (``models/lfm2.py``),
``Mistral4Chat`` (``models/mistral4.py``) and ``FalconH1Chat`` (``models/falcon_h1.py``) are it
over their decoders.
"""

from __future__ import annotations

import json
from typing import Any, List, Optional

from pathway_tpu.internals import expression as expr
from pathway_tpu.internals.json import Json
from pathway_tpu.internals.udfs import (
    AsyncRetryStrategy,
    CacheStrategy,
    UDF,
    async_executor,
    fully_async_executor,
)


class BaseChat(UDF):
    """Common surface: call on a messages column (list of {role, content} dicts)."""

    def _accepts_call_arg(self, arg_name: str) -> bool:
        return True


def _coerce_messages(messages: Any) -> List[dict]:
    if isinstance(messages, Json):
        messages = messages.value
    if isinstance(messages, str):
        return [{"role": "user", "content": messages}]
    out = []
    for m in messages:
        if isinstance(m, Json):
            m = m.value
        out.append(dict(m))
    return out


class OpenAIChat(BaseChat):
    def __init__(
        self,
        capacity: int | None = None,
        model: str | None = "gpt-4o-mini",
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
        self.api_key = api_key
        self._client: Any = None
        self._client_loop: Any = None

        async def chat(messages: Any, **kwargs: Any) -> str | None:
            import asyncio

            # the engine runs each commit batch under its own asyncio.run() loop — a
            # client's connection pool is loop-bound, so cache per loop, reuse per batch
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
            merged = {k: v for k, v in {**self.kwargs, **kwargs}.items() if v is not None}
            merged.setdefault("model", self.model)
            response = await self._client.chat.completions.create(
                messages=_coerce_messages(messages), **merged
            )
            return response.choices[0].message.content

        self.func = chat


class LiteLLMChat(BaseChat):
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

        async def chat(messages: Any, **kwargs: Any) -> str | None:
            try:
                import litellm
            except ImportError as e:
                raise ImportError("litellm is not installed") from e
            merged = {k: v for k, v in {**self.kwargs, **kwargs}.items() if v is not None}
            merged.setdefault("model", self.model)
            response = await litellm.acompletion(messages=_coerce_messages(messages), **merged)
            return response.choices[0].message.content

        self.func = chat


class HFPipelineChat(BaseChat):
    """Local HuggingFace text-generation pipeline (CPU; reference ``:441``)."""

    def __init__(
        self,
        model: str | None = None,
        call_kwargs: "dict | None" = None,
        device: str = "cpu",
        cache_strategy: CacheStrategy | None = None,
        **pipeline_kwargs: Any,
    ):
        super().__init__(cache_strategy=cache_strategy)
        import os

        os.environ.setdefault("HF_HUB_OFFLINE", "1")
        os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
        from transformers import pipeline

        self.pipeline = pipeline("text-generation", model=model, device=device, **pipeline_kwargs)
        self.call_kwargs = dict(call_kwargs or {})

        def chat(messages: Any, **kwargs: Any) -> str | None:
            coerced = _coerce_messages(messages)
            merged = {k: v for k, v in {**self.call_kwargs, **kwargs}.items() if v is not None}
            output = self.pipeline(coerced, **merged)
            result = output[0]["generated_text"]
            if isinstance(result, list):
                return result[-1]["content"]
            return result

        self.func = chat

    def crop_to_max_length(self, input_string: str, max_prompt_length: int = 500) -> str:
        tokens = self.pipeline.tokenizer.tokenize(input_string)
        if len(tokens) > max_prompt_length:
            tokens = tokens[-max_prompt_length:]
        return self.pipeline.tokenizer.convert_tokens_to_string(tokens)


class CohereChat(BaseChat):
    def __init__(
        self,
        capacity: int | None = None,
        model: str | None = "command",
        retry_strategy: AsyncRetryStrategy | None = None,
        cache_strategy: CacheStrategy | None = None,
        **cohere_kwargs: Any,
    ):
        super().__init__(
            executor=async_executor(capacity=capacity),
            retry_strategy=retry_strategy,
            cache_strategy=cache_strategy,
        )
        self.model = model
        self.kwargs = dict(cohere_kwargs)

        async def chat(messages: Any, **kwargs: Any) -> tuple:
            try:
                import cohere
            except ImportError as e:
                raise ImportError("cohere client library is not installed") from e
            merged = {k: v for k, v in {**self.kwargs, **kwargs}.items() if v is not None}
            merged.setdefault("model", self.model)
            coerced = _coerce_messages(messages)
            client = cohere.AsyncClient()
            response = await client.chat(
                message=coerced[-1]["content"],
                chat_history=coerced[:-1],
                **merged,
            )
            cited_documents = [dict(d) for d in (response.documents or [])]
            return response.text, cited_documents

        self.func = chat


class DeviceChat(BaseChat):
    """A chat model on this process's device: a slot decoder
    (``models/slot_decoder.py``) behind a ``GenerationService``. It owns the
    tokenizer, ``render``, the ``generate`` span, the executor and the
    service; ``Lfm2Chat``, ``Mistral4Chat`` and ``FalconH1Chat`` differ only in
    the decoder they build and hand over.

    Its limits: greedy decoding, exactly ``max_new_tokens`` tokens a reply, no
    stop token; the tokenizer is the repository's ``HashTokenizer`` over the
    decoder's vocabulary (one token a lower-cased whitespace word, no
    vocabulary file), so the reply is the generated ids written as words,
    ``t<id>`` each (``reply_ids`` reads them back). A prompt longer than
    ``max_prompt_tokens`` keeps its last tokens. The messages are rendered as
    their contents, one a line: there is no chat template without a
    checkpoint's tokenizer.

    Its executor is ``fully_async_executor``: the commit that carries a prompt
    hands it to the service and ends, and the reply is a row of a later commit
    (a ``select`` that calls the chat has a row once its reply is there). The
    API chats above keep ``async_executor`` and are awaited inside the commit."""

    def __init__(self, decoder: Any, cache_strategy: CacheStrategy | None = None):
        # a reply takes a prefill and many steps of the service's own loop, which admits a
        # prompt between any two of them: the call leaves the commit that carried the prompt, and
        # the answer re-enters when it is ready (a wake-up and the REST connector's tick, no timer)
        super().__init__(executor=fully_async_executor(autocommit_duration_ms=1), cache_strategy=cache_strategy)
        from pathway_tpu.models.encoder import HashTokenizer
        from pathway_tpu.models.generation_service import GenerationService

        self.config, self.decoder = decoder.cfg, decoder
        self.service = GenerationService(decoder)
        self._tokenizer = HashTokenizer(vocab_size=self.config.vocab_size, max_length=1 << 30)

        async def chat(messages: Any, **kwargs: Any) -> str:
            import asyncio

            from pathway_tpu.engine import tracing

            ids = self.tokenize(self.render(messages))
            tracer = tracing.get_tracer()
            # lives across the await, so a span and never an annotation
            span = tracer.start("generate", attrs={"prompt_tokens": len(ids)})
            try:
                future = self.service.submit(ids, ctx=span.context() if span is not None else None)
                tokens = await asyncio.wrap_future(future)
            finally:
                if span is not None:
                    tracer.finish(span)
            return " ".join(f"t{t}" for t in tokens)

        self.func = chat

    @staticmethod
    def render(messages: Any) -> str:
        return "\n".join(str(m.get("content", "")) for m in _coerce_messages(messages))

    def tokenize(self, text: str) -> List[int]:
        """One id a word (``HashTokenizer`` without its [CLS]/[SEP]), the last
        ``max_prompt_tokens`` of them."""
        ids, _ = self._tokenizer([text])
        return ids[0, 1:-1].tolist()[-self.decoder.max_prompt_tokens :]

    @staticmethod
    def reply_ids(reply: str) -> List[int]:
        """The generated ids of a reply of this chat, exactly."""
        return [int(word[1:]) for word in reply.split()]


class Lfm2Chat(DeviceChat):
    """``DeviceChat`` over the ``lfm2_moe`` decoder (LFM2-8B-A1B's family,
    ``models/lfm2.py``); the shared chat has the limits, the tokenizer and the
    executor. ``config`` is a published ``config.json`` as a dict, cut to what
    the chip holds: LFM2-8B-A1B's 24 layers are 16.7 GB in bfloat16, over one
    chip's 16, so there is no default (``benchmarks/configs/lfm2-8b-a1b-rag.json``
    serves its layers 0-13). The weights are random from ``seed`` unless
    ``params`` (a tree of ``models/lfm2.param_shapes``) is given."""

    def __init__(
        self,
        config: dict,
        params: Any = None,
        *,
        slots: int = 16,
        max_prompt_tokens: int = 1024,
        max_new_tokens: int = 32,
        prefill_buckets: tuple = (256, 512, 1024),
        seed: int = 0,
        cache_strategy: CacheStrategy | None = None,
    ):
        # the device is touched here, never at import: a process that builds no such chat loads no jax
        from pathway_tpu.models.lfm2 import Lfm2Config, Lfm2Decoder

        super().__init__(Lfm2Decoder(
            Lfm2Config.from_dict(config), params, slots=slots, max_prompt_tokens=max_prompt_tokens,
            max_new_tokens=max_new_tokens, prefill_buckets=prefill_buckets, seed=seed,
        ), cache_strategy)


class Mistral4Chat(DeviceChat):
    """``DeviceChat`` over the ``mistral4`` decoder (Mistral-Small-4-119B-2603's
    family, ``models/mistral4.py``: latent attention over a compressed cache, a
    shared expert beside the routed ones). ``config`` is a published
    ``config.json`` as a dict, cut to one chip's share: ``num_hidden_layers``
    the layers of its pipeline stage, ``n_routed_experts`` the experts held of
    the router's ``n_router_experts`` from ``first_expert`` on, ``vocab_size``
    the rows of the table and the head held (the whole model is 238 GB in
    bfloat16, so there is no default;
    ``benchmarks/configs/mistral-small-4-119b-rag.json`` serves one chip's share
    of six layers). The weights are random from ``seed`` unless ``params`` (a
    tree of ``models/mistral4.param_shapes``) is given."""

    def __init__(
        self,
        config: dict,
        params: Any = None,
        *,
        slots: int = 16,
        max_prompt_tokens: int = 2048,
        max_new_tokens: int = 64,
        prefill_buckets: tuple = (1024, 1536, 2048),
        seed: int = 0,
        cache_strategy: CacheStrategy | None = None,
    ):
        from pathway_tpu.models.mistral4 import Mistral4Config, Mistral4Decoder

        super().__init__(Mistral4Decoder(
            Mistral4Config.from_dict(config), params, slots=slots, max_prompt_tokens=max_prompt_tokens,
            max_new_tokens=max_new_tokens, prefill_buckets=prefill_buckets, seed=seed,
        ), cache_strategy)


class FalconH1Chat(DeviceChat):
    """``DeviceChat`` over the ``falcon_h1`` decoder (Falcon-H1-34B-Instruct's
    family, ``models/falcon_h1.py``: a Mamba-2 state-space mixer and
    grouped-query attention side by side in every block, the mixer's recurrent
    state kept in the slot beside keys and values). ``config`` is a published
    ``config.json`` as a dict, cut to what the chip holds: the whole model's 72
    blocks with the table and the untied head are 67.3 GB in bfloat16, over any
    chip's 16, so there is no default (``benchmarks/configs/falcon-h1-34b-rag.json``
    serves its blocks 0-5). The weights are random from ``seed`` unless
    ``params`` (a tree of ``models/falcon_h1.param_shapes``) is given."""

    def __init__(
        self,
        config: dict,
        params: Any = None,
        *,
        slots: int = 32,
        max_prompt_tokens: int = 1024,
        max_new_tokens: int = 128,
        prefill_buckets: tuple = (256, 512, 1024),
        seed: int = 0,
        cache_strategy: CacheStrategy | None = None,
    ):
        from pathway_tpu.models.falcon_h1 import FalconH1Config, FalconH1Decoder

        super().__init__(FalconH1Decoder(
            FalconH1Config.from_dict(config), params, slots=slots, max_prompt_tokens=max_prompt_tokens,
            max_new_tokens=max_new_tokens, prefill_buckets=prefill_buckets, seed=seed,
        ), cache_strategy)


def prompt_chat_single_qa(question: str) -> Json:
    """Wrap a question into a single-message chat prompt (reference helper)."""
    return Json([{"role": "user", "content": str(question)}])
