"""UDF system: ``pw.udf`` decorator, executors, caching, retries.

Parity: reference ``internals/udfs/`` (``class UDF`` ``__init__.py:68``, executors
``executors.py:36-132``, caches ``caches.py:35,120``, retries ``retries.py:58,107``).
UDF calls are batched column-wise by the engine; async UDFs gather per-batch with capacity
control, mirroring the reference's tokio-futures batching (``dataflow.rs:1442``).
"""

from __future__ import annotations

import asyncio
import functools
import hashlib
import inspect
import pickle
import time
from typing import Any, Callable, Optional

from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import expression as expr


# -- retries ----------------------------------------------------------------


class AsyncRetryStrategy:
    async def invoke(self, fun: Callable, /, *args: Any, **kwargs: Any) -> Any:
        return await fun(*args, **kwargs)


class NoRetryStrategy(AsyncRetryStrategy):
    pass


class ExponentialBackoffRetryStrategy(AsyncRetryStrategy):
    # NOTE: persistence.backends.RetryingObjectStore mirrors this schedule in a
    # sync loop (exact-type-gated); changing the retry behavior here means
    # changing it there too, or subclassing so the sync fast path is bypassed.
    def __init__(
        self,
        max_retries: int = 3,
        initial_delay: int = 1000,
        backoff_factor: float = 2,
        jitter_ms: int = 300,
    ):
        self.max_retries = max_retries
        self.initial_delay = initial_delay / 1000
        self.backoff_factor = backoff_factor
        self.jitter = jitter_ms / 1000

    async def invoke(self, fun: Callable, /, *args: Any, **kwargs: Any) -> Any:
        delay = self.initial_delay
        for attempt in range(self.max_retries + 1):
            try:
                return await fun(*args, **kwargs)
            except Exception:
                if attempt == self.max_retries:
                    raise
                import random

                await asyncio.sleep(delay + random.random() * self.jitter)
                delay *= self.backoff_factor
        raise RuntimeError("unreachable")


class FixedDelayRetryStrategy(ExponentialBackoffRetryStrategy):
    def __init__(self, max_retries: int = 3, delay_ms: int = 1000):
        super().__init__(max_retries=max_retries, initial_delay=delay_ms, backoff_factor=1, jitter_ms=0)


# -- caches -----------------------------------------------------------------


class CacheStrategy:
    def get(self, key: str) -> Any:
        raise KeyError(key)

    def set(self, key: str, value: Any) -> None:
        pass


class InMemoryCache(CacheStrategy):
    def __init__(self) -> None:
        self._data: dict[str, Any] = {}

    def get(self, key: str) -> Any:
        return self._data[key]

    def set(self, key: str, value: Any) -> None:
        self._data[key] = value


class DiskCache(CacheStrategy):
    """Sqlite-backed persistent cache (reference uses a disk KV store)."""

    def __init__(self, name: str | None = None, directory: str | None = None):
        import os
        import sqlite3

        directory = directory or os.environ.get("PATHWAY_PERSISTENT_STORAGE", "/tmp/pathway-cache")
        os.makedirs(directory, exist_ok=True)
        self._path = os.path.join(directory, f"udf-cache-{name or 'default'}.db")
        self._conn = sqlite3.connect(self._path, check_same_thread=False)
        self._conn.execute("CREATE TABLE IF NOT EXISTS cache (k TEXT PRIMARY KEY, v BLOB)")
        import threading

        self._lock = threading.Lock()

    def get(self, key: str) -> Any:
        with self._lock:
            row = self._conn.execute("SELECT v FROM cache WHERE k=?", (key,)).fetchone()
        if row is None:
            raise KeyError(key)
        return pickle.loads(row[0])

    def set(self, key: str, value: Any) -> None:
        with self._lock:
            self._conn.execute(
                "INSERT OR REPLACE INTO cache VALUES (?, ?)", (key, pickle.dumps(value))
            )
            self._conn.commit()


DefaultCache = DiskCache


def wrap_async(
    fun: Callable,
    *,
    capacity: int | None = None,
    timeout: float | None = None,
    retry_strategy: "AsyncRetryStrategy | None" = None,
    cache_strategy: "CacheStrategy | None" = None,
    name: str = "async_fn",
) -> Callable:
    """Compose capacity/timeout/retries/caching around an async callable — the ONE
    wrapper both ``pw.udf`` async executors and ``AsyncTransformer.with_options``
    build on (``CacheStrategy.get`` raises ``KeyError`` on miss)."""
    import asyncio as _asyncio

    if timeout is not None:
        inner_t = fun

        async def with_timeout(*args: Any, **kwargs: Any) -> Any:
            return await _asyncio.wait_for(inner_t(*args, **kwargs), timeout=timeout)

        fun = with_timeout
    if retry_strategy is not None:
        inner_r = fun

        async def with_retries(*args: Any, **kwargs: Any) -> Any:
            return await retry_strategy.invoke(inner_r, *args, **kwargs)

        fun = with_retries
    if capacity:
        inner_c = fun
        semaphore = _asyncio.Semaphore(capacity)

        async def with_capacity(*args: Any, **kwargs: Any) -> Any:
            async with semaphore:
                return await inner_c(*args, **kwargs)

        fun = with_capacity
    if cache_strategy is not None:
        inner_k = fun

        async def cached(*args: Any, **kwargs: Any) -> Any:
            key = _cache_key(name, args, kwargs)
            try:
                return cache_strategy.get(key)
            except KeyError:
                value = await inner_k(*args, **kwargs)
                cache_strategy.set(key, value)
                return value

        fun = cached
    return fun


def _cache_key(name: str, args: tuple, kwargs: dict) -> str:
    payload = pickle.dumps((name, args, sorted(kwargs.items())))
    return hashlib.sha256(payload).hexdigest()


# -- executors --------------------------------------------------------------


class Executor:
    pass


class AutoExecutor(Executor):
    pass


class SyncExecutor(Executor):
    pass


class AsyncExecutor(Executor):
    def __init__(self, capacity: int | None = None, timeout: float | None = None):
        self.capacity = capacity
        self.timeout = timeout


class FullyAsyncExecutor(AsyncExecutor):
    def __init__(self, capacity: int | None = None, timeout: float | None = None, autocommit_duration_ms: int | None = 100):
        super().__init__(capacity, timeout)
        self.autocommit_duration_ms = autocommit_duration_ms


def auto_executor() -> AutoExecutor:
    return AutoExecutor()


def sync_executor() -> SyncExecutor:
    return SyncExecutor()


def async_executor(capacity: int | None = None, timeout: float | None = None, retry_strategy: AsyncRetryStrategy | None = None) -> AsyncExecutor:
    """The coroutines of one commit's rows are gathered inside that commit: it ends
    when the last of them has, and the rows that arrive meanwhile wait for the next."""
    ex = AsyncExecutor(capacity, timeout)
    ex.retry_strategy = retry_strategy  # type: ignore[attr-defined]
    return ex


def fully_async_executor(capacity: int | None = None, timeout: float | None = None, autocommit_duration_ms: int | None = 100) -> FullyAsyncExecutor:
    """The call leaves the commit that carried its arguments (``internals/fully_async.py``):
    that commit ends at once, the coroutine runs on a loop of its own, and the result
    re-enters the graph as a later commit through a loop-back source whose tick is
    ``autocommit_duration_ms``. Such a UDF is called in ``select`` / ``with_columns``, and
    the row of that select appears when its result is in: there is no pending value to
    filter out, and a retracted row starts no call. Elsewhere the call is refused when
    the graph runs."""
    return FullyAsyncExecutor(capacity, timeout, autocommit_duration_ms)


# -- the UDF class ----------------------------------------------------------


class UDF:
    """Base class for user-defined functions; also produced by the ``@pw.udf`` decorator.

    Subclasses implement ``__wrapped__`` (sync) or an async ``__wrapped__``.
    """

    def __init__(
        self,
        *,
        return_type: Any = None,
        propagate_none: bool = False,
        deterministic: bool = False,
        executor: Executor | None = None,
        cache_strategy: CacheStrategy | None = None,
        retry_strategy: AsyncRetryStrategy | None = None,
        max_batch_size: int | None = None,
    ):
        self.return_type = return_type
        self.propagate_none = propagate_none
        self.deterministic = deterministic
        self.executor = executor or AutoExecutor()
        self.cache_strategy = cache_strategy
        self.retry_strategy = retry_strategy or getattr(executor, "retry_strategy", None)
        self.max_batch_size = max_batch_size
        self.func: Callable | None = getattr(self, "__wrapped__", None)

    def _resolved_return_type(self) -> Any:
        if self.return_type is not None:
            return self.return_type
        fun = self.func
        if fun is not None:
            hints = None
            try:
                import typing

                hints = typing.get_type_hints(fun)
            except Exception:
                hints = getattr(fun, "__annotations__", {})
            if hints and "return" in hints:
                return hints["return"]
        return Any

    def _wrapped_fun(self) -> tuple[Callable, bool]:
        fun = self.func
        assert fun is not None, "UDF must define __wrapped__"
        is_async = asyncio.iscoroutinefunction(fun)
        if isinstance(self.executor, (AsyncExecutor,)) and not is_async:
            # wrap sync fn as async for capacity control
            sync_fun = fun

            async def as_async(*args: Any, **kwargs: Any) -> Any:
                return sync_fun(*args, **kwargs)

            fun = as_async
            is_async = True
        if is_async and self.retry_strategy is not None:
            inner = fun

            async def with_retries(*args: Any, **kwargs: Any) -> Any:
                return await self.retry_strategy.invoke(inner, *args, **kwargs)

            fun = with_retries
        if is_async and isinstance(self.executor, AsyncExecutor) and self.executor.capacity:
            inner2 = fun
            semaphore = asyncio.Semaphore(self.executor.capacity)

            async def with_capacity(*args: Any, **kwargs: Any) -> Any:
                async with semaphore:
                    return await inner2(*args, **kwargs)

            fun = with_capacity
        if self.cache_strategy is not None:
            name = getattr(self.func, "__name__", "udf")
            cache = self.cache_strategy
            if is_async:
                inner3 = fun

                async def cached(*args: Any, **kwargs: Any) -> Any:
                    key = _cache_key(name, args, kwargs)
                    try:
                        return cache.get(key)
                    except KeyError:
                        value = await inner3(*args, **kwargs)
                        cache.set(key, value)
                        return value

                fun = cached
            else:
                inner4 = fun

                def cached_sync(*args: Any, **kwargs: Any) -> Any:
                    key = _cache_key(name, args, kwargs)
                    try:
                        return cache.get(key)
                    except KeyError:
                        value = inner4(*args, **kwargs)
                        cache.set(key, value)
                        return value

                fun = cached_sync
        return fun, is_async

    def __call__(self, *args: Any, **kwargs: Any) -> expr.ColumnExpression:
        fun, is_async = self._wrapped_fun()
        ret = self._resolved_return_type()
        if isinstance(self.executor, FullyAsyncExecutor):
            e: expr.ApplyExpression = expr.FullyAsyncApplyExpression(
                fun, ret, self.propagate_none, self.deterministic, args, kwargs, self.max_batch_size
            )
            e.autocommit_duration_ms = self.executor.autocommit_duration_ms
        elif is_async:
            e = expr.AsyncApplyExpression(
                fun, ret, self.propagate_none, self.deterministic, args, kwargs, self.max_batch_size
            )
        else:
            e = expr.ApplyExpression(
                fun, ret, self.propagate_none, self.deterministic, args, kwargs, self.max_batch_size
            )
        # the executor wrappers above hide the user function from bytecode
        # inspection; keep the raw callable reachable for the PWA001 graph-lint
        # determinism pass (pathway_tpu/analysis)
        e._source_fun = self.func
        return e


def udf(
    fun: Callable | None = None,
    /,
    *,
    return_type: Any = None,
    propagate_none: bool = False,
    deterministic: bool = False,
    executor: Executor | None = None,
    cache_strategy: CacheStrategy | None = None,
    retry_strategy: AsyncRetryStrategy | None = None,
    max_batch_size: int | None = None,
) -> Any:
    """Decorator turning a function into a column UDF (parity: ``pw.udf``)."""

    def wrapper(f: Callable) -> UDF:
        instance = UDF(
            return_type=return_type,
            propagate_none=propagate_none,
            deterministic=deterministic,
            executor=executor,
            cache_strategy=cache_strategy,
            retry_strategy=retry_strategy,
            max_batch_size=max_batch_size,
        )
        instance.func = f
        functools.update_wrapper(instance, f)  # type: ignore[arg-type]
        return instance

    if fun is not None:
        return wrapper(fun)
    return wrapper


udf_async = functools.partial(udf, executor=AsyncExecutor())
