"""AsyncTransformer — fully-async row transformer with a loop-back connector.

Parity: reference ``stdlib/utils/async_transformer.py`` (``_AsyncConnector:61-527``).
Each input row is handed to ``async def invoke(self, **row)`` on a dedicated worker
event loop; results re-enter the graph through a loop-back streaming source as the
``output_table`` (keyed by the INPUT row's key, upsert semantics), so invocations never
block the commit that carried their inputs. Statuses mirror the reference:
``successful`` (rows whose invoke returned), ``failed`` (rows that raised — and, with
``instance`` grouping, successful rows of an instance-time group in which ANY row
failed), ``finished``, ``output_table``. Instance consistency: an (instance, time)
group's results are released atomically, in time order per instance, only when every
invocation of the group completed. ``with_options`` applies capacity / timeout /
retry / cache around ``invoke`` (``internals/udfs`` strategies).

A ``fully_async`` UDF's call is one more user of this connector
(``internals/fully_async.py``). Where a row is a traced request's (a REST row whose
commit link is registered), the invocation's spans are children of the request's
span, and the commit that carries the result in links the request as the commit
that took the row did.
"""

from __future__ import annotations

import asyncio
import collections
import threading
from dataclasses import dataclass, field
from typing import Any, ClassVar, Dict, Optional

from pathway_tpu.engine import tracing
from pathway_tpu.engine.datasource import StreamingDataSource
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import parse_graph as pg
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.keys import pointers_to_keys
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.table import Table

_ASYNC_STATUS_COLUMN = "_async_status"
_SUCCESS = "-SUCCESS-"
_FAILURE = "-FAILURE-"
_INSTANCE_NAME = "_pw_instance"


@dataclass(frozen=True)
class _Entry:
    key: Any
    time: int
    seq: int
    is_addition: bool
    # (row key's bytes, context) of the traced request whose row this is: read
    # inside the commit that took the row, kept until its result is pushed
    request: Optional[tuple] = field(default=None, compare=False)


@dataclass
class _Instance:
    pending: collections.deque = field(default_factory=collections.deque)
    finished: Dict[_Entry, Any] = field(default_factory=dict)
    buffer: list = field(default_factory=list)
    buffer_time: Optional[int] = None
    correct: bool = True


class AsyncTransformer:
    """Subclass with ``output_schema`` (class kwarg or attribute) and
    ``async def invoke(self, **row) -> dict``."""

    output_schema: ClassVar[Any] = None

    def __init_subclass__(cls, /, output_schema: Any = None, **kwargs: Any):
        super().__init_subclass__(**kwargs)
        if output_schema is not None:
            cls.output_schema = output_schema

    def __init__(
        self,
        input_table: Table,
        *,
        instance: Any = None,
        autocommit_duration_ms: int | None = 100,
        **kwargs: Any,
    ):
        assert self.output_schema is not None, "define output_schema"
        self._input_table = input_table
        self._instance_expr = instance  # None -> per-row instance (the row key)
        self._autocommit_ms = autocommit_duration_ms
        self._options: Dict[str, Any] = {}
        self._built: Optional[Table] = None

    async def invoke(self, **kwargs: Any) -> Dict[str, Any]:  # pragma: no cover
        raise NotImplementedError

    def open(self) -> None:
        pass

    def close(self) -> None:
        pass

    def with_options(
        self,
        capacity: int | None = None,
        timeout: float | None = None,
        retry_strategy: Any = None,
        cache_strategy: Any = None,
    ) -> "AsyncTransformer":
        self._options = {
            "capacity": capacity,
            "timeout": timeout,
            "retry_strategy": retry_strategy,
            "cache_strategy": cache_strategy,
        }
        return self

    # -- result tables -------------------------------------------------------

    @property
    def output_table(self) -> Table:
        """All rows that finished execution, with ``_async_status``."""
        if self._built is None:
            self._built = self._build()
        return self._built

    @property
    def successful(self) -> Table:
        out = self.output_table
        result = out.filter(out[_ASYNC_STATUS_COLUMN] == _SUCCESS).without(
            _ASYNC_STATUS_COLUMN
        )
        result._schema = self.output_schema
        return result

    @property
    def failed(self) -> Table:
        out = self.output_table
        return out.filter(out[_ASYNC_STATUS_COLUMN] == _FAILURE).without(
            _ASYNC_STATUS_COLUMN
        )

    @property
    def finished(self) -> Table:
        return self.output_table

    @property
    def result(self) -> Table:
        return self.successful

    # -- machinery -----------------------------------------------------------

    def _apply_options(self, fn: Any) -> Any:
        """Wrap invoke with the shared async UDF composition
        (``internals/udfs.wrap_async``: capacity/timeout/retries/caching)."""
        if not any(v is not None for v in self._options.values()):
            return fn
        from pathway_tpu.internals.udfs import wrap_async

        return wrap_async(
            fn,
            capacity=self._options.get("capacity"),
            timeout=self._options.get("timeout"),
            retry_strategy=self._options.get("retry_strategy"),
            cache_strategy=self._options.get("cache_strategy"),
            name=type(self).__name__,
        )

    def _build(self) -> Table:
        from pathway_tpu.internals import expression as expr
        from pathway_tpu.io._subscribe import subscribe

        input_table = self._input_table
        if self._instance_expr is not None:
            inst_e = self._instance_expr
            if not isinstance(inst_e, expr.ColumnExpression):
                inst_e = expr.ColumnConstExpression(inst_e)
            input_table = input_table.with_columns(**{_INSTANCE_NAME: inst_e})
        names = [
            n for n in input_table.column_names() if n != _INSTANCE_NAME
        ]
        out_names = list(self.output_schema.column_names())
        self.open()
        invoke = self._apply_options(self.invoke)

        source = StreamingDataSource(autocommit_ms=self._autocommit_ms, loopback=True)
        state: Dict[bytes, dict] = {}  # key bytes -> last emitted row (upserts)

        loop = asyncio.new_event_loop()
        threading.Thread(
            target=loop.run_forever, daemon=True, name="pathway:async-transformer"
        ).start()
        instances: Dict[Any, _Instance] = {}
        inflight: set = set()
        tasks: set = set()
        seq_box = [0]
        ended = [False]
        closed_time = [-1]  # flushes gate on time-end markers (reference semantics)

        def upsert(key: Any, row: dict, status: str, request: Optional[tuple]) -> None:
            data = {**row, _ASYNC_STATUS_COLUMN: status}
            kb = repr(key).encode()
            old = state.pop(kb, None)
            if old is not None:
                source.push(old, key=key, diff=-1)
            if request is not None:
                # the commit that carries the result links the request, as the
                # commit that took its row did (GraphRunner._trace_commit_queries)
                tracing.get_tracer().register_commit_link(*request)
            source.push(data, key=key, diff=1)
            state[kb] = data

        def remove(key: Any) -> None:
            old = state.pop(repr(key).encode(), None)
            if old is not None:
                source.push(old, key=key, diff=-1)

        def flush_buffer(inst: _Instance) -> None:
            for entry, result in inst.buffer:
                if entry.is_addition and inst.correct:
                    upsert(entry.key, result, _SUCCESS, entry.request)
                elif entry.is_addition:
                    # instance consistency: one failure poisons the whole
                    # (instance, time) group (reference .failed contract)
                    upsert(entry.key, {n: None for n in out_names}, _FAILURE, entry.request)
                else:
                    remove(entry.key)
            inst.buffer.clear()

        def maybe_produce(instance_key: Any) -> None:
            inst = instances.get(instance_key)
            if inst is None:
                return
            while inst.pending:
                entry = inst.pending[0]
                if entry.time > closed_time[0] or entry not in inst.finished:
                    # the entry's commit is still delivering (its time is not
                    # closed) or its invocation is still running
                    break
                inst.pending.popleft()
                result = inst.finished.pop(entry)
                if inst.buffer_time != entry.time:
                    if inst.buffer:
                        flush_buffer(inst)
                        inst.correct = True
                    inst.buffer_time = entry.time
                if entry.is_addition and result is None:
                    inst.correct = False
                inst.buffer.append((entry, result))
            if not inst.pending:
                flush_buffer(inst)
                del instances[instance_key]
            elif inst.buffer and inst.pending[0].time != inst.buffer_time:
                # the (instance, time) group completed even though later times wait
                flush_buffer(inst)
                inst.correct = True

        def maybe_close() -> None:
            if ended[0] and not inflight and not instances:
                self.close()
                source.close()

        def task_done(instance_key: Any, entry: _Entry, result: Any) -> None:
            inflight.discard(entry)
            inst = instances.get(instance_key)
            if inst is not None:
                inst.finished[entry] = result
            maybe_produce(instance_key)
            maybe_close()

        def on_change(key: Any, row: dict, time: int, is_addition: bool) -> None:
            # registration AND completion both run on the worker loop thread, in
            # arrival order: a fast task can never flush its (instance, time)
            # group before a sibling entry registered
            instance_key = row.get(_INSTANCE_NAME, key) if self._instance_expr is not None else key
            seq_box[0] += 1
            request = None
            tracer = tracing.get_tracer()
            if is_addition and tracer.recording():
                row_key = pointers_to_keys([key]).tobytes()
                ctx = tracer.commit_link_context(row_key)
                if ctx is not None:
                    request = (row_key, ctx)
            entry = _Entry(key, time, seq_box[0], is_addition, request)
            values = {n: row[n] for n in names} if is_addition else None

            def register_and_spawn() -> None:
                instances.setdefault(instance_key, _Instance()).pending.append(entry)
                inflight.add(entry)
                if not is_addition:
                    task_done(instance_key, entry, None)
                    return

                async def run_one() -> None:
                    try:
                        # no commit is open around the invocation: its spans are the request's
                        with tracing.adopt_context(request and request[1]):
                            result = await invoke(**values)
                        if set(result.keys()) != set(out_names):
                            raise ValueError(
                                "result of async function does not match output_schema"
                            )
                    except Exception:
                        result = None
                    task_done(instance_key, entry, result)

                task = loop.create_task(run_one())
                tasks.add(task)  # the loop holds its tasks weakly
                task.add_done_callback(tasks.discard)

            loop.call_soon_threadsafe(register_and_spawn)

        def on_time_end(time: int) -> None:
            def mark() -> None:
                closed_time[0] = max(closed_time[0], time)
                for instance_key in list(instances):
                    maybe_produce(instance_key)
                maybe_close()

            loop.call_soon_threadsafe(mark)

        def on_end() -> None:
            def finish() -> None:
                ended[0] = True
                maybe_close()

            loop.call_soon_threadsafe(finish)

        subscribe(input_table, on_change=on_change, on_end=on_end, on_time_end=on_time_end)

        out_schema = sch.schema_from_columns(
            {
                **{
                    n: sch.ColumnSchema(n, dt.Optional_(c.dtype))
                    for n, c in self.output_schema.columns().items()
                },
                _ASYNC_STATUS_COLUMN: sch.ColumnSchema(_ASYNC_STATUS_COLUMN, dt.STR),
            },
            name="async_transformer",
        )
        node = G.add_node(
            pg.InputNode(source=source, streaming=True, name="async-transformer")
        )
        return Table(node, out_schema, name="async_transformer")
