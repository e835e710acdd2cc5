"""What ``fully_async_executor()`` means: the call leaves the commit.

``Table.select`` (and so ``with_columns``) hands a select that holds a
``FullyAsyncApplyExpression`` to ``lower_select``. The commit that carries a
row evaluates the call's arguments, and every column the select reads beside
the call, into one table; the ``AsyncTransformer`` connector
(``stdlib/utils/async_transformer.py``: results keyed by the input row's key,
upserts, retractions forwarded without an invocation, batch runs drained before
the end) hands the arguments to the coroutine on its own loop and the commit
ends. The result comes back as a row of the connector's loop-back source, in
a later commit, and is joined by key to the row kept at the first commit; the
select's row exists from then on. So nothing downstream ever sees a pending
value (the reference's ``pw.Pending``, and its ``await_futures()``, have no
counterpart here), and a row whose call never returns never appears.

A call that raises comes back as a value too and is raised where the result is
read, on the commit's thread: under ``terminate_on_error`` the run fails, else
the cell is ``ERROR`` and the error log has the message, as for any other UDF.
"""

from __future__ import annotations

import asyncio
from typing import Any, Dict, List

from pathway_tpu.engine import telemetry
from pathway_tpu.engine.columnar import ERROR, Error
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import expression as expr
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.parse_graph import universe_solver


def holds_call(e: expr.ColumnExpression) -> bool:
    stack = [e]
    while stack:
        node = stack.pop()
        if isinstance(node, expr.FullyAsyncApplyExpression):
            return True
        stack.extend(node._deps())
    return False


class _Raised:
    """A call's exception on its way back through the loop-back source."""

    def __init__(self, exc: BaseException):
        self.exc = exc


def _result(value: Any) -> Any:
    if isinstance(value, _Raised):
        raise value.exc
    return value


async def _call(e: expr.FullyAsyncApplyExpression, args: list, kwargs: dict) -> Any:
    values = args + list(kwargs.values())
    if e._propagate_none and any(v is None for v in values):
        return None
    if any(isinstance(v, Error) for v in values):
        return ERROR
    try:
        return await e._fun(*args, **kwargs)
    except Exception as exc:  # raised again where the result is read
        return _Raised(exc)


def lower_select(table: Any, exprs: Dict[str, expr.ColumnExpression]) -> Any:
    """The table ``table.select(**exprs)`` describes, each of its rows present
    from the commit in which the results of its ``fully_async`` calls arrive."""
    from pathway_tpu.stdlib.utils.async_transformer import AsyncTransformer

    calls: List[tuple] = []  # (call, its result's column, names of its args, {kwarg: name})
    column_of: Dict[int, str] = {}  # id(call) -> its result's column
    kept: Dict[str, expr.ColumnExpression] = {}  # the first commit's columns, by name
    ref_names: Dict[tuple, str] = {}

    def collect(e: expr.ColumnExpression) -> None:
        if isinstance(e, expr.FullyAsyncApplyExpression):
            if id(e) in column_of:
                return
            column = column_of[id(e)] = f"_pw_call{len(calls)}"
            args = [f"{column}_arg{j}" for j in range(len(e._args))]
            kwargs = {k: f"{column}_kwarg_{k}" for k in e._kwargs}
            kept.update(zip(args, e._args))
            kept.update((name, e._kwargs[k]) for k, name in kwargs.items())
            calls.append((e, column, args, kwargs))
        elif isinstance(e, expr.ColumnReference):
            name = ref_names.setdefault((id(e.table), e.name), f"_pw_ref{len(ref_names)}")
            kept[name] = e
        else:
            for dep in e._deps():
                collect(dep)

    for e in exprs.values():
        collect(e)
    first = table.select(**kept)

    class FullyAsyncUdf(
        AsyncTransformer,
        output_schema=sch.schema_from_columns(
            {column: sch.ColumnSchema(column, dt.ANY) for column in column_of.values()}, name="fully_async_udf"
        ),
    ):
        async def invoke(self, **row: Any) -> Dict[str, Any]:
            telemetry.stage_add("eval.fully_async_rows")
            try:
                results = await asyncio.gather(*(
                    _call(e, [row[n] for n in args], {k: row[n] for k, n in kwargs.items()})
                    for e, _, args, kwargs in calls
                ))
            finally:
                telemetry.stage_add("eval.fully_async_returned")
            return dict(zip(column_of.values(), results))

    ticks = [e.autocommit_duration_ms for e, *_ in calls if e.autocommit_duration_ms]
    returned = FullyAsyncUdf(first, autocommit_duration_ms=min(ticks) if ticks else None).output_table

    def replace(e: expr.ColumnExpression) -> "expr.ColumnExpression | None":
        if isinstance(e, expr.FullyAsyncApplyExpression):
            return expr.ApplyExpression(_result, e._return_type, False, True, (returned[column_of[id(e)]],), {})
        if isinstance(e, expr.ColumnReference):
            return first[ref_names[(id(e.table), e.name)]]
        return None

    result = first.join_inner(returned, first.id == returned.id, id=first.id).select(
        **{name: expr.rewrite(e, replace) for name, e in exprs.items()}
    )
    universe_solver.register_subset(result._universe, table._universe)
    return result
