"""A ``fully_async`` UDF's call leaves the commit that carried its arguments
(``internals/fully_async.py``): the commit ends, the coroutine runs on the
loop-back connector's loop, and the result re-enters as a later commit, joined
by key to the row it belongs to. An ``async_executor`` UDF is still awaited
inside its commit.
"""

from __future__ import annotations

import asyncio
import queue
import threading
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.engine import telemetry
from pathway_tpu.engine.columnar import Error
from tests.utils import T, capture_update_stream

WAIT_S = 60.0


class Rows(pw.io.python.ConnectorSubject):
    """A streaming source the test drives: ``send(("add" | "remove", n))``, then ``send(None)``."""

    def __init__(self) -> None:
        self._commands: queue.Queue = queue.Queue()
        self.send = self._commands.put

    def run(self) -> None:
        while (command := self._commands.get()) is not None:
            kind, n = command
            self._emit({"n": n}, diff=1 if kind == "add" else -1)


class RowSchema(pw.Schema):
    n: int = pw.column_definition(primary_key=True)


class Streamed:
    """``pw.run`` on a thread over ``n -> result``; what every commit delivered, and when each ended."""

    def __init__(self, udf, terminate_on_error: bool = True) -> None:
        self.rows = Rows()
        self.events: list = []  # (table, key, row, time, is_addition)
        self.ended: list = []  # (table, time)
        self.seen = threading.Condition()
        self.failure: list = []
        inputs = pw.io.python.read(self.rows, schema=RowSchema, autocommit_duration_ms=1)
        results = inputs.select(inputs.n, result=udf(inputs.n))
        for name, table in (("inputs", inputs), ("results", results)):
            pw.io.subscribe(table, on_change=self._on_change(name), on_time_end=self._on_time_end(name))
        self.thread = threading.Thread(target=self._run, args=(terminate_on_error,), daemon=True)
        self.thread.start()

    def _run(self, terminate_on_error: bool) -> None:
        try:
            pw.run(monitoring_level=pw.MonitoringLevel.NONE, terminate_on_error=terminate_on_error)
        except BaseException as exc:  # noqa: BLE001 - handed to the test
            self.failure.append(exc)

    def _on_change(self, name):
        def on_change(key, row, time, is_addition):
            with self.seen:
                self.events.append((name, key, row, time, is_addition))
                self.seen.notify_all()
        return on_change

    def _on_time_end(self, name):
        def on_time_end(time):
            with self.seen:
                self.ended.append((name, time))
                self.seen.notify_all()
        return on_time_end

    def wait(self, what) -> None:
        """Until ``what()`` holds (it may read what no delivery announces, so it is polled)."""
        deadline = time.monotonic() + WAIT_S
        with self.seen:
            while not what():
                assert time.monotonic() < deadline and not self.failure, (self.events, self.ended, self.failure)
                self.seen.wait(0.005)

    def of(self, name):
        return [e for e in self.events if e[0] == name]

    def input_ended(self, n: int) -> int:
        """Wait for the commit that took input ``n`` to end; its time."""
        self.wait(lambda: any(e[2]["n"] == n and ("inputs", e[3]) in self.ended for e in self.of("inputs")))
        return next(e[3] for e in self.of("inputs") if e[2]["n"] == n)

    def finish(self) -> None:
        self.rows.send(None)
        self.thread.join(WAIT_S)
        assert not self.thread.is_alive()


def held_udf(executor):
    """``n -> 10 n`` once the test sets ``release[n]``; ``started`` has the calls made so far."""
    started: list = []
    release = {n: threading.Event() for n in range(1, 6)}

    @pw.udf(executor=executor)
    async def times_ten(n: int) -> int:
        started.append(n)
        while not release[n].is_set():
            await asyncio.sleep(0.002)
        return 10 * n

    return times_ten, started, release


def test_the_commit_ends_before_the_coroutine_and_the_next_row_gets_a_commit_of_its_own():
    udf, started, release = held_udf(pw.udfs.fully_async_executor(autocommit_duration_ms=1))
    run = Streamed(udf)
    run.rows.send(("add", 1))
    first = run.input_ended(1)  # the commit that carried row 1 is over
    run.wait(lambda: started == [1])
    assert not release[1].is_set() and run.of("results") == []
    run.rows.send(("add", 2))
    second = run.input_ended(2)  # and row 2 was taken by another, while call 1 is still held
    run.wait(lambda: started == [1, 2])
    assert second > first and run.of("results") == []
    release[2].set()
    run.wait(lambda: len(run.of("results")) == 1)  # the later call's result does not wait for the earlier's
    assert run.of("results")[0][2] == {"n": 2, "result": 20} and run.of("results")[0][3] > second
    release[1].set()
    run.wait(lambda: len(run.of("results")) == 2)
    run.finish()
    assert run.failure == [] and len(run.of("results")) == 2


def test_the_result_arrives_once_keyed_as_its_input_row_with_the_returned_value():
    udf, started, release = held_udf(pw.udfs.fully_async_executor(autocommit_duration_ms=1))
    for event in release.values():
        event.set()
    before = telemetry.stage_snapshot("eval.fully_async_")
    run = Streamed(udf)
    for n in (1, 2, 3):
        run.rows.send(("add", n))
    run.wait(lambda: len(run.of("results")) == 3)
    run.finish()
    key_of = {e[2]["n"]: e[1] for e in run.of("inputs")}
    assert sorted(started) == [1, 2, 3]
    assert sorted((e[2]["n"], e[2]["result"], e[4]) for e in run.of("results")) == [(1, 10, True), (2, 20, True), (3, 30, True)]
    assert all(e[1] == key_of[e[2]["n"]] for e in run.of("results"))
    grew = {k: v - before.get(k, 0.0) for k, v in telemetry.stage_snapshot("eval.fully_async_").items()}
    assert grew == {"eval.fully_async_rows": 3.0, "eval.fully_async_returned": 3.0}


def test_a_retracted_input_row_starts_no_call_and_retracts_its_result():
    udf, started, release = held_udf(pw.udfs.fully_async_executor(autocommit_duration_ms=1))
    release[1].set()
    run = Streamed(udf)
    run.rows.send(("add", 1))
    run.wait(lambda: len(run.of("results")) == 1)
    run.rows.send(("remove", 1))
    run.wait(lambda: len(run.of("results")) == 2)
    # a row retracted while its call is in flight: the result it brings is withdrawn with it
    run.rows.send(("add", 2))
    run.input_ended(2)
    run.wait(lambda: started == [1, 2])
    run.rows.send(("remove", 2))
    run.wait(lambda: len(run.of("inputs")) == 4)
    release[2].set()
    run.finish()
    assert started == [1, 2] and run.failure == []
    assert [(e[2], e[4]) for e in run.of("results")] == [({"n": 1, "result": 10}, True), ({"n": 1, "result": 10}, False)]


@pytest.mark.parametrize("terminate_on_error", [False, True])
def test_a_raising_coroutine_yields_the_error_and_no_hang(terminate_on_error):
    @pw.udf(executor=pw.udfs.fully_async_executor(autocommit_duration_ms=1))
    async def refuses_two(n: int) -> int:
        await asyncio.sleep(0.002)
        if n == 2:
            raise ValueError("no twos")
        return 10 * n

    run = Streamed(refuses_two, terminate_on_error=terminate_on_error)
    run.rows.send(("add", 1))
    run.wait(lambda: len(run.of("results")) == 1)
    run.rows.send(("add", 2))
    if terminate_on_error:
        run.thread.join(WAIT_S)  # the run fails where the result is read, as for any other UDF
        assert not run.thread.is_alive()
        [failure] = run.failure
        assert "ValueError: no twos" in str(failure)
    else:
        run.wait(lambda: len(run.of("results")) == 2)
        run.finish()
        assert run.failure == [] and isinstance(run.of("results")[1][2]["result"], Error)
    assert run.of("results")[0][2] == {"n": 1, "result": 10}


def test_a_batch_run_ends_only_after_every_result_is_in():
    @pw.udf(executor=pw.udfs.fully_async_executor())
    async def slowly(a: int) -> int:
        await asyncio.sleep(0.05 * a)
        return a + 100

    table = T(
        """
          | a | b
        1 | 1 | x
        2 | 2 | y
        3 | 3 | z
        """
    )
    result = table.select(table.b, c=slowly(table.a) * 2, d=table.a)
    assert result.schema.typehints() == {"b": str, "c": int, "d": int}
    stream = capture_update_stream(result)
    assert sorted((r["b"], r["c"], r["d"], r["__diff__"]) for r in stream) == [
        ("x", 202, 1, 1), ("y", 204, 2, 1), ("z", 206, 3, 1)]


def test_an_async_executor_udf_is_still_gathered_inside_its_commit():
    udf, started, release = held_udf(pw.udfs.async_executor())
    run = Streamed(udf)
    run.rows.send(("add", 1))
    run.wait(lambda: started == [1])
    run.rows.send(("add", 2))
    # the commit that carried row 1 is still open, and row 2 waits for the next
    time.sleep(0.2)
    assert run.ended == [] and started == [1] and run.of("results") == []
    release[1].set()
    release[2].set()
    run.wait(lambda: len(run.of("results")) == 2)
    run.finish()
    results = {e[2]["n"]: e for e in run.of("results")}
    inputs = {e[2]["n"]: e for e in run.of("inputs")}
    # each result in the commit of its own input row
    assert all(results[n][3] == inputs[n][3] and results[n][2]["result"] == 10 * n for n in (1, 2))
    assert run.failure == []


def test_a_fully_async_call_outside_a_select_says_where_it_belongs():
    @pw.udf(executor=pw.udfs.fully_async_executor())
    async def positive(a: int) -> bool:
        return a > 0

    table = T(
        """
          | a
        1 | 1
        """
    )
    with pytest.raises(Exception, match="TypeError: a fully_async UDF is called in select"):
        capture_update_stream(table.filter(positive(table.a)))


@pytest.mark.parametrize("executor", [pw.udfs.async_executor, pw.udfs.fully_async_executor])
def test_summarize_query_answers_once_whichever_executor_the_chat_has(executor):
    """``BaseRAGQuestionAnswerer`` calls its chat in a ``select``: the chat's executor alone decides
    whether the answer's row belongs to the question's commit or to a later one."""
    from pathway_tpu.internals.json import Json
    from pathway_tpu.xpacks.llm.llms import BaseChat
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer

    class CountingChat(BaseChat):
        def __init__(self) -> None:
            super().__init__(executor=executor())

            async def chat(messages, **kwargs) -> str:
                await asyncio.sleep(0.01)
                [message] = messages.value
                return f"{len(message['content'].split())} words"

            self.func = chat

    queries = pw.debug.table_from_rows(
        pw.schema_builder({"text_list": pw.Json}), [(Json(["a b c", "d e"]),), (Json(["f"]),)]
    )
    answers = BaseRAGQuestionAnswerer(CountingChat(), indexer=object()).summarize_query(queries)
    stream = capture_update_stream(answers)
    assert sorted((r["result"], r["__diff__"]) for r in stream) == sorted(
        (f"{len(pw.xpacks.llm.prompts.prompt_summarize(texts).split())} words", 1) for texts in (("a b c", "d e"), ("f",)))
    took = {r["__time__"] for r in capture_update_stream(queries)}
    assert ({r["__time__"] for r in stream} == took) == (executor is pw.udfs.async_executor)


def test_the_idle_loop_waits_for_a_push_and_not_for_the_smallest_tick(monkeypatch):
    """While a call is out of every commit the loop has nothing to do: it must not step at the
    sources' 1 ms tick (an idle step is Python under the interpreter lock, taken from the thread
    that generates), and a push still wakes it at once."""
    from pathway_tpu.engine.runner import GraphRunner

    steps: list = []
    step = GraphRunner.step
    monkeypatch.setattr(GraphRunner, "step", lambda self: (steps.append(time.monotonic()), step(self))[1])
    udf, started, release = held_udf(pw.udfs.fully_async_executor(autocommit_duration_ms=1))
    run = Streamed(udf)
    run.rows.send(("add", 1))
    run.input_ended(1)
    run.wait(lambda: started == [1])
    t0 = time.monotonic()
    time.sleep(0.5)
    idle = [t for t in steps if t0 <= t <= t0 + 0.5]
    assert len(idle) <= 75, len(idle)  # one every 10 ms, not one a millisecond
    pushed = time.monotonic()
    run.rows.send(("add", 2))
    run.input_ended(2)
    assert time.monotonic() - pushed < 0.2
    release[1].set()
    release[2].set()
    run.wait(lambda: len(run.of("results")) == 2)
    run.finish()
    assert run.failure == []


def test_events_inside_an_autocommit_window_are_taken_when_it_ends():
    from pathway_tpu.engine.datasource import StreamingDataSource

    source = StreamingDataSource(autocommit_ms=50)
    assert source.release_at() is None
    source.push({"n": 1})
    assert source.release_at() <= time.monotonic() and len(source.next_batch(["n"])) == 1
    source.push({"n": 2})  # inside the window the first batch opened
    held_until = source.release_at()
    assert 0.0 < held_until - time.monotonic() <= 0.05 and len(source.next_batch(["n"])) == 0
    time.sleep(max(0.0, held_until - time.monotonic()))
    assert len(source.next_batch(["n"])) == 1 and source.release_at() is None
