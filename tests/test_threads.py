"""Worker-thread parallelism (``PATHWAY_THREADS``): the transparent shared-graph
lane and the explicit ``run_threads`` lane.

Parity: reference ``src/engine/dataflow/config.rs:63-70`` (N timely worker
threads per process over a shared-memory allocator) and
``external/timely-dataflow/communication/src/initialize.rs:25-31``. Here the
spawn cluster's key-partitioning policies run unchanged over an in-memory
exchange; outputs centralize on rank 0 so results are exactly the
single-thread run's.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine.runner import GraphRunner
from pathway_tpu.internals import config as config_mod
from pathway_tpu.internals.parse_graph import G


@pytest.fixture(autouse=True)
def _clear_graph():
    G.clear()
    yield
    G.clear()


def _threads_config(n: int, processes: int = 1):
    return config_mod.PathwayConfig(threads=n, processes=processes)


def _collect(table):
    rows = {}
    calls = []

    def cb(key, row, time, is_addition):
        calls.append(threading.get_ident())
        if is_addition:
            rows[key] = row
        else:
            rows.pop(key, None)

    pw.io.subscribe(table, cb)
    return rows, calls


def _run_with_threads(n: int) -> None:
    config_mod.set_thread_config(_threads_config(n))
    try:
        GraphRunner(G._current).run()
    finally:
        config_mod.set_thread_config(None)


def test_shared_graph_wordcount_matches_single_thread():
    t = pw.debug.table_from_markdown(
        """
        word | n
        cat  | 1
        dog  | 2
        cat  | 3
        owl  | 5
        dog  | 1
        """
    )
    out = t.groupby(t.word).reduce(t.word, total=pw.reducers.sum(t.n))
    rows, calls = _collect(out)
    _run_with_threads(3)
    got = sorted((r["word"], r["total"]) for r in rows.values())
    assert got == [("cat", 4), ("dog", 3), ("owl", 5)]
    # outputs centralize on one rank: the callback thread is unique
    assert len(set(calls)) == 1


def test_shared_graph_join_and_filter():
    left = pw.debug.table_from_rows(
        pw.schema_builder({"k": str, "v": int}), [(f"k{i}", i) for i in range(60)]
    )
    right = pw.debug.table_from_rows(
        pw.schema_builder({"k": str, "w": int}),
        [(f"k{i}", 100 + i) for i in range(0, 60, 2)],
    )
    joined = left.join(right, left.k == right.k).select(
        left.k, s=left.v + right.w
    ).filter(pw.this.s % 2 == 0)
    rows, _ = _collect(joined)
    _run_with_threads(4)
    expected = sorted(
        (f"k{i}", 100 + 2 * i) for i in range(0, 60, 2) if (100 + 2 * i) % 2 == 0
    )
    assert sorted((r["k"], r["s"]) for r in rows.values()) == expected


def test_shared_graph_streaming_updates():
    """Update-stream semantics survive the fan-out: retractions route like adds."""
    t = pw.debug.table_from_markdown(
        """
        grp | v | __time__ | __diff__
        a   | 1 | 2        | 1
        a   | 2 | 2        | 1
        b   | 5 | 2        | 1
        a   | 1 | 4        | -1
        """
    )
    out = t.groupby(pw.this.grp).reduce(pw.this.grp, total=pw.reducers.sum(pw.this.v))
    rows, _ = _collect(out)
    _run_with_threads(2)
    assert sorted((r["grp"], r["total"]) for r in rows.values()) == [("a", 2), ("b", 5)]


def test_threads_with_processes_refuses_loudly():
    t = pw.debug.table_from_markdown("a\n1")
    _collect(t)
    config_mod.set_thread_config(_threads_config(2, processes=2))
    try:
        with pytest.raises(NotImplementedError, match="hierarchical exchange"):
            GraphRunner(G._current).run()
    finally:
        config_mod.set_thread_config(None)


def test_run_threads_explicit_per_worker_shards():
    """The spawn-like lane: each worker builds its own graph over its own input
    shard; grouped totals are exact global counts, keys owned once."""
    from pathway_tpu.internals.config import get_pathway_config
    from pathway_tpu.parallel.threads import run_threads

    rng = np.random.default_rng(3)
    pool = [f"w{i}" for i in range(30)]
    shards = [[pool[i] for i in rng.integers(0, 30, 200)] for _ in range(3)]

    def program():
        rank = get_pathway_config().process_id
        tbl = pw.debug.table_from_rows(
            pw.schema_builder({"word": str}), [(w,) for w in shards[rank]]
        )
        counts = tbl.groupby(pw.this.word).reduce(
            pw.this.word, cnt=pw.reducers.count()
        )
        got = {}
        pw.io.subscribe(
            counts,
            lambda key, row, time, is_addition: got.__setitem__(row["word"], row["cnt"])
            if is_addition
            else got.pop(row["word"], None),
        )
        pw.run(monitoring_level=pw.MonitoringLevel.NONE)
        return got

    outs = run_threads(program, 3)
    import collections

    expected = collections.Counter()
    for shard in shards:
        expected.update(shard)
    merged: dict = {}
    for rank, out in enumerate(outs):
        for word, cnt in out.items():
            assert word not in merged, f"{word} owned twice"
            merged[word] = cnt
    assert merged == dict(expected)
    assert sum(bool(o) for o in outs) > 1, "all keys landed on one worker"


def test_shared_graph_worker_failure_propagates():
    @pw.udf
    def boom(x: int) -> int:
        if x == 13:
            raise ValueError("poof")
        return x

    t = pw.debug.table_from_rows(
        pw.schema_builder({"x": int}), [(i,) for i in range(20)]
    )
    out = t.select(y=boom(pw.this.x)).groupby(pw.this.y).reduce(
        pw.this.y, c=pw.reducers.count()
    )
    _collect(out)
    config_mod.set_thread_config(_threads_config(2))
    try:
        with pytest.raises(RuntimeError, match="worker thread"):
            GraphRunner(G._current).run(terminate_on_error=True)
    finally:
        config_mod.set_thread_config(None)


def test_cli_spawn_threads_end_to_end(tmp_path):
    """`spawn -t 2`: PATHWAY_THREADS env -> transparent fan-out inside pw.run."""
    import json
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = tmp_path / "prog.py"
    prog.write_text(
        textwrap.dedent(
            """
            import json, os, sys
            import pathway_tpu as pw
            t = pw.debug.table_from_markdown(\"\"\"
            word | n
            cat  | 1
            dog  | 2
            cat  | 3
            \"\"\")
            out = t.groupby(t.word).reduce(t.word, total=pw.reducers.sum(t.n))
            rows = {}
            pw.io.subscribe(out, lambda key, row, time, is_addition:
                rows.__setitem__(row["word"], row["total"]) if is_addition
                else rows.pop(row["word"], None))
            pw.run(monitoring_level=pw.MonitoringLevel.NONE)
            json.dump(rows, open(sys.argv[1], "w"))
            """
        )
    )
    out_path = tmp_path / "out.json"
    env = os.environ.copy()
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn", "-t", "2",
            sys.executable, str(prog), str(out_path),
        ],
        env=env, capture_output=True, text=True, timeout=180, cwd=str(tmp_path),
    )
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert json.loads(out_path.read_text()) == {"cat": 4, "dog": 2}


def test_run_threads_fs_reader_shards_not_duplicated(tmp_path):
    """Connector reader threads must inherit the worker's config override:
    partition-sharded fs readers on 2 workers each read THEIR shard of the
    files; without the override handoff both read everything and every count
    doubles."""
    from pathway_tpu.parallel.threads import run_threads

    for i in range(6):
        (tmp_path / f"f{i}.csv").write_text("word\n" + "\n".join(["cat"] * 3) + "\n")

    def program():
        t = pw.io.csv.read(
            str(tmp_path), schema=pw.schema_builder({"word": str}), mode="static"
        )
        counts = t.groupby(pw.this.word).reduce(
            pw.this.word, cnt=pw.reducers.count()
        )
        got = {}
        pw.io.subscribe(
            counts,
            lambda key, row, time, is_addition: got.__setitem__(row["word"], row["cnt"])
            if is_addition
            else got.pop(row["word"], None),
        )
        pw.run(monitoring_level=pw.MonitoringLevel.NONE)
        return got

    outs = run_threads(program, 2)
    merged: dict = {}
    for out in outs:
        for word, cnt in out.items():
            assert word not in merged
            merged[word] = cnt
    assert merged == {"cat": 18}, merged


def test_groupby_reducer_cross_ref_refused_under_cluster():
    """Reducer arguments evaluate AFTER the group-key exchange, where a foreign
    table's shard is not resident — must refuse loudly, not ERROR-poison."""
    t = pw.debug.table_from_rows(
        pw.schema_builder({"k": str, "v": int}), [(f"k{i}", i) for i in range(10)]
    )
    other = t.select(w=pw.this.v * 2)
    agg = t.groupby(pw.this.k).reduce(pw.this.k, s=pw.reducers.sum(other.w))
    _collect(agg)
    config_mod.set_thread_config(_threads_config(2))
    try:
        with pytest.raises(RuntimeError, match="reducer arguments reference"):
            GraphRunner(G._current).run()
    finally:
        config_mod.set_thread_config(None)


# -- typed peer-failure triage + exchange immutability (PR 1 satellites) -------


def test_primary_error_with_timeout_phrasing_not_misclassified():
    """A genuine worker failure whose MESSAGE contains 'timed out waiting' must
    still be picked as the root cause (triage is by exception type now, not by
    repr substring): the peer that dies waiting raises a typed
    PeerShutdownError and is the one classified secondary."""
    import pytest

    from pathway_tpu.engine.columnar import Delta
    from pathway_tpu.parallel.cluster import get_cluster
    from pathway_tpu.parallel.threads import run_threads

    def program():
        from pathway_tpu.internals.config import get_pathway_config

        rank = get_pathway_config().process_id
        if rank == 0:
            raise RuntimeError("backend timed out waiting for quota")
        get_cluster().exchange_to_root(b"t0", Delta.empty(["x"]))

    with pytest.raises(RuntimeError, match="worker thread 0 failed") as ei:
        run_threads(program, 2)
    assert "timed out waiting for quota" in str(ei.value)


def test_exchanged_delta_arrays_are_read_only():
    """The zero-serialization thread exchange hands LIVE arrays to peers; they
    must be frozen on handoff so an in-place mutation fails fast in the
    violating worker instead of corrupting its peers."""
    import numpy as np
    import pytest

    from pathway_tpu.engine.columnar import Delta
    from pathway_tpu.internals.keys import KEY_DTYPE
    from pathway_tpu.parallel.cluster import get_cluster
    from pathway_tpu.parallel.threads import run_threads

    def program():
        keys = np.zeros(2, dtype=KEY_DTYPE)
        diffs = np.ones(2, dtype=np.int64)
        cols = {"x": np.arange(2, dtype=np.float64)}
        d = Delta(keys, diffs, cols)
        merged = get_cluster().broadcast_merge(b"bm", d)
        return d, merged

    outs = run_threads(program, 2)
    for own, merged in outs:
        assert not own.keys.flags.writeable
        assert not own.columns["x"].flags.writeable
        with pytest.raises(ValueError):
            own.columns["x"][0] = 99.0
        assert len(merged) == 4
