"""Checkpoint/resume: input journal, replay, offset seek, crash recovery.

Mirrors the reference's persistence test surface: ``test_persistence.py`` unit level plus
the ``integration_tests/wordcount`` kill-and-restart rig (``base.py:320``) at small scale.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

import pathway_tpu as pw
from pathway_tpu.engine.runner import GraphRunner
from pathway_tpu.internals.parse_graph import G


def _collect(table):
    rows = {}

    def on_change(key, row, time, is_addition):
        if is_addition:
            rows[key] = row
        else:
            rows.pop(key, None)

    pw.io.subscribe(table, on_change)
    return rows


def _build_static_pipeline():
    t = pw.debug.table_from_markdown(
        """
        word  | n
        cat   | 1
        dog   | 2
        cat   | 3
        """
    )
    counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.sum(t.n))
    return _collect(counts)


def test_journal_replay_reproduces_state(tmp_path):
    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(tmp_path / "pstore"))

    rows1 = _build_static_pipeline()
    GraphRunner(G._current).run(persistence_config=cfg)
    result1 = {tuple(sorted(r.items())) for r in rows1.values()}
    assert {dict(r)["word"] for r in result1} == {"cat", "dog"}

    # "restart": fresh graph + fresh runner over the same store — rows must come from
    # the journal (the static source is marked consumed by the restored offsets)
    G.clear()
    rows2 = _build_static_pipeline()
    GraphRunner(G._current).run(persistence_config=cfg)
    result2 = {tuple(sorted(r.items())) for r in rows2.values()}
    assert result2 == result1

    # journal only holds ONE copy of the input (no duplicate journaling on resume)
    from pathway_tpu.persistence.engine import PersistenceManager

    frames = PersistenceManager(cfg).load_journal(G._current.sig())
    total_rows = sum(len(d) for _, deltas, _ in frames for d in deltas.values())
    assert total_rows == 3


def test_streaming_resume_after_partial_run(tmp_path):
    """Simulated crash: stop mid-stream without finish(), resume, verify exact result."""

    class NumbersSubject:
        """Deterministically pushes 0..19; re-pushed events dedup via skip-count."""

        def run(self, source):
            for i in range(20):
                source.push({"v": i})

    def build():
        from pathway_tpu.engine.datasource import StreamingDataSource
        from pathway_tpu.internals import parse_graph as pg
        from pathway_tpu.internals.table import Table

        schema = pw.schema_builder({"v": int})
        source = StreamingDataSource(subject=NumbersSubject(), autocommit_ms=5)
        node = G.add_node(pg.InputNode(source=source, streaming=True, name="numbers"))
        t = Table(node, schema, name="numbers")
        total = t.reduce(total=pw.reducers.sum(t.v))
        return _collect(total)

    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(tmp_path / "ps"))

    rows1 = build()
    r1 = GraphRunner(G._current)
    r1.run(persistence_config=cfg, max_commits=3)  # stop early; finish() not called

    G.clear()
    rows2 = build()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert [r["total"] for r in rows2.values()] == [sum(range(20))]


def test_silent_replay_suppresses_sink_redelivery(tmp_path):
    cfg = pw.persistence.Config(
        pw.persistence.Backend.filesystem(tmp_path / "ps"),
        persistence_mode="silent_replay",
    )
    rows1 = _build_static_pipeline()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert len(rows1) == 2

    G.clear()
    rows2 = _build_static_pipeline()
    GraphRunner(G._current).run(persistence_config=cfg)
    # replayed history was not re-delivered to the sink, and no new data arrived
    assert rows2 == {}


_CRASH_SCRIPT = r"""
import os, sys
import pathway_tpu as pw

input_path, out_path, store = sys.argv[1], sys.argv[2], sys.argv[3]

class Sch(pw.Schema):
    word: str

t = pw.io.csv.read(input_path, schema=Sch, mode="streaming", autocommit_duration_ms=20)
counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())

import json
rows = {}
def on_change(key, row, time, is_addition):
    if is_addition:
        rows[repr(key)] = {k: int(v) if hasattr(v, "item") else v for k, v in row.items()}
    else:
        rows.pop(repr(key), None)
    with open(out_path + ".tmp", "w") as f:
        json.dump(list(rows.values()), f)
    os.replace(out_path + ".tmp", out_path)

pw.io.subscribe(counts, on_change)
cfg = pw.persistence.Config(
    pw.persistence.Backend.filesystem(store), snapshot_interval_ms=10
)
pw.run(persistence_config=cfg)
"""


def test_crash_kill_and_restart_wordcount(tmp_path):
    """The wordcount torture rig at small scale: kill -9 mid-run, restart, exact output."""
    input_dir = tmp_path / "in"
    input_dir.mkdir()
    out_path = str(tmp_path / "out.json")
    store = str(tmp_path / "store")
    script = tmp_path / "prog.py"
    script.write_text(_CRASH_SCRIPT)

    (input_dir / "a.csv").write_text("word\n" + "\n".join(["cat"] * 5 + ["dog"] * 3) + "\n")

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "/root/repo"}
    proc = subprocess.Popen(
        [sys.executable, str(script), str(input_dir), out_path, store],
        env=env,
        cwd="/root/repo",
    )
    # wait for it to process the first file, then kill -9
    deadline = time.time() + 60
    while time.time() < deadline and not os.path.exists(out_path):
        time.sleep(0.1)
    assert os.path.exists(out_path), "pipeline never produced output"
    time.sleep(0.5)
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait()

    # add more data while the pipeline is down
    (input_dir / "b.csv").write_text("word\n" + "\n".join(["cat"] * 2 + ["owl"] * 4) + "\n")

    # restart; it must resume (not double-count a.csv) and pick up b.csv
    proc = subprocess.Popen(
        [sys.executable, str(script), str(input_dir), out_path, store],
        env=env,
        cwd="/root/repo",
    )
    try:
        deadline = time.time() + 90
        expected = {"cat": 7, "dog": 3, "owl": 4}
        import json

        while time.time() < deadline:
            try:
                with open(out_path) as f:
                    rows = {r["word"]: r["total"] for r in json.load(f)}
            except Exception:
                rows = {}
            if rows == expected:
                break
            time.sleep(0.2)
        assert rows == expected, f"got {rows}, want {expected}"
    finally:
        proc.kill()
        proc.wait()


def _run_segmented(tmp_store, script, max_commits=None):
    """Build a pipeline over a scripted segment-pushing subject; return captured rows."""
    from pathway_tpu.engine.datasource import StreamingDataSource
    from pathway_tpu.internals import parse_graph as pg
    from pathway_tpu.internals.table import Table

    class ScriptedSubject:
        def __init__(self, steps):
            self.steps = steps
            self.folded = []

        def restore(self, state_deltas):
            self.folded = list(state_deltas)

        def run(self, source):
            for step in self.steps(self.folded):
                kind = step[0]
                if kind == "begin":
                    source.push_begin(step[1], step[2])
                elif kind == "row":
                    source.push(step[1], diff=step[2] if len(step) > 2 else 1)
                elif kind == "state":
                    source.push_state(step[1])
                elif kind == "barrier":
                    source.push_barrier()

    schema = pw.schema_builder({"v": int})
    subject = ScriptedSubject(script)
    source = StreamingDataSource(subject=subject, autocommit_ms=5)
    node = G.add_node(pg.InputNode(source=source, streaming=True, name="seg"))
    t = Table(node, schema, name="seg")
    rows = _collect(t)
    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(tmp_store))
    GraphRunner(G._current).run(persistence_config=cfg, max_commits=max_commits)
    return rows


def test_segment_skip_on_unchanged_fingerprint(tmp_path):
    """Crash mid-segment; segment unchanged on resume → re-push deduped, no dupes."""
    store = tmp_path / "ps"

    def first_run(folded):
        yield ("begin", "fileA", "fp1")
        yield ("row", {"v": 1})
        yield ("state", {"file": "fileA"})
        yield ("begin", "fileB", "fp2")
        yield ("row", {"v": 10})
        yield ("row", {"v": 20})
        # crash before fileB's marker

    rows1 = _run_segmented(store, first_run, max_commits=30)
    assert sorted(r["v"] for r in rows1.values()) == [1, 10, 20]

    G.clear()

    def resume_run(folded):
        # subject deterministically re-pushes the unfinished segment
        assert folded == [{"file": "fileA"}]
        yield ("begin", "fileB", "fp2")
        yield ("row", {"v": 10})
        yield ("row", {"v": 20})
        yield ("row", {"v": 30})
        yield ("state", {"file": "fileB"})

    rows2 = _run_segmented(store, resume_run)
    assert sorted(r["v"] for r in rows2.values()) == [1, 10, 20, 30]


def test_segment_retract_on_changed_fingerprint(tmp_path):
    store = tmp_path / "ps"

    def first_run(folded):
        yield ("begin", "fileB", "fp_old")
        yield ("row", {"v": 10})
        yield ("row", {"v": 20})

    rows1 = _run_segmented(store, first_run, max_commits=30)
    assert sorted(r["v"] for r in rows1.values()) == [10, 20]

    G.clear()

    def resume_run(folded):
        # the segment changed while down: journaled 10/20 must be retracted
        yield ("begin", "fileB", "fp_new")
        yield ("row", {"v": 77})
        yield ("state", {"file": "fileB"})

    rows2 = _run_segmented(store, resume_run)
    assert sorted(r["v"] for r in rows2.values()) == [77]


def test_segment_vanished_barrier_retracts_tail(tmp_path):
    store = tmp_path / "ps"

    def first_run(folded):
        yield ("begin", "fileB", "fp")
        yield ("row", {"v": 10})

    _run_segmented(store, first_run, max_commits=30)

    G.clear()

    def resume_run(folded):
        # fileB is gone; a full scan pass without it must undo its journaled rows
        yield ("barrier",)

    rows2 = _run_segmented(store, resume_run)
    assert [r["v"] for r in rows2.values()] == []


def test_torn_journal_tail_is_truncated(tmp_path):
    store = tmp_path / "ps"
    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(store))

    rows1 = _build_static_pipeline()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert len(rows1) == 2

    # simulate a crash mid-frame-write: garbage tail bytes after the last valid frame
    journal = store / "journal.bin"
    with open(journal, "ab") as f:
        f.write(b"\x00\x00\x00\x00\x00\x00\x10\x00partialgarbage")

    G.clear()
    rows2 = _build_static_pipeline()
    GraphRunner(G._current).run(persistence_config=cfg)
    result2 = {tuple(sorted(r.items())) for r in rows2.values()}
    assert {dict(r)["word"] for r in result2} == {"cat", "dog"}

    # and the journal must be readable again on a third run (torn tail truncated)
    G.clear()
    rows3 = _build_static_pipeline()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert {dict(tuple(sorted(r.items())))["word"] for r in rows3.values()} == {"cat", "dog"}


def test_fs_file_modified_while_down(tmp_path):
    """A fully-processed file modified during downtime is retracted and re-read."""
    input_dir = tmp_path / "in"
    input_dir.mkdir()
    store = tmp_path / "ps"
    (input_dir / "a.csv").write_text("word\ncat\ncat\n")

    class Sch(pw.Schema):
        word: str

    def build():
        t = pw.io.csv.read(str(input_dir), schema=Sch, mode="static")
        counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())
        return _collect(counts)

    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(store))
    rows1 = build()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert {r["word"]: r["total"] for r in rows1.values()} == {"cat": 2}

    time.sleep(0.05)
    (input_dir / "a.csv").write_text("word\nowl\nowl\nowl\n")
    os.utime(input_dir / "a.csv")

    G.clear()
    rows2 = build()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert {r["word"]: r["total"] for r in rows2.values()} == {"owl": 3}


def test_checkpoint_resume_and_journal_compaction(tmp_path):
    """Operator snapshots: state restored from checkpoint, journal compacted, sinks
    re-receive the restored state as a snapshot."""
    store = tmp_path / "ps"

    class NumbersSubject:
        def __init__(self, n):
            self.n = n

        def run(self, source):
            for i in range(self.n):
                source.push({"v": i})

    def build(n):
        from pathway_tpu.engine.datasource import StreamingDataSource
        from pathway_tpu.internals import parse_graph as pg
        from pathway_tpu.internals.table import Table

        schema = pw.schema_builder({"v": int})
        source = StreamingDataSource(subject=NumbersSubject(n), autocommit_ms=5)
        node = G.add_node(pg.InputNode(source=source, streaming=True, name="numbers"))
        t = Table(node, schema, name="numbers")
        total = t.reduce(total=pw.reducers.sum(t.v))
        return _collect(total)

    cfg = pw.persistence.Config(
        pw.persistence.Backend.filesystem(store), snapshot_interval_ms=1
    )
    rows1 = build(10)
    GraphRunner(G._current).run(persistence_config=cfg)
    assert [r["total"] for r in rows1.values()] == [sum(range(10))]
    assert (store / "checkpoint.pkl").exists()
    # compaction kept the journal small (some frames may follow the last checkpoint)
    journal_size_after_run1 = (store / "journal.bin").stat().st_size

    # resume: subject pushes 15 values now; first 10 journaled/checkpointed, deduped
    G.clear()
    rows2 = build(15)
    GraphRunner(G._current).run(persistence_config=cfg)
    assert [r["total"] for r in rows2.values()] == [sum(range(15))]
    assert journal_size_after_run1 < 10_000


def test_checkpoint_groupby_state_survives_compaction(tmp_path):
    """After compaction the journal no longer holds history; accumulators must come
    from the operator snapshot."""
    store = tmp_path / "ps"
    input_dir = tmp_path / "in"
    input_dir.mkdir()
    (input_dir / "a.csv").write_text("word\ncat\ncat\ndog\n")

    class Sch(pw.Schema):
        word: str

    def build():
        t = pw.io.csv.read(str(input_dir), schema=Sch, mode="static")
        counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())
        return _collect(counts)

    cfg = pw.persistence.Config(
        pw.persistence.Backend.filesystem(store), snapshot_interval_ms=1
    )
    rows1 = build()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert {r["word"]: r["total"] for r in rows1.values()} == {"cat": 2, "dog": 1}

    # new file while down; groupby must ADD to checkpointed accumulators
    (input_dir / "b.csv").write_text("word\ncat\nowl\n")

    G.clear()
    rows2 = build()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert {r["word"]: r["total"] for r in rows2.values()} == {
        "cat": 3,
        "dog": 1,
        "owl": 1,
    }


def test_double_crash_mid_segment_skip_width(tmp_path):
    """Crash, resume, crash again before the marker: the second resume must skip the
    full re-pushed prefix (regression: emitted restarted at 0 after an fp-matched
    resume, undercounting the skip)."""
    store = tmp_path / "ps"

    def run1(folded):
        yield ("begin", "fileB", "fp")
        yield ("row", {"v": 10})
        yield ("row", {"v": 20})

    _run_segmented(store, run1, max_commits=30)

    G.clear()

    def run2(folded):
        yield ("begin", "fileB", "fp")
        yield ("row", {"v": 10})
        yield ("row", {"v": 20})
        yield ("row", {"v": 30})
        # crash again before the marker

    _run_segmented(store, run2, max_commits=30)

    G.clear()

    def run3(folded):
        yield ("begin", "fileB", "fp")
        yield ("row", {"v": 10})
        yield ("row", {"v": 20})
        yield ("row", {"v": 30})
        yield ("row", {"v": 40})
        yield ("state", {"file": "fileB"})

    rows = _run_segmented(store, run3)
    assert sorted(r["v"] for r in rows.values()) == [10, 20, 30, 40]


def test_nondet_udf_memo_survives_checkpoint(tmp_path):
    """A deterministic=False UDF's replay memo rides operator snapshots: after a
    restore-from-checkpoint (journal compacted, history not re-run), a retraction
    of a pre-checkpoint row must replay the ORIGINAL value, not re-invoke."""
    store = tmp_path / "ps"
    calls = []

    def nondet(x: str) -> str:
        calls.append(x)
        return f"{x}#{len(calls)}"

    class Subject:
        def __init__(self, rows):
            self.rows = rows

        def run(self, source):
            from pathway_tpu.internals.keys import pointer_from

            for key, value, diff in self.rows:
                source.push({"k": value}, key=pointer_from(key), diff=diff)

    def build(rows):
        from pathway_tpu.engine.datasource import StreamingDataSource
        from pathway_tpu.internals import parse_graph as pg
        from pathway_tpu.internals.table import Table

        schema = pw.schema_builder({"k": str})
        source = StreamingDataSource(subject=Subject(rows), autocommit_ms=5)
        node = G.add_node(pg.InputNode(source=source, streaming=True, name="s"))
        t = Table(node, schema, name="s")
        udf = pw.udf(nondet, deterministic=False)
        res = t.select(t.k, v=udf(t.k))
        events = []
        pw.io.subscribe(
            res,
            on_batch=lambda keys, diffs, columns, time: events.extend(
                zip(columns["v"].tolist(), diffs.tolist())
            ),
        )
        return events

    cfg = pw.persistence.Config(
        pw.persistence.Backend.filesystem(store), snapshot_interval_ms=1
    )
    ev1 = build([("a", "a", 1), ("b", "b", 1)])
    GraphRunner(G._current).run(persistence_config=cfg)
    a_value = next(v for v, d in ev1 if d == 1 and v.startswith("a#"))
    assert (store / "checkpoint.pkl").exists()

    # restart: source replays its first two rows (deduped by the journal) and
    # then retracts "a" — the retraction must carry a_value verbatim
    G.clear()
    ev2 = build([("a", "a", 1), ("b", "b", 1), ("a", "a", -1)])
    GraphRunner(G._current).run(persistence_config=cfg)
    retractions = [v for v, d in ev2 if d < 0]
    assert retractions == [a_value]


# -- format versioning (PR 1 satellites) ---------------------------------------


def test_v1_journal_magic_refused(tmp_path):
    """A journal from the pre-splitmix build must fail LOUDLY: its stored row
    keys no longer match keys this build derives for the same values."""
    import os

    import pytest

    from pathway_tpu.persistence.engine import PersistenceManager

    store = tmp_path / "ps_v1"
    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(store))
    mgr = PersistenceManager(cfg)
    os.makedirs(mgr.root, exist_ok=True)
    with open(os.path.join(str(mgr.root), "journal.bin"), "wb") as f:
        f.write(b"PWTPUJ1\nsome-graph-sig\n")
    with pytest.raises(ValueError, match="incompatible earlier build"):
        mgr.load_journal("some-graph-sig")


def test_worker_count_mismatch_refused(tmp_path):
    """A store written under -n 2 reopened single-process must raise instead of
    silently resuming from an empty root shard (the shard layout differs)."""
    from dataclasses import replace

    import pytest

    from pathway_tpu.internals import config as config_mod
    from pathway_tpu.persistence.engine import PersistenceManager

    store = tmp_path / "ps_workers"
    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(store))
    base = config_mod.PathwayConfig.from_env()
    config_mod.set_thread_config(replace(base, processes=2, process_id=0))
    try:
        writer = PersistenceManager(cfg)
        writer.load_journal("sig")
        writer.open_for_append("sig")
        writer.close()
    finally:
        config_mod.set_thread_config(None)
    reader = PersistenceManager(cfg)  # single-process reopen
    with pytest.raises(ValueError, match="worker process"):
        reader.open_for_append("sig")


def test_same_worker_count_reopens_cleanly(tmp_path):
    """The guard must not fire on a faithful reopen."""
    from pathway_tpu.persistence.engine import PersistenceManager

    store = tmp_path / "ps_ok"
    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(store))
    writer = PersistenceManager(cfg)
    writer.load_journal("sig")
    writer.open_for_append("sig")
    writer.record_commit(0, {}, {})
    writer.close()
    reader = PersistenceManager(cfg)
    frames = reader.load_journal("sig")
    reader.open_for_append("sig")
    reader.close()
    assert len(frames) == 1


def test_fs_state_markers_not_duplicated_in_journal(tmp_path):
    """TODO item fixed this PR: fs per-file state deltas used to carry the
    full row payload ALONGSIDE the same rows' input deltas in the same frame
    (~2x journal size). Markers are now slim (file, mtime, n_rows) and the
    restore path re-derives rows from the frames' input deltas — asserted
    both structurally (no ``rows`` key journaled) and by byte count (the
    journal stays close to one copy of the corpus, not two)."""
    import pickle

    from pathway_tpu.persistence.engine import PersistenceManager

    input_dir = tmp_path / "in"
    input_dir.mkdir()
    store = tmp_path / "ps"
    payload = "word\n" + "\n".join(f"word-{i:05d}-{'x' * 64}" for i in range(500))
    (input_dir / "a.csv").write_text(payload)

    class Sch(pw.Schema):
        word: str

    def build():
        t = pw.io.csv.read(str(input_dir), schema=Sch, mode="static")
        counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())
        return _collect(counts)

    cfg = pw.persistence.Config(pw.persistence.Backend.filesystem(store))
    rows1 = build()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert len(rows1) == 500

    sig = G._current.sig()
    frames = PersistenceManager(cfg).load_journal(sig)
    markers = [
        d
        for _cid, _deltas, offs in frames
        for o in offs.values()
        for d in o.get("state_deltas", [])
    ]
    assert markers, "the fs completion marker must still be journaled"
    assert all("rows" not in d for d in markers), markers
    assert all(d.get("n_rows") == 500 for d in markers if not d.get("deleted"))

    # byte honesty: the journal holds ~one copy of the corpus. The OLD
    # behavior (marker carrying the rows) would add a second full copy —
    # simulate it from the journaled input deltas and assert the real journal
    # is well under journal+copy.
    journal_bytes = (store / "journal.bin").stat().st_size
    one_copy = sum(
        len(pickle.dumps({n: c[i] for n, c in d.columns.items()}))
        for _cid, deltas, _offs in frames
        for d in deltas.values()
        for i in range(len(d))
    )
    # measured ~1.04x one copy after the fix; the duplicated-rows behavior
    # was >= 2x by construction (rows in the delta AND in the marker)
    assert journal_bytes < 1.5 * one_copy, (journal_bytes, one_copy)

    # the resume path must rehydrate emitted rows well enough that a file
    # changed during downtime is retracted exactly (the behavioral half)
    time.sleep(0.05)
    (input_dir / "a.csv").write_text("word\nfresh\nfresh\n")
    os.utime(input_dir / "a.csv")
    G.clear()
    rows2 = build()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert {r["word"]: r["total"] for r in rows2.values()} == {"fresh": 2}
