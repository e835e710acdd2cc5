"""Object-store (S3/Azure) persistence backends: journal frames as immutable
objects, single-PUT checkpoints, compaction by object delete, cached-object
storage over the same store.

Parity: reference ``src/persistence/backends/mod.rs:50`` (PersistenceBackend
trait) + ``backends/s3.rs``; the crash-kill rig mirrors
``integration_tests/wordcount`` over the S3 backend instead of filesystem.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pathway_tpu as pw
from pathway_tpu.engine.runner import GraphRunner
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.persistence.backends import MemoryObjectStore, S3ObjectStore

from .mocks import DirS3Client


def _collect(table):
    rows = {}

    def on_change(key, row, time, is_addition):
        if is_addition:
            rows[key] = row
        else:
            rows.pop(key, None)

    pw.io.subscribe(table, on_change)
    return rows


def _wordcount_pipeline():
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


def _s3_backend(client):
    return pw.persistence.Backend.s3(
        "s3://bucket/pipelines/p1", _client_factory=lambda settings: client
    )


def test_s3_journal_replay_reproduces_state(tmp_path):
    client = DirS3Client(str(tmp_path / "fake-s3"))
    cfg = pw.persistence.Config(_s3_backend(client))

    rows1 = _wordcount_pipeline()
    GraphRunner(G._current).run(persistence_config=cfg)
    result1 = {tuple(sorted(r.items())) for r in rows1.values()}
    assert {dict(r)["word"] for r in result1} == {"cat", "dog"}

    # journal frame objects exist under the prefix
    frames = client.list_objects_v2(
        Bucket="bucket", Prefix="pipelines/p1/journal/"
    )["Contents"]
    assert frames, "no journal frame objects written"

    # "restart": fresh graph + fresh runner over the same store — rows must
    # come from the frame objects
    G.clear()
    rows2 = _wordcount_pipeline()
    cfg2 = pw.persistence.Config(_s3_backend(client))
    GraphRunner(G._current).run(persistence_config=cfg2)
    result2 = {tuple(sorted(r.items())) for r in rows2.values()}
    assert result2 == result1


def test_s3_checkpoint_compacts_frame_objects(tmp_path):
    client = DirS3Client(str(tmp_path / "fake-s3"))
    cfg = pw.persistence.Config(_s3_backend(client), snapshot_interval_ms=1)

    rows = _wordcount_pipeline()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert rows

    listing = client.list_objects_v2(Bucket="bucket", Prefix="pipelines/p1/")
    keys = [c["Key"] for c in listing["Contents"]]
    assert any(k.endswith("checkpoint.pkl") for k in keys), keys
    # frames at/before the checkpoint were deleted (compaction)
    assert not any(k.endswith(".frame") for k in keys), keys

    # resume from the checkpoint alone
    G.clear()
    rows2 = _wordcount_pipeline()
    cfg2 = pw.persistence.Config(_s3_backend(client), snapshot_interval_ms=1)
    GraphRunner(G._current).run(persistence_config=cfg2)
    assert {dict(r)["word"] for r in rows2.values()} == {"cat", "dog"}


def test_s3_graph_signature_mismatch_raises(tmp_path):
    import pytest

    client = DirS3Client(str(tmp_path / "fake-s3"))
    cfg = pw.persistence.Config(_s3_backend(client))
    rows = _wordcount_pipeline()
    GraphRunner(G._current).run(persistence_config=cfg)
    assert rows

    G.clear()
    t = pw.debug.table_from_markdown(
        """
        city   | pop
        lisbon | 5
        """
    )
    _collect(t.select(t.city))
    cfg2 = pw.persistence.Config(_s3_backend(client))
    with pytest.raises(ValueError, match="different dataflow graph"):
        GraphRunner(G._current).run(persistence_config=cfg2)


def test_cached_objects_over_s3_store(tmp_path):
    from pathway_tpu.persistence.cached_objects import CachedObjectStorage

    client = DirS3Client(str(tmp_path / "fake-s3"))
    store = S3ObjectStore(client, "bucket", "cache")
    c1 = CachedObjectStorage(None, store=store)
    v1 = c1.place_object("s3://x/a", b"alpha", {"etag": "1"})
    c1.place_object("s3://x/b", b"beta", {"etag": "2"})
    c1.remove_object("s3://x/a")
    assert not c1.contains_object("s3://x/a")
    assert c1.get_object("s3://x/b") == b"beta"

    # a fresh instance over the same store replays the surviving events
    c2 = CachedObjectStorage(None, store=store)
    assert c2.actual_key_set() == {"s3://x/b"}
    assert c2.get_object("s3://x/b") == b"beta"
    assert c2.get_metadata("s3://x/b") == {"etag": "2"}

    # rewind durably drops newer events
    c2.rewind(v1)
    c3 = CachedObjectStorage(None, store=store)
    assert c3.actual_key_set() == {"s3://x/a"}
    assert c3.get_object("s3://x/a") == b"alpha"


def test_memory_object_store_contract():
    s = MemoryObjectStore()
    s.put("a/1", b"x")
    s.put("a/2", b"y")
    s.put("b/1", b"z")
    assert s.list("a/") == ["a/1", "a/2"]
    assert s.get("a/1") == b"x"
    assert s.get("missing") is None
    s.delete("a/1")
    assert s.list("a/") == ["a/2"]


_CRASH_SCRIPT = """
import json, os, sys
sys.path.insert(0, "/root/repo")
import pathway_tpu as pw
from tests.mocks import DirS3Client

input_dir, out_path, s3_dir = sys.argv[1], sys.argv[2], sys.argv[3]
t = pw.io.csv.read(input_dir, schema=pw.schema_builder({"word": str}), mode="streaming", autocommit_duration_ms=20)
counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())
rows = {}

def on_change(key, row, time, is_addition):
    if is_addition:
        rows[key] = row
    else:
        rows.pop(key, None)
    with open(out_path + ".tmp", "w") as f:
        json.dump(list(rows.values()), f)
    os.replace(out_path + ".tmp", out_path)

pw.io.subscribe(counts, on_change)
client = DirS3Client(s3_dir)
backend = pw.persistence.Backend.s3("s3://bucket/ps", _client_factory=lambda settings: client)
cfg = pw.persistence.Config(backend, snapshot_interval_ms=10)
pw.run(persistence_config=cfg)
"""


def test_s3_crash_kill_and_restart_wordcount(tmp_path):
    """kill -9 mid-run with the S3 backend; restart resumes from frame objects
    + checkpoint blobs without double-counting."""
    input_dir = tmp_path / "in"
    input_dir.mkdir()
    out_path = str(tmp_path / "out.json")
    s3_dir = str(tmp_path / "fake-s3")
    script = tmp_path / "prog.py"
    script.write_text(_CRASH_SCRIPT)

    (input_dir / "a.csv").write_text("word\n" + "\n".join(["cat"] * 5 + ["dog"] * 3) + "\n")

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "/root/repo"}
    proc = subprocess.Popen(
        [sys.executable, str(script), str(input_dir), out_path, s3_dir],
        env=env,
        cwd="/root/repo",
    )
    deadline = time.time() + 60
    while time.time() < deadline and not os.path.exists(out_path):
        time.sleep(0.1)
    assert os.path.exists(out_path), "pipeline never produced output"
    time.sleep(0.5)
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait()

    (input_dir / "b.csv").write_text("word\n" + "\n".join(["cat"] * 2 + ["owl"] * 4) + "\n")

    proc = subprocess.Popen(
        [sys.executable, str(script), str(input_dir), out_path, s3_dir],
        env=env,
        cwd="/root/repo",
    )
    try:
        deadline = time.time() + 90
        expected = {"cat": 7, "dog": 3, "owl": 4}
        rows = {}
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
