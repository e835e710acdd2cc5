"""CLI tests: spawn multi-process partitioned ingest, record/replay flow.

Mirrors the reference's CLI contract (cli.py spawn/-t/-n env vars, record/replay)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pathway_tpu as pw
from pathway_tpu.internals.config import PathwayConfig


def _env():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "/root/repo"}
    return env


def test_config_from_env(monkeypatch):
    monkeypatch.setenv("PATHWAY_THREADS", "4")
    monkeypatch.setenv("PATHWAY_PROCESSES", "2")
    monkeypatch.setenv("PATHWAY_PROCESS_ID", "1")
    monkeypatch.setenv("PATHWAY_SNAPSHOT_ACCESS", "replay")
    cfg = PathwayConfig.from_env()
    assert (cfg.threads, cfg.processes, cfg.process_id) == (4, 2, 1)
    assert cfg.continue_after_replay is False
    monkeypatch.setenv("PATHWAY_CONTINUE_AFTER_REPLAY", "true")
    assert PathwayConfig.from_env().continue_after_replay is True


_SPAWN_PROG = r"""
import os, sys, json
import pathway_tpu as pw

input_dir, out_prefix = sys.argv[1], sys.argv[2]

class Sch(pw.Schema):
    word: str

t = pw.io.csv.read(input_dir, schema=Sch, mode="static")
rows = []
pw.io.subscribe(t, lambda key, row, time, is_addition: rows.append(row["word"]))
pw.run()
pid = os.environ.get("PATHWAY_PROCESS_ID", "0")
with open(f"{out_prefix}.{pid}", "w") as f:
    json.dump(sorted(rows), f)
"""


def test_spawn_two_processes_partition_files(tmp_path):
    input_dir = tmp_path / "in"
    input_dir.mkdir()
    for i in range(8):
        (input_dir / f"f{i}.csv").write_text(f"word\nw{i}\n")
    prog = tmp_path / "prog.py"
    prog.write_text(_SPAWN_PROG)
    out_prefix = str(tmp_path / "out")

    res = subprocess.run(
        [
            sys.executable,
            "-m",
            "pathway_tpu.cli",
            "spawn",
            "-n",
            "2",
            sys.executable,
            str(prog),
            str(input_dir),
            out_prefix,
        ],
        env=_env(),
        cwd="/root/repo",
        capture_output=True,
        timeout=120,
    )
    assert res.returncode == 0, res.stderr.decode()
    words0 = json.load(open(out_prefix + ".0"))
    words1 = json.load(open(out_prefix + ".1"))
    # disjoint partition covering all files
    assert set(words0) & set(words1) == set()
    assert set(words0) | set(words1) == {f"w{i}" for i in range(8)}
    assert words0 and words1  # both processes got a share (8 files, hash split)


_RECORD_PROG = r"""
import os, sys, json
import pathway_tpu as pw

input_dir, out_path = sys.argv[1], sys.argv[2]

class Sch(pw.Schema):
    word: str

t = pw.io.csv.read(input_dir, schema=Sch, mode="static")
counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())
rows = {}
def on_change(key, row, time, is_addition):
    if is_addition:
        rows[repr(key)] = dict(word=row["word"], total=int(row["total"]))
    else:
        rows.pop(repr(key), None)
pw.io.subscribe(counts, on_change)
pw.run()
with open(out_path, "w") as f:
    json.dump(sorted((r["word"], r["total"]) for r in rows.values()), f)
"""


def test_record_then_replay(tmp_path):
    input_dir = tmp_path / "in"
    input_dir.mkdir()
    (input_dir / "a.csv").write_text("word\ncat\ncat\ndog\n")
    prog = tmp_path / "prog.py"
    prog.write_text(_RECORD_PROG)
    record_path = str(tmp_path / "recording")

    res = subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "--record", "--record-path", record_path,
            sys.executable, str(prog), str(input_dir), str(tmp_path / "out1.json"),
        ],
        env=_env(), cwd="/root/repo", capture_output=True, timeout=120,
    )
    assert res.returncode == 0, res.stderr.decode()
    out1 = json.load(open(tmp_path / "out1.json"))
    assert out1 == [["cat", 2], ["dog", 1]]

    # replay from the recording with the INPUT GONE — results must come from the journal
    (input_dir / "a.csv").unlink()
    res = subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu.cli", "replay",
            "--record-path", record_path, "--mode", "batch",
            sys.executable, str(prog), str(input_dir), str(tmp_path / "out2.json"),
        ],
        env=_env(), cwd="/root/repo", capture_output=True, timeout=120,
    )
    assert res.returncode == 0, res.stderr.decode()
    out2 = json.load(open(tmp_path / "out2.json"))
    assert out2 == out1
