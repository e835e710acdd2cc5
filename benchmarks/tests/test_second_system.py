"""The room for a second system: one added to a copy of ``benchmarks/`` as files
of its own (``second_system/``: a system module, a configuration, a cell, and
its two entries appended to the manifest as it stands) runs through the copy's
``run.py`` with no file that was there edited and no entry that was there
changed, is ``correct``, and is not with its fault planted. Every per-layer
reader of the manifest as it stands meets the second system's context and
raises nothing; those that are the vector store's own find nothing to read."""

import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NEW = os.path.join(HERE, "second_system")
ARGS = ["--workload", "top-words-steady", "--seed", "2147484027", "--seconds", "2"]
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
# the readers any system behind rest_connector leaves something for: no ``workloads`` key
COMMON = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m}


def digests(top):
    out = {}
    for folder, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(folder, name)
            out[os.path.relpath(path, top)] = hashlib.sha256(open(path, "rb").read()).hexdigest()
    return out


def result_of(command, cwd, trace=0):
    out = subprocess.run([sys.executable] + command + ARGS + ["--trace", str(trace)], capture_output=True, text=True,
                         timeout=600, cwd=cwd, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_second_system_is_added_as_files_only(tmp_path):
    copy = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(copy)
    for kind in ("systems", "configs", "workloads"):
        for name in os.listdir(os.path.join(NEW, kind)):
            assert os.path.join(kind, name) not in before  # a file added, none replaced
            shutil.copy(os.path.join(NEW, kind, name), os.path.join(copy, kind, name))
    with open(os.path.join(NEW, "manifest_entries.json")) as f:
        added = json.load(f)
    # the manifest as it stands, every entry of it, with the new configuration and cell appended
    manifest = dict(MANIFEST, **{key: MANIFEST[key] + added[key] for key in added})
    with open(str(tmp_path / "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    sound = result_of([os.path.join(copy, "run.py"), "--rehearse"], str(tmp_path))
    assert sound["correct"] is True, sound["compared"]
    assert sound["attempted"] == 100 and sound["failed"] == 0 and sound["device"]["platform"] == "cpu"
    assert {"bad_replies", "wrong_answers"} <= set(sound["compared"])
    assert sound["metrics"] == {} and set(sound["read_not_reported"]) == {m["name"] for m in MANIFEST["end_to_end"]}

    # the driver's other pass: the per-layer readers that hold for this cell, on what an untraced CPU run leaves
    traced = result_of([os.path.join(copy, "run.py"), "--rehearse"], str(tmp_path), trace=1)
    assert traced["correct"] is True and traced["metrics"] == {}
    assert set(traced["read_not_reported"]) == {"retrieve_p95_ms", "gen_late_p95_ms"} <= COMMON

    faulty = result_of([os.path.join(copy, "tests", "second_system", "plant_fault.py")], str(tmp_path))
    assert faulty["correct"] is False and faulty["failed"] == 0
    assert faulty["compared"]["wrong_answers"]["value"] > faulty["compared"]["wrong_answers"]["limit"] == 0

    after = digests(copy)
    assert {path: after.get(path) for path in before} == before  # nothing that was there differs
    assert os.path.join("systems", "top_words.py") in after


def test_every_reader_of_the_manifest_meets_the_second_systems_context():
    """``run.read_metrics`` over all sixteen ``per_layer`` entries, also those the
    manifest keeps to ``serve-dense-2m``, with what a traced run of the second
    system would hand them: its ``metric_context``, no work count, no counter,
    the recorded cut's device events under another program's name and the spans
    that the REST connector and the engine leave. No reader raises; the eight
    with no ``workloads`` key read a value, the vector store's eight nothing."""
    import run
    import trace_reduce
    from record_cut import events_of

    with open(os.path.join(HERE, "trace_cut.json")) as f:
        cut = json.load(f)
    path = os.path.join(NEW, "systems", "top_words.py")
    spec_ = importlib.util.spec_from_file_location("bench_systems_top_words", path)
    top_words = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(top_words)
    with open(os.path.join(NEW, "configs", "top-words-host.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(NEW, "workloads", "top-words-steady.json")) as f:
        traffic = json.load(f)
    events = [(plane, line, "jit_some_program" if line == trace_reduce.MODULE_LINE else name, start, dur)
              for plane, line, name, start, dur in events_of(cut)]
    spans = [s for s in cut["spans"] if s["kind"] in ("rest", "admit", "queue", "commit", "reply")]
    spec = {"cell": {"name": "top-words-steady"}, "config": cfg, "traffic": traffic, "system": top_words}
    ctx = {"spec": spec, "stats": {"latency_ms": [1.0, 2.0], "late_ms": [0.1], "good": 2},
           "gen": {"records": cut["records"], "start_at": 0.0}, "setup_s": 1.0, "seconds": 2.0,
           "counters_before": {}, "counters_after": {}, "peaks": run.load_json("peaks.json")["TPU v5 lite"],
           "percentile": run.percentile, "trace": trace_reduce.reduce(events, cut["seconds"]),
           "trace_span": {"t0": 0.0, "t1": cut["seconds"]}, "spans": spans,
           "work": None, **top_words.metric_context(cfg)}
    assert len(MANIFEST["per_layer"]) == 16 and len(COMMON) == 8
    got = run.read_metrics(MANIFEST["per_layer"], ctx)
    assert set(got) == COMMON, sorted(set(got) ^ COMMON)
    assert all(isinstance(m["value"], float) for m in got.values())
    # and a configuration that does state a model and a work count still gets no MiniLM step as its own
    with_model = dict(ctx, spec=dict(spec, config=dict(cfg, model={"hidden_size": 8, "intermediate_size": 8,
                                                                   "num_hidden_layers": 1})),
                      work=run.load_module("work", "dense_scan"))
    assert set(run.read_metrics(MANIFEST["per_layer"], with_model)) == COMMON
