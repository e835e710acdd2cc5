"""Multi-process scale-out: ``pathway_tpu spawn -n 2`` with exact global counts.

Each spawned process ingests its own shard; the cluster exchange hash-routes
rows so every group is owned by exactly one process and the merged answer is
exact. This driver script launches the spawn and checks the merged output.

    JAX_PLATFORMS=cpu python examples/06_multiprocess_spawn.py
"""

import collections
import json
import os
import subprocess
import sys
import tempfile
import textwrap

PROG = textwrap.dedent(
    """
    import json, os
    import pathway_tpu as pw

    tmp = os.environ["EXAMPLE_DIR"]
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    words = json.load(open(os.path.join(tmp, f"shard_{pid}.json")))
    t = pw.debug.table_from_rows(pw.schema_builder({"word": str}), [(w,) for w in words])
    counts = t.groupby(t.word).reduce(t.word, n=pw.reducers.count())
    got = {}
    pw.io.subscribe(
        counts,
        lambda key, row, time, is_addition: got.__setitem__(row["word"], row["n"])
        if is_addition
        else got.pop(row["word"], None),
    )
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    json.dump(got, open(os.path.join(tmp, f"out_{pid}.json"), "w"))
    """
)

with tempfile.TemporaryDirectory() as tmp:
    shards = {0: ["cat", "dog", "cat"], 1: ["cat", "owl"]}
    for pid, words in shards.items():
        with open(os.path.join(tmp, f"shard_{pid}.json"), "w") as f:
            json.dump(words, f)
    prog = os.path.join(tmp, "prog.py")
    with open(prog, "w") as f:
        f.write(PROG)
    env = {**os.environ, "EXAMPLE_DIR": tmp}
    subprocess.run(
        [sys.executable, "-m", "pathway_tpu.cli", "spawn", "-n", "2",
         "--first-port", "27300", sys.executable, prog],
        env=env, check=True, timeout=180,
    )
    merged = collections.Counter()
    for pid in shards:
        with open(os.path.join(tmp, f"out_{pid}.json")) as f:
            merged.update(json.load(f))
    print("merged:", dict(merged))
    assert dict(merged) == {"cat": 3, "dog": 1, "owl": 1}
    print("OK")
