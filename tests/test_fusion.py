"""Whole-commit fusion: planner boundaries, bitwise fused-vs-unfused parity
(interpreter AND forced-XLA paths), the PATHWAY_FUSION=off escape hatch,
``fuse.*`` telemetry + the ``fusion`` flight event, the one-AnalysisContext
regression, the <1 s planning-overhead guard, and a chaos-marked fenced-rejoin
replay over a fused pipeline."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine.runner import GraphRunner
from pathway_tpu.internals import parse_graph as pg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.fusion


@pytest.fixture(autouse=True)
def _clean_graph(monkeypatch):
    pg.G.clear()
    monkeypatch.setenv("PATHWAY_LINT", "off")
    yield
    pg.G.clear()


def _run_capture(build, fusion: str, jit_rows: "int | None" = None) -> list:
    """Build the graph via ``build(capture_list)`` and run it under the given
    PATHWAY_FUSION mode; returns the captured per-batch sink bytes."""
    prev = {
        k: os.environ.get(k) for k in ("PATHWAY_FUSION", "PATHWAY_FUSION_JIT_ROWS")
    }
    os.environ["PATHWAY_FUSION"] = fusion
    if jit_rows is not None:
        os.environ["PATHWAY_FUSION_JIT_ROWS"] = str(jit_rows)
    try:
        pg.G.clear()
        got: list = []
        out = build()
        pw.io.subscribe(out, on_batch=lambda keys, diffs, columns, time: got.append(
            (
                keys.tobytes(),
                diffs.tobytes(),
                tuple(
                    (nm, col.tobytes())
                    if np.asarray(col).dtype != object
                    else (nm, repr(np.asarray(col).tolist()).encode())
                    for nm, col in sorted(columns.items())
                ),
            )
        ))
        runner = GraphRunner(pg.G._current)
        runner.run(monitoring_level=pw.MonitoringLevel.NONE)
        got.append(("schedule", runner._fusion_schedule is not None))
        return got
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _chain_rows(n=3_000, commits=4, seed=7):
    rng = np.random.default_rng(seed)
    per = n // commits
    return [
        (int(a), int(q), int(t), int(c), 2 * (i // per), 1)
        for i, (a, q, t, c) in enumerate(
            zip(
                rng.integers(1, 10**6, n),
                rng.integers(1, 50, n),
                rng.integers(0, 10**9, n),
                rng.integers(0, 32, n),
            )
        )
    ]


_CHAIN_SCHEMA = {"amount": int, "qty": int, "ts": int, "cat": int}


def _int_chain(rows):
    t = pw.debug.table_from_rows(pw.schema_builder(_CHAIN_SCHEMA), rows, is_stream=True)
    t1 = t.select(t.cat, total=t.amount * t.qty, day=t.ts // 86400, hod=(t.ts >> 7) & 31)
    t2 = t1.select(t1.cat, t1.day,
                   net=pw.if_else(t1.total > 10**7, t1.total - (t1.total >> 4), t1.total),
                   bucket=(t1.day & 7) * 32 + t1.cat + t1.hod)
    t3 = t2.filter((t2.net > 500_000) & ((t2.bucket & 3) != 0))
    t4 = t3.select(t3.cat, score=t3.net * 3 - t3.day, band=t3.bucket ^ (t3.net & 0xFF))
    return t4.groupby(t4.cat).reduce(
        t4.cat, s=pw.reducers.sum(t4.score), b=pw.reducers.sum(t4.band),
        n=pw.reducers.count(),
    )


# -- planner ------------------------------------------------------------------


def test_planner_chains_and_regions():
    from pathway_tpu.analysis import AnalysisContext, plan_fusion

    rows = _chain_rows(200, 2)
    _int_chain(rows)
    plan = plan_fusion(AnalysisContext(pg.G._current))
    assert plan.chains, "select/filter chain did not plan"
    # one chain covering the rowwise/filter run (4 nodes: t1 t2 filter t4)
    assert max(len(c) for c in plan.chains) == 4
    assert plan.regions and any(
        "groupby" in r.kinds for r in plan.regions
    ), "groupby member should join the fused region"
    ev = plan.to_event()
    assert ev["ops_fused"] == plan.ops_fused > 0


def test_host_udf_mid_chain_splits_region():
    """PWA004's condition is a fusion boundary: an apply() in the middle of a
    chain splits it — the surrounding pure segments still fuse separately."""
    from pathway_tpu.analysis import AnalysisContext, plan_fusion

    rows = _chain_rows(200, 2)
    t = pw.debug.table_from_rows(pw.schema_builder(_CHAIN_SCHEMA), rows, is_stream=True)
    a = t.select(t.cat, x=t.amount * t.qty)
    b = a.select(a.cat, y=a.x + 1)
    mid = b.select(b.cat, z=pw.apply(lambda y: y * 2, b.y))  # host UDF boundary
    c = mid.select(mid.cat, w=mid.z)
    d = c.select(c.cat, v=c.w)
    d.groupby(d.cat).reduce(d.cat, n=pw.reducers.count())
    plan = plan_fusion(AnalysisContext(pg.G._current))
    chain_nodes = {nid for ch in plan.chains for nid in ch.node_ids}
    assert mid._node.id not in chain_nodes, "UDF node must not fuse"
    assert mid._node.id in plan.boundaries
    assert plan.boundaries[mid._node.id] == "host_udf"
    # the pre-UDF pair and the post-UDF pair each form their own chain
    assert {a._node.id, b._node.id} <= chain_nodes
    assert {c._node.id, d._node.id} <= chain_nodes
    assert len(plan.chains) == 2


def test_drain_sensitive_ops_never_fused():
    """REWIND_SAFE=False evaluators (buffer/freeze/forget flush on the live
    ``draining`` signal) must never appear in a chain or region."""
    from pathway_tpu.analysis import AnalysisContext, plan_fusion
    from pathway_tpu.engine.evaluators import EVALUATORS

    rows = _chain_rows(200, 2)
    _int_chain(rows)
    plan = plan_fusion(AnalysisContext(pg.G._current))
    drain_kinds = {
        node_cls.kind
        for node_cls, ev in EVALUATORS.items()
        if not getattr(ev, "REWIND_SAFE", True)
    }
    node_by_id = {n.id: n for n in pg.G._current.nodes}
    for ch in plan.chains:
        for nid in ch.node_ids:
            assert node_by_id[nid].kind not in drain_kinds
    for r in plan.regions:
        for nid in r.member_ids:
            assert node_by_id[nid].kind not in drain_kinds


def test_cross_table_ref_is_boundary():
    from pathway_tpu.analysis import AnalysisContext, plan_fusion

    rows = _chain_rows(200, 2)
    t = pw.debug.table_from_rows(pw.schema_builder(_CHAIN_SCHEMA), rows, is_stream=True)
    a = t.select(t.cat, x=t.amount * t.qty)
    b = a.select(a.cat, y=a.x + 1)
    c = b.select(b.cat, z=b.y + a.x)  # cross-table reference: live dependency
    c.groupby(c.cat).reduce(c.cat, n=pw.reducers.count())
    plan = plan_fusion(AnalysisContext(pg.G._current))
    chain_nodes = {nid for ch in plan.chains for nid in ch.node_ids}
    assert c._node.id not in chain_nodes
    assert plan.boundaries[c._node.id] == "cross_table_ref"


# -- bitwise parity -----------------------------------------------------------


def test_parity_int_chain_interpreter():
    rows = _chain_rows()
    a = _run_capture(lambda: _int_chain(rows), "off")
    b = _run_capture(lambda: _int_chain(rows), "on")
    assert a[-1] == ("schedule", False) and b[-1] == ("schedule", True)
    assert a[:-1] == b[:-1]


def test_parity_int_chain_jit_forced():
    rows = _chain_rows()
    a = _run_capture(lambda: _int_chain(rows), "off")
    b = _run_capture(lambda: _int_chain(rows), "on", jit_rows=64)
    assert a[:-1] == b[:-1]


def test_parity_float_fma_chain_rejects_jit_stays_exact():
    """A float mul→add chain is where XLA:CPU contracts to FMA; the first-use
    parity probe must catch it, downgrade the program, and keep fused output
    byte-identical anyway."""
    from pathway_tpu.engine import telemetry

    rng = np.random.default_rng(3)
    n = 1_000
    rows = [
        (float(x), float(y), 2 * (i // 250), 1)
        for i, (x, y) in enumerate(
            zip(rng.standard_normal(n), rng.standard_normal(n) * 1e3)
        )
    ]

    def build():
        t = pw.debug.table_from_rows(
            pw.schema_builder({"x": float, "y": float}), rows, is_stream=True
        )
        t1 = t.select(z=t.x * t.y + t.x, w=t.x - t.y)
        t2 = t1.select(v=t1.z * 2.0 + t1.w)
        return t2.select(out=t2.v * 0.5 + 1.0)

    before = telemetry.stage_snapshot("fuse.").get("fuse.jit_parity_rejects", 0.0)
    a = _run_capture(build, "off")
    b = _run_capture(build, "on", jit_rows=64)
    assert a[:-1] == b[:-1], "fused float chain diverged from unfused"
    after = telemetry.stage_snapshot("fuse.").get("fuse.jit_parity_rejects", 0.0)
    assert after > before, "FMA contraction should have tripped the parity probe"


def test_parity_filter_empties_mid_chain():
    rows = _chain_rows(400, 2)

    def build():
        t = pw.debug.table_from_rows(
            pw.schema_builder(_CHAIN_SCHEMA), rows, is_stream=True
        )
        t1 = t.select(t.cat, x=t.amount * t.qty)
        dead = t1.filter(t1.x < 0)  # drops every row
        t2 = dead.select(dead.cat, y=dead.x + 1)
        return t2.groupby(t2.cat).reduce(t2.cat, n=pw.reducers.count())

    a = _run_capture(build, "off")
    b = _run_capture(build, "on", jit_rows=64)
    assert a[:-1] == b[:-1]


def test_parity_retraction_stream():
    """Insert/retract pairs flow through a fused chain bit-identically
    (retraction rows carry values; filters/maps must treat them alike)."""
    rows = []
    for i in range(300):
        rows.append((1000 + i, 3, i * 1000, i % 8, 0, 1))
    for i in range(0, 300, 3):
        rows.append((1000 + i, 3, i * 1000, i % 8, 2, -1))

    def build():
        t = pw.debug.table_from_rows(
            pw.schema_builder(_CHAIN_SCHEMA), rows, is_stream=True
        )
        t1 = t.select(t.cat, x=t.amount * t.qty + (t.ts >> 3))
        t2 = t1.filter((t1.x & 1) == 0)
        t3 = t2.select(t2.cat, y=t2.x * 5)
        return t3.groupby(t3.cat).reduce(t3.cat, s=pw.reducers.sum(t3.y))

    a = _run_capture(build, "off")
    b = _run_capture(build, "on", jit_rows=32)
    assert a[:-1] == b[:-1]


def test_parity_object_columns_fall_back():
    """String/object columns in the chain: the XLA path declines at runtime
    (dtype gate), composed interpreter execution stays bit-identical."""
    rows = [
        (f"u{i % 7}", i * 3, 2 * (i // 100), 1) for i in range(400)
    ]

    def build():
        t = pw.debug.table_from_rows(
            pw.schema_builder({"name": str, "v": int}), rows, is_stream=True
        )
        t1 = t.select(t.name, x=t.v * 2 + 1)
        t2 = t1.filter(t1.x > 100)
        t3 = t2.select(t2.name, y=t2.x - 50)
        return t3.groupby(t3.name).reduce(t3.name, s=pw.reducers.sum(t3.y))

    a = _run_capture(build, "off")
    b = _run_capture(build, "on", jit_rows=32)
    assert a[:-1] == b[:-1]


def test_examples_01_05_parity_fused_vs_unfused(tmp_path):
    """The example programs print their outputs and assert their results:
    identical stdout under PATHWAY_FUSION=on and =off is end-to-end bitwise
    parity over real pipelines (02 is joins, 03 temporal behaviors — the neu
    phase flows through fused chains there)."""
    examples = [
        "01_streaming_wordcount.py",
        "02_etl_joins.py",
        "03_windows_and_behaviors.py",
        "04_vector_index_rag.py",
        "05_persistence_resume.py",
    ]
    for name in examples:
        outs = {}
        for mode in ("on", "off"):
            env = dict(os.environ)
            env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
            env["JAX_PLATFORMS"] = "cpu"
            env["PATHWAY_FUSION"] = mode
            env["PATHWAY_FUSION_JIT_ROWS"] = "64"
            proc = subprocess.run(
                [sys.executable, os.path.join(REPO, "examples", name)],
                capture_output=True, text=True, timeout=120,
                cwd=str(tmp_path), env=env,
            )
            assert proc.returncode == 0, f"{name} [{mode}]: {proc.stderr[-2000:]}"
            outs[mode] = proc.stdout
        assert outs["on"] == outs["off"], f"{name}: fused stdout differs"


# -- the off gate and shared analysis context ---------------------------------


def test_fusion_off_builds_no_schedule():
    rows = _chain_rows(200, 2)
    got = _run_capture(lambda: _int_chain(rows), "off")
    assert got[-1] == ("schedule", False)


def test_single_analysis_context_per_run(monkeypatch):
    """The lint gate and the fusion planner share ONE AnalysisContext — the
    regression here was each building its own (two full DAG walks per run)."""
    from pathway_tpu.analysis import framework

    counts = {"n": 0}
    orig = framework.AnalysisContext.__init__

    def counting(self, *a, **k):
        counts["n"] += 1
        return orig(self, *a, **k)

    monkeypatch.setattr(framework.AnalysisContext, "__init__", counting)
    monkeypatch.setenv("PATHWAY_LINT", "warn")
    monkeypatch.setenv("PATHWAY_FUSION", "on")
    rows = _chain_rows(200, 2)
    pg.G.clear()
    out = _int_chain(rows)
    pw.io.subscribe(out, on_batch=lambda *a: None)
    GraphRunner(pg.G._current).run(monitoring_level=pw.MonitoringLevel.NONE)
    assert counts["n"] == 1, (
        f"lint gate + fusion planner built {counts['n']} AnalysisContexts; "
        "they must share one"
    )


# -- telemetry / flight recorder ----------------------------------------------


def test_fuse_counters_and_flight_event():
    from pathway_tpu.engine import telemetry
    from pathway_tpu.engine.profile import get_flight_recorder

    rec = get_flight_recorder()
    before = telemetry.stage_snapshot("fuse.")
    rows = _chain_rows(600, 3)
    _run_capture(lambda: _int_chain(rows), "on", jit_rows=64)
    after = telemetry.stage_snapshot("fuse.")

    def grew(key):
        return after.get(key, 0.0) > before.get(key, 0.0)

    assert grew("fuse.chains_built")
    assert grew("fuse.ops_fused")
    assert grew("fuse.schedules_built")
    assert grew("fuse.jit_compiles")
    assert grew("fuse.jit_hits")
    events = [e for e in rec.payload("test")["events"] if e["kind"] == "fusion"]
    assert events, "fusion flight event missing (post-mortems must name the plan)"
    ev = events[-1]
    assert ev["chains"] and ev["ops_fused"] > 0


def test_fused_region_profiler_attribution():
    """The PR-5 profiler shows a region row AND per-member estimate rows, so
    /metrics operator families stay live under fusion."""
    from pathway_tpu.engine.profile import get_profiler, reset_profile

    reset_profile()
    prev = os.environ.get("PATHWAY_PROFILE")
    os.environ["PATHWAY_PROFILE"] = "1"
    try:
        rows = _chain_rows(600, 3)
        _run_capture(lambda: _int_chain(rows), "on")
        totals = get_profiler().operator_totals()
    finally:
        if prev is None:
            os.environ.pop("PATHWAY_PROFILE", None)
        else:
            os.environ["PATHWAY_PROFILE"] = prev
    kinds = {e["kind"] for e in totals}
    assert "fused_chain" in kinds, "region row missing"
    members = [e for e in totals if e["kind"] in ("rowwise", "filter")]
    assert members and any(e["rows"] > 0 for e in members), (
        "per-member estimates missing: operator families went dark"
    )
    region = next(e for e in totals if e["kind"] == "fused_chain")
    member_s = sum(e["seconds"] for e in members)
    assert member_s <= region["seconds"] * 1.001, (
        "member estimates must partition the region's wall time"
    )
    reset_profile()


# -- jit cache discipline -----------------------------------------------------


def test_jit_cache_bounded_over_ragged_commits():
    """pow2 shape bucketing: many distinct commit sizes, few compiles."""
    sizes = [130, 260, 510, 140, 390, 770, 120, 515, 1030, 253]
    rows = []
    pos = 0
    rng = np.random.default_rng(11)
    for ci, sz in enumerate(sizes):
        for _ in range(sz):
            rows.append(
                (int(rng.integers(1, 10**6)), int(rng.integers(1, 50)),
                 int(rng.integers(0, 10**9)), int(rng.integers(0, 32)), 2 * ci, 1)
            )
        pos += sz

    prev = os.environ.get("PATHWAY_FUSION_JIT_ROWS")
    os.environ["PATHWAY_FUSION_JIT_ROWS"] = "64"
    os.environ["PATHWAY_FUSION"] = "on"
    try:
        pg.G.clear()
        out = _int_chain(rows)
        pw.io.subscribe(out, on_batch=lambda *a: None)
        runner = GraphRunner(pg.G._current)
        runner.run(monitoring_level=pw.MonitoringLevel.NONE)
        stats = [
            it.stats()
            for it in (runner._fusion_schedule or [])
            if hasattr(it, "stats")
        ]
    finally:
        os.environ.pop("PATHWAY_FUSION", None)
        if prev is None:
            os.environ.pop("PATHWAY_FUSION_JIT_ROWS", None)
        else:
            os.environ["PATHWAY_FUSION_JIT_ROWS"] = prev
    assert stats
    for s in stats:
        # 10 ragged sizes spanning 130..1030 collapse into <= 5 pow2 buckets
        assert s["jit_compiles"] <= 5 * max(1, s["runs"]), s
        assert len(s["jit_buckets"]) <= 5, s


def test_planning_overhead_under_lint_bound():
    """Tier-1 guard: fusion planning + schedule compilation on a 30-node chain
    stays under the same <1 s bound as the lint gate — planner cost must never
    show up in commit latency."""
    rows = [(i, 2 * i, 0, 1) for i in range(64)]
    t = pw.debug.table_from_rows(
        pw.schema_builder({"v": int, "w": int}), rows, is_stream=True
    )
    cur = t
    for _ in range(30):
        cur = cur.select(v=cur.v + 1, w=cur.w * 2)
    out = cur.groupby(cur.v).reduce(cur.v, n=pw.reducers.count())
    pw.io.subscribe(out, on_batch=lambda *a: None)
    from pathway_tpu.analysis import AnalysisContext, plan_fusion
    from pathway_tpu.engine.fusion import build_schedule

    runner = GraphRunner(pg.G._current)
    t0 = time.perf_counter()
    runner.setup(None)  # includes _build_fusion
    elapsed = time.perf_counter() - t0
    assert runner._fusion_schedule is not None
    assert elapsed < 1.0, f"setup incl. fusion planning took {elapsed:.3f}s"
    t0 = time.perf_counter()
    plan = plan_fusion(AnalysisContext(pg.G._current))
    build_schedule(runner, plan)
    replan = time.perf_counter() - t0
    assert replan < 1.0, f"planning alone took {replan:.3f}s on a 30-node chain"
    runner.finish()


# -- chaos: fused commits replay bit-identical through a fenced rejoin --------

FUSED_REJOIN_PROG = r"""
import json, os
import pathway_tpu as pw

tmp = os.environ["PATHWAY_TPU_TEST_DIR"]
pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

class RowSchema(pw.Schema):
    word: str
    v: int

t = pw.io.fs.read(
    os.path.join(tmp, "in"), format="csv", schema=RowSchema, mode="streaming"
)
t1 = t.select(t.word, x=t.v * 3 + 1)
t2 = t1.filter(t1.x > 0)
t3 = t2.select(t2.word, y=t2.x * 2 - 1)
counts = t3.groupby(t3.word).reduce(
    t3.word, total=pw.reducers.count(), s=pw.reducers.sum(t3.y)
)

out_path = os.path.join(tmp, f"out_{pid}.json")
rows = {}
def on_change(key, row, time, is_addition):
    if is_addition:
        rows[repr(key)] = {"word": row["word"], "total": int(row["total"]), "s": int(row["s"])}
    else:
        rows.pop(repr(key), None)
    with open(out_path + ".tmp", "w") as f:
        json.dump(list(rows.values()), f)
    os.replace(out_path + ".tmp", out_path)

pw.io.subscribe(counts, on_change)
cfg = pw.persistence.Config(
    pw.persistence.Backend.filesystem(os.path.join(tmp, "store"))
)
pw.run(persistence_config=cfg, monitoring_level=pw.MonitoringLevel.NONE)
"""


@pytest.mark.chaos
def test_fused_rejoin_replays_bit_identical(tmp_path):
    """SIGKILL one rank of a fused spawn -n 2 pipeline mid-run: the fenced
    survivor + relaunched rank replay fused commits and converge on output
    bit-identical to the failure-free run (fusion stays ON throughout)."""
    (tmp_path / "in").mkdir()
    first_port = 33000 + os.getpid() % 400 * 4
    for i in range(3):
        (tmp_path / "in" / f"a{i}.csv").write_text(
            "word,v\n" + "\n".join(
                f"w{j % 5},{j + i}" for j in range(8 * (i + 1))
            ) + "\n"
        )
    plan = {"kill": [{"rank": 1, "commit": 3, "run": 0}]}
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_CHAOS_SEED"] = "7"
    env["PATHWAY_CHAOS_PLAN"] = json.dumps(plan)
    env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
    env["PATHWAY_BARRIER_TIMEOUT_S"] = "30"
    env["PATHWAY_FUSION"] = "on"
    env["PATHWAY_FUSION_JIT_ROWS"] = "4"  # force the XLA path at test scale
    env["PATHWAY_LINT"] = "off"
    prog = tmp_path / "prog.py"
    prog.write_text(FUSED_REJOIN_PROG)
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "--first-port", str(first_port),
            "--max-restarts", "2",
            sys.executable, str(prog),
        ],
        env=env, cwd=str(tmp_path), start_new_session=True,
        stdout=subprocess.DEVNULL, stderr=open(tmp_path / "spawn.err", "w"),
    )

    def supervisor_log() -> str:
        return (tmp_path / "spawn.err").read_text()

    def read_merged() -> dict:
        merged: dict = {}
        for p in range(2):
            path = tmp_path / f"out_{p}.json"
            if not path.exists():
                continue
            try:
                for r in json.loads(path.read_text()):
                    merged[r["word"]] = (r["total"], r["s"])
            except ValueError:
                pass
        return merged

    # failure-free reference, computed in-process over the same pipeline math;
    # the late file lands only AFTER the failover window, so convergence on
    # these totals proves the HEALED cluster ingested and processed it through
    # the fused chain
    def fold(expected: dict, w: str, v: int) -> None:
        x = v * 3 + 1
        y = x * 2 - 1
        tot, s = expected.get(w, (0, 0))
        expected[w] = (tot + 1, s + y)

    expected: dict = {}
    for i in range(3):
        for j in range(8 * (i + 1)):
            fold(expected, f"w{j % 5}", j + i)
    late_rows = [(f"w{j % 5}", 100 + j) for j in range(10)]
    for w, v in late_rows:
        fold(expected, w, v)

    def wait_for(done, what) -> None:
        deadline = time.time() + 120
        while not done():
            if proc.poll() is not None:
                raise AssertionError(f"spawn exited early: {supervisor_log()[-3000:]}")
            assert time.time() < deadline, f"{what()}\n{supervisor_log()[-3000:]}"
            time.sleep(0.3)

    try:
        # a wait on state, not on the clock: the late file is written once the
        # supervisor has said the failover is over. A fixed sleep lost the race
        # under load in two ways: ranks still importing when the file landed
        # ingested all four files before commit 3, and a run that converged
        # before the rejoin was logged was torn down mid-fence
        wait_for(
            lambda: "rejoined the cluster" in supervisor_log()
            or "restarting the cluster" in supervisor_log(),
            lambda: "no recovery happened — the kill never fired?",
        )
        (tmp_path / "in" / "late.csv").write_text(
            "word,v\n" + "\n".join(f"{w},{v}" for w, v in late_rows) + "\n"
        )
        wait_for(
            lambda: read_merged() == expected,
            lambda: f"never converged: got {read_merged()}, want {expected}",
        )
    finally:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
