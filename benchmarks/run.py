"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration or metric is found by the
name ``BENCHMARK.json`` gives it: the cell's traffic in ``workloads/<cell>.json``,
its configuration in ``configs/<config>.json``, the configuration's work count in
``work/<name>.py`` and each metric's reader in ``metrics/<metric>.py``. The last
line of standard output is the result; everything else goes to standard error.

``--sweep r1,r2,...`` runs one set-up and then a ladder of rates to find the knee;
``--calibrate n`` reads the numbers ``correct`` compares on ``n`` query seeds, and
on the first four the controls' (the reference in lower precision, or with a
guarantee broken, in the program's place), each judged by the cell's limits, in
one process; ``--rehearse`` runs the whole command at a tiny size on the CPU and
prints no metric.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import faulthandler
import importlib.util
import json
import math
import os
import random
import subprocess
import sys
import traceback
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)
OUT_DIR = os.path.join(ROOT, ".bench_out")  # run-time files: generator logs, traces
TRACE_START_S, TRACE_SECONDS = 2.0, 4.0  # the profiler covers this much of the window
DRAIN_TIMEOUT_S = 60.0  # a reply is waited for this long past its due instant


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:6.1f}s] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> Any:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str) -> Any:
    """``work/<name>.py``, ``metrics/<name>.py`` or ``hooks/<name>.py``, by the name the data gives."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(cell_name: str, rehearse: bool) -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {c["name"]: c for c in manifest["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"unknown workload {cell_name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[cell_name]
    cfg = load_json("configs", cell["config"] + ".json")
    if rehearse:
        r = cfg["rehearse"]
        cfg["model"] = {**cfg["model"], **r["model"]}
        cfg["corpus"] = {**cfg["corpus"], **r["corpus"]}
        cfg["index"]["args"] = {**cfg["index"]["args"], **r["index_args"]}

    def wanted(metric: Dict[str, Any]) -> bool:
        return cell_name in metric.get("workloads", [cell_name])

    return {
        "cell": cell, "config": cfg, "traffic": load_json("workloads", cell_name + ".json"),
        "end_to_end": [m for m in manifest["end_to_end"] if wanted(m)],
        "per_layer": [m for m in manifest["per_layer"] if wanted(m)],
    }


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def device_report(rehearse: bool, chips: int) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device['platform']} kind={device['kind']!r} count={device['count']} jax {jax.__version__}")
    if rehearse:
        assert device["platform"] == "cpu", device
    elif device["platform"] != "tpu" or device["count"] < chips:
        raise SystemExit(f"run.py: needs {chips} TPU chip(s), JAX found {device}; no result")
    return device


def run_generator(spec: Dict[str, Any], system: Any, seed: int, seconds: float, rate: Optional[float],
                  tag: str, during: Any = None) -> Dict[str, Any]:
    """Start the generator child, let ``during(start_at)`` act while it runs,
    wait for it and read its log. Returns the records and the window's instants."""
    traffic = dict(spec["traffic"])
    if rate is not None:
        traffic["rate_rps"] = rate
    lead_in = float(traffic.get("lead_in_s", 0.0))
    os.makedirs(OUT_DIR, exist_ok=True)
    out = os.path.join(OUT_DIR, f"gen_{spec['cell']['name']}_{tag}.jsonl")
    start_at = time.monotonic() + lead_in + 1.5  # the child needs about a second to start
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"), "--traffic", json.dumps(traffic),
         "--corpus", json.dumps(spec["config"]["corpus"]), "--seed", str(seed),
         "--docs-seed", str(system.seed),
         "--seconds", str(seconds), "--lead-in", str(lead_in), "--port", str(system.port),
         "--start-at", repr(start_at), "--timeout", str(DRAIN_TIMEOUT_S), "--out", out],
        env={k: v for k, v in os.environ.items() if not k.startswith(("JAX", "XLA", "TPU"))},
    )
    try:
        extra = during(start_at) if during is not None else None
        rc = child.wait(timeout=lead_in + seconds + DRAIN_TIMEOUT_S + 60.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0:
        raise RuntimeError(f"the load generator exited with {rc}")
    with open(out) as f:
        records = [json.loads(line) for line in f]
    os.remove(out)
    return {"records": records, "start_at": start_at, "seconds": seconds, "during": extra,
            "rate_rps": float(traffic["rate_rps"])}


def window_stats(gen: Dict[str, Any], k: int) -> Dict[str, Any]:
    """What the harness itself takes from the generator's log: per request the
    latency from its due instant (a request with no good reply counts as the
    drain's whole wait) and how late it was sent."""
    import compare

    window = [r for r in gen["records"] if r["phase"] == "window"]
    latencies, late, good = [], [], 0
    for r in window:
        answer = compare.parse_reply(r["body"]) if r["status"] == 200 else None
        r["answer"] = answer
        ok = answer is not None and len(answer) == k
        good += ok
        latencies.append((r["done"] - r["due"]) * 1e3 if ok else DRAIN_TIMEOUT_S * 1e3)
        if r["sent"] is not None:
            late.append((r["sent"] - r["due"]) * 1e3)
    shed = sum(r["status"] == 429 for r in window)
    return {"window": window, "latency_ms": sorted(latencies), "late_ms": sorted(late),
            "attempted": len(window), "good": good, "failed": len(window) - good, "shed": shed}


def percentile(sorted_values: List[float], q: float) -> Optional[float]:
    """Nearest rank: the smallest value with at least ``q`` of the values at or under it."""
    if not sorted_values:
        return None
    rank = math.ceil(round(q * len(sorted_values), 6))
    return sorted_values[min(len(sorted_values), max(1, rank)) - 1]


def profile_span(trace_dir: str, start_at: float) -> Dict[str, float]:
    """Trace ``TRACE_SECONDS`` of the window; the span's instants, window-relative."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    time.sleep(max(0.0, start_at + TRACE_START_S - time.monotonic()))
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.monotonic()
    time.sleep(TRACE_SECONDS)
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    return {"t0": t0 - start_at, "t1": t1 - start_at, "stop_s": time.monotonic() - t1}


# what --calibrate puts in the program's place: (the encoder's precision, the
# scoring's precision, whether the resident rows are scanned)
CONTROLS = {
    "fp8_encoder": ("fp8", "f32", True),   # the control: the nearest precision below bfloat16
    "fp8_index": ("f32", "fp8", True),     # the same step down in the index's scoring passes
    "int8_encoder": ("int8", "f32", True),  # per-tensor int8, read beside the control
    "live_rows_only": ("f32", "f32", False),  # a guarantee broken: resident rows left out
}


def reference_check(spec: Dict[str, Any], system: Any, sample: List[dict], controls=()):
    """The plain reference over the sampled queries: its embeddings of the live
    documents (made in set-up, where the resident rows' neighbours are drawn
    around them) and of the queries, and its exact top-k over ALL rows (live and
    resident, the resident ones drawn again from the seed block by block). For
    each name in ``controls`` also that control's answers, in the program's place."""
    import compare
    import reference

    cfg, k = spec["config"], int(spec["traffic"]["k"])
    model, corpus = cfg["model"], cfg["corpus"]
    queries = [r["query"] for r in sample]
    query_vecs = reference.embed_texts(system.weights, queries, model)
    n_res, block = int(corpus["resident_rows"]), int(corpus["install_block_rows"])

    def resident(b: int, lo: int):
        return lambda: (system.resident_block(b)[: n_res - lo], len(system.docs) + lo)

    def blocks(doc_vecs, with_resident: bool = True):
        rest = [resident(b, lo) for b, lo in enumerate(range(0, n_res, block))] if with_resident else []
        return [lambda: (doc_vecs, 0)] + rest

    ref_topk, ref_ids = reference.exact_topk(query_vecs, blocks(system.doc_vecs), k)
    out = {"ref_scores": reference.cosine_to(query_vecs, system.doc_vecs), "ref_topk": ref_topk,
           "ref_ids": ref_ids, "control_answers": {}}
    for name in controls:
        encoder, scoring, with_resident = CONTROLS[name]
        docs_low, queries_low = system.doc_vecs, query_vecs
        if encoder != "f32":
            docs_low = reference.embed_texts(system.weights, system.docs, model, encoder)
            queries_low = reference.embed_texts(system.weights, queries, model, encoder)
        scores, ids = reference.exact_topk(queries_low, blocks(docs_low, with_resident), k, scoring)
        out["control_answers"][name] = compare.answers_from(ids, scores, system.docs)
    return out


def judge_window(spec, system, stats, seed, counters_before, counters_after, controls=()):
    """Sample the window's requests from the seed, run the reference over them
    and compare. Returns (correct, compared table, each control's (correct, table))."""
    import compare

    traffic = spec["traffic"]
    k, limits = int(traffic["k"]), traffic["limits"]
    window = stats["window"]
    rng = random.Random(seed)
    sample = rng.sample(window, min(int(traffic["sample"]), len(window)))
    ref = reference_check(spec, system, sample, controls)
    n_live, block = len(system.docs), int(spec["config"]["corpus"]["install_block_rows"])
    res_ids = ref["ref_ids"][ref["ref_ids"] >= n_live] - n_live
    log(f"reference top-{k} of {len(sample)} sampled queries: {res_ids.size} of {ref['ref_ids'].size} "
        f"entries are resident rows, {len(set(res_ids.tolist()))} distinct, from install blocks "
        f"{sorted(set((res_ids // block).tolist()))}")
    judged = lambda answers: compare.compare(answers, k, system.docs, ref["ref_scores"], ref["ref_topk"])
    numbers = judged([r["answer"] for r in sample])
    grew = sum(max(0.0, counters_after[n] - counters_before[n]) for n in counters_before
               if n.startswith("kernel.") or n == "svc_prewarm_compiles")
    late = percentile(stats["late_ms"], 0.95) or 0.0
    # a batch past the warmed sizes compiles; where the generator itself was late the
    # machine stalled and the backlog is the stall's, not the program's: printed, not judged
    stalled = late > float(traffic["stall_late_p95_ms"])
    if not stalled:
        numbers["compiles_in_window"] = grew
    correct, table = compare.judge(numbers, limits)
    if stalled:
        table["compiles_in_window"] = {"value": grew, "limit": None,
                                       "not_judged": f"generator late p95 {late:.1f} ms: the machine stalled"}
    return correct, table, {name: compare.judge(judged(a), limits) for name, a in ref["control_answers"].items()}


def search_time(ctx: Dict[str, Any]):
    """(device seconds, calls, queries per call) of the configuration's search
    programs inside the traced span; None where the trace shows none."""
    import trace_reduce

    if ctx.get("trace") is None:
        return None
    seconds, calls = trace_reduce.program_seconds(ctx["trace"], ctx["spec"]["config"]["search_programs"])
    if calls <= 0 or seconds <= 0:
        return None
    span = ctx["trace_span"]
    served = sum(1 for r in ctx["gen"]["records"]
                 if r["done"] is not None and r["status"] == 200 and span["t0"] <= r["done"] < span["t1"])
    return seconds, calls, served / calls


def read_metrics(names: List[Dict[str, Any]], ctx: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for m in names:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:  # a reader that finds nothing to read returns nothing
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="", help="comma-separated rates: one set-up, one step each")
    ap.add_argument("--calibrate", type=int, default=0, help="query seeds to read the compared numbers on")
    ap.add_argument("--rehearse", action="store_true", help="tiny size on the CPU; prints no metric")
    ap.add_argument("--keep-trace", action="store_true", help="leave the profiler's files in .bench_out")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    spec = resolve(args.workload, args.rehearse)
    cfg, traffic = spec["config"], spec["traffic"]

    import serving
    import textgen

    hooks = [load_module("hooks", name) for name in cfg.get("hooks", [])]
    for hook in hooks:
        if hasattr(hook, "before_server"):
            hook.before_server(cfg, log)
    device = device_report(args.rehearse, int(spec["cell"]["chips"]))
    import jax

    peaks = load_json("peaks.json")
    if not args.rehearse and device["kind"] not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind {device['kind']!r} in peaks.json")
    compile_events: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_events.append(time.monotonic())
        if name == "/jax/core/compile/backend_compile_duration" else None
    )

    docs = textgen.documents(cfg["corpus"], args.seed)
    system = serving.System(cfg, args.seed, free_port(), docs, log)
    system.wait_ready()
    system.warm_up(int(traffic["k"]), int(traffic["warm_max_batch"]))
    for hook in hooks:
        if hasattr(hook, "after_ready"):
            hook.after_ready(system, log)
    log("set-up parts (s): " + json.dumps({k: round(v, 2) for k, v in system.timings.items()}))
    k = int(traffic["k"])

    if args.sweep:
        for i, rate in enumerate(float(r) for r in args.sweep.split(",")):
            c0 = system.counters()
            gen = run_generator(spec, system, args.seed + i, args.seconds, rate, f"sweep{i}")
            c1 = system.counters()
            st = window_stats(gen, k)
            done = sorted(r["done"] for r in st["window"] if r["done"] is not None)
            half = args.seconds / 2

            def p50_of(first: bool) -> Optional[float]:
                lat = sorted(((r["done"] or 1e9) - r["due"]) * 1e3 for r in st["window"] if (r["due"] < half) == first)
                return lat[len(lat) // 2] if lat else None

            def in_flight(t: float) -> int:
                return sum(1 for r in st["window"] if r["sent"] is not None and r["sent"] <= t
                           and (r["done"] is None or r["done"] > t))

            med = [p50_of(True), p50_of(False)]
            inflight_mid, inflight_end = in_flight(half), in_flight(args.seconds)
            print(json.dumps({
                "sweep_rate_rps": rate, "attempted": st["attempted"], "failed": st["failed"], "shed": st["shed"],
                "p50_ms": percentile(st["latency_ms"], 0.5), "p95_ms": percentile(st["latency_ms"], 0.95),
                "p50_first_half_ms": med[0], "p50_second_half_ms": med[1],
                "inflight_mid": inflight_mid, "inflight_end": inflight_end,
                "last_done_s": done[-1] if done else None,
                "late_p95_ms": percentile(st["late_ms"], 0.95),
                "rows_per_tick": (c1["svc_rows"] - c0["svc_rows"]) / max(c1["svc_ticks"] - c0["svc_ticks"], 1.0),
                "device": device}), flush=True)
        return 0

    if args.calibrate:
        for i in range(args.calibrate):
            before = system.counters()
            gen = run_generator(spec, system, args.seed + 1 + i, args.seconds, None, f"cal{i}")
            st = window_stats(gen, k)
            correct, table, controls = judge_window(
                spec, system, st, args.seed + 1 + i, before, system.counters(),
                controls=tuple(CONTROLS) if i < 4 else ())
            values = lambda t: {n: v["value"] for n, v in t.items()}
            print(json.dumps({"calibrate_seed": args.seed + 1 + i, "attempted": st["attempted"],
                              "failed": st["failed"], "late_p95_ms": percentile(st["late_ms"], 0.95),
                              "correct": correct, "program": values(table),
                              "controls": {n: {"correct": c, **values(t)} for n, (c, t) in controls.items()},
                              "device": device}), flush=True)
        return 0

    trace_dir = os.path.join(OUT_DIR, f"trace_{args.workload}")
    before = system.counters()
    setup_s = None

    def during(start_at: float):
        nonlocal setup_s
        setup_s = start_at - T_START
        return profile_span(trace_dir, start_at) if args.trace and not args.rehearse else None

    gen = run_generator(spec, system, args.seed, args.seconds, None, "run", during)
    after = system.counters()
    stats = window_stats(gen, k)
    in_window = [t for t in compile_events if gen["start_at"] <= t <= gen["start_at"] + args.seconds]
    mem = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    log(f"window: attempted={stats['attempted']} good={stats['good']} shed={stats['shed']} "
        f"generator late p95={percentile(stats['late_ms'], 0.95)} ms; XLA compiles of any program "
        f"inside the window: {len(in_window)}; "
        f"setup_s={setup_s:.1f}; peak bytes {device['memory_peak_bytes']}")

    ctx: Dict[str, Any] = {
        "spec": spec, "stats": stats, "gen": gen, "setup_s": setup_s, "seconds": args.seconds,
        "counters_before": before, "counters_after": after, "peaks": peaks.get(device["kind"]),
        "percentile": percentile, "search_time": search_time, "trace": None, "work": load_module("work", cfg["work"]),
        "n_rows": int(cfg["corpus"]["total_rows"]),
    }
    breakdown: Dict[str, Any] = {}
    if args.trace and not args.rehearse:
        import shutil

        import trace_reduce

        t0 = time.monotonic()
        span = gen["during"]
        ctx["trace"] = trace_reduce.reduce(trace_reduce.load(trace_dir), span["t1"] - span["t0"])
        ctx["trace_span"] = span
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = ctx["trace"]["busy_s"], ctx["trace"]["window_s"]
        breakdown = {"breakdown": {"device_ops": ctx["trace"]["device_ops"], "idle_gaps": ctx["trace"]["idle_gaps"]}}
        log(f"trace: {span}; read in {time.monotonic() - t0:.1f} s; programs: "
            + json.dumps({n: [round(e['seconds'], 4), e['calls']] for n, e in sorted(
                ctx['trace']['programs'].items(), key=lambda kv: -kv[1]['seconds'])[:8]}))

    t0 = time.monotonic()
    correct, table, _ = judge_window(spec, system, stats, args.seed, before, after)
    log(f"reference and comparison: {time.monotonic() - t0:.1f} s (not part of setup_s)")
    metrics = {} if args.rehearse else read_metrics(spec["per_layer"] if args.trace else spec["end_to_end"], ctx)
    result = {"correct": bool(correct), "attempted": stats["attempted"], "failed": stats["failed"],
              "metrics": metrics, "device": device, **breakdown}
    if args.rehearse:
        result["rehearsal"] = "tiny size on the CPU: no metric is reported"
    result["compared"] = table
    for name, row in table.items():
        print(f"compared {name}: {row['value']} (limit {row['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # the contract allows a first run 1200 s: a hang dumps every thread's stack and ends
    faulthandler.dump_traceback_later(1180, exit=True)
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else (print(e.code, file=sys.stderr) or 1)
    except BaseException:  # noqa: BLE001 - print it and leave non-zero, with no result line
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the REST server and the engine run in daemon threads with no stop call
    os._exit(code)
