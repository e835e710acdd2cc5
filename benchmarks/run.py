"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration or metric is found by the
name ``BENCHMARK.json`` gives it: the cell's traffic in ``workloads/<cell>.json``,
its configuration in ``configs/<config>.json``, the configuration's system under
test in ``systems/<name>.py`` (what is served, how it is built from the seed, its
plain reference and the numbers compared), its work count in ``work/<name>.py``
and each metric's reader in ``metrics/<metric>.py``. This file keeps what every
cell shares: the window, the generator child, the sample, the limits, the trace
and the result line. The last line of standard output is the result; everything
else goes to standard error.

``--sweep r1,r2,...`` runs one set-up and then a ladder of rates to find the knee;
``--calibrate n`` reads the numbers ``correct`` compares on ``n`` query seeds, and
on the first four those of the system's ``CONTROLS`` (its reference in lower
precision, or with a guarantee broken, in the program's place), each judged by
the cell's limits, in one process; ``--rehearse`` runs the whole command at a
tiny size on the CPU and prints no metric.
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
    """``systems/<name>.py``, ``work/<name>.py``, ``metrics/<name>.py`` or
    ``hooks/<name>.py``, by the name the data gives."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def merged(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``base`` with ``over`` laid on it key by key, groups inside groups too."""
    out = dict(base)
    for key, value in over.items():
        both = isinstance(value, dict) and isinstance(out.get(key), dict)
        out[key] = merged(out[key], value) if both else value
    return out


def resolve(cell_name: str, rehearse: bool) -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {c["name"]: c for c in manifest["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"unknown workload {cell_name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[cell_name]
    cfg = load_json("configs", cell["config"] + ".json")
    if rehearse:
        cfg = merged(cfg, cfg["rehearse"])

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


def window_stats(gen: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, Any]:
    """What the harness itself takes from the generator's log: per request the
    latency from its due instant (a request with no good reply, as the system's
    module reads and judges it, counts as the drain's whole wait) and how late
    it was sent."""
    window = [r for r in gen["records"] if r["phase"] == "window"]
    latencies, late, good = [], [], 0
    for r in window:
        r["answer"] = spec["system"].parse_reply(r["body"]) if r["status"] == 200 else None
        ok = spec["system"].good(r["answer"], spec["traffic"])
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


def judge_window(spec, system, stats, seed, counters_before, counters_after, controls=()):
    """Sample the window's requests from the seed, have the system's module run
    its reference over them, and hold each number it compares, and the programs
    compiled inside the window, to the cell's limits. Returns (correct, compared
    table, each control's (correct, table))."""
    import compare

    traffic = spec["traffic"]
    limits, window = traffic["limits"], stats["window"]
    sample = random.Random(seed).sample(window, min(int(traffic["sample"]), len(window)))
    numbers, control_numbers = spec["system"].judge(spec, system, sample, controls)
    # each counter the system names as counting compiled programs, by its own growth
    grew = sum(max(0.0, value - counters_before.get(name, 0.0)) for name, value in counters_after.items()
               if name.startswith(tuple(spec["system"].COMPILE_COUNTERS)))
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
    return correct, table, {name: compare.judge(n, limits) for name, n in control_numbers.items()}


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
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files in .bench_out, and what the readers read beside them")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    spec = resolve(args.workload, args.rehearse)
    cfg, traffic = spec["config"], spec["traffic"]

    import textgen

    system_kind = spec["system"] = load_module("systems", cfg.get("system", "vector_store"))
    for name in ("System", "parse_reply", "good", "judge", "CONTROLS", "COMPILE_COUNTERS", "metric_context"):
        assert hasattr(system_kind, name), f"{system_kind.__file__} gives no {name}"  # before any set-up
    hooks = [load_module("hooks", name) for name in cfg.get("hooks", [])]
    for hook in hooks:
        if hasattr(hook, "before_server"):
            hook.before_server(cfg, log)
    t0 = time.monotonic()
    device = device_report(args.rehearse, int(spec["cell"]["chips"]))
    device_s = time.monotonic() - t0  # importing jax and the runtime's start: the part of set-up that moves most
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
    system = system_kind.System(cfg, args.seed, free_port(), docs, log)
    system.wait_ready()
    system.warm_up(traffic)
    for hook in hooks:
        if hasattr(hook, "after_ready"):
            hook.after_ready(system, log)
    parts = {"device_s": device_s, **system.timings}
    log("set-up parts (s): " + json.dumps({k: round(v, 2) for k, v in parts.items()}))

    if args.sweep:
        for i, rate in enumerate(float(r) for r in args.sweep.split(",")):
            c0 = system.counters()
            gen = run_generator(spec, system, args.seed + i, args.seconds, rate, f"sweep{i}")
            c1 = system.counters()
            st = window_stats(gen, spec)
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
                "counters": {n: c1[n] - c0.get(n, 0.0) for n in c1 if c1[n] != c0.get(n)},
                "device": device}), flush=True)
        return 0

    if args.calibrate:
        for i in range(args.calibrate):
            before = system.counters()
            gen = run_generator(spec, system, args.seed + 1 + i, args.seconds, None, f"cal{i}")
            st = window_stats(gen, spec)
            correct, table, controls = judge_window(
                spec, system, st, args.seed + 1 + i, before, system.counters(),
                controls=tuple(system_kind.CONTROLS) if i < 4 else ())
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
    stats = window_stats(gen, spec)
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
        "percentile": percentile, "trace": None,
        "work": load_module("work", cfg["work"]) if "work" in cfg else None,
        **system_kind.metric_context(cfg),
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
    metrics = read_metrics(spec["per_layer"] if args.trace else spec["end_to_end"], ctx)
    if args.keep_trace and ctx["trace"] is not None:  # what the readers read, beside the profiler's files
        import gzip

        with gzip.open(os.path.join(trace_dir, "window.json.gz"), "wt") as f:
            records = [{k: v for k, v in r.items() if k not in ("body", "answer")} for r in gen["records"]]
            json.dump({"from": f"run.py {' '.join(sys.argv[1:])} on one {device['kind']}",
                       "start_at": gen["start_at"], "trace_span": ctx["trace_span"],
                       "spans": ctx.get("spans_in_window"), "records": records,
                       "counters_before": before, "counters_after": after, "metrics": metrics}, f)
    result = {"correct": bool(correct), "attempted": stats["attempted"], "failed": stats["failed"],
              "metrics": metrics, "device": device, **breakdown}
    if args.rehearse:  # the readers have run on what a CPU run leaves; a CPU number is never reported
        result["metrics"], result["read_not_reported"] = {}, sorted(metrics)
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
