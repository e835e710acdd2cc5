"""From a profiler trace to device numbers: busy time, per-program time, idle gaps.

A reduction over plain event tuples ``(plane, line, name, start_ns, duration_ns)``
so that it can be checked on a hand-made list (``tests/test_trace_reduce.py``).
``load`` reads them from the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX. The program has no named scopes or trace annotations yet, but a
device plane names each XLA program after its jitted function
(``jit__search_kernel(<fingerprint>)``), which is what the sums are keyed on.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Tuple

Event = Tuple[str, str, str, int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"


def load(trace_dir: str) -> List[Event]:
    """Every event of every device plane under ``trace_dir`` (newest profile)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events: List[Event] = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.append((plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return events


def program_name(event_name: str) -> str:
    """``jit__search_kernel(1234)`` -> ``jit__search_kernel``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def op_name(event_name: str) -> str:
    """An XLA op's event is its whole HLO line; keep its name, what it is and the
    shape it makes: ``%fusion = f32[8,2097152]{...} fusion(...)`` ->
    ``%fusion fusion f32[8,2097152]``, a custom call with its target."""
    m = re.match(r"^(%[\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\])?[^ ]* ?.*?([a-z\-]+)\(", event_name)
    if not m:
        return event_name[:80]
    target = re.search(r'custom_call_target="([^"]+)"', event_name)
    kind = m.group(3) + (f":{target.group(1)}" if target else "")
    return " ".join(x for x in (m.group(1), kind, m.group(2)) if x)


def union_ns(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint sorted intervals covering the same instants."""
    out: List[List[int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def reduce(events: List[Event], window_s: float) -> Dict[str, Any]:
    """Per device plane: the union of the intervals in which an operation ran
    (``busy_s``, averaged over the planes), device seconds and calls per program,
    the operations that took most time and the longest idle gaps, each gap named
    by the programs that ran before and after it."""
    events = [e for e in events if DEVICE_PLANE.match(e[0])]
    planes = sorted({e[0] for e in events})
    if not planes:
        return {"busy_s": None, "window_s": window_s, "planes": 0, "programs": {}, "device_ops": [],
                "idle_gaps": []}
    busy, programs, ops, gaps = [], {}, {}, {}
    for plane in planes:
        mine = [e for e in events if e[0] == plane]
        op_events = [e for e in mine if e[1] == OP_LINE]
        mod_events = sorted((e for e in mine if e[1] == MODULE_LINE), key=lambda e: e[3])
        covered = union_ns((e[3], e[3] + e[4]) for e in (op_events or mod_events))
        busy.append(sum(b - a for a, b in covered) / 1e9)
        for e in mod_events:
            entry = programs.setdefault(program_name(e[2]), [0.0, 0])
            entry[0] += e[4] / 1e9
            entry[1] += 1
        for e in op_events:
            ops[op_name(e[2])] = ops.get(op_name(e[2]), 0.0) + e[4] / 1e9
        starts = [m[3] for m in mod_events]
        for (_, end), (start, _) in zip(covered, covered[1:]):
            i, j = bisect.bisect_left(starts, end), bisect.bisect_left(starts, start)
            before = program_name(mod_events[i - 1][2]) if i else "start"
            after = program_name(mod_events[j][2]) if j < len(starts) else "end"
            label = f"after {before}, before {after}"
            gaps[label] = gaps.get(label, 0.0) + (start - end) / 1e9
    n = len(planes)
    top = lambda d: [[k, v / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / n, "window_s": window_s, "planes": n,
        "programs": {k: {"seconds": v[0] / n, "calls": v[1] / n} for k, v in programs.items()},
        "device_ops": top(ops), "idle_gaps": top(gaps),
    }


def program_seconds(reduced: Dict[str, Any], patterns: Iterable[str]) -> Tuple[float, float]:
    """(device seconds, calls) of the programs whose name matches any pattern."""
    seconds = calls = 0.0
    for name, entry in reduced["programs"].items():
        if any(re.search(p, name) for p in patterns):
            seconds += entry["seconds"]
            calls += entry["calls"]
    return seconds, calls
