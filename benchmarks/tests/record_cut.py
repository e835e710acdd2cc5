"""Record ``trace_cut.json``: a quarter second of one traced chip run, with
everything the per-layer readers read in it.

    python3 benchmarks/run.py --workload serve-dense-2m --seed 1 --seconds 40 --trace 1 --keep-trace   # on the chip
    python3 benchmarks/tests/record_cut.py .bench_out/trace_serve-dense-2m benchmarks/tests/trace_cut.json

``run.py --keep-trace`` leaves the profiler's files and, beside them in
``window.json.gz``, what its readers read: the program's spans that ended in the
traced span, the generator's records, the counters before and after the window
and the values the readers returned. This takes the profiler's events out of the
former (the device planes' ``XLA Modules`` and ``XLA Ops`` lines and the host
plane's ``pw.*`` annotations) and cuts ``CUT_SECONDS`` out of all of it, every
instant counted from the cut's start. The profiler's clock starts with its
session and the spans' is ``time.monotonic``; the commits, which are on both (a
``pw.commit`` annotation and a ``commit`` span each), give the distance. To stay
small the cut names each plane, line and event once (``strings``; ``events_of``
gives the tuples back), and leaves out the ``operator`` spans (three fifths of
all, one per operator of every commit; no metric reads them).
"""

import glob
import gzip
import json
import os
import statistics
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
CUT_FROM_S, CUT_SECONDS = 1.0, 0.25  # inside the traced span


def profile_events(trace_dir):
    """``trace_reduce.load``'s device events, and the host plane's ``pw.*`` ones."""
    from jax.profiler import ProfileData

    import trace_reduce

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = [e for e in trace_reduce.load(trace_dir) if e[1] in (trace_reduce.MODULE_LINE, trace_reduce.OP_LINE)]
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            events += [(plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns))
                       for line in plane.lines for ev in line.events if ev.name.startswith("pw.")]
    return events


def session_start(events, spans):
    """The profiler session's start on the spans' clock (seconds): the commits
    are on both, in the same order; a few at either end may be on one alone."""
    on_profile = sorted(e[3] / 1e9 for e in events if e[2] == "pw.commit")
    on_monotonic = sorted(s["ts_mono"] for s in spans if s["kind"] == "commit")
    best = None
    for skip_p in range(4):
        for skip_m in range(4):
            gaps = [m - p for p, m in zip(on_profile[skip_p:], on_monotonic[skip_m:])]
            if len(gaps) >= 10 and (best is None or max(gaps) - min(gaps) < best[0]):
                best = (max(gaps) - min(gaps), statistics.median(gaps))
    assert best is not None and best[0] < 1e-3, f"the commits of the two clocks do not pair: {best}"
    return best[1]


def events_of(made):
    """The cut's events as ``trace_reduce``'s tuples."""
    names = made["strings"]
    return [(names[p], names[line], names[n], t, d) for p, line, n, t, d in made["events"]]


def cut(raw, from_s=CUT_FROM_S, seconds=CUT_SECONDS):
    start_at, span = raw["start_at"], raw["trace_span"]
    lo = span["t0"] + from_s  # the cut's start, window-relative like the records' instants
    lo_mono = start_at + lo
    lo_profile = (lo_mono - session_start(raw["events"], raw["spans"])) * 1e9
    strings = {}
    index = lambda text: strings.setdefault(text, len(strings))
    events = [[index(p), index(line), index(name), int(t - lo_profile), d] for p, line, name, t, d in raw["events"]
              if 0 <= t - lo_profile and t + d - lo_profile < seconds * 1e9]
    spans = [dict(s, ts_mono=s["ts_mono"] - lo_mono) for s in raw["spans"]
             if s["kind"] != "operator" and 0.0 <= s["ts_mono"] + s["duration_s"] - lo_mono < seconds]
    shifted = lambda r: {k: (v - lo if k in ("due", "sent", "done") and v is not None else v) for k, v in r.items()}
    records = [shifted(r) for r in raw["records"] if r["done"] is not None and 0.0 <= r["done"] - lo < seconds]
    return {"from": raw["from"], "seconds": seconds, "strings": list(strings), "events": events, "spans": spans,
            "records": records,
            "counters_before": raw["counters_before"], "counters_after": raw["counters_after"],
            "read_over_the_whole_span": raw["metrics"]}


def main():
    trace_dir, out_path = sys.argv[1:]
    with gzip.open(os.path.join(trace_dir, "window.json.gz"), "rt") as f:
        raw = json.load(f)
    made = cut(dict(raw, events=profile_events(trace_dir)))
    with open(out_path, "w") as f:
        json.dump(made, f, separators=(",", ":"))
    print(f"record_cut: {out_path}: {os.path.getsize(out_path)} bytes; " + ", ".join(
        f"{len(made[k])} {k}" for k in ("events", "spans", "records")), file=sys.stderr)


if __name__ == "__main__":
    main()
