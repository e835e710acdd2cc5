"""What the readers of the program's host spans share; not a metric itself.

The program records a span at each layer boundary of a request while a
profiler session is on (``pathway_tpu/engine/tracing.py``; the server runs in
``run.py``'s own process, so its ring is read directly). A span is a dict with
``kind``, ``trace_id``, ``span_id``, ``parent_id``, ``ts_mono`` (its start on
``time.monotonic``, the clock of ``ctx["gen"]["start_at"]``), ``duration_s``
and ``attrs``. A program without these spans leaves the ring empty and every
reader returns None.
"""


def in_window(ctx):
    """The spans that ended inside the traced span; None where the run was not
    traced or the program recorded none. ``ctx["spans"]``, where a test hands
    one in, takes the ring's place."""
    span = ctx.get("trace_span")
    if span is None:
        return None
    if "spans_in_window" not in ctx:
        spans = ctx.get("spans")
        if spans is None:
            from pathway_tpu.engine import tracing

            spans = tracing.get_tracer().recent_spans(limit=1 << 30)
        if spans:
            lo, hi = (ctx["gen"]["start_at"] + span[t] for t in ("t0", "t1"))
            spans = [s for s in spans if lo <= s["ts_mono"] + s["duration_s"] <= hi]
        ctx["spans_in_window"] = spans
    return ctx["spans_in_window"] or None


def median_ms(ctx, seconds):
    """The benchmark's own median (nearest rank) of a list of seconds, in ms."""
    value = ctx["percentile"](sorted(seconds), 0.50)
    return None if value is None else value * 1e3


def kind_median_ms(ctx, kind, keep=lambda s: True):
    """Median duration of the window's spans of one kind."""
    spans = in_window(ctx)
    if spans is None:
        return None
    return median_ms(ctx, [s["duration_s"] for s in spans if s["kind"] == kind and keep(s)])


def end(span):
    return span["ts_mono"] + span["duration_s"]


def requests(ctx):
    """One row per answered request all of whose parts ended in the window:
    its ``rest`` span, the ``admit``, ``queue`` and ``reply`` children, and
    the ``commit`` span that took its row (a commit links the ``rest`` span of
    every request it took). None where there are none."""
    spans = in_window(ctx)
    if spans is None:
        return None
    children, commits = {}, {}
    for s in spans:
        children.setdefault(s["parent_id"], {})[s["kind"]] = s
        if s["kind"] == "commit":
            commits.update((link["span_id"], s) for link in s["links"])
    rows = []
    for rest in spans:
        kids = children.get(rest["span_id"], {})
        if rest["kind"] != "rest" or not {"admit", "queue", "reply"} <= kids.keys():
            continue
        commit = commits.get(rest["span_id"])
        if commit is not None:
            rows.append({"rest": rest, "commit": commit, **{k: kids[k] for k in ("admit", "queue", "reply")}})
    return rows or None
