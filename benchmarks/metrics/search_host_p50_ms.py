"""Median self time of a ``search`` span: ``KnnIndex.search_many`` less its
``search.device_wait`` child (the fetch that blocks on the device); what is
left is the host's side of a search: stack, casts, pad, enqueue, the loop
over the results."""

from metrics import _spans


def read(ctx):
    spans = _spans.in_window(ctx)
    if spans is None:
        return None
    waited = {}
    for s in spans:
        if s["kind"] == "search.device_wait":
            waited[s["parent_id"]] = waited.get(s["parent_id"], 0.0) + s["duration_s"]
    return _spans.median_ms(ctx, [s["duration_s"] - waited.get(s["span_id"], 0.0)
                                  for s in spans if s["kind"] == "search"])
