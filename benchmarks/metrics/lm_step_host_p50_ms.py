"""Median self time of an ``lm.decode_step`` span: the generation service's step
less its ``lm.decode_step.device_wait`` child (the fetch that blocks on the
device); what is left is the host's side of a step: the mask, the enqueue, the
tokens handed to their requests."""

from metrics import _spans


def read(ctx):
    spans = _spans.in_window(ctx)
    if spans is None:
        return None
    waited = {s["parent_id"]: s["duration_s"] for s in spans if s["kind"] == "lm.decode_step.device_wait"}
    return _spans.median_ms(ctx, [s["duration_s"] - waited.get(s["span_id"], 0.0)
                                  for s in spans if s["kind"] == "lm.decode_step"])
