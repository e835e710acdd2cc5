"""Median ``queue`` span: from a request row's push into the engine to the
start of the commit that took it (time the work waited for the commit loop)."""

from metrics import _spans


def read(ctx):
    return _spans.kind_median_ms(ctx, "queue")
