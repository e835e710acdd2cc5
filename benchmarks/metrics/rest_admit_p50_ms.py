"""Median ``admit`` span: parse, validate, admission check and ``source.push``
of a request, on the REST server's event-loop thread."""

from metrics import _spans


def read(ctx):
    return _spans.kind_median_ms(ctx, "admit")
