"""Median ``generate`` span: the chat UDF from a prompt's submission to the
generation service until its tokens are resolved."""

from metrics import _spans


def read(ctx):
    return _spans.kind_median_ms(ctx, "generate")
