"""Median ``commit`` span among the commits that took at least one request."""

from metrics import _spans


def read(ctx):
    return _spans.kind_median_ms(ctx, "commit", lambda s: s["attrs"].get("queries", 0) > 0)
