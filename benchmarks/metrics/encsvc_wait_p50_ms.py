"""Median ``embed_wait`` span: the commit thread inside
``EmbedPipeline.embed_query_rows``, until the encoder service hands rows back."""

from metrics import _spans


def read(ctx):
    return _spans.kind_median_ms(ctx, "embed_wait")
