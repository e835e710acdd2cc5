"""Median, over the window's requests, of the time from the end of the commit
that served a request to the end of its ``rest`` span: the hand-over from the
commit thread to the event loop, and the reply's assembly. 0 where the reply
left before its commit had ended."""

from metrics import _spans


def read(ctx):
    rows = _spans.requests(ctx)
    if rows is None:
        return None
    return _spans.median_ms(ctx, [max(0.0, _spans.end(r["rest"]) - _spans.end(r["commit"])) for r in rows])
