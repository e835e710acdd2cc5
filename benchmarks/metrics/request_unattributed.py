"""Median share of a request's ``rest`` span that none of its ``admit``,
``queue``, commit and ``reply`` spans covers: what the stage metrics leave out."""

from metrics import _spans


def read(ctx):
    rows = _spans.requests(ctx)
    if rows is None:
        return None
    shares = []
    for r in rows:
        lo, hi = r["rest"]["ts_mono"], _spans.end(r["rest"])
        covered, at = 0.0, lo
        for a, b in sorted((s["ts_mono"], _spans.end(s)) for k, s in r.items() if k != "rest"):
            a, b = max(a, at), min(b, hi)
            if b > a:
                covered, at = covered + b - a, b
        if hi > lo:
            shares.append(1.0 - covered / (hi - lo))
    value = ctx["percentile"](sorted(shares), 0.50)
    return None if value is None else 100.0 * value
