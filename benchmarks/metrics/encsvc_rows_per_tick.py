"""Rows the encoder service packed per tick over the window (program counters)."""


def read(ctx):
    before, after = ctx["counters_before"], ctx["counters_after"]
    ticks = after.get("svc_ticks", 0.0) - before.get("svc_ticks", 0.0)
    if ticks <= 0:
        return None
    return (after["svc_rows"] - before["svc_rows"]) / ticks
