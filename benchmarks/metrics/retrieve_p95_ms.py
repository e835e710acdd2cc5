"""95th percentile, over every request due in the window, of reply complete minus due."""


def read(ctx):
    return ctx["percentile"](ctx["stats"]["latency_ms"], 0.95)
