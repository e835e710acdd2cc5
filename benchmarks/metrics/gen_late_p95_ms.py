"""How late the generator sent its requests: 95th percentile of sent minus due."""


def read(ctx):
    return ctx["percentile"](ctx["stats"]["late_ms"], 0.95)
