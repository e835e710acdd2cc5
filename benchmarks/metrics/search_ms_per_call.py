"""Device time of the index search program(s) over their calls, from the trace."""


def read(ctx):
    found = ctx["search_time"](ctx)
    return None if found is None else found[0] / found[1] * 1e3
