"""Device time of the index search program(s) over their calls, from the trace."""


def read(ctx):
    found = ctx["search_time"](ctx) if "search_time" in ctx else None  # the system's own: another has none
    return None if found is None else found[0] / found[1] * 1e3
