"""Device time of the generator's decode program over its calls, from the trace
(``jit_lm_decode``: the program is named after its jitted function)."""


def read(ctx):
    found = ctx["lm_program_time"](ctx, "^jit_lm_decode$") if "lm_program_time" in ctx else None
    return None if found is None else found[0] / found[1] * 1e3
