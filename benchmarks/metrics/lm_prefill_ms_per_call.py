"""Device time of the generator's prefill program over its calls, from the trace
(``jit_lm_prefill``, every bucket)."""


def read(ctx):
    found = ctx["lm_program_time"](ctx, "^jit_lm_prefill$") if "lm_program_time" in ctx else None
    return None if found is None else found[0] / found[1] * 1e3
