"""Device time of the encoder's forward program over its calls, from the trace
(the program is named ``jit_encoder_forward`` since the program's forward is a
named function)."""

import trace_reduce


def read(ctx):
    if ctx.get("trace") is None:
        return None
    seconds, calls = trace_reduce.program_seconds(ctx["trace"], ["^jit_encoder_forward$"])
    return seconds / calls * 1e3 if calls > 0 else None
