"""Share of the decode steps' rows that held a request over the window: delta
``lm_decode_rows`` over delta ``lm_decode_steps`` times ``lm_slots``."""

from metrics import _lm


def read(ctx):
    steps, rows = _lm.grew(ctx, "lm_decode_steps"), _lm.grew(ctx, "lm_decode_rows")
    if not steps or rows is None:
        return None
    return 100.0 * rows / (steps * ctx["counters_after"]["lm_slots"])
