"""The decode program's share of its roofline (``_lm.roofline``): a step's work
at the window's average rows, distinct experts chosen and context a row."""

from metrics import _lm


def work_of(ctx):
    steps, rows = _lm.grew(ctx, "lm_decode_steps"), _lm.grew(ctx, "lm_decode_rows")
    calls, tokens = _lm.grew(ctx, "lm_prefill_calls"), _lm.grew(ctx, "lm_prefill_tokens")
    if not steps or not rows or not calls:
        return None
    # a row's context: its prompt and, on average over its steps, half of what it generates
    context = tokens / calls + ctx["lm_serving"]["max_new_tokens"] / 2
    return ctx["work"].decode_step(ctx["lm_config"], rows / steps, _lm.grew(ctx, "lm_experts_touched") / steps, context)


def read(ctx):
    return _lm.roofline(ctx, "lm_decode_roofline", "^jit_lm_decode$", work_of)
