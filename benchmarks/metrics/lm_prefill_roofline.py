"""The prefill program's share of its roofline (``_lm.roofline``): a call's
work at the window's average prompt (its real tokens, not its bucket) and
distinct experts chosen."""

from metrics import _lm


def work_of(ctx):
    calls = _lm.grew(ctx, "lm_prefill_calls")
    if not calls:
        return None
    return ctx["work"].prefill_call(ctx["lm_config"], _lm.grew(ctx, "lm_prefill_tokens") / calls,
                                    _lm.grew(ctx, "lm_prefill_experts_touched") / calls)


def read(ctx):
    return _lm.roofline(ctx, "lm_prefill_roofline", "^jit_lm_prefill$", work_of)
