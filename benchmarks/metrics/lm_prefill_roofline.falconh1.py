"""The ``falcon_h1`` prefill program's share of its roofline (``_lm.roofline``):
a call's work (``work/falcon_h1.py``: the scan in its chunked form) at the
window's average prompt (its real tokens, not its bucket). A reader of its
own, as ``metrics/lm_decode_roofline.falconh1.py`` says."""

from metrics import _lm


def work_of(ctx):
    calls = _lm.grew(ctx, "lm_prefill_calls")
    if not calls:
        return None
    return ctx["work"].prefill_call(ctx["lm_config"], _lm.grew(ctx, "lm_prefill_tokens") / calls)


def read(ctx):
    return _lm.roofline(ctx, "lm_prefill_roofline.falconh1", "^jit_lm_prefill$", work_of)
