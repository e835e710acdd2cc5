"""The ``falcon_h1`` decode program's share of its roofline (``_lm.roofline``): a
step's work (``work/falcon_h1.py``: the weights once, each live row's state-space
state read and written, its tail, its keys and values so far) at the window's
average rows that held a request and context a row. A reader of its own:
``metrics/lm_decode_roofline.py`` hands the work file a count of experts
touched, which this generator has none of."""

from metrics import _lm


def work_of(ctx):
    steps, rows = _lm.grew(ctx, "lm_decode_steps"), _lm.grew(ctx, "lm_decode_rows")
    calls, tokens = _lm.grew(ctx, "lm_prefill_calls"), _lm.grew(ctx, "lm_prefill_tokens")
    if not steps or not rows or not calls:
        return None
    # a row's context: its prompt and, on average over its steps, half of what it generates
    context = tokens / calls + ctx["lm_serving"]["max_new_tokens"] / 2
    return ctx["work"].decode_step(ctx["lm_config"], rows / steps, context)


def read(ctx):
    return _lm.roofline(ctx, "lm_decode_roofline.falconh1", "^jit_lm_decode$", work_of)
