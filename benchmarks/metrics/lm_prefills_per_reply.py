"""Other prompts' prefills enqueued between a generated reply's own prefill
and its last step, each of which stalled its slot: growth of
``lm_prefills_in_decode`` over growth of ``lm_requests_done``. None where the
program has no such counters or resolved no request."""

from metrics import _lm


def read(ctx):
    prefills, done = _lm.grew(ctx, "lm_prefills_in_decode"), _lm.grew(ctx, "lm_requests_done")
    if prefills is None or not done:
        return None
    return prefills / done
