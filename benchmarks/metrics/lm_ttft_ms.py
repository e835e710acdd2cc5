"""Mean time of a generated reply from its submission to the generation
service until its first token was read on the host (its prefill's fetch):
growth of ``lm_ttft_s`` over growth of ``lm_requests_done``, in ms, over every
reply resolved in the window. None where the program has no such counters or
resolved no request."""

from metrics import _lm


def read(ctx):
    seconds, done = _lm.grew(ctx, "lm_ttft_s"), _lm.grew(ctx, "lm_requests_done")
    if seconds is None or not done:
        return None
    return 1e3 * seconds / done
