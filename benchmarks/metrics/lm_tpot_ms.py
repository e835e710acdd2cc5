"""Mean time a token after the first, as a generated reply sees it: growth of
``lm_decode_phase_s`` (first token read to last, summed over replies) over
growth of ``lm_reply_tokens`` less growth of ``lm_requests_done``, in ms. It
holds the reply's own steps and every other prompt's prefill that ran between
them. None where the program has no such counters or no reply had a second
token."""

from metrics import _lm


def read(ctx):
    seconds, tokens, done = (_lm.grew(ctx, n) for n in ("lm_decode_phase_s", "lm_reply_tokens", "lm_requests_done"))
    if seconds is None or tokens is None or done is None or tokens <= done:
        return None
    return 1e3 * seconds / (tokens - done)
