"""Device time of the ``falcon_h1`` generator's prefill program over its calls (``jit_lm_prefill``, every bucket).
The reader is ``metrics/lm_prefill_ms_per_call.py``'s: the generation service, its spans and its counters are the same."""

from metrics.lm_prefill_ms_per_call import read  # noqa: F401
