"""Device time of A.X-K1's prefill program over its calls (``jit_lm_prefill``, every bucket).
The reader is ``metrics/lm_prefill_ms_per_call.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.lm_prefill_ms_per_call import read  # noqa: F401
