"""Device time of A.X-K1's decode program over its calls (``jit_lm_decode``: one generator a process, so the name is the other generators' too).
The reader is ``metrics/lm_decode_ms_per_step.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.lm_decode_ms_per_step import read  # noqa: F401
