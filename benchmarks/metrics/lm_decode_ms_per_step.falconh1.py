"""Device time of the ``falcon_h1`` generator's decode program over its calls (``jit_lm_decode``: one generator a process, so the name is the other generators' too).
The reader is ``metrics/lm_decode_ms_per_step.py``'s: the generation service, its spans and its counters are the same."""

from metrics.lm_decode_ms_per_step import read  # noqa: F401
