"""Median self time of an ``lm.decode_step`` span, in ``answer-falconh1-steady``.
The reader is ``metrics/lm_step_host_p50_ms.py``'s: the generation service and its spans are the same."""

from metrics.lm_step_host_p50_ms import read  # noqa: F401
