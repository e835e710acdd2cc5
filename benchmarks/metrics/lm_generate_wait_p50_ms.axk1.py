"""Median ``generate`` span (``Mistral4Chat``'s submission to its tokens), in ``answer-axk1-steady``.
The reader is ``metrics/lm_generate_wait_p50_ms.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.lm_generate_wait_p50_ms import read  # noqa: F401
