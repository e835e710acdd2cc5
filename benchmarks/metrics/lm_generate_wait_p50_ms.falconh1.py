"""Median ``generate`` span (``FalconH1Chat``'s submission to its tokens), in ``answer-falconh1-steady``.
The reader is ``metrics/lm_generate_wait_p50_ms.py``'s: the chat and its span are the same."""

from metrics.lm_generate_wait_p50_ms import read  # noqa: F401
