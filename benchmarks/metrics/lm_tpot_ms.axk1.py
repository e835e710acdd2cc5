"""Mean time a token after the first, as a generated reply sees it, in ``answer-axk1-steady``.
The reader is ``metrics/lm_tpot_ms.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.lm_tpot_ms import read  # noqa: F401
