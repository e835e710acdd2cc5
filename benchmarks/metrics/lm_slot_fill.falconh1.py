"""Share of the decode steps' rows that held a request over the window, in ``answer-falconh1-steady``.
The reader is ``metrics/lm_slot_fill.py``'s: the generation service and its counters are the same."""

from metrics.lm_slot_fill import read  # noqa: F401
