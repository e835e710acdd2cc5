"""Share of the decode steps' rows that held a request over the window, in ``answer-mistral4-steady``.
The reader is ``metrics/lm_slot_fill.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.lm_slot_fill import read  # noqa: F401
