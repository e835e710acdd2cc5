"""The ``mistral4`` prefill program's share of its roofline: a call's work (``work/mistral4_moe.py``: the expanded form) at the window's average prompt and distinct held experts chosen.
The reader is ``metrics/lm_prefill_roofline.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.lm_prefill_roofline import read  # noqa: F401
