"""A.X-K1's prefill program's share of its roofline: a call's work (``work/axk1_moe.py``: the expanded form, heads of 192 and 128, the dense layer) at the window's average prompt and distinct held experts chosen.
The reader is ``metrics/lm_prefill_roofline.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.lm_prefill_roofline import read  # noqa: F401
