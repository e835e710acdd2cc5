"""The ``mistral4`` decode program's share of its roofline: a step's work (``work/mistral4_moe.py``: the absorbed form over the latent cache) at the window's average rows, distinct held experts chosen and context a row.
The reader is ``metrics/lm_decode_roofline.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.lm_decode_roofline import read  # noqa: F401
