"""The whole step's share of the chip's bf16 peak over the traced span in ``answer-axk1-steady``: the encoder's forward, the scan, and A.X-K1's prefill and decode rows (``work/axk1_moe.py:reply_flops``).
The reader is ``metrics/answer_mfu.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.answer_mfu import read  # noqa: F401
