"""The whole step's share of the chip's bf16 peak over the traced span in ``answer-mistral4-steady``: the encoder's forward, the scan, and the ``mistral4`` generator's prefill and decode rows (``work/mistral4_moe.py:reply_flops``).
The reader is ``metrics/answer_mfu.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.answer_mfu import read  # noqa: F401
