"""The whole step's share of the chip's bf16 peak over the traced span in ``answer-falconh1-steady``: the encoder's forward, the scan of the passages, and the ``falcon_h1`` generator's prefill and decode rows (``work/falcon_h1.py:reply_flops``).
The reader is ``metrics/answer_mfu.py``'s: the generation service and its spans are the same, and ``reply_flops`` has the same signature."""

from metrics.answer_mfu import read  # noqa: F401
