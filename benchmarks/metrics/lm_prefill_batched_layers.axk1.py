"""Expert layers of a prefill whose product ran batched over the capacity-padded groups, in ``answer-axk1-steady`` (six, where no call fell back; the dense layer 0 counts nothing).
The reader is ``metrics/lm_prefill_batched_layers.py``'s: the generation service, its spans and its counters are the same, and the
work file has the same signatures."""

from metrics.lm_prefill_batched_layers import read  # noqa: F401
