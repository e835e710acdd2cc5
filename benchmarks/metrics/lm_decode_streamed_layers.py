"""Expert layers of a decode step whose product ran through the streamed kernel
(``pathway_tpu/models/moe.py``: each touched held expert's weights read once):
growth of ``lm_streamed_layers`` over growth of ``lm_decode_steps``. Every step
takes that form, so this reads the configuration's expert layers (12, or 6).
None where the program has no such counter."""

from metrics import _lm


def read(ctx):
    layers, steps = _lm.grew(ctx, "lm_streamed_layers"), _lm.grew(ctx, "lm_decode_steps")
    if layers is None or not steps:
        return None
    return layers / steps
