"""Expert layers of a prefill whose product ran batched over the capacity-padded
groups (``pathway_tpu/models/moe.py``): growth of ``lm_prefill_batched_layers``
over growth of ``lm_prefill_calls``. A layer of a call in which a held expert
got more rows than its capacity runs the grouped product whole and counts
nothing, so this reads the configuration's expert layers (12, or 6) when no
call fell back. None where the program has no such counter."""

from metrics import _lm


def read(ctx):
    layers, calls = _lm.grew(ctx, "lm_prefill_batched_layers"), _lm.grew(ctx, "lm_prefill_calls")
    if layers is None or not calls:
        return None
    return layers / calls
