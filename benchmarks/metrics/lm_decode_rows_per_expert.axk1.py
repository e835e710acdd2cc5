"""Rows a held expert gets in a decode step, where it gets any, in
``answer-axk1-steady``: growth of the steps' routed pairs whose expert is held
here (``lm_routed_pairs_held``) over growth of the distinct held experts they
chose, summed over the expert layers (``lm_experts_touched``), as
``metrics/lm_decode_rows_per_expert.mistral4.py`` reads them. None where the
program has no such counter."""

from metrics import _lm


def read(ctx):
    pairs, touched = _lm.grew(ctx, "lm_routed_pairs_held"), _lm.grew(ctx, "lm_experts_touched")
    if pairs is None or not touched:
        return None
    return pairs / touched
