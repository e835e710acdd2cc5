"""Rows a held expert gets in a decode step, where it gets any: growth of the
decode steps' routed pairs whose expert is held here (``lm_routed_pairs_held``)
over growth of the distinct held experts they chose, summed over the layers
(``lm_experts_touched``). It is the group size of the grouped expert product in
a step, on which a kernel with small row tiles hinges (ROADMAP A3). None where
the program has no such counter."""

from metrics import _lm


def read(ctx):
    pairs, touched = _lm.grew(ctx, "lm_routed_pairs_held"), _lm.grew(ctx, "lm_experts_touched")
    if pairs is None or not touched:
        return None
    return pairs / touched
