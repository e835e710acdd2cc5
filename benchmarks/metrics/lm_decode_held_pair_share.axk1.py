"""Share of the rows the step's streamed kernel multiplies that it needs:
growth of the decode steps' routed pairs whose expert is held here
(``lm_routed_pairs_held``) over growth of their routed pairs in all
(``lm_routed_pairs``). The kernel multiplies every pair's row of the call
against each touched expert's weights and masks the others' out
(``pathway_tpu/models/moe.py``), so this is the useful share of its rows: 12 of
192 held experts make it about 1/16 under even routing. None where the program
has no such counter."""

from metrics import _lm


def read(ctx):
    held, pairs = _lm.grew(ctx, "lm_routed_pairs_held"), _lm.grew(ctx, "lm_routed_pairs")
    if held is None or not pairs:
        return None
    return held / pairs
