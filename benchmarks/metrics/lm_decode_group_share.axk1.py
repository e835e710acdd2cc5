"""Share of a step's (row, expert layer) pairs whose kept groups include
this chip's group: growth of the decode steps' ``lm_group_rows`` over growth of
their ``lm_routed_pairs`` over the experts a token chooses (8). These are the
rows a group-limited dispatch would send to this chip: 4 of 8 groups kept make
it about 1/2 under even routing. None where the program has no such counter
(a decoder that does not count groups)."""

from metrics import _lm


def read(ctx):
    rows, pairs = _lm.grew(ctx, "lm_group_rows"), _lm.grew(ctx, "lm_routed_pairs")
    if rows is None or not pairs or "lm_config" not in ctx:
        return None
    return rows / (pairs / ctx["lm_config"]["num_experts_per_tok"])
