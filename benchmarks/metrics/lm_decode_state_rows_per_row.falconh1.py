"""State-space states a decode step moved for each one it needed: growth of the
steps' ``lm_state_rows`` (the (slot, layer) states the program read and rewrote,
as it counts them) over growth of ``lm_decode_rows`` (rows that held a request)
times the configuration's blocks. 1.0 is the floor; a step written over all
slots reads ``slots / live rows``. None where the program has no such counter."""

from metrics import _lm


def read(ctx):
    moved, rows = _lm.grew(ctx, "lm_state_rows"), _lm.grew(ctx, "lm_decode_rows")
    if moved is None or not rows or "lm_config" not in ctx:
        return None
    return moved / (rows * ctx["lm_config"]["num_hidden_layers"])
