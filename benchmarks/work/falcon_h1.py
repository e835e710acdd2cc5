"""Work of the ``falcon_h1`` generator's two programs over the blocks held, as
the configuration's semantics need it. Counts what the mathematics reads and
computes whatever implements it: never a padded bucket, an empty slot or the
implementation's temporaries.

Bytes of a call: every weight of the blocks once (both mixers, the SwiGLU, the
norms and the mixer's vectors), the last norm, the head whole (a token is
produced), the embedding rows fetched (one a token), and the state of each row
that holds a request: its state-space state **read and written** (float32:
heads x d_head x state numbers a block, rewritten whole at every token), its
convolution tail and its keys and values so far (a decode row reads them; a
prefill reads none and writes its state once). FLOPs: 2 x the parameters a
token is multiplied by (the head only where a token is produced) x tokens,
attention's scores and mixing over the pairs of positions, and the scan in the
cheaper form for each program whatever the program does: the recurrence for a
step (decay, outer product, sum and the read against C: five operations a
number of the state) and the chunked form for a prefill (inside a chunk of Q
tokens C B^T and its product with the inputs, 2 Q (groups x state + d_ssm) a
token; a chunk's own state and the read of the state before it, 4 x d_ssm x
state a token: 5.4 MFLOP a token and block at the published sizes against 860
in its matrices).
"""

BYTES = 2  # bfloat16, as the configuration's precision states
STATE_BYTES = 4  # the state-space state is float32


def parameters(cfg: dict) -> dict:
    """Parameter counts by part."""
    h, f, d, heads = cfg["hidden_size"], cfg["intermediate_size"], cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    conv_dim = d + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    hd, layers = cfg["head_dim"], cfg["num_hidden_layers"]
    ssm = h * (d + conv_dim + heads) + conv_dim * cfg["mamba_d_conv"] + conv_dim + 3 * heads + d + d * h
    attention = 2 * h * cfg["num_attention_heads"] * hd + 2 * h * cfg["num_key_value_heads"] * hd
    return {
        "ssm": layers * ssm, "attention": layers * attention, "mlp": layers * 3 * h * f,
        "norms": layers * 2 * h + h, "table": cfg["vocab_size"] * h, "head": h * cfg["vocab_size"],
        "layers": layers, "state": heads * cfg["mamba_d_head"] * cfg["mamba_d_state"],
        "tail": (cfg["mamba_d_conv"] - 1) * conv_dim, "conv": conv_dim * cfg["mamba_d_conv"],
    }


def _blocks(p: dict) -> float:
    """Every weight of the blocks, and the last norm."""
    return p["ssm"] + p["attention"] + p["mlp"] + p["norms"]


def _chunked_scan(cfg: dict, p: dict) -> float:
    """The chunked scan's operations a token and block."""
    inside = 2 * cfg["mamba_chunk_size"] * (cfg["mamba_n_groups"] * cfg["mamba_d_state"] + cfg["mamba_d_ssm"])
    return inside + 4 * p["state"] + 2 * p["conv"]


def decode_step(cfg: dict, rows: float, context_tokens: float) -> dict:
    """One step over ``rows`` rows that hold a request, each with ``context_tokens`` tokens so far."""
    p = parameters(cfg)
    kv_row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * context_tokens * p["layers"]
    weights = _blocks(p) + p["head"] + rows * cfg["hidden_size"]
    state_row = p["layers"] * (2 * STATE_BYTES * p["state"] + BYTES * p["tail"])
    attention = 2 * 2 * context_tokens * cfg["num_attention_heads"] * cfg["head_dim"] * p["layers"]
    recurrence = p["layers"] * (5 * p["state"] + 2 * p["conv"])
    return {"bytes": BYTES * (weights + rows * kv_row) + rows * state_row,
            "flops": rows * (2.0 * (_blocks(p) + p["head"]) + attention + recurrence)}


def prefill_call(cfg: dict, tokens: float) -> dict:
    """One prompt of ``tokens`` tokens; the head runs at its last position only; the state is written once."""
    p = parameters(cfg)
    weights = _blocks(p) + p["head"] + tokens * cfg["hidden_size"]
    attention = 2 * 2 * (tokens * (tokens + 1) / 2) * cfg["num_attention_heads"] * cfg["head_dim"] * p["layers"]
    return {"bytes": BYTES * weights + p["layers"] * STATE_BYTES * p["state"],
            "flops": 2.0 * (tokens * _blocks(p) + p["head"]) + attention + tokens * p["layers"] * _chunked_scan(cfg, p)}


def reply_flops(cfg: dict, prompt_tokens: float, new_tokens: int) -> float:
    """FLOPs one reply's generation needs: its prefill, then a step's row for every further token."""
    total = prefill_call(cfg, prompt_tokens)["flops"]
    for j in range(1, new_tokens):
        total += decode_step(cfg, 1.0, prompt_tokens + j)["flops"]
    return total
