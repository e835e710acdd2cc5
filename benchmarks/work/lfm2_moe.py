"""Work of the ``lfm2_moe`` generator's two programs, as the configuration's
semantics need it. Counts what the mathematics reads and computes: never a
padded bucket, an empty slot or the implementation's temporaries.

Bytes of a call: the weights of the experts its tokens chose (``experts_touched``
distinct ones, summed over the expert layers, as the program counts them),
every other weight once (operators, dense feed-forwards, routers, norms, and
the whole table, which the tied head reads), and the state read (a decode row
reads its keys and values so far in every attention layer and its tail in
every convolution layer; a prefill reads none). FLOPs: 2 x the parameters a
token is multiplied by (its ``num_experts_per_tok`` experts, not all; the head
only where a token is produced) x tokens, plus attention's scores and mixing.
"""

BYTES = 2  # bfloat16, as the configuration's precision states


def parameters(cfg: dict) -> dict:
    """Parameter counts by part."""
    h, hd = cfg["hidden_size"], cfg["hidden_size"] // cfg["num_attention_heads"]
    conv = h * 3 * h + h * h + h * cfg["conv_L_cache"]
    attn = 2 * h * cfg["num_attention_heads"] * hd + 2 * h * cfg["num_key_value_heads"] * hd + 2 * hd
    kinds = cfg["layer_types"]
    n_dense = cfg["num_dense_layers"]
    return {
        "operators": sum(conv if kind == "conv" else attn for kind in kinds) + 2 * h * len(kinds) + h,
        "dense_ffn": n_dense * 3 * h * cfg["intermediate_size"],
        "routers": (len(kinds) - n_dense) * (h * cfg["num_experts"] + cfg["num_experts"]),
        "one_expert": 3 * h * cfg["moe_intermediate_size"],
        "table": cfg["vocab_size"] * h,
        "expert_layers": len(kinds) - n_dense,
        "attention_layers": sum(kind == "full_attention" for kind in kinds),
        "conv_layers": sum(kind == "conv" for kind in kinds),
    }


def _per_token(cfg: dict, p: dict) -> float:
    """Parameters one token is multiplied by in the layers (no head)."""
    return p["operators"] + p["dense_ffn"] + p["routers"] + p["expert_layers"] * cfg["num_experts_per_tok"] * p["one_expert"]


def decode_step(cfg: dict, rows: float, experts_touched: float, context_tokens: float) -> dict:
    """One step over ``rows`` rows that hold a request, each with ``context_tokens`` tokens so far."""
    p = parameters(cfg)
    h, hd = cfg["hidden_size"], cfg["hidden_size"] // cfg["num_attention_heads"]
    kv_row = 2 * cfg["num_key_value_heads"] * hd * context_tokens * p["attention_layers"]
    tail_row = (cfg["conv_L_cache"] - 1) * h * p["conv_layers"]
    weights = experts_touched * p["one_expert"] + p["operators"] + p["dense_ffn"] + p["routers"] + p["table"]
    attention = 2 * 2 * context_tokens * cfg["num_attention_heads"] * hd * p["attention_layers"]
    return {"bytes": BYTES * (weights + rows * (kv_row + tail_row)),
            "flops": rows * (2.0 * (_per_token(cfg, p) + p["table"]) + attention)}


def prefill_call(cfg: dict, tokens: float, experts_touched: float) -> dict:
    """One prompt of ``tokens`` tokens; the head runs at its last position only."""
    p = parameters(cfg)
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    weights = experts_touched * p["one_expert"] + p["operators"] + p["dense_ffn"] + p["routers"] + p["table"]
    attention = 2 * 2 * (tokens * (tokens + 1) / 2) * cfg["num_attention_heads"] * hd * p["attention_layers"]
    return {"bytes": BYTES * weights, "flops": 2.0 * (tokens * _per_token(cfg, p) + p["table"]) + attention}


def reply_flops(cfg: dict, prompt_tokens: float, new_tokens: int) -> float:
    """FLOPs one reply's generation needs: its prefill, then a step's row for every further token."""
    total = prefill_call(cfg, prompt_tokens, 0.0)["flops"]
    for j in range(1, new_tokens):
        total += decode_step(cfg, 1.0, 0.0, prompt_tokens + j)["flops"]
    return total
