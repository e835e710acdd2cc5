"""Work of the ``mistral4`` generator's two programs over one chip's share, as
the configuration's semantics need it. Counts what the mathematics reads and
computes: never a padded bucket, an empty slot or the implementation's
temporaries. The signatures are ``work/lfm2_moe.py``'s, so the readers that are
there read this file too.

Bytes of a call: the weights of the held experts its tokens chose
(``experts_touched`` distinct ones, summed over the layers, as the program
counts them), every other weight of the layers once (attention's seven
matrices and two norms, the router over its whole width, the shared expert,
the block's two norms), the last norm, the vocabulary slices' share read (the
embedding rows fetched, one a token; the head's slice whole, since a token is
produced), and the state read (a decode row reads its latent cache so far,
``kv_lora_rank + qk_rope_head_dim`` numbers a position and layer; a prefill
reads none). FLOPs: 2 x the parameters a token is multiplied by (the routed
pairs whose expert is held only: ``num_experts_per_tok * held / router width``
a token and layer under even routing; the head only where a token is
produced) x tokens, plus attention in the cheaper form for each program
whatever the program does: absorbed for a step (a query against
``kv_lora_rank + qk_rope_head_dim`` numbers a position, the mix over
``kv_lora_rank``; expanding every cached position would cost ``Wukv`` a
position) and expanded for a prefill (``qk_head_dim`` and ``v_head_dim`` a pair
of positions, ``Wukv`` once a token, counted among the parameters).
"""

BYTES = 2  # bfloat16, as the configuration's precision states


def parameters(cfg: dict) -> dict:
    """Parameter counts by part, of the share held here."""
    h, heads, layers = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_hidden_layers"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attention = (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * qk
                 + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) + cfg["kv_lora_rank"]
                 + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
                 + heads * cfg["v_head_dim"] * h)
    one_expert = 3 * h * cfg["moe_intermediate_size"]
    return {
        "attention": layers * attention,
        "routers": layers * h * cfg["n_router_experts"],
        "shared_experts": layers * cfg["n_shared_experts"] * one_expert,
        "norms": layers * 2 * h + h,
        "one_expert": one_expert,
        "table": cfg["vocab_size"] * h,
        "head": h * cfg["vocab_size"],
        "layers": layers,
        "held_pairs_per_token": cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["n_router_experts"],
    }


def _outside_experts(p: dict) -> float:
    """Every weight of the layers but the routed experts, and the last norm."""
    return p["attention"] + p["routers"] + p["shared_experts"] + p["norms"]


def _per_token(p: dict) -> float:
    """Parameters one token is multiplied by in the layers (no head)."""
    return _outside_experts(p) + p["layers"] * p["held_pairs_per_token"] * p["one_expert"]


def decode_step(cfg: dict, rows: float, experts_touched: float, context_tokens: float) -> dict:
    """One step over ``rows`` rows that hold a request, each with ``context_tokens`` tokens so far."""
    p = parameters(cfg)
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    weights = experts_touched * p["one_expert"] + _outside_experts(p) + p["head"] + rows * cfg["hidden_size"]
    cache_row = latent * context_tokens * p["layers"]
    attention = 2 * context_tokens * cfg["num_attention_heads"] * (latent + cfg["kv_lora_rank"]) * p["layers"]
    return {"bytes": BYTES * (weights + rows * cache_row),
            "flops": rows * (2.0 * (_per_token(p) + p["head"]) + attention)}


def prefill_call(cfg: dict, tokens: float, experts_touched: float) -> dict:
    """One prompt of ``tokens`` tokens; the head runs at its last position only."""
    p = parameters(cfg)
    weights = experts_touched * p["one_expert"] + _outside_experts(p) + p["head"] + tokens * cfg["hidden_size"]
    per_pair = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    attention = 2 * (tokens * (tokens + 1) / 2) * cfg["num_attention_heads"] * per_pair * p["layers"]
    return {"bytes": BYTES * weights, "flops": 2.0 * (tokens * _per_token(p) + p["head"]) + attention}


def reply_flops(cfg: dict, prompt_tokens: float, new_tokens: int) -> float:
    """FLOPs one reply's generation needs: its prefill, then a step's row for every further token."""
    total = prefill_call(cfg, prompt_tokens, 0.0)["flops"]
    for j in range(1, new_tokens):
        total += decode_step(cfg, 1.0, 0.0, prompt_tokens + j)["flops"]
    return total
