"""The whole step's share of the chip's bf16 peak over the traced span: FLOPs the
replies completed in the span need (the encoder's forward of the question, the
scan of the live passages, the generator's prefill of the prompt and its
decode rows) over the span's seconds times the peak."""

from metrics import retrieve_mfu


def read(ctx):
    if ctx.get("trace") is None or "lm_reply_tokens" not in ctx or not hasattr(ctx.get("work"), "reply_flops"):
        return None
    span, cfg = ctx["trace_span"], ctx["spec"]["config"]
    encoder, new_tokens = cfg["encoder"], int(ctx["lm_serving"]["max_new_tokens"])
    flops = 0.0
    for r in ctx["gen"]["records"]:
        if r["done"] is None or r["status"] != 200 or not span["t0"] <= r["done"] < span["t1"]:
            continue
        prompt_tokens = ctx["lm_reply_tokens"](r)
        if prompt_tokens is None:
            continue
        flops += retrieve_mfu.encoder_flops(encoder, len(r["query"].split()) + 2)
        flops += 2.0 * ctx["live_rows"] * encoder["hidden_size"]
        flops += ctx["work"].reply_flops(ctx["lm_config"], prompt_tokens, new_tokens)
    if flops <= 0:
        return None
    return 100.0 * flops / ((span["t1"] - span["t0"]) * ctx["peaks"]["bf16_flops_per_s"])
