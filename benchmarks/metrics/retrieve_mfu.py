"""The whole step's share of the chip's bf16 peak over the traced span: FLOPs the
replies completed in the span need (the encoder's forward for the tokens the
generator sent, plus the configuration's scoring FLOPs per query) over the
span's seconds times the peak."""


def encoder_flops(model, tokens_per_query):
    h, f, layers = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    per_token = layers * (2 * 4 * h * h + 2 * 2 * h * f)  # projections and feed-forward
    attention = layers * 2 * 2 * tokens_per_query * h  # scores and context, per token
    return tokens_per_query * (per_token + attention)


def read(ctx):
    if ctx.get("trace") is None or not ctx.get("work") or "n_rows" not in ctx:
        return None  # not traced, or not the system this step is: an encoder forward and a scan of n_rows
    span, model = ctx["trace_span"], ctx["spec"]["config"]["model"]
    flops = 0.0
    for r in ctx["gen"]["records"]:
        if r["done"] is not None and r["status"] == 200 and span["t0"] <= r["done"] < span["t1"]:
            tokens = len(r["query"].split()) + 2  # one token per word, [CLS] and [SEP]
            flops += encoder_flops(model, tokens) + ctx["work"].query_flops(ctx["n_rows"], model["hidden_size"])
    if flops <= 0:
        return None
    return 100.0 * flops / ((span["t1"] - span["t0"]) * ctx["peaks"]["bf16_flops_per_s"])
