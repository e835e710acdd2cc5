"""Share of the tokens the encoder service sent to the device over the window
that were real: delta ``svc_real_tokens`` over delta ``svc_padded_tokens``
(tokens under the attention mask; batch bucket x sequence bucket)."""


def read(ctx):
    before, after = ctx["counters_before"], ctx["counters_after"]
    if "svc_padded_tokens" not in after:
        return None
    padded = after["svc_padded_tokens"] - before.get("svc_padded_tokens", 0.0)
    if padded <= 0:
        return None
    return 100.0 * (after["svc_real_tokens"] - before.get("svc_real_tokens", 0.0)) / padded
