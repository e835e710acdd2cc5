"""The search program's share of its roofline: the least time the chip could
take for the calls' work (the larger of bytes over the memory peak and FLOPs
over the bf16 peak; the configuration's work file counts both) over the device
time the trace shows. The bound that held is printed on standard error."""

import sys


def read(ctx):
    found = ctx["search_time"](ctx) if "search_time" in ctx and ctx.get("work") else None
    if found is None:  # no search program in the trace, or a system that states none and counts no work
        return None
    seconds, calls, per_call = found
    work = ctx["work"].search_call(ctx["n_rows"], ctx["spec"]["config"]["model"]["hidden_size"], per_call)
    by_bytes = work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    by_flops = work["flops"] / ctx["peaks"]["bf16_flops_per_s"]
    print(f"search_roofline: bound by {'memory' if by_bytes >= by_flops else 'compute'} "
          f"({by_bytes * 1e3:.3f} ms bytes, {by_flops * 1e3:.3f} ms flops per call of "
          f"{per_call:.2f} queries; {seconds / calls * 1e3:.3f} ms measured)", file=sys.stderr)
    return 100.0 * calls * max(by_bytes, by_flops) / seconds
