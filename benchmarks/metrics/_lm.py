"""What the generator's readers share; not a metric itself. The program's
counters over the window (``GenerationService.stats``) and the roofline of one
of its programs: the least time the chip could take for the calls' work (the
larger of bytes over the memory peak and FLOPs over the bf16 peak; the
configuration's work file counts both from the window's averages a call) over
the device time the trace shows. The bound that held is printed on standard error."""

import sys


def grew(ctx, name):
    """A counter's growth over the window; None where the program has no such counter."""
    after = ctx["counters_after"]
    return None if name not in after else after[name] - ctx["counters_before"].get(name, 0.0)


def roofline(ctx, name, pattern, work_of):
    found = ctx["lm_program_time"](ctx, pattern) if "lm_program_time" in ctx else None
    work = work_of(ctx) if found is not None and hasattr(ctx.get("work"), "decode_step") else None
    if work is None:
        return None
    seconds, calls = found
    by_bytes = work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    by_flops = work["flops"] / ctx["peaks"]["bf16_flops_per_s"]
    print(f"{name}: bound by {'memory' if by_bytes >= by_flops else 'compute'} ({by_bytes * 1e3:.3f} ms bytes, "
          f"{by_flops * 1e3:.3f} ms flops a call; {seconds / calls * 1e3:.3f} ms measured over {calls:.0f} calls)",
          file=sys.stderr)
    return 100.0 * calls * max(by_bytes, by_flops) / seconds
