"""Model FLOPs of a serve step at the context its slots hold, over the
step's device time times the chip's peak FLOP/s."""
from benchlib.readers import program_time


def read(ctx):
    n, secs = program_time(ctx, "jit_serve_step")
    steps = ctx.get("serve_steps_traced")
    if not n or not steps:
        return None
    flops_per_step = ctx["serve_flops_traced"] / steps
    return 100.0 * flops_per_step / (secs / n * ctx["peaks"].flops)
