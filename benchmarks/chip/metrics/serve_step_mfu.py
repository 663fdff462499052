"""Model FLOPs of the tokens the decode step ran in the traced steps,
each at its own context (a chunked prompt's first token, made by its
last chunk, left out), per step, over the step's device time times the
chip's peak FLOP/s."""
from benchlib.readers import program_time


def read(ctx):
    n, secs = program_time(ctx, "jit_serve_step")
    steps, flops = ctx.get("serve_steps_traced"), ctx.get("serve_flops_traced")
    if not n or not steps or not flops:
        return None
    return 100.0 * flops / steps / (secs / n * ctx["peaks"].flops)
