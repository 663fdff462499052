"""Forward and backward FLOPs of a train step (recomputation not
counted) over the step's device time times the chip's peak FLOP/s."""
from benchlib.readers import program_time


def read(ctx):
    n, secs = program_time(ctx, "jit_train_step")
    if not n or "train_step_flops" not in ctx:
        return None
    return 100.0 * ctx["train_step_flops"] / (secs / n * ctx["peaks"].flops)
