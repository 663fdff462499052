"""The prompt chunks' share of the serving programs' device time in the
traced window: `jit_prefill_chunk` over itself plus `jit_serve_step`.
Where prompts are teacher-forced through the decode step (a model with
no chunk program), they run inside `jit_serve_step`, where the trace
cannot tell them from decoding: then it reads nothing (the driver's
notes give the window's teacher-forced tokens)."""
from benchlib.readers import program_time


def read(ctx):
    n, step_s = program_time(ctx, "jit_serve_step")
    if not n or not ctx.get("prefill_chunked"):
        return None
    _, chunk_s = program_time(ctx, "jit_prefill_chunk")
    return 100.0 * chunk_s / (chunk_s + step_s)
