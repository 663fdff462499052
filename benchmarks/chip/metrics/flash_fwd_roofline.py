"""The flash attention forward kernel's least time from its shapes (the
larger of FLOPs over peak and bytes over bandwidth) over its trace time
per call. The kernel is the train step's only Pallas call, so its calls
are the ops whose custom-call target is "tpu_custom_call"."""
from benchlib.flops import flash_fwd
from benchlib.readers import roofline_pct
from benchlib.tracered import op_stats

KERNEL = "tpu_custom_call:"


def read(ctx):
    red = ctx.get("reduced")
    if red is None or "flash_fwd_call" not in ctx:
        return None
    n, secs = op_stats(red, KERNEL)
    flops, nbytes = flash_fwd(*ctx["flash_fwd_call"])
    return roofline_pct(flops, nbytes, n, secs, ctx["peaks"])
