"""The `_gemm_block` task body's least time from its shapes (FLOPs over
peak, or bytes over bandwidth, whichever is larger) over its trace time."""
from benchlib.flops import gemm_block
from benchlib.readers import program_time, roofline_pct


def read(ctx):
    if "gemm_block" not in ctx:
        return None
    bs, itemsize = ctx["gemm_block"]
    n, secs = program_time(ctx, "_gemm_block")
    flops, nbytes = gemm_block(bs, itemsize)
    return roofline_pct(flops, nbytes, n, secs, ctx["peaks"])
