"""Device time of the jitted serve step per engine step, from the trace."""
from benchlib.readers import program_time


def read(ctx):
    n, secs = program_time(ctx, "jit_serve_step")
    return 1e3 * secs / n if n else None
