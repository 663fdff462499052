"""95th percentile, over the window's requests, of the time from a
request's due time to the end of the engine step that admitted it."""
from benchlib.common import percentile


def read(ctx):
    waits = ctx.get("admit_wait_s")
    if not waits:
        return None
    return 1e3 * percentile(waits, 95)
