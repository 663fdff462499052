"""Time the task runtime's threads waited on its locks, per task
executed in the window (RuntimeStats counters)."""


def read(ctx):
    if not ctx.get("tasks"):
        return None
    return 1e6 * ctx["lock_wait_s"] / ctx["tasks"]
