"""Manager messages processed per task executed in the window."""


def read(ctx):
    if not ctx.get("tasks"):
        return None
    return ctx["messages"] / ctx["tasks"]
