"""Share of the traced window in which no operation ran on the chip,
in the task-runtime cells."""
from benchlib.readers import idle_pct as read  # noqa: F401
