"""Share of the traced window (%) in which no operation ran on the device,
from the profiler trace (``benchlib.trace``)."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.window_s <= 0 or not tr.ops:
        return None
    return 100.0 * tr.idle_share
