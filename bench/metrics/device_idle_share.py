"""Share of the traced window in which no operation ran on the device:
1 minus the union of the ``XLA Ops`` intervals over the window, the mean
over the cell's chips."""
from bench import trace


def read(ctx):
    busy = trace.busy_s(ctx.trace)
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / ctx.trace.window_s)
