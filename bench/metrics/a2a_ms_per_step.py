"""Median device time per call of the decode step (``jit_decode_step``)
under the EP exchange's scopes ``moe.dispatch`` and ``moe.combine`` (the
all-to-alls with their packing and the weighted combine), the mean over
the cell's chips (``bench/layers.py``)."""
from bench import layers


def read(ctx):
    return layers.METRICS["a2a_ms_per_step"](ctx.trace)
