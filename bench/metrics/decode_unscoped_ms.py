"""Median device time per call of the decode step (``jit_decode_step``)
under no layer scope: the layer scan's KV-cache copies and weight slices,
norms and residual adds between the layers, XLA's own copies; the mean
over the cell's chips (``bench/layers.py``)."""
from bench import layers


def read(ctx):
    return layers.METRICS["decode_unscoped_ms"](ctx.trace)
