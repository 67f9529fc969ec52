"""Median device time per call of the decode step (``jit_decode_step``)
under the MoE layer's scopes (``moe.*``: router, experts or the EP island,
dispatch, combine, shared experts), the mean over the cell's chips
(``bench/layers.py``)."""
from bench import layers


def read(ctx):
    return layers.METRICS["decode_moe_ms"](ctx.trace)
