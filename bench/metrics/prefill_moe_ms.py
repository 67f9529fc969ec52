"""Median device time per call of the batched prefill (``jit_prefill``)
under the MoE layer's scopes (``moe.*``), the mean over the cell's chips
(``bench/layers.py``)."""
from bench import layers


def read(ctx):
    return layers.METRICS["prefill_moe_ms"](ctx.trace)
