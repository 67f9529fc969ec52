"""Median device time per call of the decode step (``jit_decode_step``)
under the layer scope ``attention`` (``ln1`` through ``wo``, the KV-cache
write included), the mean over the cell's chips (``bench/layers.py``)."""
from bench import layers


def read(ctx):
    return layers.METRICS["decode_attention_ms"](ctx.trace)
