"""The grouped expert kernel's share of its roofline: the least time its
work could take on the chip (``bench/rooflines.py``: the routed
token-choices' FLOPs at the bf16 peak, or this chip's experts' weights and
those tokens' rows at HBM bandwidth, whichever is longer) over the median
device time of one call of ``grouped_swiglu_pallas`` inside the decode
step, the mean over the cell's chips (``bench/layers.py``)."""
from bench import cells, layers, rooflines


def read(ctx):
    ms = layers.METRICS["expert_kernel_ms"](ctx.trace)
    if ms is None:
        return None
    ep = cells.mesh_shape(ctx.config, ctx.chips)[1]
    flops, nbytes = rooflines.grouped_swiglu(ctx.config,
                                             ctx.traffic["batch"], ep)
    return 100.0 * rooflines.roofline_s(flops, nbytes, ctx.peak) / (ms * 1e-3)
