"""Whole-step model FLOP utilisation: the published architecture's FLOPs
of every prompt and generated token of the traced window's rounds
(``bench/flops.py``), over window x chips x the chip's bf16 peak."""
from bench import flops


def read(ctx):
    t = ctx.traffic
    work = ctx.rounds * flops.round_flops(ctx.config, t["prompt"], t["gen"],
                                          t["batch"])
    peak = ctx.peak["bf16_flops_per_s"] * ctx.chips
    return 100.0 * work / (ctx.trace.window_s * peak)
