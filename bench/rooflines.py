"""Operations and bytes of one call of a kernel, counted from a
configuration file and the cell's traffic, and the least time the chip
could take for them (the roofline).

Only the work the kernel has to do counts: for the grouped expert kernel,
the token-choices routed to this chip's experts, not the capacity padding
of its buffers; the bytes are this chip's experts' weights, read once, and
those tokens' rows in and out.
"""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def _experts(c: dict) -> int:
    return c.get("n_routed_experts", c.get("num_experts"))


def grouped_swiglu(c: dict, batch: int, ep_degree: int) -> tuple:
    """(FLOPs, bytes) of one call of the grouped SwiGLU expert kernel on
    one chip of an EP group of ``ep_degree`` chips, in one MoE layer of a
    decode step of ``batch`` tokens.

    Every chip dispatches its own copy of the batch, so the chips together
    route ``ep_degree x batch x top_k`` token-choices and each receives
    ``batch x top_k`` on average.  A choice costs the three matrices of one
    expert, 3 x 2 x D x F FLOPs; the weights of the chip's
    ``experts / ep_degree`` experts are read once, and each choice's row of
    D values is read and written, all in the served dtype."""
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    size = DTYPE_BYTES[c["dtypes"]["compute"]]
    choices = batch * c["num_experts_per_tok"]
    flops = choices * 3 * 2 * D * F
    weights = _experts(c) // ep_degree * 3 * D * F * size
    rows = 2 * choices * D * size
    return flops, weights + rows


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time for the work: the larger of the FLOPs at the chip's
    bf16 peak and the bytes at its HBM bandwidth (``bench/peaks.json``)."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
