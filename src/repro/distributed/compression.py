"""Gradient compression: int8 block-quantised ring reduce-scatter with error
feedback (beyond-paper distributed-optimization trick; DESIGN.md §7).

On a ring of P shards (the "data" axis), each hop sends an int8-quantised
partial sum instead of fp32 — 4x fewer bytes over the wire.  Error feedback
accumulates the per-shard quantisation residual into the next step's
gradient, which keeps the compressed SGD unbiased over time.

The quantizer itself lives in ``repro.core.transport.codec`` (the repo's
single block-quantization implementation, shared with the wire-dispatch
codec; DESIGN.md §14) — this module only supplies the ring/EF orchestration
on top of it, at the gradient-friendly block width ``BLOCK``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.transport.codec import dequantize_blocked, quantize_blocked

Array = jax.Array

BLOCK = 256


class QChunk(NamedTuple):
    q: Array        # int8 payload, (nb, BLOCK)
    scale: Array    # fp32 per-block scales, (nb,)


def quantize(x: Array) -> QChunk:
    """Symmetric per-block int8 quantisation of a flat fp32 vector."""
    n = x.shape[0]
    nb = -(-n // BLOCK)
    xp = jnp.pad(x, (0, nb * BLOCK - n)).reshape(nb, BLOCK)
    q, scale = quantize_blocked(xp, "int8", block=BLOCK)
    return QChunk(q=q, scale=scale[:, 0])


def dequantize(c: QChunk, n: int) -> Array:
    return dequantize_blocked(c.q, c.scale[:, None],
                              block=BLOCK).reshape(-1)[:n]


def compressed_psum_scatter(x: Array, axis: str) -> Array:
    """Ring reduce-scatter of a flat fp32 vector with int8 hops.

    Runs inside shard_map.  x: (n,) identical-shape on each shard; returns
    this shard's (n/P,) reduced chunk.  Each of the P-1 hops dequantises,
    adds its local chunk, and requantises (fp32 accumulation, int8 wire).
    """
    P = lax.axis_size(axis)
    n = x.shape[0]
    assert n % P == 0, (n, P)
    chunk = n // P
    idx = lax.axis_index(axis)
    xc = x.reshape(P, chunk)
    perm = [(i, (i + 1) % P) for i in range(P)]

    # node idx starts accumulating chunk (idx-1); chunks move rightward one
    # hop per step so that after P-1 hops node i holds chunk i fully reduced
    # (required for the tiled all-gather to reassemble in order).
    acc_i = (idx - 1) % P
    q = quantize(lax.dynamic_index_in_dim(xc, acc_i, 0, keepdims=False))
    for step in range(P - 1):
        q = QChunk(q=lax.ppermute(q.q, axis, perm),
                   scale=lax.ppermute(q.scale, axis, perm))
        acc_i = (acc_i - 1) % P          # chunk id now held locally
        local = lax.dynamic_index_in_dim(xc, acc_i, 0, keepdims=False)
        acc = dequantize(q, chunk) + local
        q = quantize(acc)
    return dequantize(q, chunk)


def ef_compressed_mean(per_shard: Array, mesh, axis: str,
                       residual: Array | None = None) -> tuple[Array, Array]:
    """Error-feedback compressed all-reduce mean (EF14 + int8 ring hops).

    ``per_shard``: (P, n) — row i is shard i's local gradient vector,
    sharded ``P(axis)`` on dim 0 (the manual-DP layout used by examples and
    benchmarks).  ``residual``: (P, n) per-shard EF memory from the previous
    step (same layout), or None.

    Each shard adds its residual, quantises its contribution to int8 (the
    wire format), keeps the quantisation error as the new residual, and the
    ring reduce-scatter (int8 hops, fp32 accumulation) + all-gather produces
    the mean on every shard.  Returns (mean (n,), new_residual (P, n)).
    """
    from jax.sharding import PartitionSpec as P
    Pax = mesh.shape[axis]
    n = per_shard.shape[1]
    assert n % (Pax * BLOCK) == 0, f"pad input to a multiple of {Pax * BLOCK}"
    if residual is None:
        residual = jnp.zeros_like(per_shard)

    def island(g, e):
        g, e = g[0], e[0]                       # local row
        contrib = g + e
        q = quantize(contrib)
        deq = dequantize(q, n)
        new_e = contrib - deq                   # EF memory
        mine = compressed_psum_scatter(deq, axis)       # (n/P,) summed
        full = lax.all_gather(mine, axis, axis=0, tiled=True)
        return (full / Pax)[None], new_e[None]

    other = tuple(a for a in mesh.axis_names if a != axis)
    mean, new_res = jax.shard_map(
        island, mesh=mesh, in_specs=(P(axis, None), P(axis, None)),
        out_specs=(P(axis, None), P(axis, None)),
        check_vma=False)(per_shard, residual)
    # every row of `mean` is identical; return row 0 plus the residuals
    return mean[0], new_res


def pad_to_ring(x: Array, P: int) -> Array:
    pad = (-x.size) % (P * BLOCK)
    return jnp.pad(x.reshape(-1), (0, pad))
