"""Explicit sequence-parallel collectives (beyond-paper optimisation).

GSPMD resolves the SP layout transitions around attention/MLP blocks
(seq-sharded residual -> gathered compute -> seq-sharded residual) with
all-reduce + dynamic-slice pairs in the backward pass — ~P x more bytes than
needed.  These custom-vjp shard_map islands pin the minimal schedule:

    sp_gather :  fwd all-gather(seq)      bwd reduce-scatter(seq)
    sp_scatter:  fwd reduce-scatter(seq)  bwd all-gather(seq)

(Megatron-LM sequence parallelism, done manually because the automatic
partitioner picks the slow transpose; see EXPERIMENTS.md §Perf.)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import DistCtx


def _mk(dist: DistCtx, bd, m):
    mesh = dist.mesh

    def gather_island(x):
        return lax.all_gather(x, m, axis=1, tiled=True)

    def scatter_island(x):
        return lax.psum_scatter(x, m, scatter_dimension=1, tiled=True)

    g = jax.shard_map(gather_island, mesh=mesh,
                      in_specs=P(bd, m, None), out_specs=P(bd, None, None),
                      check_vma=False)
    s = jax.shard_map(scatter_island, mesh=mesh,
                      in_specs=P(bd, None, None), out_specs=P(bd, m, None),
                      check_vma=False)
    return g, s


def sp_gather(dist: DistCtx, x: jax.Array) -> jax.Array:
    """(B, S/m sharded, D) -> (B, S, D) replicated over model."""
    if dist is None or dist.model_axis is None:
        return x
    bd, m = dist.batch_axes, dist.model_axis
    if x.shape[1] % dist.mesh.shape[m] or x.shape[0] % _bdsz(dist):
        return dist.constraint(x, bd, None, None)
    g, s = _mk(dist, bd, m)

    @jax.custom_vjp
    def f(x):
        return g(x)

    def fwd(x):
        return g(x), None

    def bwd(_, ct):
        # cotangent of all-gather is the SUM-scatter of per-shard grads;
        # replicated-compute cotangents are identical, so scatter-slice of
        # psum == psum_scatter of one copy
        return (s(ct),)

    f.defvjp(fwd, bwd)
    return f(x)


def sp_scatter(dist: DistCtx, x: jax.Array) -> jax.Array:
    """(B, S, D) partial-sums over model -> (B, S/m sharded, D) reduced."""
    if dist is None or dist.model_axis is None:
        return x
    bd, m = dist.batch_axes, dist.model_axis
    if x.shape[1] % dist.mesh.shape[m] or x.shape[0] % _bdsz(dist):
        return dist.constraint(x, bd, m, None)
    g, s = _mk(dist, bd, m)

    @jax.custom_vjp
    def f(x):
        return s(x)

    def fwd(x):
        return s(x), None

    def bwd(_, ct):
        return (g(ct),)

    f.defvjp(fwd, bwd)
    return f(x)


def _bdsz(dist: DistCtx) -> int:
    import math
    return math.prod(dist.mesh.shape[a] for a in dist.batch_axes)
