"""Fused routing-gather -> block-quantize -> scale-pack Pallas TPU kernel
(the dispatch half of the low-precision wire path, DESIGN.md §14).

After planning, each kept (token, choice) owns a receive slot; the dispatch
payload for slot ``s`` is token row ``src_of_slot[s]`` quantized to the wire
dtype with one fp32 absmax scale per :data:`repro.core.plan.WIRE_BLOCK`
features.  This kernel fuses the slot gather with the quantize so the
``(n_slots, D)`` fp32 send buffer never materializes: rows are gathered
through the scalar-prefetched indirection into VMEM, masked by the
occupied-prefix counts (occupancy-aware like ``grouped_matmul``: slots
beyond a bucket's count cost no VPU work and emit exact zeros/zero scales),
quantized per 128-feature block, and written straight into the command
payload layout — quantized bytes and scale blocks as separate dense arrays
that the caller packs or all-to-alls.

The rounding contract is pinned by ``repro.core.transport.codec``
(fp8: f32 -> f16 -> f8e4m3, int8: RTNE + clip), so the jnp/numpy refs here
are bit-identical to the kernel in interpret mode and to the substrate's
byte codec.  Dequantize accumulates in fp32 by contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import WIRE_BLOCK, occupancy_mask, wire_layout
from repro.core.transport.codec import (_QINV, FP8_MAX, INT8_MAX,
                                        dequantize_blocked, quantize_blocked)


def _qdtype(wire_dtype: str):
    return jnp.float8_e4m3fn if wire_dtype == "fp8" else jnp.int8


# ------------------------------------------------------------------- refs --
def gather_quantize_ref(x_ext, src_of_slot, counts=None, *,
                        wire_dtype: str = "fp8"):
    """Dual-dialect (numpy/jnp) oracle for the fused kernel.

    x_ext: (T+1, D); row T is the zero scratch row empty slots gather.
    src_of_slot: (n_slots,) int32; counts: (E,) occupied-prefix counts with
    E * C == n_slots (None = fully dense).  Returns ``(q, scales)`` of
    shapes (n_slots, D) and (n_slots, n_blocks) — rows at or beyond their
    bucket's count are exact zeros with zero scales, matching the kernel's
    occupancy skip bit-for-bit.
    """
    import numpy as np
    xp = np if isinstance(x_ext, (np.ndarray, np.generic)) else jnp
    buf = x_ext[src_of_slot].astype(xp.float32)
    if counts is not None:
        E = int(counts.shape[0])          # static even for traced counts
        n_slots = src_of_slot.shape[0]
        C = n_slots // E
        m = occupancy_mask(counts.reshape(E), E, C).reshape(-1)
        buf = xp.where(m[:, None], buf, xp.float32(0))
    return quantize_blocked(buf, wire_dtype)


# ----------------------------------------------------------------- kernel --
def _round_to_f16(y):
    """Round f32 to the nearest-even f16 value, kept in f32: the codec's
    f32 -> f16 step without an f16 type, which Mosaic cannot lower on
    every TPU generation.  Exact for |y| <= 448 (the clipped fp8 range);
    below f16's normal range it keeps 10 mantissa bits, and every such
    value still rounds to zero in the fp8 step that follows."""
    b = jax.lax.bitcast_convert_type(y, jnp.uint32)
    lsb = (b >> 13) & 1
    b = (b + 0x0FFF + lsb) & ~jnp.uint32(0x1FFF)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _gq_kernel(src_ref, cnt_ref, x_ref, q_ref, s_ref, xs_ref, *, bm: int,
               C: int, d: int, nb: int, qmax: float, qinv: float, f8: bool):
    e, i = pl.program_id(0), pl.program_id(1)
    n_slots = pl.num_programs(0) * C
    cnt = cnt_ref[e]
    occ = i * bm < cnt

    @pl.when(occ)
    def _():
        # in-kernel gather through the scalar-prefetched slot table
        def gather(r, _):
            s = src_ref[jnp.minimum(e * C + i * bm + r, n_slots - 1)]
            xs_ref[pl.ds(r, 1), :] = x_ref[pl.ds(s, 1), :]
            return 0
        jax.lax.fori_loop(0, bm, gather, 0)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + i * bm
        xm = jnp.where(rows < cnt, xs_ref[...].astype(jnp.float32), 0)
        scales = []
        for j in range(nb):                      # static unroll over blocks
            seg = xm[:, j * WIRE_BLOCK:min((j + 1) * WIRE_BLOCK, d)]
            # reciprocal multiply, same pre-rounded f32 constant as the
            # codec (division by a constant strength-reduces differently)
            scale = jnp.max(jnp.abs(seg), axis=1, keepdims=True) * qinv
            sg = jnp.where(scale == 0, 1.0, scale)
            y = jnp.clip(seg / sg, -qmax, qmax)
            if f8:   # wire rounding contract: f32 -> f16 -> f8e4m3 (codec)
                qv = _round_to_f16(y).astype(jnp.float8_e4m3fn)
            else:
                qv = jnp.clip(jnp.round(y), -127, 127).astype(jnp.int8)
            q_ref[0, :, j * WIRE_BLOCK:min((j + 1) * WIRE_BLOCK, d)] = qv
            scales.append(scale)
        s_ref[0] = jnp.concatenate(scales, axis=1)

    @pl.when(~occ)
    def _():
        q_ref[...] = jnp.zeros_like(q_ref)
        s_ref[...] = jnp.zeros_like(s_ref)


@functools.partial(jax.jit,
                   static_argnames=("wire_dtype", "bm", "interpret"))
def gather_quantize_pallas(x_ext: jax.Array, src_of_slot: jax.Array,
                           counts: jax.Array | None = None, *,
                           wire_dtype: str = "fp8", bm: int = 128,
                           interpret: bool = False):
    """Fused gather + block-quantize; see :func:`gather_quantize_ref` for
    the contract.  The (T+1, D) token table is VMEM-resident (callers gate
    on size — ``kernels.ops.gather_quantize`` falls back to the ref)."""
    Tp1, D = x_ext.shape
    n_slots = src_of_slot.shape[0]
    if counts is None:
        E, C = 1, n_slots
        cnt = jnp.full((1,), n_slots, jnp.int32)
    else:
        cnt = jnp.asarray(counts, jnp.int32).reshape(-1)
        E = cnt.shape[0]
        assert n_slots % E == 0, (n_slots, E)
        C = n_slots // E
        cnt = jnp.minimum(cnt, C)
    lo = wire_layout(D, wire_dtype)
    nb = lo.n_blocks
    bm = min(bm, C)
    nm = pl.cdiv(C, bm)
    qmax = FP8_MAX if wire_dtype == "fp8" else INT8_MAX
    qinv = float(_QINV[wire_dtype])
    q, s = pl.pallas_call(
        functools.partial(_gq_kernel, bm=bm, C=C, d=D, nb=nb, qmax=qmax,
                          qinv=qinv, f8=(wire_dtype == "fp8")),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E, nm),
            in_specs=[pl.BlockSpec((Tp1, D), lambda e, i, s, c: (0, 0))],
            out_specs=[
                pl.BlockSpec((1, bm, D), lambda e, i, s, c: (e, i, 0)),
                pl.BlockSpec((1, bm, nb), lambda e, i, s, c: (e, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bm, D), x_ext.dtype)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((E, C, D), _qdtype(wire_dtype)),
            jax.ShapeDtypeStruct((E, C, nb), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(src_of_slot, jnp.int32), cnt, x_ext)
    return q.reshape(n_slots, D), s.reshape(n_slots, nb)


def _dq_kernel(q_ref, s_ref, o_ref, *, d: int, nb: int):
    qf = q_ref[...].astype(jnp.float32)
    for j in range(nb):
        seg = slice(j * WIRE_BLOCK, min((j + 1) * WIRE_BLOCK, d))
        o_ref[:, seg] = qf[:, seg] * s_ref[:, j:j + 1]


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def dequantize_pallas(q: jax.Array, scales: jax.Array, *, bm: int = 256,
                      interpret: bool = False) -> jax.Array:
    """(N, D) wire dtype + (N, nb) fp32 scales -> (N, D) fp32 (the combine
    side's fp32 accumulation input)."""
    N, D = q.shape
    nb = scales.shape[1]
    bm = min(bm, N)
    return pl.pallas_call(
        functools.partial(_dq_kernel, d=D, nb=nb),
        grid=(pl.cdiv(N, bm),),
        in_specs=[pl.BlockSpec((bm, D), lambda i: (i, 0)),
                  pl.BlockSpec((bm, nb), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(q, scales)


def dequantize_ref(q, scales):
    """Dual-dialect oracle for :func:`dequantize_pallas`."""
    return dequantize_blocked(q, scales)
