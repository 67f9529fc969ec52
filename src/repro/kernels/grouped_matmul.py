"""Grouped expert matmul / fused grouped SwiGLU Pallas TPU kernels,
occupancy-aware (MegaBlocks-style, adapted to static TPU capacity buckets).

This is the compute hot-spot of EP: after dispatch, each EP shard applies its
local experts to capacity-bucketed token blocks — a batch of per-expert
matmuls with static capacity buckets (the TPU-native formulation: MXU wants
dense 128-aligned tiles, not CSR).  At ``capacity_factor=2.0`` roughly half
of every bucket is zero padding, so all kernels here take optional
scalar-prefetched per-expert **occupied counts** (computed by
``core/plan.py``) and skip row-blocks beyond each expert's occupancy with a
``pl.when`` guard on the row grid dimension: padding rows cost zero MXU
flops, and out rows beyond occupancy are written as exact zeros (bit-equal
to the masked jnp refs in ``repro.kernels.ref``).

Counts may be bucketed: a ``(E, B)`` counts array describes ``B`` sub-buckets
per expert (each ``N // B`` rows, occupied-prefix each) — the layout the LL
receive buffer has after the all-to-all, where each source shard contributes
its own capacity-``C`` bucket.  The kernel then runs over ``E*B`` groups and
indexes the weights with ``g // B``.

Three entry points:

- ``grouped_matmul_pallas(x, w, counts=None)``  — blocked GEMM per group.
- ``grouped_swiglu_pallas(x, wg, wu, wd, counts=None)`` — fused expert FFN,
  streaming the hidden dim F in blocks (gate/up activations live in VMEM
  only).  VMEM working set per grid step: x (bm x D) + Wg/Wu (D x bf) +
  Wd (bf x D) + acc (bm x D), all 128-aligned for the MXU.
- ``gather_swiglu_scatter_pallas(x_ext, src, w_slot, wg, wu, wd, counts)``
  — the fully fused post-dispatch hot path: gathers token rows in-kernel
  from the extended token table via the scalar-prefetched ``src_of_slot``
  indirection, applies the expert SwiGLU, and scatter-adds the weighted
  fp32 outputs straight into the per-token accumulator.  No ``(E, C, D)``
  send buffer and no ``(E*C, D)`` expert-output intermediate ever touch HBM.

``grouped_swiglu_db`` is the double-buffered variant: token blocks stay in
HBM (``pl.ANY``) and are DMA'd manually through two VMEM slots, so
skipped (unoccupied) row-blocks skip their HBM traffic too — the BlockSpec
pipeline cannot elide fetches for ``pl.when``-skipped steps, manual DMA can.

All kernels are ragged-safe: partial edge blocks (C % bm, F % bf, K % bk)
are masked explicitly, because Pallas pads out-of-bounds input blocks with
undefined values (NaN in interpret mode — by design, to catch exactly this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dim_sem(n: int, vmem_need: int | None = None):
    """Grid annotation: groups are parallel, row/col/reduce dims arbitrary;
    with ``vmem_need`` bytes, a scoped-VMEM limit to fit them."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) + ("arbitrary",) * (n - 1),
        vmem_limit_bytes=(None if vmem_need is None
                          else _vmem_limit(vmem_need)))


def _vmem_limit(need: int) -> int:
    """Scoped-VMEM limit for a kernel whose pipeline buffers and scratch
    take ``need`` bytes: a quarter more for the compiler's temporaries,
    and never below 32 MiB (v5e has 128 MiB of VMEM; its default scoped
    limit of 16 MiB is exceeded by f32 weight blocks at D=2048)."""
    return max(32 << 20, need + need // 4)


def _swiglu_vmem(bm: int, bf: int, D: int, x_bytes: int,
                 w_bytes: int) -> int:
    """Bytes of one grouped-SwiGLU grid step: double-buffered token, gate,
    up, down and output blocks plus the f32 accumulator."""
    return 2 * (2 * bm * D * x_bytes + 3 * D * bf * w_bytes) + bm * D * 4


def _norm_counts(counts, n_groups: int, cap: int):
    """Normalize counts to a flat (n_groups,) int32 vector clipped to the
    per-group capacity; None means fully dense."""
    if counts is None:
        return jnp.full((n_groups,), cap, jnp.int32), 1
    counts = jnp.asarray(counts, jnp.int32)
    B = 1 if counts.ndim == 1 else counts.shape[1]
    return jnp.minimum(counts.reshape(-1), cap), B


# ======================================================== grouped matmul ==
def _gm_kernel(cnt_ref, x_ref, w_ref, o_ref, acc_ref, *, bm: int, bk: int,
               K: int, nk: int, mask_rows: bool):
    g, i, k = pl.program_id(0), pl.program_id(1), pl.program_id(3)
    cnt = cnt_ref[g]
    occ = i * bm < cnt
    mask_k = K % bk != 0          # static: ragged reduce-dim edge block

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occ)
    def _():
        # mask rows beyond occupancy and (ragged K) reduce-dim padding —
        # OOB input blocks are undefined, and masked rows must contribute
        # 0.  Both masks are statically elided when shapes make them no-ops
        # (fully dense aligned blocks keep a pure MXU loop).
        xm, wm = x_ref[0], w_ref[0]
        if mask_rows:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + i * bm
            xm = jnp.where(rows < cnt, xm, 0)
        if mask_k:
            cols = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) + k * bk
            xm = jnp.where(cols < K, xm, 0)
            wm = jnp.where(cols.reshape(-1, 1) < K, wm, 0)
        acc_ref[...] += jnp.dot(xm, wm, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        # rows beyond occupancy are exact zeros (the masked-ref contract)
        o_ref[0] = jnp.where(occ, acc_ref[...], 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def grouped_matmul_pallas(x: jax.Array, w: jax.Array,
                          counts: jax.Array | None = None, *, bm: int = 128,
                          bn: int = 128, bk: int = 512,
                          interpret: bool = False) -> jax.Array:
    """x: (G, M, K) @ w: (G, K, N) -> (G, M, N); rows >= counts[g] are
    skipped on the MXU and written as zeros."""
    G, M, K = x.shape
    _, _, N = w.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    nm, nn, nk = pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk)
    cnt, B = _norm_counts(counts, G, M)
    assert B == 1, "bucketed counts are a grouped_swiglu feature"
    mask_rows = counts is not None or M % bm != 0
    return pl.pallas_call(
        functools.partial(_gm_kernel, bm=bm, bk=bk, K=K, nk=nk,
                          mask_rows=mask_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, nm, nn, nk),
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda g, i, j, k, c: (g, i, k)),
                pl.BlockSpec((1, bk, bn), lambda g, i, j, k, c: (g, k, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k, c: (g, i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((G, M, N), x.dtype),
        compiler_params=_dim_sem(4),
        interpret=interpret,
    )(cnt, x, w)


# ======================================================== grouped swiglu ==
def _swiglu_block(x, wg, wu, wd, f, bf: int, F: int):
    """One f-block SwiGLU partial: silu(x@wg)*(x@wu) @ wd, masking the
    (ragged F) hidden-dim padding of the edge block — statically elided
    when bf divides F.  f32 operands multiply at f32 precision (Mosaic's
    default may round them to bf16); bf16 operands take one MXU pass."""
    prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32, precision=prec)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32, precision=prec)
    h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    wdm = wd
    if F % bf != 0:
        fcols = jax.lax.broadcasted_iota(jnp.int32, (1, h.shape[1]), 1) \
            + f * bf
        h = jnp.where(fcols < F, h, 0)
        wdm = jnp.where(fcols.reshape(-1, 1) < F, wd, 0)
    return jnp.dot(h, wdm, preferred_element_type=jnp.float32, precision=prec)


def _swiglu_kernel(cnt_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *,
                   bm: int, bf: int, F: int, nf: int, mask_rows: bool):
    g, i, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cnt = cnt_ref[g]
    occ = i * bm < cnt

    @pl.when(f == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occ)
    def _():
        xm = x_ref[0]
        if mask_rows:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + i * bm
            xm = jnp.where(rows < cnt, xm, 0)
        acc_ref[...] += _swiglu_block(xm, wg_ref[0], wu_ref[0], wd_ref[0],
                                      f, bf, F)

    @pl.when(f == nf - 1)
    def _():
        o_ref[0] = jnp.where(occ, acc_ref[...], 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def grouped_swiglu_pallas(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                          w_down: jax.Array,
                          counts: jax.Array | None = None, *, bm: int = 128,
                          bf: int = 256, interpret: bool = False) -> jax.Array:
    """Fused grouped expert SwiGLU.  x: (E, C, D); w_*: (E, D, F)/(E, F, D).

    ``counts``: per-expert occupied row counts, (E,) — or (E, B) sub-bucket
    counts where B divides C and each C//B sub-bucket is occupied-prefix
    (the post-a2a receive layout).  Rows beyond occupancy are skipped on
    the MXU and written as exact zeros.
    """
    E, C, D = x.shape
    F = w_gate.shape[2]
    if counts is None:
        cnt, B = jnp.full((E,), C, jnp.int32), 1
    else:
        counts = jnp.asarray(counts, jnp.int32)
        B = 1 if counts.ndim == 1 else counts.shape[1]
        assert C % B == 0, (C, B)
        cnt = jnp.minimum(counts.reshape(-1), C // B)
    if B > 1:
        C = C // B
        x = x.reshape(E * B, C, D)
    G = E * B
    bm, bf = min(bm, C), min(bf, F)
    nm, nf = pl.cdiv(C, bm), pl.cdiv(F, bf)
    mask_rows = counts is not None or C % bm != 0
    out = pl.pallas_call(
        functools.partial(_swiglu_kernel, bm=bm, bf=bf, F=F, nf=nf,
                          mask_rows=mask_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, nm, nf),
            in_specs=[
                pl.BlockSpec((1, bm, D), lambda g, i, f, c: (g, i, 0)),
                pl.BlockSpec((1, D, bf), lambda g, i, f, c: (g // B, 0, f)),
                pl.BlockSpec((1, D, bf), lambda g, i, f, c: (g // B, 0, f)),
                pl.BlockSpec((1, bf, D), lambda g, i, f, c: (g // B, f, 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, D), lambda g, i, f, c: (g, i, 0)),
            scratch_shapes=[pltpu.VMEM((bm, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((G, C, D), x.dtype),
        compiler_params=_dim_sem(3, _swiglu_vmem(
            bm, bf, D, x.dtype.itemsize, w_gate.dtype.itemsize)),
        interpret=interpret,
    )(cnt, x, w_gate, w_up, w_down)
    return out.reshape(E, B * C, D) if B > 1 else out


# ===================================== double-buffered grouped swiglu =====
def _swiglu_db_kernel(cnt_ref, x_hbm, wg_ref, wu_ref, wd_ref, o_ref,
                      xbuf_ref, acc_ref, sem_ref, *, bm: int, bf: int,
                      F: int, nf: int, mask_rows: bool):
    g, i, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cnt = cnt_ref[g]
    occ = i * bm < cnt

    def dma(slot, grp, blk):
        return pltpu.make_async_copy(x_hbm.at[grp, pl.ds(blk * bm, bm), :],
                                     xbuf_ref.at[slot], sem_ref.at[slot])

    # warm-up: first occupied block of this group (i == 0 iff cnt > 0)
    @pl.when(occ & (i == 0) & (f == 0))
    def _():
        dma(0, g, 0).start()

    @pl.when(occ & (f == 0))
    def _():
        # prefetch the next occupied row-block while this one computes
        @pl.when((i + 1) * bm < cnt)
        def _():
            dma((i + 1) % 2, g, i + 1).start()
        dma(i % 2, g, i).wait()
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occ)
    def _():
        xm = xbuf_ref[i % 2]
        if mask_rows:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + i * bm
            xm = jnp.where(rows < cnt, xm, 0)
        acc_ref[...] += _swiglu_block(xm, wg_ref[0], wu_ref[0], wd_ref[0],
                                      f, bf, F)

    @pl.when(f == nf - 1)
    def _():
        o_ref[0] = jnp.where(occ, acc_ref[...], 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def grouped_swiglu_db_pallas(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                             w_down: jax.Array,
                             counts: jax.Array | None = None, *,
                             bm: int = 128, bf: int = 256,
                             interpret: bool = False) -> jax.Array:
    """Double-buffered occupancy-aware grouped SwiGLU: token row-blocks stay
    in HBM and are DMA'd through two VMEM slots, so skipped blocks skip
    their HBM reads too.  Requires bm | C (manual DMA sizes are static);
    when the largest divisor of C degenerates below a useful sublane count
    (< 8 rows, e.g. prime C) the pipelined kernel is used instead."""
    E, C, D = x.shape
    F = w_gate.shape[2]
    bm = min(bm, C)
    while C % bm:           # largest divisor of C <= requested bm
        bm -= 1
    if bm < min(8, C):
        return grouped_swiglu_pallas(x, w_gate, w_up, w_down, counts,
                                     bm=min(8, C), bf=bf,
                                     interpret=interpret)
    bf = min(bf, F)
    nm, nf = C // bm, pl.cdiv(F, bf)
    cnt, B = _norm_counts(counts, E, C)
    assert B == 1, "bucketed counts: reshape to (E*B, C//B, D) first"
    return pl.pallas_call(
        functools.partial(_swiglu_db_kernel, bm=bm, bf=bf, F=F, nf=nf,
                          mask_rows=counts is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E, nm, nf),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, D, bf), lambda g, i, f, c: (g, 0, f)),
                pl.BlockSpec((1, D, bf), lambda g, i, f, c: (g, 0, f)),
                pl.BlockSpec((1, bf, D), lambda g, i, f, c: (g, f, 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, D), lambda g, i, f, c: (g, i, 0)),
            scratch_shapes=[pltpu.VMEM((2, bm, D), x.dtype),
                            pltpu.VMEM((bm, D), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((E, C, D), x.dtype),
        compiler_params=_dim_sem(3, _swiglu_vmem(
            bm, bf, D, x.dtype.itemsize, w_gate.dtype.itemsize)),
        interpret=interpret,
    )(cnt, x, w_gate, w_up, w_down)


# ================================== fused gather -> swiglu -> scatter =====
def _gss_kernel(src_ref, cnt_ref, x_ref, ws_ref, wg_ref, wu_ref, wd_ref,
                o_ref, xs_ref, acc_ref, oacc_ref, *, bm: int, bf: int,
                C: int, F: int, nf: int, x_dtype):
    e, i, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ne, nm = pl.num_programs(0), pl.num_programs(1)
    n_slots = ne * C
    cnt = cnt_ref[e]
    occ = i * bm < cnt

    @pl.when((e == 0) & (i == 0) & (f == 0))
    def _():
        oacc_ref[...] = jnp.zeros_like(oacc_ref)

    # in-kernel gather, driven by the scalar-prefetched src_of_slot table:
    # row r of this slot-block reads token row src[e*C + i*bm + r]
    @pl.when(occ & (f == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def gather(r, _):
            s = src_ref[jnp.minimum(e * C + i * bm + r, n_slots - 1)]
            xs_ref[pl.ds(r, 1), :] = x_ref[pl.ds(s, 1), :]
            return 0
        jax.lax.fori_loop(0, bm, gather, 0)

    @pl.when(occ)
    def _():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + i * bm
        xm = jnp.where(rows < cnt, xs_ref[...], 0).astype(x_dtype)
        acc_ref[...] += _swiglu_block(xm, wg_ref[0], wu_ref[0], wd_ref[0],
                                      f, bf, F)

    # weighted fp32 scatter-add into the persistent per-token accumulator
    @pl.when(occ & (f == nf - 1))
    def _():
        # weight the block in place; rows are then read back by ref slice
        # (Mosaic has no dynamic_slice of a value)
        acc_ref[...] = acc_ref[...] * ws_ref[0, 0, :].astype(
            jnp.float32)[:, None]

        def scatter(r, _):
            @pl.when(i * bm + r < cnt)
            def _():
                s = src_ref[jnp.minimum(e * C + i * bm + r, n_slots - 1)]
                oacc_ref[pl.ds(s, 1), :] += acc_ref[pl.ds(r, 1), :]
            return 0
        jax.lax.fori_loop(0, bm, scatter, 0)

    @pl.when((e == ne - 1) & (i == nm - 1) & (f == nf - 1))
    def _():
        o_ref[...] = oacc_ref[...]


def _gss_vmem(Tp1: int, D: int, bm: int, bf: int, w_bytes: int) -> int:
    """Bytes of the fused kernel's working set: the f32 token table and
    f32 output block (two pipeline buffers each) and the f32 accumulator,
    all (T+1, D); double-buffered gate/up/down weight and slot-weight
    blocks; the two (bm, D) f32 scratch blocks."""
    return (Tp1 * D * 4 * 5 + 2 * 3 * D * bf * w_bytes
            + 2 * bm * 4 + 2 * bm * D * 4)


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def gather_swiglu_scatter_pallas(x_ext: jax.Array, src_of_slot: jax.Array,
                                 w_slot: jax.Array, w_gate: jax.Array,
                                 w_up: jax.Array, w_down: jax.Array,
                                 counts: jax.Array | None = None, *,
                                 bm: int = 128, bf: int = 256,
                                 interpret: bool = False) -> jax.Array:
    """Fused EP hot path: for each occupied receive slot, gather its token
    row from ``x_ext`` ((T+1, D); row T is the zero scratch row), apply the
    owning expert's SwiGLU, and scatter-add ``w_slot[slot] * y`` in fp32
    into the per-token output.

    src_of_slot: (E*C,) int32 token row per slot (T for empty slots);
    w_slot: (E*C,) combine weights (0 for empty slots); counts: (E,)
    occupied prefix per expert bucket.  Returns (T, D) float32 partials.

    The (T+1, D) token table (gathered in fp32: Mosaic loads single rows
    of 32-bit data only, then casts each block back to ``x_ext.dtype``)
    and the fp32 accumulator are VMEM-resident, which bounds T: callers
    should fall back to gather -> grouped_swiglu -> scatter (the unfused
    composition, same math) when they do not fit — see
    ``kernels.ops.gather_swiglu_scatter``.
    """
    Tp1, D = x_ext.shape
    E, _, F = w_gate.shape
    n_slots = src_of_slot.shape[0]
    assert n_slots % E == 0, (n_slots, E)
    C = n_slots // E
    cnt, B = _norm_counts(counts, E, C)
    assert B == 1, "fused kernel takes flat per-expert counts"
    bm, bf = min(bm, C), min(bf, F)
    nm, nf = pl.cdiv(C, bm), pl.cdiv(F, bf)
    # pad the per-slot weights to whole row-blocks so the (1, 1, bm) weight
    # block of the ragged edge never reads past C; the unit middle dim
    # keeps that block on the TPU's (8, 128) tiling rule
    ws = jnp.zeros((E, 1, nm * bm), jnp.float32).at[:, 0, :C].set(
        jnp.asarray(w_slot, jnp.float32).reshape(E, C))
    out = pl.pallas_call(
        functools.partial(_gss_kernel, bm=bm, bf=bf, C=C, F=F, nf=nf,
                          x_dtype=x_ext.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E, nm, nf),
            in_specs=[
                pl.BlockSpec((Tp1, D), lambda e, i, f, s, c: (0, 0)),
                pl.BlockSpec((1, 1, bm), lambda e, i, f, s, c: (e, 0, i)),
                pl.BlockSpec((1, D, bf), lambda e, i, f, s, c: (e, 0, f)),
                pl.BlockSpec((1, D, bf), lambda e, i, f, s, c: (e, 0, f)),
                pl.BlockSpec((1, bf, D), lambda e, i, f, s, c: (e, f, 0)),
            ],
            out_specs=pl.BlockSpec((Tp1, D), lambda e, i, f, s, c: (0, 0)),
            scratch_shapes=[pltpu.VMEM((bm, D), jnp.float32),
                            pltpu.VMEM((bm, D), jnp.float32),
                            pltpu.VMEM((Tp1, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Tp1, D), jnp.float32),
        # every grid dim is 'arbitrary': the per-token accumulator crosses
        # the expert dim (zero-init at the first step, flush at the last),
        # so a Megacore-parallel split of it would shear the accumulation
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_vmem_limit(_gss_vmem(Tp1, D, bm, bf,
                                                   w_gate.dtype.itemsize))),
        interpret=interpret,
    )(jnp.asarray(src_of_slot, jnp.int32), cnt, x_ext.astype(jnp.float32),
      ws,
      w_gate, w_up, w_down)
    return out[:-1]
