"""Jitted public wrappers around the Pallas kernels with mode dispatch.

Modes (the ``mode=`` argument; None means :func:`platform_mode`):
- "ref":       pure-jnp oracle (the default off the TPU)
- "interpret": pl.pallas_call(interpret=True) — CPU validation of kernel code
- "pallas":    compiled Pallas kernel (the default on the TPU)
"""
from __future__ import annotations

import jax

from repro.kernels import ref as _ref


def platform_mode() -> str:
    """Compiled Pallas kernels on a TPU backend, the jnp references
    elsewhere.  There is no fallback: on the TPU a kernel that does not
    compile fails the program."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _mode(override: str | None = None) -> str:
    return override or platform_mode()


def grouped_matmul(x, w, counts=None, *, mode: str | None = None):
    m = _mode(mode)
    if m == "ref":
        return _ref.grouped_matmul_ref(x, w, counts=counts)
    from repro.kernels.grouped_matmul import grouped_matmul_pallas
    return grouped_matmul_pallas(x, w, counts, interpret=(m == "interpret"))


def grouped_swiglu(x, w_gate, w_up, w_down, counts=None, *,
                   mode: str | None = None, zero_padded: bool = False):
    """Grouped expert SwiGLU; ``counts`` are per-expert (or per-sub-bucket,
    shape (E, B)) occupied row counts — rows beyond occupancy cost no MXU
    flops on the kernel path and are zero on every path.

    ``zero_padded=True`` declares that rows beyond occupancy are already
    exact zeros (EP dispatch buffers: scratch-row gathers); since
    swiglu(0) == 0, the jnp ref then skips the occupancy mask — it would
    be pure overhead on XLA — while the kernel paths still use counts to
    skip the padding's flops.
    """
    m = _mode(mode)
    if m == "ref":
        return _ref.grouped_swiglu_ref(x, w_gate, w_up, w_down,
                                       counts=None if zero_padded else counts)
    from repro.kernels.grouped_matmul import grouped_swiglu_pallas
    return grouped_swiglu_pallas(x, w_gate, w_up, w_down, counts,
                                 interpret=(m == "interpret"))


# VMEM budget for the fused kernel's (T+1, D)-sized resident buffers: the
# fp32 token table + the fp32 accumulator scratch + the fp32 output block,
# all live simultaneously (see gather_swiglu_scatter_pallas); above this
# the unfused composition is used — same math, one materialized
# intermediate.
GSS_VMEM_BYTES = 8 * 1024 * 1024


def gather_swiglu_scatter(x_ext, src_of_slot, w_slot, w_gate, w_up, w_down,
                          counts=None, *, mode: str | None = None,
                          zero_padded: bool = False):
    """Fused EP hot path (gather -> expert SwiGLU -> weighted fp32
    scatter-add); see kernels.grouped_matmul.gather_swiglu_scatter_pallas.
    Returns (T, D) float32 where T = x_ext rows - 1.

    ``zero_padded`` as in :func:`grouped_swiglu`: empty slots gather the
    zero scratch row and carry zero weights, so the jnp ref skips the
    occupancy mask."""
    m = _mode(mode)
    Tp1, D = x_ext.shape
    resident = Tp1 * D * (4 + 4 + 4)
    if m != "ref" and resident <= GSS_VMEM_BYTES:
        from repro.kernels.grouped_matmul import gather_swiglu_scatter_pallas
        return gather_swiglu_scatter_pallas(
            x_ext, src_of_slot, w_slot, w_gate, w_up, w_down, counts,
            interpret=(m == "interpret"))
    if m == "ref":
        return _ref.gather_swiglu_scatter_ref(
            x_ext, src_of_slot, w_slot, w_gate, w_up, w_down,
            counts=None if zero_padded else counts)
    # unfused fallback: same math through the occupancy-aware grouped kernel
    import jax.numpy as jnp

    E = w_gate.shape[0]
    C = src_of_slot.shape[0] // E
    buf = x_ext[src_of_slot].reshape(E, C, D)
    y = grouped_swiglu(buf, w_gate, w_up, w_down, counts, mode=m)
    w_f = jnp.asarray(w_slot, jnp.float32)
    return jnp.zeros((Tp1, D), jnp.float32).at[src_of_slot].add(
        y.reshape(E * C, D).astype(jnp.float32) * w_f[:, None])[:-1]


def gather_quantize(x_ext, src_of_slot, counts=None, *, wire_dtype: str,
                    mode: str | None = None):
    """Fused routing-gather -> block-quantize for low-precision wire
    dispatch (DESIGN.md §14): returns ``(q, scales)`` of shapes
    (n_slots, D) wire dtype and (n_slots, n_blocks) fp32.  Slots beyond a
    bucket's occupied count are exact zeros with zero scales on every path.
    """
    from repro.kernels.quantize_pack import (gather_quantize_pallas,
                                             gather_quantize_ref)
    m = _mode(mode)
    Tp1, D = x_ext.shape
    if m == "ref" or Tp1 * D * x_ext.dtype.itemsize > GSS_VMEM_BYTES:
        return gather_quantize_ref(x_ext, src_of_slot, counts,
                                   wire_dtype=wire_dtype)
    return gather_quantize_pallas(x_ext, src_of_slot, counts,
                                  wire_dtype=wire_dtype,
                                  interpret=(m == "interpret"))


def dequantize_tokens(q, scales, *, mode: str | None = None):
    """Inverse of :func:`gather_quantize` (per-row): fp32 out, the combine
    side's accumulation dtype."""
    m = _mode(mode)
    if m == "ref":
        from repro.kernels.quantize_pack import dequantize_ref
        return dequantize_ref(q, scales)
    from repro.kernels.quantize_pack import dequantize_pallas
    return dequantize_pallas(q, scales, interpret=(m == "interpret"))


def flash_attention(q, k, v, *, causal: bool = True, mode: str | None = None):
    m = _mode(mode)
    if m == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal)
    from repro.kernels.flash_attention import flash_attention_pallas
    return flash_attention_pallas(q, k, v, causal=causal,
                                  interpret=(m == "interpret"))


def mamba_scan(x, dt, A, B, C, D, *, mode: str | None = None):
    m = _mode(mode)
    if m == "ref":
        return _ref.mamba_scan_ref(x, dt, A, B, C, D)
    from repro.kernels.mamba_scan import mamba_scan_pallas
    return mamba_scan_pallas(x, dt, A, B, C, D, interpret=(m == "interpret"))


def combine_reduce(parts, weights, *, mode: str | None = None):
    m = _mode(mode)
    if m == "ref":
        return _ref.combine_reduce_ref(parts, weights)
    from repro.kernels.combine_reduce import combine_reduce_pallas
    return combine_reduce_pallas(parts, weights, interpret=(m == "interpret"))


def decode_attention(q, k, v, pos, *, start: int = 0, mode: str | None = None):
    m = _mode(mode)
    if m == "ref":
        import jax.numpy as jnp
        from repro.models.layers import decode_attention_local
        part = decode_attention_local(q[:, None], k, v, pos, start=start)
        l = jnp.where(part.l == 0, 1.0, part.l)
        return (part.o / l[..., None])[:, 0].astype(q.dtype)
    from repro.kernels.decode_attention import decode_attention_pallas
    return decode_attention_pallas(q, k, v, pos, start=start,
                                   interpret=(m == "interpret"))


def rmsnorm(x, scale, eps: float = 1e-5, *, mode: str | None = None):
    m = _mode(mode)
    if m == "ref":
        return _ref.rmsnorm_ref(x, scale, eps)
    from repro.kernels.rmsnorm import rmsnorm_pallas
    return rmsnorm_pallas(x, scale, eps, interpret=(m == "interpret"))
