"""Kernel-variant benchmark (ISSUE 3): occupancy-aware grouped expert
compute and the scatter-based combine across the EP hot path.

Wall clock is the **XLA path on the fake-device mesh** (CPU devices can't
compile Mosaic kernels; the Pallas bodies are validated in interpret mode by
the test suite).  The kernels' win is therefore reported two ways:

- measured: dispatch+combine wall clock with the occupancy-aware expert_fn
  and scatter-add combine vs the legacy dense expert_fn + gather/einsum
  combine formulations, at fig08 scale;
- analytical ``derived`` columns: MXU flops and HBM bytes for the kernel
  variants, computed from the *actual plan-derived occupancy* of the same
  routing tables the wall-clock runs use (block granularity bm=128 — what
  the ``pl.when`` grid guard skips).  Acceptance: >= 1.5x flop reduction at
  ``capacity_factor=2.0`` balanced load.

Flops model (per occupied row): 3 matmuls of D*F MACs = 6*D*F flops.
Bytes model (fused gather_swiglu_scatter vs unfused): the unfused path
writes + re-reads the (E, C, D) gather buffer and the (E*C, D) expert
output intermediate; the fused kernel touches token rows once and
accumulates in VMEM.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from benchmarks.common import emit, timeit
from repro.core import plan as planlib
from repro.core.ep import EPSpec, dispatch_combine_ht, dispatch_combine_ll
from repro.kernels.ref import grouped_swiglu_ref

E, K, D, F = 32, 6, 256, 128
BM = 128                         # kernel row-block: pl.when skip granularity


def _cdiv(a, b):
    return -(-a // b)


def swiglu_flops(counts, C: int) -> int:
    """MXU flops at block granularity for per-bucket occupied counts."""
    blocks = int(np.sum(_cdiv(np.minimum(np.asarray(counts), C), BM)))
    return blocks * BM * 6 * D * F


def occupancy_model(ti: np.ndarray, n_shards: int, cf: float):
    """Plan-derived per-(expert, source) occupancy for an LL round: returns
    (flops_dense, flops_occupied, occupancy) summed over shards."""
    from repro.core.ep import _cap

    T, Kk = ti.shape
    Tl = T // n_shards
    C = _cap(Tl * Kk / E, cf, hard_max=Tl * Kk)
    f_dense = f_occ = f_rows = 0
    occ_n = occ_d = 0
    for s in range(n_shards):
        pl = planlib.make_plan(ti[s * Tl:(s + 1) * Tl], E, C)
        cnt = np.minimum(np.asarray(pl.counts), C)
        f_dense += E * _cdiv(C, BM) * BM * 6 * D * F
        f_occ += swiglu_flops(cnt, C)
        f_rows += int(cnt.sum()) * 6 * D * F     # row-granular lower bound
        occ_n += int(cnt.sum())
        occ_d += E * C
    return f_dense, f_occ, f_rows, occ_n / occ_d


def fused_bytes_model(n_slots: int, occupancy: float, dtype_bytes: int = 2):
    """HBM bytes for the HT local compute: unfused (gather buffer + expert
    output intermediate materialized) vs fused (tokens touched once,
    accumulator in VMEM)."""
    row = D * dtype_bytes
    occ_rows = int(n_slots * occupancy)
    unfused = (n_slots * row * 2          # gather buffer write + read
               + n_slots * row * 2        # expert output write + read
               + occ_rows * 4 * D)        # fp32 scatter-add traffic
    fused = occ_rows * row + occ_rows * 4 * D
    return unfused, fused


def build(mesh, mode, n_tokens, occupancy_aware: bool):
    axes = ("model",)
    sizes = tuple(mesh.shape[a] for a in axes)
    spec = EPSpec(axes=axes, sizes=sizes, n_experts=E, top_k=K,
                  capacity_factor=2.0, dtype=jnp.bfloat16)

    def island(x, ti, tw, wg, wu, wd):
        if occupancy_aware:
            # production ref semantics: accept counts (exercising the whole
            # occupancy plumbing — plan counts a2a included) but skip the
            # mask, since EP buffers pad with exact zeros and swiglu(0)==0;
            # the kernel paths are where counts turn into skipped flops
            fn = lambda t, c=None: grouped_swiglu_ref(t, wg, wu, wd)  # noqa: E731
        else:
            fn = lambda t: grouped_swiglu_ref(t, wg, wu, wd)  # noqa: E731
        d = {"ll": dispatch_combine_ll, "ht": dispatch_combine_ht}[mode]
        return d(spec, x, ti, tw, fn).out

    f = jax.jit(jax.shard_map(
        island, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes[0], None, None),
                  P(axes[0], None, None), P(axes[0], None, None)),
        out_specs=P(axes), check_vma=False))
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (n_tokens, D), jnp.bfloat16)
    # balanced load: every expert sees exactly T*K/E choices
    ti = np.arange(n_tokens * K, dtype=np.int32) % E
    np.random.default_rng(0).shuffle(ti)
    ti = jnp.asarray(ti.reshape(n_tokens, K))
    tw = jax.nn.softmax(jax.random.normal(ks[2], (n_tokens, K)), -1)
    tw = tw.astype(jnp.bfloat16)
    wg = (jax.random.normal(ks[3], (E, D, F)) * 0.1).astype(jnp.bfloat16)
    wu = (jax.random.normal(ks[4], (E, D, F)) * 0.1).astype(jnp.bfloat16)
    wd = (jax.random.normal(ks[5], (E, F, D)) * 0.1).astype(jnp.bfloat16)
    args = (x, ti, tw, wg, wu, wd)
    return lambda: jax.block_until_ready(f(*args)), np.asarray(ti)


def combine_formulations(n_tokens: int):
    """Old (T, K, D) gather + einsum combine vs the scatter-add combine on
    identical slot tables — the formulations dispatch_combine_ll swapped."""
    from repro.core.ep import _cap

    T = n_tokens
    C = _cap(T * K / E, 2.0, hard_max=T * K)
    rng = np.random.default_rng(1)
    ti = rng.integers(0, E, size=(T, K)).astype(np.int32)
    pl = planlib.make_plan(jnp.asarray(ti), E, C)
    flat_e = jnp.asarray(ti).reshape(-1)
    keep, rank = pl.keep.reshape(-1), pl.rank.reshape(-1)
    slot = planlib.flat_slots(flat_e, rank, keep, C, E)
    rows = jnp.arange(T * K, dtype=jnp.int32) // K
    src_of_slot = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        rows, mode="drop")[:-1]
    back = jax.random.normal(jax.random.PRNGKey(2), (E * C, D), jnp.bfloat16)
    tw = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(3), (T, K)), -1)

    @jax.jit
    def gather_einsum(back, tw):
        gathered = jnp.where(
            keep[:, None], back[jnp.where(keep, flat_e * C + rank, 0)],
            0).reshape(T, K, D)
        return jnp.einsum("tkd,tk->td", gathered.astype(jnp.float32),
                          tw.astype(jnp.float32))

    w_of_slot = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, tw.reshape(-1).astype(jnp.float32), 0.0),
        mode="drop")[:-1]

    @jax.jit
    def scatter_add(back, w_of_slot):
        return jnp.zeros((T + 1, D), jnp.float32).at[src_of_slot].add(
            back.astype(jnp.float32) * w_of_slot[:, None])[:-1]

    np.testing.assert_allclose(
        np.asarray(gather_einsum(back, tw), np.float32),
        np.asarray(scatter_add(back, w_of_slot), np.float32),
        rtol=1e-2, atol=1e-2)
    t_old = timeit(lambda: jax.block_until_ready(gather_einsum(back, tw)))
    t_new = timeit(lambda: jax.block_until_ready(
        scatter_add(back, w_of_slot)))
    return t_old, t_new


def main():
    mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
    for n in (2048, 8192):
        for mode in ("ll", "ht"):
            fns = {}
            for aware in (False, True):
                fn, ti = build(mesh, mode, n, occupancy_aware=aware)
                fns[aware] = (timeit(fn, warmup=2, iters=5), ti)
            f_dense, f_occ, f_rows, occ = occupancy_model(fns[True][1], 8,
                                                          2.0)
            unf_b, fus_b = fused_bytes_model(
                int(f_dense / (6 * D * F)), occ)
            derived = (f"flops_dense={f_dense},flops_occ={f_occ},"
                       f"flop_reduction={f_dense / max(f_occ, 1):.2f}x,"
                       f"row_flop_reduction={f_dense / max(f_rows, 1):.2f}x,"
                       f"occupancy={occ:.3f},"
                       f"hbm_unfused={unf_b},hbm_fused={fus_b}")
            emit(f"bench_kernels/{mode}/dense/tokens={n}", fns[False][0],
                 "legacy dense expert_fn")
            emit(f"bench_kernels/{mode}/occupancy/tokens={n}", fns[True][0],
                 derived)
    for n in (2048, 8192):
        t_old, t_new = combine_formulations(n)
        emit(f"bench_kernels/combine/gather_einsum/tokens={n}", t_old,
             "materialized (T,K,D) + einsum")
        emit(f"bench_kernels/combine/scatter_add/tokens={n}", t_new,
             f"segment scatter-add ({t_old / max(t_new, 1e-9):.2f}x vs "
             "gather_einsum)")


if __name__ == "__main__":
    main()
