"""Paper Figs. 8/9/10/12 + Fig. 4: dispatch+combine latency vs #tokens for
LL / HT / nccl_bulk baselines on an 8-device CPU mesh (EP8), plus modeled
bytes-on-wire (derived column) showing dedup + hierarchical-reduce savings.

Run via ``python -m benchmarks.run`` (it spawns this with 8 devices).
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from benchmarks.common import emit, timeit
from benchmarks.ep_baselines import moe_nccl_bulk
from repro.core.ep import EPSpec, dispatch_combine_ht, dispatch_combine_ll
from repro.kernels.ref import grouped_swiglu_ref

E, K, D, F = 32, 6, 256, 128


def build(mesh, axes, mode, n_tokens_global, chunks=1, wire_dtype="fp32"):
    sizes = tuple(mesh.shape[a] for a in axes)
    spec = EPSpec(axes=axes, sizes=sizes, n_experts=E, top_k=K,
                  capacity_factor=2.0, chunks=chunks, dtype=jnp.bfloat16,
                  wire_dtype=wire_dtype)
    ep_p = axes if len(axes) > 1 else axes[0]

    def island(x, ti, tw, wg, wu, wd, with_aux):
        fn = {"ll": dispatch_combine_ll, "ht": dispatch_combine_ht}.get(mode)
        if fn is None:
            out = moe_nccl_bulk(spec, x, ti, tw, wg, wu, wd)
            return (out, jnp.float32(0.0), jnp.float32(1.0)) if with_aux \
                else out
        # occupancy-carrying expert_fn contract; the jnp ref needs no mask
        # (EP buffers pad with exact zeros), the kernel paths skip the rows
        r = fn(spec, x, ti, tw,
               lambda t, c=None: grouped_swiglu_ref(t, wg, wu, wd))
        if not with_aux:
            return r.out
        ax = axes if len(axes) > 1 else axes[0]
        return (r.out, jax.lax.pmean(r.aux["dropped"], ax),
                jax.lax.pmean(jnp.float32(r.aux["occupancy"]), ax))

    in_specs = (P(axes), P(axes), P(axes), P(ep_p, None, None),
                P(ep_p, None, None), P(ep_p, None, None))
    # the timed function returns only `out` (the aux pmean collectives are
    # dead-code-eliminated, keeping the timing comparable across PRs); the
    # aux scalars for the derived column come from one separate call
    f = jax.jit(jax.shard_map(
        partial(island, with_aux=False), mesh=mesh, in_specs=in_specs,
        out_specs=P(axes), check_vma=False))
    f_aux = jax.jit(jax.shard_map(
        partial(island, with_aux=True), mesh=mesh, in_specs=in_specs,
        out_specs=(P(axes), P(), P()), check_vma=False))
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (n_tokens_global, D), jnp.bfloat16)
    ti = jax.random.randint(ks[1], (n_tokens_global, K), 0, E).astype(jnp.int32)
    tw = jax.nn.softmax(jax.random.normal(ks[2], (n_tokens_global, K)), -1)
    tw = tw.astype(jnp.bfloat16)
    wg = (jax.random.normal(ks[3], (E, D, F)) * 0.1).astype(jnp.bfloat16)
    wu = (jax.random.normal(ks[4], (E, D, F)) * 0.1).astype(jnp.bfloat16)
    wd = (jax.random.normal(ks[5], (E, F, D)) * 0.1).astype(jnp.bfloat16)
    args = (x, ti, tw, wg, wu, wd)

    def run():
        jax.block_until_ready(f(*args))

    def aux():
        _, dropped, occ = f_aux(*args)
        return float(dropped), float(occ)
    run.aux = aux
    return run


def wire_bytes_model(n_tokens, mode, P_ep=8, pods=2, wire_dtype="fp32"):
    """Modeled inter-shard payload bytes (dispatch+combine), global.

    Compressed wire dtypes shrink the *dispatch* leg to the wire-row size
    (quantized bytes + inline fp32 scales); the combine leg stays full
    precision (the fp32-accumulation contract, DESIGN.md §14)."""
    from repro.core.plan import wire_layout
    tok = D * 2
    disp = tok if wire_dtype == "fp32" else wire_layout(D, wire_dtype).token_bytes
    if mode == "nccl":
        return n_tokens * tok * (P_ep - 1) * 2          # all-gather + psum
    if mode == "ll":
        return n_tokens * K * (disp + tok)              # per choice, both ways
    # ht: dedup per shard group + one combined return per (token, group)
    frac = 1.0 - (1.0 - 1.0 / P_ep) ** K
    groups_hit = P_ep * frac
    return int(n_tokens * groups_hit * (disp + tok))


def main():
    mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
    for n in (128, 512, 2048, 8192):
        for mode in ("ll", "ht", "nccl"):
            try:
                fn = build(mesh, ("model",), mode, n,
                           chunks=2 if mode == "ht" and n >= 512 else 1)
                us = timeit(fn, warmup=2, iters=5)
                dropped, occ = fn.aux()
            except Exception as e:  # noqa: BLE001
                emit(f"fig08_dispatch_combine/{mode}/tokens={n}", float("nan"),
                     f"error:{type(e).__name__}")
                continue
            wb = wire_bytes_model(n, mode)
            emit(f"fig08_dispatch_combine/{mode}/tokens={n}", us,
                 f"wire_bytes={wb},occupancy={occ:.3f},dropped={dropped:.4f}")
    # compression columns: fp8/int8 wire dispatch on the LL path (the
    # decode-latency regime compression targets); derived shows the modeled
    # payload reduction vs the fp32 row alongside the measured time
    for n in (512, 2048):
        wb32 = wire_bytes_model(n, "ll")
        for wdt in ("fp8", "int8"):
            try:
                fn = build(mesh, ("model",), "ll", n, wire_dtype=wdt)
                us = timeit(fn, warmup=2, iters=5)
                dropped, occ = fn.aux()
            except Exception as e:  # noqa: BLE001
                emit(f"fig08_dispatch_combine/ll_{wdt}/tokens={n}",
                     float("nan"), f"error:{type(e).__name__}")
                continue
            wb = wire_bytes_model(n, "ll", wire_dtype=wdt)
            emit(f"fig08_dispatch_combine/ll_{wdt}/tokens={n}", us,
                 f"wire_bytes={wb},payload_reduction={wb32 / wb:.2f}x,"
                 f"occupancy={occ:.3f},dropped={dropped:.4f}")
    # two-level (pod x model) HT: the hierarchical/dedup path (Fig. 12 analog)
    mesh2 = jax.make_mesh((2, 4), ("pod", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    for n in (512, 2048):
        fn = build(mesh2, ("pod", "model"), "ht", n, chunks=2)
        us = timeit(fn, warmup=2, iters=5)
        dropped, occ = fn.aux()
        emit(f"fig08_dispatch_combine/ht2level/tokens={n}", us,
             f"hierarchical+dedup,occupancy={occ:.3f},dropped={dropped:.4f}")


if __name__ == "__main__":
    main()
