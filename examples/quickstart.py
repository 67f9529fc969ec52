"""Quickstart: UCCL-EP dispatch/combine on a local device mesh.

Runs the paper's two EP modes (LL one-shot, HT dedup+hierarchical) on an
8-device CPU mesh and checks both against the dense MoE oracle — the
60-second tour of the core API.

  python examples/quickstart.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.core.ep import (EPSpec, dispatch_combine_ht, dispatch_combine_ll,
                           moe_ref)
from repro.kernels.ref import grouped_swiglu_ref


def main():
    E, K, D, F, T = 16, 3, 64, 96, 128
    mesh = jax.make_mesh((2, 4), ("pod", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    key = jax.random.PRNGKey(0)
    kx, kw, ki, kg, ku, kd = jax.random.split(key, 6)
    x = jax.random.normal(kx, (T, D), jnp.float32)
    top_idx = jax.random.randint(ki, (T, K), 0, E).astype(jnp.int32)
    top_w = jax.nn.softmax(jax.random.normal(kw, (T, K)), axis=-1)
    wg = jax.random.normal(kg, (E, D, F)) * 0.1
    wu = jax.random.normal(ku, (E, D, F)) * 0.1
    wd = jax.random.normal(kd, (E, F, D)) * 0.1

    ref = moe_ref(x, top_idx, top_w, wg, wu, wd)

    for mode, fn in [("LL (one-shot, decode)", dispatch_combine_ll),
                     ("HT (dedup + hierarchical, train)", dispatch_combine_ht)]:
        spec = EPSpec(axes=("pod", "model"), sizes=(2, 4), n_experts=E,
                      top_k=K, capacity_factor=4.0,
                      chunks=2 if "HT" in mode else 1, dtype=jnp.float32)

        def island(x_l, ti, tw, g, u, d):
            r = fn(spec, x_l, ti, tw,
                   lambda t: grouped_swiglu_ref(t, g, u, d))
            return r.out, r.aux["dropped"]

        out, dropped = jax.jit(jax.shard_map(
            island, mesh=mesh,
            in_specs=(P(("pod", "model")), P(("pod", "model")),
                      P(("pod", "model")), P(("pod", "model"), None, None),
                      P(("pod", "model"), None, None),
                      P(("pod", "model"), None, None)),
            out_specs=(P(("pod", "model")), P()),
            check_vma=False))(x, top_idx, top_w, wg, wu, wd)
        err = float(jnp.abs(out - ref).max())
        print(f"{mode:36s} max|err| vs oracle = {err:.2e}  "
              f"dropped = {float(dropped):.3f}")
        assert err < 1e-4, "EP output diverged from the oracle"
    print("quickstart OK")


if __name__ == "__main__":
    main()
