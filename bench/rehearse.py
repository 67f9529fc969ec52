"""Compile a cell's serving programs for a described TPU v5e, without the
chip, and print what ``memory_analysis()`` says of each.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py moonlight.decode [more cells]

Each cell compiles for one device of a described ``v5e:2x2``.  The
programs are the ones ``repro.launch.serve.compile_steps`` builds on one
chip (``model_zoo.decode_step`` in LL mode, ``model_zoo.prefill`` in HT
mode), lowered from shapes alone, with the Pallas kernels the chip would
run.
Nothing runs, so nothing here is a time.
"""
from __future__ import annotations

import os
import sys
from functools import partial
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = Path(__file__).resolve().parents[1]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

GB = 1e9


def rehearse(name: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench import cells
    from repro.models import model_zoo as Z

    cell = cells.resolve(name)
    cfg = cells.model_config(cell.config)
    t = cell.traffic
    B, S, max_len = t["batch"], t["prompt"], t["prompt"] + t["gen"]
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shapes):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), shapes)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = sds(jax.eval_shape(partial(Z.init_params, cfg), key))
    cache = sds(jax.eval_shape(partial(Z.init_cache, cfg, B, max_len,
                                       jnp.dtype(cfg.dtype))))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one)
    prompts = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one)
    step = jax.jit(partial(Z.decode_step, cfg, moe_mode="ll"),
                   donate_argnums=(1,))
    pre = jax.jit(partial(Z.prefill, cfg, moe_mode="ht"),
                  donate_argnums=(1,))
    return {"decode_step": step.lower(params, cache, tok,
                                      jnp.int32(0)).compile(),
            "prefill": pre.lower(params, cache, prompts).compile()}


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies

    from repro.kernels import ops as kops

    names = (argv if argv is not None else sys.argv[1:])
    if not names:
        from bench import cells
        names = [w["name"] for w in cells.benchmark()["workloads"]]
    jax.config.update("jax_enable_compilation_cache", False)
    kops.platform_mode = lambda: "pallas"     # the kernels the chip runs
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        for prog, c in rehearse(name, topo).items():
            ma = c.memory_analysis()
            total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            print(f"{name} {prog}: arguments "
                  f"{ma.argument_size_in_bytes / GB:.3f} GB, temporaries "
                  f"{ma.temp_size_in_bytes / GB:.3f} GB, outputs "
                  f"{ma.output_size_in_bytes / GB:.3f} GB, aliased "
                  f"{ma.alias_size_in_bytes / GB:.3f} GB; per device "
                  f"{total / GB:.3f} GB; pallas calls "
                  f"{c.as_text().count('tpu_custom_call')}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
