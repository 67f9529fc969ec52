"""Compile a cell's serving programs for a described TPU v5e, without the
chip, and print what ``memory_analysis()`` says of each, per chip.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py moonlight.decode [more cells]

The programs are the ones the harness serves with:
``repro.launch.serve.compile_steps``' ``jit_serve_weights`` (the f32
masters cast once to the served bf16 copy), ``jit_decode_step`` and
``jit_prefill`` (none on a model-axis mesh, where the prompt goes through
the step), lowered from shapes alone, with the Pallas kernels the chip
would run.  A cell on one chip compiles for one device of a described
``v5e:2x2``; a configuration with a ``mesh`` compiles for that mesh over
the topology's devices, with the weights placed by ``param_shardings`` and
the KV cache by ``cache_pspecs``.  Nothing runs, so nothing here is a time.
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


def _prompts(shape, sharding):
    """Shapes of a prompt batch that ``compile_steps`` can slice (it lowers
    the step on ``prompts[:, :1]``)."""
    import jax
    import jax.numpy as jnp

    class Prompts(jax.ShapeDtypeStruct):
        def __getitem__(self, index):
            import numpy as np

            return jax.ShapeDtypeStruct(
                np.empty(self.shape, np.int8)[index].shape, self.dtype,
                sharding=self.sharding)

    return Prompts(shape, jnp.int32, sharding=sharding)


def rehearse(name: str, topo) -> dict:
    """The cell's compiled programs by name, for the described devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from bench import cells
    from repro.distributed.sharding import (cache_pspecs, make_dist_ctx,
                                            param_shardings)
    from repro.launch import serve
    from repro.models import model_zoo as Z

    cell = cells.resolve(name)
    cfg = cells.model_config(cell.config)
    t = cell.traffic
    B, S, max_len = t["batch"], t["prompt"], t["prompt"] + t["gen"]
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(partial(Z.init_params, cfg), key)
    cache = jax.eval_shape(partial(Z.init_cache, cfg, B, max_len,
                                   jnp.dtype(cfg.dtype)))
    shape = cells.mesh_shape(cell.config, cell.chips)
    dist = None
    if shape is None:
        one = SingleDeviceSharding(topo.devices[0])
        p_sh = jax.tree.map(lambda _: one, params)
        c_sh = jax.tree.map(lambda _: one, cache)
        t_sh = one
    else:
        mesh = Mesh(np.array(topo.devices[:cell.chips]).reshape(shape),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        dist = make_dist_ctx(cfg, mesh)
        p_sh = param_shardings(cfg, dist, params)
        c_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            cache_pspecs(cfg, dist, cache, B))
        t_sh = NamedSharding(mesh, PartitionSpec())

    def sds(shapes, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), shapes, shardings)

    prefill, step = serve.compile_steps(cfg, dist, sds(params, p_sh),
                                        sds(cache, c_sh),
                                        _prompts((B, S), t_sh))
    out = {"serve_weights": step.weights.cast, "decode_step": step.compiled}
    if prefill is not None:
        out["prefill"] = prefill.compiled
    return out


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
            text = c.as_text()
            print(f"{name} {prog}: per chip arguments "
                  f"{ma.argument_size_in_bytes / GB:.3f} GB, temporaries "
                  f"{ma.temp_size_in_bytes / GB:.3f} GB, outputs "
                  f"{ma.output_size_in_bytes / GB:.3f} GB, aliased "
                  f"{ma.alias_size_in_bytes / GB:.3f} GB; in all "
                  f"{total / GB:.3f} GB; pallas calls "
                  f"{text.count('tpu_custom_call')}, all-to-alls "
                  f"{text.count(' all-to-all(')}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
