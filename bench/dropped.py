"""The share of token-choices that a cell's EP layers drop at its traffic,
read once for PERF.md (no benchmark run reads it).

  python3 bench/dropped.py --workload moonlight-ep4.decode --seed 7

It sets up the cell as ``bench/run.py`` does, serves one round, and feeds
each of the round's positions (its prompts and served tokens, 64 x 1
tokens at a time, as a decode step does) through the program's forward
pass in LL mode on the cell's mesh, reading the forward's
``aux["dropped"]``: the mean over the layers of each EP layer's dropped
share.  The forward has no KV cache, so each position's hidden states lack
its context; the routing, capacity and exchange are the decode step's.
Prints one JSON line: the positions read, the largest and the mean share.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import cells, serve_loop, weights
    from repro.distributed.sharding import make_dist_ctx
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_bench_mesh
    from repro.models import model_zoo as Z

    enable_compile_cache()
    cell = cells.resolve(args.workload)
    cfg = cells.model_config(cell.config)
    _, model = cells.mesh_shape(cell.config, cell.chips)
    dist = make_dist_ctx(cfg, make_bench_mesh(cell.chips, model=model))
    params = weights.make_params(cfg, args.seed, dist, getattr(
        cells.reference(cell.config), "FAN_IN", None))
    server = serve_loop.Server(cfg, params, cell.traffic, args.seed, dist)
    tokens = server.round(0).tokens
    seqs = np.concatenate([np.asarray(server.prompts(0)), tokens[:, :-1]], 1)
    served = server.step.weights(params)

    @jax.jit
    def dropped(p, t):
        return Z.forward(cfg, p, t, dist=dist, moe_mode="ll")[1]["dropped"]

    shares = [float(dropped(served, jnp.asarray(seqs[:, t:t + 1])))
              for t in range(seqs.shape[1])]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "positions": len(shares), "max": max(shares),
                      "mean": sum(shares) / len(shares)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
