"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch moonshot_v1_16b_a3b \
      --steps 200 --batch 8 --seq 256 --reduced --ckpt-dir /tmp/ckpt

``--reduced`` runs the family-faithful small config on local devices (the
CPU path used by examples/CI); the full config targets the production mesh.
As in ``launch/serve.py``, ``--layers``/``--vocab`` without ``--reduced``
cut only the depth and the vocabulary of the published config.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth cut (default: published, or 2 if --reduced)")
    ap.add_argument("--d-model", type=int, default=128,
                    help="width of the --reduced config")
    ap.add_argument("--vocab", type=int, default=None,
                    help="vocabulary cut (default: published, or 512 if "
                         "--reduced)")
    ap.add_argument("--moe-mode", default="ht", choices=["ht", "ll", "ref"])
    ap.add_argument("--moe-chunks", type=int, default=1)
    ap.add_argument("--ep-backend", default="",
                    help="EP transport backend (e.g. jax_collectives, "
                         "simulated_rdma); default: the config's choice")
    ap.add_argument("--wire-dtype", default="",
                    choices=["", "fp32", "fp8", "int8"],
                    help="dispatch wire payload dtype (DESIGN §14)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="none",
                    choices=["none", "local", "single", "multi"])
    ap.add_argument("--local-model-axis", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fail-at", default="",
                    help="comma-separated steps to inject failures (demo)")
    ap.add_argument("--history-out", default="")
    args = ap.parse_args(argv)

    import jax
    from repro.checkpoint import Checkpointer
    from repro.data.pipeline import DataConfig, data_iterator
    from repro.distributed.fault import FailureInjector
    from repro.distributed.sharding import make_dist_ctx
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_bench_mesh, make_production_mesh
    from repro.launch.serve import build_config
    from repro.training.train_loop import HParams, Watchdog, train_loop

    enable_compile_cache()
    cfg = build_config(args.arch, reduced=args.reduced, layers=args.layers,
                       d_model=args.d_model, vocab=args.vocab,
                       ep_backend=args.ep_backend,
                       wire_dtype=args.wire_dtype)
    dist = None
    if args.mesh == "local":
        mesh = make_bench_mesh(len(jax.devices()), model=args.local_model_axis)
        dist = make_dist_ctx(cfg, mesh)
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        dist = make_dist_ctx(cfg, mesh)

    hp = HParams(peak_lr=args.lr, total_steps=args.steps,
                 warmup=max(1, args.steps // 10), moe_mode=args.moe_mode,
                 moe_chunks=args.moe_chunks, seed=args.seed)
    dc = DataConfig(vocab_size=cfg.vocab_size, batch=args.batch,
                    seq_len=args.seq, seed=args.seed,
                    prefix_len=cfg.frontend_prefix, d_model=cfg.d_model)
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    injector = None
    if args.fail_at:
        injector = FailureInjector(tuple(int(s) for s in
                                         args.fail_at.split(",")))
    state, history = train_loop(
        cfg, hp, dist, data_iterator(dc), steps=args.steps,
        checkpointer=ckpt, ckpt_every=args.ckpt_every,
        log_every=args.log_every, watchdog=Watchdog(),
        fail_injector=injector)
    if args.history_out:
        Path(args.history_out).write_text(json.dumps(history))
    first = history[0]["loss"] if history else float("nan")
    last = history[-1]["loss"] if history else float("nan")
    print(f"[train] finished: loss {first:.4f} -> {last:.4f} "
          f"over {len(history)} steps")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
