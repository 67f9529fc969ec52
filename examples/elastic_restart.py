"""Elastic EP demo (paper §6 made concrete): train on an 8-device mesh,
checkpoint, "lose" half the nodes, re-mesh to 4 devices, restore, and keep
training — loss continues from where it left off.

  python examples/elastic_restart.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import tempfile

import jax

from repro.checkpoint import Checkpointer
from repro.configs import get_config, reduced_config
from repro.data.pipeline import DataConfig, data_iterator
from repro.distributed.elastic import plan_remesh, reshard_state
from repro.distributed.sharding import make_dist_ctx
from repro.launch.mesh import make_bench_mesh
from repro.training.train_loop import HParams, init_state, train_loop


def main():
    cfg = reduced_config(get_config("moonshot_v1_16b_a3b"), n_layers=2,
                         d_model=128, n_experts=8, vocab=1024)
    hp = HParams(peak_lr=1e-3, total_steps=120, warmup=10, moe_mode="ht",
                 loss_chunk=64)
    dc = DataConfig(vocab_size=cfg.vocab_size, batch=8, seq_len=64, seed=0)

    mesh8 = make_bench_mesh(8, model=4)          # (data=2, model=4)
    dist8 = make_dist_ctx(cfg, mesh8)
    with tempfile.TemporaryDirectory() as td:
        ckpt = Checkpointer(td)
        print("[elastic] phase 1: 8 devices", dict(zip(
            mesh8.axis_names, mesh8.devices.shape)))
        state, hist1 = train_loop(cfg, hp, dist8, data_iterator(dc), steps=60,
                                  checkpointer=ckpt, ckpt_every=30,
                                  log_every=20)
        ckpt.save(state, 60)

        # "node failure": only 4 devices remain -> re-mesh (data=2, model=2)
        mesh4 = jax.make_mesh((2, 2), ("data", "model"),
                              axis_types=(jax.sharding.AxisType.Auto,) * 2,
                              devices=jax.devices()[:4])
        plan = plan_remesh(cfg, dist8, mesh4)
        print(f"[elastic] re-mesh {plan.old_shape} -> {plan.new_shape}; "
              f"EP {plan.ep_degree_old} -> {plan.ep_degree_new}; {plan.notes}")
        restored, _ = ckpt.restore_latest(init_state(cfg, jax.random.PRNGKey(0)))
        state4, dist4 = reshard_state(cfg, restored, mesh4)
        state4, hist2 = train_loop(cfg, hp, dist4,
                                   data_iterator(dc, start_step=60),
                                   steps=120, state=state4, log_every=20)
    l0, l1, l2 = hist1[0]["loss"], hist1[-1]["loss"], hist2[-1]["loss"]
    print(f"[elastic] loss: start={l0:.4f} before-failure={l1:.4f} "
          f"after-remesh-end={l2:.4f}")
    assert l2 <= l1 + 0.2, "training regressed after elastic re-mesh"
    print("[elastic] OK: training continued across the re-mesh")


if __name__ == "__main__":
    main()
