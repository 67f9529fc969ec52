"""Paper Fig. 16: sensitivity to EP degree (2/4/8) for LL and HT dispatch +
combine on CPU-device meshes, plus the skew sweep (--skew): Zipf-skewed
routing at EP=8 with and without replicated expert placement, measured on
the transport substrate's deterministic event clock.

The skew section is the acceptance measurement for the replicated-experts
PR: per-token dispatch+combine completion times come from the simulated
network's event clock (return-region write delivery times), so the p50/p99
columns are exact deterministic counters — gated at exact equality under
``fig16_ep_sweep/skew_clock/`` — while wall-clock rows stay under the
normal 1.25x gate.  At alpha >= 1.0 the replicated placement must improve
p99 completion by >= 1.3x (asserted here, same-session).

Run via benchmarks.run (8 devices); the skew section itself is host-side
numpy and needs no devices:

  PYTHONPATH=src python -m benchmarks.fig16_ep_sweep --skew 0.0,1.0,1.5
"""
import argparse

import numpy as np

from benchmarks.common import emit, timeit

# skew-sweep problem: EP=8 ranks, 32 logical experts, payloads big enough
# (1KB/token) that the hot rank's ingest links dominate completion time
R, E, K, D, F, TL = 8, 32, 2, 256, 64, 128
REPL_FACTOR = 2                     # 2x physical slots for the balancer
P99_GATE_ALPHA = 1.0                # assert the win at alpha >= this
P99_GATE_RATIO = 1.3


def _net_cfg():
    from repro.core.transport.simulator import NetConfig
    # slow-ish links so serialization (the thing replication fixes)
    # dominates the event clock, not the base latency
    return NetConfig(mode="rc", seed=0, base_latency_us=2.0,
                     bw_bytes_per_us=2500.0)


def _skew_problem(alpha: float):
    rng = np.random.default_rng(7)
    x = rng.standard_normal((R, TL, D)).astype(np.float32)
    p = (1.0 + np.arange(E)) ** -float(alpha)
    p /= p.sum()
    ti = rng.choice(E, size=(R, TL, K), p=p).astype(np.int32)
    tw = rng.random((R, TL, K)).astype(np.float32)
    tw /= tw.sum(-1, keepdims=True)
    wg = (rng.standard_normal((E, D, F)) * 0.1).astype(np.float32)
    wu = (rng.standard_normal((E, D, F)) * 0.1).astype(np.float32)
    wd = (rng.standard_normal((E, F, D)) * 0.1).astype(np.float32)
    return x, ti, tw, wg, wu, wd


def _run_world(tis, x, tw, wg, wu, wd, n_experts):
    from repro.core.transport.ep_executor import EPWorld
    w = EPWorld(n_ranks=R, n_experts=n_experts, top_k=K, d=D, f=F,
                capacity=TL * K, net_cfg=_net_cfg())
    w.run(x, tis, tw, wg, wu, wd)
    comp = w.timeline["token_completion_us"].reshape(-1)
    return (float(np.percentile(comp, 50)), float(np.percentile(comp, 99)),
            w)


def run_skew_point(alpha: float) -> dict:
    """One skew point: single placement vs online-rebalanced replicated
    placement, both on the event clock.  Returns the stats dict the CI
    smoke and the emit loop consume."""
    from repro.core import plan as planlib
    from repro.distributed.elastic import LoadBalancer, migrate_expert_weights

    x, ti, tw, wg, wu, wd = _skew_problem(alpha)
    load = planlib.group_counts(ti.reshape(-1), E, ti.reshape(-1) >= 0)

    # --- round 1: single placement (one slot per logical expert) ---------
    p50_s, p99_s, _ = _run_world(ti, x, tw, wg, wu, wd, E)
    imb_s = float(planlib.load_imbalance(load))

    # --- online re-placement: observe the round's load, greedily re-place
    # over 2x physical slots, migrate weights through the substrate --------
    lb = LoadBalancer(n_logical=E, n_ranks=R,
                      slots_per_rank=REPL_FACTOR * E // R,
                      interval=1, threshold=1.0)
    lb.observe(load)
    new = lb.maybe_replace() or lb.placement
    eps0 = E // R
    holdings = [[r * eps0 + i for i in range(eps0)] for r in range(R)]
    rows = np.concatenate([wg.reshape(E, -1), wu.reshape(E, -1),
                           wd.reshape(E, -1)], 1).astype(np.float32)
    w_full = np.ascontiguousarray(rows).view(np.uint8).reshape(E, -1)
    tables, mig = migrate_expert_weights(holdings, new, w_full,
                                         net_cfg=_net_cfg())
    # the migrated rows ARE the physical weights round 2 runs on
    flat = tables.reshape(new.n_physical, -1).view(np.float32)
    n = D * F
    wg_p = flat[:, :n].reshape(-1, D, F).copy()
    wu_p = flat[:, n:2 * n].reshape(-1, D, F).copy()
    wd_p = flat[:, 2 * n:].reshape(-1, F, D).copy()

    # --- round 2: replicated placement, deterministic replica split ------
    tis = planlib.split_to_physical_world(new, ti)
    p50_r, p99_r, w2 = _run_world(tis, x, tw, wg_p, wu_p, wd_p,
                                  new.n_physical)
    load_p = planlib.group_counts(tis.reshape(-1), new.n_physical,
                                  tis.reshape(-1) >= 0)
    imb_r = float(planlib.load_imbalance(load_p))
    return {"alpha": alpha, "p50_single": p50_s, "p99_single": p99_s,
            "p50_repl": p50_r, "p99_repl": p99_r,
            "imb_single": imb_s, "imb_repl": imb_r,
            "migrate_us": mig.clock_us, "migrate_bytes": mig.bytes_moved,
            "p99_ratio": p99_s / p99_r}


def skew_sweep(alphas):
    for alpha in alphas:
        s = run_skew_point(alpha)
        tag = f"alpha={alpha:g}"
        # wall rows (1.25x gate): full A/B cost incl. migration
        emit(f"fig16_ep_sweep/skew/ll/{tag}/single", s["p99_single"],
             f"imbalance={s['imb_single']:.2f} p50={s['p50_single']:.1f}")
        emit(f"fig16_ep_sweep/skew/ll/{tag}/replicated", s["p99_repl"],
             f"imbalance={s['imb_repl']:.2f} p50={s['p50_repl']:.1f} "
             f"migrate_us={s['migrate_us']:.1f} "
             f"p99_ratio={s['p99_ratio']:.2f}")
        # exact rows: deterministic event-clock percentiles (seeded network,
        # seeded routing — any drift is a transport behaviour change)
        emit(f"fig16_ep_sweep/skew_clock/ll/{tag}/single_p50",
             s["p50_single"])
        emit(f"fig16_ep_sweep/skew_clock/ll/{tag}/single_p99",
             s["p99_single"])
        emit(f"fig16_ep_sweep/skew_clock/ll/{tag}/replicated_p50",
             s["p50_repl"])
        emit(f"fig16_ep_sweep/skew_clock/ll/{tag}/replicated_p99",
             s["p99_repl"])
        if alpha >= P99_GATE_ALPHA:
            assert s["p99_ratio"] >= P99_GATE_RATIO, (
                f"replicated placement p99 win {s['p99_ratio']:.2f}x < "
                f"{P99_GATE_RATIO}x at alpha={alpha}")


def ep_degree_sweep():
    import jax
    from jax.sharding import AxisType

    from benchmarks.fig08_dispatch_combine import build

    for ep in (2, 4, 8):
        mesh = jax.make_mesh((ep,), ("model",), axis_types=(AxisType.Auto,))
        for mode in ("ll", "ht"):
            fn = build(mesh, ("model",), mode, 2048,
                       chunks=2 if mode == "ht" else 1)
            us = timeit(fn, warmup=2, iters=5)
            emit(f"fig16_ep_sweep/{mode}/ep={ep}", us, "tokens=2048")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skew", default="0.0,1.0,1.5",
                    help="comma-separated Zipf alphas for the skew sweep "
                         "('' disables)")
    ap.add_argument("--no-degree", action="store_true",
                    help="skip the EP-degree sweep (skew section only)")
    args = ap.parse_args()
    if not args.no_degree:
        ep_degree_sweep()
    if args.skew:
        skew_sweep([float(a) for a in args.skew.split(",")])


if __name__ == "__main__":
    main()
