#!/usr/bin/env bash
# CI entrypoint: install dev deps (best-effort in hermetic envs), run the
# tier-1 suite exactly as ROADMAP.md specifies, then a benchmark smoke step
# (fig15 + JSON schema validation) so benchmark bit-rot fails fast.
set -euo pipefail
cd "$(dirname "$0")/.."

# Dev extras (pytest, hypothesis).  Offline/hermetic containers already bake
# in what they allow; a failed install must not fail CI — the conftest shim
# skips property tests when hypothesis is absent.
python -m pip install -e '.[dev]' 2>/dev/null \
    || echo "ci.sh: pip install skipped (offline env); running with baked-in deps"

# Repo lint (repro.analysis.lint, DESIGN.md §17): no magic bit masks
# outside wire_format.py, no constant division in quantization-scale math,
# no bare protocol asserts in the transport, occupancy kernels gated with
# pl.when.  Fails fast before the test suite.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.analysis.lint src/repro

# Tier-1 suite (includes the transport-semantics conformance fuzz harness,
# tests/test_transport_fuzz.py).  The default run is bounded: the slowest
# arch/kernel sweeps sit behind `-m slow` (pyproject addopts deselects
# them; run `scripts/ci.sh -m ''` for the full matrix), every test carries
# a wall-clock timeout (conftest, REPRO_TEST_TIMEOUT_S) so a hung transport
# quiesce fails fast, and --durations keeps the slowest-test list visible
# so the bound doesn't silently erode.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q \
    --durations=20 "$@"

# Interpret-mode step: the Pallas kernel bodies (not just the jnp refs) at
# every shape, the slow-marked ones included.  Each test passes the mode
# as an argument (mode="interpret" / interpret=True); without one the
# kernel mode follows the platform (Pallas on TPU, refs elsewhere).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -x -q -m "" tests/test_kernel_modes.py tests/test_kernels.py

# Static-analysis gate (DESIGN.md §17): the protocol verifier over
# fig08-shaped one-shot plans and the fig14-shaped persistent-session slot
# layout (zero findings on everything the generators emit), plus the
# Eraser-style race detector — zero findings on the shipped threaded path,
# while a seeded lock-removal mutant IS flagged (detector liveness).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import numpy as np
import threading

from repro.analysis import verify
from repro.analysis.racecheck import RaceChecker
from repro.analysis.verify import verify_session_slots
from repro.core.plan import wire_layout
from repro.core.transport import EPWorld, NetConfig
from repro.core.transport.ep_executor import build_command_streams
from repro.core.transport.fifo import FifoChannel, pack_cmds

# fig08-shaped one-shot LL plans (EP degree 4, 64 experts, dispatch +
# combine) across {fp32, fp8} x {rc, srd}: zero findings
rng = np.random.default_rng(0)
R, eps, Tl, K, D = 4, 16, 32, 4, 32
E = R * eps
cap = Tl * K
ti = rng.integers(0, E, size=(R, Tl, K)).astype(np.int32)
for wdt in ("fp32", "fp8"):
    wb = wire_layout(D, wdt).token_bytes
    recv0 = Tl * wb
    cs = build_command_streams(ti, E, eps, cap, 4 * D, 8, 0, recv0,
                               recv0 + R * eps * cap * wb, wire_bytes=wb)
    for mode in ("rc", "srd"):
        fs = verify(cs, net_cfg=NetConfig(mode=mode, seed=0), n_channels=8)
        assert fs == [], [str(f) for f in fs]

# fig14-shaped persistent session (mirrored, L=2): the slot layout passes
# the namespace-disjointness rules (EPV-009); verify_or_raise is also live
# inside _session_layout and on every per-layer stream build
from benchmarks.fig14_training import _make_session, _step_problem
xs, tis, tws, wg, wu, wd, occ = _step_problem(4, 2)
ws = _make_session(4, 2)
ws.run_step_serial(xs, tis, tws, wg, wu, wd)
fs = verify_session_slots(ws._slots, n_channels=ws.n_channels,
                          counter_stride=ws._counter_stride)
assert fs == [], [str(f) for f in fs]

# race gate 1: the shipped threaded path runs with ZERO candidate races
rng = np.random.default_rng(1)
R2, eps2, K2, D2, Tl2 = 2, 2, 2, 8, 4
E2 = R2 * eps2
x = rng.standard_normal((R2, Tl2, D2)).astype(np.float32)
ti2 = rng.integers(0, E2, size=(R2, Tl2, K2)).astype(np.int32)
tw2 = np.full((R2, Tl2, K2), 1.0 / K2, np.float32)
wgs = (rng.standard_normal((E2, D2, 8)) * 0.2).astype(np.float32)
wus = (rng.standard_normal((E2, D2, 8)) * 0.2).astype(np.float32)
wds = (rng.standard_normal((E2, 8, D2)) * 0.2).astype(np.float32)
with RaceChecker() as rc:
    w = EPWorld(n_ranks=R2, n_experts=E2, top_k=K2, d=D2, f=8,
                capacity=Tl2 * K2, net_cfg=NetConfig(mode="srd", seed=0),
                use_threads=True, n_threads=2)
    try:
        w.run(x, ti2, tw2, wgs, wus, wds)
    finally:
        for p in w.proxies:
            p.stop()
assert rc.findings() == [], [str(f) for f in rc.findings()]

# race gate 2: a lock-removal mutant on the SPSC ring IS flagged
with RaceChecker() as rc:
    ch = FifoChannel(16)
    rc.instrument(ch, strip_locks=True)
    words = pack_cmds(1, np.zeros(100, np.int64), 0, np.arange(100),
                      np.arange(100), 8, 0)
    got = []

    def consumer():
        while len(got) < 100:
            out = ch.pop_all()
            if out is None:
                ch.wait_nonempty(0.01)
            else:
                got.extend(out.tolist())

    t = threading.Thread(target=consumer)
    t.start()
    done = 0
    while done < 100:
        done += ch.try_push_batch(words[done:done + 7])
    t.join(timeout=10)
assert any(f.rule == "RACE-LOCKSET" for f in rc.findings()), \
    "race detector failed to flag the seeded lock-removal mutant"
print("ci.sh: static-analysis gate OK (verifier clean on fig08/fig14 "
      "plans, race detector clean on shipped path, mutant flagged)")
EOF

# Compressed-dispatch smoke: the quantize-pack kernel body (interpret mode)
# stays bit-identical to the numpy codec, and an fp8 LL run on the
# substrate hits the honest-accounting floor (>=3.5x payload reduction at
# D=1024 with the event clock improving) — the same invariants the
# exact-gated bench_transport/counters/compression rows pin.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import numpy as np
from benchmarks.bench_transport import bench_compression
from repro.kernels import ops as kops
from repro.kernels.quantize_pack import gather_quantize_ref
import jax.numpy as jnp

x_ext = np.concatenate([np.random.default_rng(0).standard_normal(
    (9, 200)).astype(np.float32), np.zeros((1, 200), np.float32)])
src = np.random.default_rng(1).integers(0, 9, 16).astype(np.int32)
for wdt in ("fp8", "int8"):
    qr, sr = gather_quantize_ref(x_ext, src, wire_dtype=wdt)
    qi, si = kops.gather_quantize(jnp.asarray(x_ext), jnp.asarray(src),
                                  wire_dtype=wdt, mode="interpret")
    assert (np.ascontiguousarray(qr).view(np.uint8) ==
            np.ascontiguousarray(np.asarray(qi)).view(np.uint8)).all(), wdt
    assert (sr == np.asarray(si)).all(), wdt
worlds = bench_compression()
p32 = worlds["fp32"].timeline["dispatch_payload_bytes"]
pq = worlds["fp8"].timeline["dispatch_payload_bytes"]
assert p32 / pq >= 3.5 and worlds["fp8"].net.clock_us < worlds["fp32"].net.clock_us
print(f"ci.sh: compressed-dispatch smoke OK ({p32 / pq:.2f}x payload reduction)")
EOF

# Replicated-experts smoke: one Zipf skew point end-to-end (single vs
# online-rebalanced replicated placement, weight migration over the
# substrate included) must hold the p99 event-clock win the exact-gated
# fig16_ep_sweep/skew_clock rows pin, plus a fast replication fuzz point
# (skewed routing x replicas x {rc, srd} against the logical oracle).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import numpy as np
from benchmarks.fig16_ep_sweep import P99_GATE_RATIO, run_skew_point
from repro.core import plan as planlib
from repro.core.transport.ep_executor import EPWorld
from repro.core.transport.simulator import NetConfig

s = run_skew_point(1.0)
assert s["p99_ratio"] >= P99_GATE_RATIO, s

# replication fuzz point: skewed routing x replicas {1, 2} x {rc, srd},
# physical world vs the LOGICAL dense oracle (pytest runs the full Part 5)
rng = np.random.default_rng(0)
R, E, K, D, F, Tl = 2, 8, 2, 8, 8, 8
x = rng.standard_normal((R, Tl, D)).astype(np.float32)
p = (1.0 + np.arange(E)) ** -1.2
ti = rng.choice(E, size=(R, Tl, K), p=p / p.sum()).astype(np.int32)
tw = rng.random((R, Tl, K)).astype(np.float32)
tw /= tw.sum(-1, keepdims=True)
wg, wu, wd = ((rng.standard_normal(sh) * 0.2).astype(np.float32)
              for sh in ((E, D, F), (E, D, F), (E, F, D)))
ref = EPWorld.oracle(x, ti, tw, wg, wu, wd)
loads = planlib.group_counts(ti.reshape(-1), E, ti.reshape(-1) >= 0)
for mode in ("rc", "srd"):
    for factor in (1, 2):
        pl = (planlib.identity_placement(E) if factor == 1
              else planlib.greedy_placement(loads, E * factor, R))
        tis = planlib.split_to_physical_world(pl, ti)
        p2l = np.asarray(pl.phys_to_logical)
        w = EPWorld(n_ranks=R, n_experts=pl.n_physical, top_k=K, d=D, f=F,
                    capacity=Tl * K, net_cfg=NetConfig(mode=mode, seed=0))
        out = w.run(x, tis, tw, wg[p2l], wu[p2l], wd[p2l])
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
        assert not w.net.pending and not any(pr.busy for pr in w.proxies)
print(f"ci.sh: replicated-experts smoke OK "
      f"(alpha=1.0 p99 win {s['p99_ratio']:.2f}x, "
      f"migrate {s['migrate_bytes']} bytes in {s['migrate_us']:.0f}us)")
EOF

# Training-step pipeline smoke (bounded fig14 point): the persistent-session
# serial-vs-pipelined A/B at EP=8, L=2 must keep bit-identical outputs, the
# exact L->1 drain collapse (drains_per_step: 2L -> 1), and a >=1.2x
# event-clock win — the invariants the exact-gated fig14_training/counters
# rows pin at the full flagship sweep.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
from benchmarks.fig14_training import run_substrate_point
s = run_substrate_point(8, 2)
assert s["drains_batched"] == 1 and s["drains_serial"] == 4, s
assert s["speedup"] >= 1.2, s
print(f"ci.sh: training-pipeline smoke OK (EP=8 L=2 "
      f"{s['speedup']:.2f}x, drains {s['drains_serial']} -> 1)")
EOF

# Serving smoke (DESIGN.md §18): a short Poisson run through the
# continuous-batching engine on the event clock — every request completes,
# the run is bit-deterministic (exact counters), the persistent session
# quiesces clean after the last microbatch, and the PR 9 verifier (already
# live on every microbatch's stream builds) re-checks the session slot
# layout with zero findings.  The naive per-layer path must cost more
# event-clock time on the same schedule.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
from repro.analysis.verify import verify_session_slots
from repro.serving import EngineConfig, ServingEngine, poisson_arrivals

def run(step_mode):
    cfg = EngineConfig(n_layers=2, n_experts=8, top_k=2, d_model=16,
                       d_ff=32, ep_degree=4, token_budget=16,
                       prefill_chunk=8, block_size=8, n_blocks=64,
                       step_mode=step_mode, nonmoe_us=10.0, seed=0)
    eng = ServingEngine(cfg)
    eng.submit_all(poisson_arrivals(50_000.0, 8, seed=11,
                                    prompt_len=(6, 20), gen_len=(3, 8)))
    return eng, eng.run()

eng, s = run("pipelined")
_, s2 = run("pipelined")
assert s == s2, "serving engine is not deterministic"
assert s["sched_completed"] == 8 and s["kv_allocs"] == s["kv_frees"], s
assert s["drains"] == s["steps"], s                # one drain/microbatch
(world,) = eng.backend._sessions.values()
assert not world.net.pending, "session left traffic in flight"
fs = verify_session_slots(world._slots, n_channels=world.n_channels,
                          counter_stride=world._counter_stride)
assert fs == [], [str(f) for f in fs]
_, n = run("per_layer")
for k in (k for k in s if k.startswith("sched_")):
    assert s[k] == n[k], k                        # identical schedule
assert s["elapsed_us"] < n["elapsed_us"], (s["elapsed_us"], n["elapsed_us"])
print(f"ci.sh: serving smoke OK ({s['generated_tokens']} tokens, "
      f"{s['steps']} microbatches, session {s['elapsed_us']:.0f}us vs "
      f"naive {n['elapsed_us']:.0f}us, verifier clean)")
EOF

# Benchmark smoke: three host benchmarks end-to-end (fig15 FIFO stress,
# the bench_transport batched-path microbench, and the fig13 serving load
# sweep — both with exact-gated counter rows), plus the machine-readable
# results file the perf trajectory is tracked with across PRs, gated
# against the committed baseline (fails on >25% us_per_call regressions;
# counter rows must match exactly).
BENCH_JSON="$(mktemp -t bench_smoke.XXXXXX.json)"
trap 'rm -f "$BENCH_JSON"' EXIT
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --only fig15,bench_transport,fig13_serving \
    --json "$BENCH_JSON" --compare BENCH_results.json > /dev/null
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} BENCH_JSON="$BENCH_JSON" python - <<'EOF'
import json, os
from benchmarks.run import validate_results
results = json.load(open(os.environ["BENCH_JSON"]))
validate_results(results)
print(f"ci.sh: benchmark smoke OK ({len(results)} results)")
EOF
