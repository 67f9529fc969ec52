"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload moonlight.decode --seed 7 --seconds 10 --trace 0

The cell is found by name in ``BENCHMARK.json``; its configuration, traffic,
correctness limits and per-layer metric readers are files under ``bench/``
(see ``bench/cells.py``).  One process: it enables the repo's compile cache,
makes the weights on the device from the seed (over the cell's chips where
the configuration names a ``mesh``), compiles the cell's programs, serves
one warm-up round, then serves rounds for ``--seconds``
(``bench/serve_loop.py``).  After the window it reads the devices' peak
memory, frees the programs and checks a sample of the served tokens against
the configuration's plain reference (``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window, which then ends early once it holds ``TRACE_LAYER_CALLS``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit.  Without a TPU, or with fewer chips than the
cell asks for, it exits 1 and prints no result.

``--control`` runs the correctness check's control: the same run, with the
tokens that the plain reference computed in float8 puts first compared in
place of the served ones (``bench/check.py``).  It has to come out
``correct: false``; the benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import cells  # noqa: E402

SETUP_SPLIT = ("start", "weights", "compile", "warm_up")
# A traced window ends after the round in which its program calls x layers x
# chips reach this: past it a trace grows too large to write and read within
# a run's time.  A whole window of a one-chip cell stays under it (about
# 12,500 in the decode cells); the four-chip cell reaches it in 2 rounds.
TRACE_LAYER_CALLS = 16000


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--control", action="store_true",
                    help="compare the float8 reference's tokens in place "
                         "of the served ones (must come out not correct)")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler output here (default: a "
                         "temporary directory, removed after reading)")
    return ap.parse_args(argv)


def peak_for(kind: str) -> dict:
    table = cells.load_json(cells.BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[kind]


def end_to_end(name: str, win, traffic: dict, setup_s: float) -> float:
    from bench import serve_loop

    if name == "out_tok_s":
        return serve_loop.served_tokens(win, traffic["batch"],
                                        traffic["gen"]) / win.seconds
    if name == "req_latency_p95_s":
        return serve_loop.latency_p95(win, traffic["batch"])
    if name == "setup_s":
        return setup_s
    raise KeyError(f"no end-to-end metric {name!r} in bench/run.py")


def traced_rounds(traffic: dict, n_layers: int, chips: int,
                  prefill: bool) -> int:
    """Rounds after which a traced window ends (``TRACE_LAYER_CALLS``): a
    round calls the prefill program once, or the step for each prompt
    position where there is none, then the step for all but the last token
    (``serve.generate``)."""
    calls = traffic["gen"] - 1 + (1 if prefill else traffic["prompt"])
    return -(-TRACE_LAYER_CALLS // (calls * n_layers * chips))


def sample_for_check(server, win, check: dict, seed: int):
    import numpy as np

    from bench import serve_loop

    picks = serve_loop.sample_requests(win, server.batch, check["requests"],
                                       seed)
    rounds = {r.index: r for r in win.rounds}
    prompts, tokens = [], []
    for index, row in picks:
        prompts.append(np.asarray(server.prompts(index))[row])
        tokens.append(rounds[index].tokens[row])
    return np.stack(prompts), np.stack(tokens)


def run_cell(args, *, require_tpu: bool = True, override=None):
    """One run of a cell.  Returns (exit code, result dict or None).
    ``require_tpu=False`` and ``override`` (a ``{"model": {..}, "moe":
    {..}}`` merged over the configuration's program fields, optional
    ``config``/``traffic``/``check`` entries merged over the files, and a
    ``peak`` in place of the peaks table's) are for the tests, which drive
    the harness at small sizes on the CPU."""
    override = dict(override or {})
    cell = cells.resolve(args.workload)
    config = {**cell.config, **override.pop("config", {})}
    traffic = {**cell.traffic, **override.pop("traffic", {})}
    check_spec = {**cell.check, **override.pop("check", {})}

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = "off"
    if require_tpu:
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        log(f"bench: needs a TPU; JAX found platform {platform!r}")
        return 1, None
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips; JAX found "
            f"{len(devices)}")
        return 1, None
    kind = devices[0].device_kind
    peak = override.pop("peak", None) or peak_for(kind)
    split = {"start": time.perf_counter() - T_START}
    log(f"bench: {cell.name} seed {args.seed} on {kind} x {len(devices)}; "
        f"compile cache {cache_dir}")

    from bench import check, serve_loop, weights

    cfg = cells.model_config(config, override)
    dist = None
    shape = cells.mesh_shape(config, cell.chips)
    if shape is not None:
        from repro.distributed.sharding import make_dist_ctx
        from repro.launch.mesh import make_bench_mesh

        dist = make_dist_ctx(cfg, make_bench_mesh(cell.chips, model=shape[1]))
    fan_in = getattr(cells.reference(config), "FAN_IN", None)
    t = time.perf_counter()
    params = jax.block_until_ready(weights.make_params(cfg, args.seed, dist,
                                                       fan_in))
    split["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    server = serve_loop.Server(cfg, params, traffic, args.seed, dist)
    split["compile"] = time.perf_counter() - t
    t = time.perf_counter()
    server.round(-1)
    split["warm_up"] = time.perf_counter() - t
    setup_s = time.perf_counter() - T_START
    log("bench: setup " + ", ".join(f"{k} {split[k]:.4f} s"
                                    for k in SETUP_SPLIT)
        + f"; setup_s {setup_s:.4f}")

    # objects made so far are kept out of the collector's scans, so that a
    # full collection inside the window does not walk the set-up's heap
    gc.collect()
    gc.freeze()
    trace_dir = None
    if args.trace:
        trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        server.probe()
    win = server.run(args.seconds, traced_rounds(
        traffic, cfg.n_layers, cell.chips, server.prefill is not None)
        if args.trace else None)
    if args.trace:
        jax.profiler.stop_trace()
    used = devices[:cell.chips]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in used)
    served = serve_loop.served_tokens(win, server.batch, server.gen)
    took = sorted(win.rounds, key=lambda r: r.end - r.start)
    slow = took[-1]
    log(f"bench: window {win.seconds:.4f} s, {len(win.rounds)} rounds "
        f"(min {took[0].end - took[0].start:.4f}, median "
        f"{took[len(took) // 2].end - took[len(took) // 2].start:.4f}, max "
        f"{slow.end - slow.start:.4f} s), {served} served tokens "
        f"({served / win.seconds:.4f} tok/s), peak_bytes_in_use {mem}")
    log(f"bench: slowest round {slow.index}: " + ", ".join(
        f"{k} {v:.4f} s" for k, v in slow.phases.items())
        + f"; trace and compile events in the window {win.compile_events}")

    attempted = len(win.rounds) * server.batch
    failed = sum(int(((r.tokens < 0) | (r.tokens >= cfg.vocab_size)).any(
        axis=1).sum()) for r in win.rounds)
    prompts, tokens = sample_for_check(server, win, check_spec, args.seed)
    server.free()
    t = time.perf_counter()
    gaps = check.control_gaps if args.control else check.served_gaps
    got = check.numbers(gaps(config, params, prompts, tokens))
    log(f"bench: {'control' if args.control else 'reference'} over "
        f"{prompts.shape[0]} requests "
        f"({tokens.size} served tokens) in {time.perf_counter() - t:.4f} s; "
        + ", ".join(f"{k} {v}" for k, v in got.items()))
    checks = {k: {"value": got[k], "limit": lim}
              for k, lim in check_spec["limits"].items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": mem}
    metrics = {}
    if args.trace:
        from bench import layers, trace

        tr = layers.load(trace_dir)
        if args.trace_dir is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        busy = trace.busy_s(tr)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = tr.window_s
        ctx = SimpleNamespace(trace=tr, peak=peak, chips=cell.chips,
                              config=config, traffic=traffic,
                              rounds=len(win.rounds))
        for m in cell.per_layer:
            value = cells.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = trace.breakdown(tr)
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {
                "value": end_to_end(m["name"], win, traffic, setup_s),
                "unit": m["unit"]}
    result.update(metrics=metrics, device=device, checks=checks)
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return 0, result


def main(argv=None) -> int:
    args = parse(argv)
    rc, result = run_cell(args)
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
