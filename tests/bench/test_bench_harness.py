"""The chip benchmark's harness on the CPU: cells found by name, the FLOP
counter against hand counts, the trace reduction on a trace recorded on a
TPU v5e, the refusal to run without a chip, and the round loop at small
widths."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402
from bench import cells, flops, trace  # noqa: E402

SPEC = cells.benchmark()
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
DATA = Path(__file__).resolve().parent / "data"


@pytest.mark.parametrize("name", WORKLOADS)
def test_every_cell_resolves_by_name(name):
    cell = cells.resolve(name)
    assert cell.chips in (1, 4)
    assert {"batch", "prompt", "gen", "loop"} <= set(cell.traffic)
    assert cell.check["requests"] > 0 and cell.check["limits"]
    names = {m["name"] for m in cell.end_to_end}
    assert {"out_tok_s", "req_latency_p95_s", "setup_s"} <= names
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"]).read)
    cfg = cells.model_config(cell.config)
    c = cell.config
    # published widths, as the program runs them
    assert cfg.d_model == c["hidden_size"]
    assert cfg.n_heads == c["num_attention_heads"]
    assert cfg.n_kv_heads == c["num_key_value_heads"]
    assert cfg.moe.d_expert == c["moe_intermediate_size"]
    assert cfg.moe.top_k == c["num_experts_per_tok"]
    assert cfg.n_layers == c["num_hidden_layers"]
    assert cfg.vocab_size == c["vocab_size"]


def test_benchmark_file_keeps_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    root = cells.REPO_DIR
    for p in SPEC["paths"]:
        assert (root / p).is_dir()
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for c in SPEC["configs"]:
        f = cells.load_json(root / c["file"])
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert c["reduced"] == f["reduced"]
        for k in c["reduced"]:
            assert f[k] != f["published"][k], k
        # what the program runs in place of a published value is no cut
        for k, v in f.get("as_run", {}).items():
            assert k not in c["reduced"] and f[k] != v, k
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)
        assert (root / "bench" / "metrics" / f"{m['name']}.py").is_file()
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(WORKLOADS) // 2)


def _config(name):
    return cells.load_json(cells.BENCH_DIR / "configs" / f"{name}.json")


def test_flops_match_hand_counts():
    moon = _config("moonlight-2l")
    # latent attention: q 2048x16x192, kv_a 2048x576, kv_b 512x16x256,
    # o 16x128x2048; experts 6 routed + 2 shared of 3x2048x1408; router
    # 2048x64
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    ffn = 8 * 3 * 2048 * 1408 + 2048 * 64
    assert attn == 13_762_560 and ffn == 69_337_088
    assert flops.layer_params(moon) == attn + ffn
    qwen = _config("qwen15moe-2l")
    # multi-head attention 4x2048x2048; 4 routed of 3x2048x1408; shared
    # 3x2048x5632 and its gate 2048; router 2048x60
    q_attn = 4 * 2048 * 2048
    q_ffn = 4 * 3 * 2048 * 1408 + 3 * 2048 * 5632 + 2048 + 2048 * 60
    assert flops.layer_params(qwen) == q_attn + q_ffn == 86_108_160
    # one request of 2 prompt and 2 generated tokens: 3 tokens fed with
    # contexts 1, 2, 3; logits read twice
    per_key = 2 * 16 * (192 + 128)
    want = (2 * 2 * (attn + ffn) * 3 + 2 * per_key * 6
            + 2 * 2 * 2048 * 20480)
    assert flops.round_flops(moon, 2, 2, 1) == want


# A prefill-cell run recorded on a TPU v5e: one round (a 4 x 2048 prefill
# and 7 decode steps) after the probes, gzipped.  The expected numbers were
# read from the trace by hand, event by event.
RECORDED = DATA / "moonlight_prefill_v5e.xplane.pb.gz"
EXPECTED = DATA / "moonlight_prefill_v5e.expected.json"


def test_trace_reduction_on_a_recorded_trace():
    tr = trace.load(RECORDED)
    want = cells.load_json(EXPECTED)
    assert sorted(tr.devices) == [0]
    assert tr.window_s == pytest.approx(want["window_s"], rel=1e-9)
    busy = trace.busy_s(tr)[0]
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert trace.program(tr, "decode_step") == want["decode_step_module"]
    assert trace.program(tr, "prefill") == want["prefill_module"]
    steps = trace.module_calls(tr, 0, want["decode_step_module"])
    assert len(steps) == want["decode_steps"]
    assert sum(steps) == pytest.approx(want["decode_step_s_total"], rel=1e-9)
    pre = trace.module_calls(tr, 0, want["prefill_module"])
    assert pre == pytest.approx([want["prefill_s"]], rel=1e-9)
    bd = trace.breakdown(tr)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in bd["device_ops"] + bd["idle_gaps"])


def test_probe_names_the_module_that_fills_its_span():
    # the next probe's long module starts just before this span ends
    tr = trace.Trace(
        devices={0: trace.Device(modules=[("init", 0, 2), ("step", 3, 20),
                                          ("prefill", 20.5, 150)])},
        spans=[("bench.probe.decode_step", 0, 21),
               ("bench.probe.prefill", 21.2, 151)])
    assert trace.program(tr, "decode_step") == "step"
    assert trace.program(tr, "prefill") == "prefill"


def test_union_and_leaves_by_hand():
    ivs = [("a", 0, 10), ("b", 2, 4), ("c", 5, 12), ("d", 20, 25)]
    assert trace.union_ns(ivs) == 17
    assert trace.clip(ivs, 3, 21) == [("a", 3, 10), ("b", 3, 4),
                                      ("c", 5, 12), ("d", 20, 21)]
    nested = [("while", 0, 10), ("x", 1, 3), ("y", 4, 9), ("z", 11, 12)]
    assert [n for n, _, _ in trace.leaves(nested)] == ["x", "y", "z"]
    assert trace.op_name("%fusion.12 = bf16[64,2048]{1,0:T(8,128)} "
                         "fusion(bf16[2]{0} %p)") == "fusion.12 bf16[64,2048]"


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(cells.BENCH_DIR / "run.py"), "--workload",
         WORKLOADS[0], "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300, cwd=str(cells.REPO_DIR))
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_run_exits_nonzero_with_the_benchmark_files_alone(tmp_path):
    import shutil

    root = cells.REPO_DIR
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(root / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", WORKLOADS[0],
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=300, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert "{" not in proc.stdout


@pytest.mark.parametrize("name", WORKLOADS)
def test_round_loop_serves_generate_at_small_widths(name, capsys):
    rc, res = bench_tiny.run_tiny(name)
    # every program of the window was compiled in set-up
    assert "trace and compile events in the window 0" in capsys.readouterr().err
    assert rc == 0 and res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] % 4 == 0
    assert res["attempted"] >= 4
    assert list(res)[-1] == "checks"
    units = {m["name"]: m["unit"] for m in SPEC["end_to_end"]}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == units
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"


def test_round_cache_is_the_programs_empty_cache():
    import jax
    import numpy as np

    from bench import serve_loop, weights
    from repro.launch import serve

    cell = cells.resolve(WORKLOADS[0])
    cfg = cells.model_config(cell.config, {k: bench_tiny.TINY[k]
                                           for k in ("model", "moe")})
    traffic = {**cell.traffic, **bench_tiny.TINY["traffic"]}
    server = serve_loop.Server(cfg, weights.make_params(cfg, 3), traffic, 3)
    ours, theirs = server.new_cache(), serve.new_cache(
        cfg, None, server.batch, server.max_len)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latency_p95_is_nearest_rank_over_requests():
    from bench import serve_loop

    # 20 rounds of 2 requests: latencies 1..20 s; the 95th percentile of
    # 40 requests is the 38th, a request of the 19th round
    win = serve_loop.Window(0.0, 20.0, [
        serve_loop.Round(i, 0.0, float(i + 1), None) for i in range(20)])
    assert serve_loop.latency_p95(win, 2) == 19.0
    assert serve_loop.served_tokens(win, 2, 3) == 120


def test_sampling_is_fixed_by_the_seed():
    from bench import serve_loop

    win = serve_loop.Window(0.0, 1.0, [serve_loop.Round(i, 0.0, 1.0, None)
                                       for i in range(5)])
    a = serve_loop.sample_requests(win, 8, 6, 2**33 + 1)
    assert a == serve_loop.sample_requests(win, 8, 6, 2**33 + 1)
    assert a != serve_loop.sample_requests(win, 8, 6, 1)
    assert len(set(a)) == 6


def test_result_line_is_json_with_checks_last():
    rc, res = bench_tiny.run_tiny(WORKLOADS[0], trace=1)
    line = json.loads(json.dumps(res))
    assert list(line)[-1] == "checks"
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert "breakdown" in line
