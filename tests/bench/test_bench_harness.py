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
import textwrap
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
        # every per-layer metric lists the cells it reads something in, so
        # that a cell added later reports only the metrics that list it
        assert m["workloads"] and set(m["workloads"]) <= set(WORKLOADS)
        assert (root / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for w in SPEC["workloads"]:
        assert any(w["name"] in m["workloads"] for m in SPEC["per_layer"])
        f = cells.load_json(root / "bench" / "configs" / f"{w['config']}.json")
        # a configuration on more than one chip names the mesh it runs on
        if w["chips"] > 1 or "mesh" in f:
            assert cells.mesh_shape(f, w["chips"]) is not None
        assert (root / "bench" / f"{f.get('reference', 'reference')}.py"
                ).is_file()
        assert callable(cells.reference(f).logits)
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


# by hand: program calls a round (the prefill and gen - 1 steps, or prompt +
# gen - 1 steps on a mesh) x layers x chips, rounds up to 16,000 layer calls
TRACED_ROUNDS = {"moonlight.decode": -(-16000 // (128 * 2)),
                 "qwen15moe.decode": -(-16000 // (128 * 2)),
                 "moonlight.prefill": -(-16000 // (8 * 2)),
                 "moonlight-ep4.decode": -(-16000 // (255 * 8 * 4))}


@pytest.mark.parametrize("name", sorted(TRACED_ROUNDS))
def test_a_traced_window_ends_by_one_rule(name):
    from bench import run

    cell = cells.resolve(name)
    cfg = cells.model_config(cell.config)
    prefill = cells.mesh_shape(cell.config, cell.chips) is None
    assert run.traced_rounds(cell.traffic, cfg.n_layers, cell.chips,
                             prefill) == TRACED_ROUNDS[name]
    assert TRACED_ROUNDS["moonlight-ep4.decode"] == 2
    # a 51-s window of the one-chip decode cells serves about 49 rounds
    assert min(TRACED_ROUNDS[n] for n in TRACED_ROUNDS
               if cells.resolve(n).chips == 1) > 60


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


MESH_WORKLOADS = [w["name"] for w in SPEC["workloads"]
                  if "mesh" in cells.resolve(w["name"]).config]


@pytest.mark.parametrize("name", MESH_WORKLOADS)
def test_mesh_cell_serves_the_references_tokens_in_float32(name):
    # in float32 the EP path and the reference agree token for token
    rc, res = bench_tiny.run_tiny(name, seed=2**33 + 17)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["device"]["count"] == cells.resolve(name).chips
    # gaps are never negative, so a mean of 0 is every token the best
    assert res["checks"]["mean_logit_gap"]["value"] == 0
    assert res["checks"]["failed_requests"]["value"] == 0


def test_the_sharded_draw_equals_the_draw_on_one_chip(dist_runner):
    tiny = {k: bench_tiny.TINY[k] for k in ("model", "moe")}
    out = dist_runner(textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(cells.REPO_DIR)!r})
        import jax, numpy as np
        from bench import cells, weights
        from repro.distributed.sharding import make_dist_ctx
        from repro.launch.mesh import make_bench_mesh

        for name in {MESH_WORKLOADS!r}:
            cell = cells.resolve(name)
            cfg = cells.model_config(cell.config, {tiny!r})
            _, model = cells.mesh_shape(cell.config, cell.chips)
            dist = make_dist_ctx(cfg, make_bench_mesh(cell.chips, model=model))
            one = weights.make_params(cfg, 2**40 + 3)
            many = weights.make_params(cfg, 2**40 + 3, dist)
            pairs = zip(jax.tree_util.tree_leaves_with_path(one),
                        jax.tree.leaves(many))
            for (path, a), b in pairs:
                assert len(b.sharding.device_set) == cell.chips, path
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=str(path))
            # each chip holds its share of the experts, not all of them
            wg = many["blocks"]["slot0"]["moe"]["w_gate"]
            assert wg.addressable_shards[0].data.shape[1] == \\
                wg.shape[1] // model
        print("DRAW-OK")
    """), n_devices=max(cells.resolve(n).chips for n in MESH_WORKLOADS),
        timeout=600)
    assert "DRAW-OK" in out


STUB_REFERENCE = '''
"""A stand-in reference: token 0 is every position's best, and each call
is recorded with the spread of the weights it was given."""
import numpy as np

FAN_IN = {"w_down": (-1,)}           # the output width, not the input's
CALLS = []


def logits(config, params, seqs, first, precision="f32"):
    w = np.asarray(params["blocks"]["slot0"]["moe"]["w_down"])
    CALLS.append((precision, float(w.std()), w.shape[-1]))
    seqs = np.asarray(seqs)
    out = np.zeros((seqs.shape[0], seqs.shape[1] - first,
                    config["vocab_size"]), np.float32)
    out[..., 0] = 1.0
    return out
'''


def test_a_configuration_names_its_reference(tmp_path, monkeypatch):
    # a configuration added with a reference file of its own needs no edit
    # to the harness: the check and the weights' fan-in come from its file
    (tmp_path / "bench").mkdir()
    (tmp_path / "bench" / "stub_reference.py").write_text(STUB_REFERENCE)
    monkeypatch.setattr(cells, "REPO_DIR", tmp_path)
    size = dict(bench_tiny.TINY, config=dict(bench_tiny.TINY["config"],
                                             reference="stub_reference"))
    rc, res = bench_tiny.run_tiny(WORKLOADS[0], size=size)
    stub = cells.reference({"reference": "stub_reference"})
    assert rc == 0 and res["correct"] is False
    # served tokens other than 0 lie 1 below the stub's best
    assert res["checks"]["mean_logit_gap"]["value"] == pytest.approx(
        res["checks"]["not_first_share"]["value"])
    assert res["checks"]["not_first_share"]["value"] > 0.5
    assert [c[0] for c in stub.CALLS] == ["f32"]
    _, std, width = stub.CALLS[0]
    assert std == pytest.approx(width ** -0.5, rel=0.05)


def test_expert_kernel_roofline_by_hand():
    from types import SimpleNamespace

    from bench import layers, rooflines

    c = _config("moonlight-8l-ep4")
    flops, nbytes = rooflines.grouped_swiglu(c, 64, 4)
    # 64 tokens x top-6 choices arrive at each chip on average, each
    # through 3 matrices of 2048 x 1408; 16 experts' bf16 weights and the
    # choices' rows in and out
    assert flops == 64 * 6 * 3 * 2 * 2048 * 1408 == 6_643_777_536
    assert (nbytes == 16 * 3 * 2048 * 1408 * 2 + 2 * 64 * 6 * 2048 * 2
            == 279_969_792)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = rooflines.roofline_s(flops, nbytes, peak)
    assert least == pytest.approx(279_969_792 / 819e9)     # bound by HBM

    def iv(name, lo, hi):
        return (name, lo * 1e6, hi * 1e6)                  # ms -> ns

    kernel = "grouped_swiglu_pallas.1 bf16[64,32,2048]"
    # two chips; chip 0's kernel calls take 0.4, 0.5 and 0.9 ms, chip 1's
    # 0.6 ms; a kernel call outside the step and other ops do not count
    dev0 = trace.Device(
        ops=[iv("while.1", 1, 9), iv(kernel, 1, 1.4), iv("fusion.2", 2, 3),
             iv(kernel, 3, 3.5), iv(kernel, 11, 11.9), iv(kernel, 20, 29)],
        modules=[iv("jit_decode_step(3)", 1, 9),
                 iv("jit_decode_step(3)", 10, 12), iv("jit_other(4)", 20, 30)])
    dev1 = trace.Device(ops=[iv(kernel, 1, 1.6)],
                        modules=[iv("jit_decode_step(3)", 1, 2)])
    tr = layers.ScopedTrace(devices={0: dev0, 1: dev1},
                            spans=[iv("bench.round", 0, 40)])
    assert layers.METRICS["expert_kernel_ms"](tr) == pytest.approx(0.55)
    ctx = SimpleNamespace(trace=tr, config=c, chips=4, peak=peak,
                          traffic={"batch": 64})
    got = cells.metric_reader("expert_kernel_roofline").read(ctx)
    assert got == pytest.approx(100 * least / 0.55e-3)
    empty = layers.ScopedTrace(devices={0: trace.Device()},
                               spans=[iv("bench.round", 0, 1)])
    assert cells.metric_reader("expert_kernel_roofline").read(
        SimpleNamespace(trace=empty)) is None
