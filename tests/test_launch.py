"""Launchers and ``chip_smoke.py`` on the CPU: config cuts, mesh and cache
placement, the compile-cache directory, and both smoke phases at a tiny
size (the chip runs them at full width)."""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.launch import serve
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
from repro.launch.mesh import make_bench_mesh

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny():
    return reduced_config(get_config("moonshot_v1_16b_a3b"), n_layers=2,
                          d_model=64, n_experts=8, vocab=512)


def test_build_config_cuts_depth_and_vocab_only():
    full = get_config("moonshot_v1_16b_a3b")
    cfg = serve.build_config("moonshot_v1_16b_a3b", layers=2, vocab=20_480)
    assert (cfg.n_layers, cfg.vocab_size) == (2, 20_480)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim_, cfg.n_kv_heads) == \
        (full.d_model, full.n_heads, full.head_dim_, full.n_kv_heads)
    assert cfg.moe == full.moe
    assert serve.build_config("moonshot_v1_16b_a3b") == full
    red = serve.build_config("moonshot_v1_16b_a3b", reduced=True, d_model=64,
                             wire_dtype="fp8")
    assert (red.n_layers, red.d_model, red.vocab_size) == (2, 64, 512)
    assert red.moe.wire_dtype == "fp8"


def test_bench_mesh_rejects_uneven_split():
    with pytest.raises(ValueError):
        make_bench_mesh(1, model=4)
    with pytest.raises(ValueError):
        make_bench_mesh(6, model=4)
    assert dict(make_bench_mesh(1, model=1).shape) == {"data": 1, "model": 1}


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_main_tiny(monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve.main(["--arch", "moonshot_v1_16b_a3b", "--reduced",
                       "--d-model", "64", "--batch", "2", "--prompt-len",
                       "8", "--gen", "4"]) == 0
    assert "generated 8 tokens" in capsys.readouterr().out


def test_generate_prompt_through_decode_step_matches_prefill():
    """Where no batched prefill exists (a model-axis mesh shards the
    cache), the prompt goes through the decode step: same tokens and, in
    f32 at the highest precision, the same logits."""
    import dataclasses

    cfg = dataclasses.replace(_tiny(), dtype="float32")
    params = serve.init_params(cfg, None, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                 cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        prefill, step = serve.compile_steps(
            cfg, None, params, serve.new_cache(cfg, None, 2, 10), prompts)
        got = [serve.generate(cfg, pre, step, params,
                              serve.new_cache(cfg, None, 2, 10), prompts, 4)
               for pre in (prefill, None)]
    (tok_a, log_a), (tok_b, log_b) = got
    assert tok_a.shape == (2, 4)
    np.testing.assert_array_equal(np.asarray(tok_a), np.asarray(tok_b))
    np.testing.assert_allclose(np.asarray(log_a), np.asarray(log_b),
                               rtol=1e-4, atol=1e-4)


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_one_chip_phase_tiny(capsys):
    _chip_smoke().one_chip(_tiny(), batch=2, prompt=8, gen=4)
    out = capsys.readouterr().out
    assert "moe path: prefill dense, decode dense" in out
    assert "f32 cached decode vs full forward" in out


def test_chip_smoke_four_chip_phase_tiny(dist_runner):
    out = dist_runner(textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs import get_config, reduced_config
        from repro.kernels import ops as kops
        kops.platform_mode = lambda: "interpret"    # kernel bodies on CPU
        cfg = reduced_config(get_config("moonshot_v1_16b_a3b"), n_layers=2,
                             d_model=64, n_experts=8, vocab=512)
        cs.four_chips(cfg, tokens=64, batch=4, steps=2)
        print("FOUR-OK")
    """), n_devices=4, timeout=600)
    assert "FOUR-OK" in out
    for mode in ("ht/fp32", "ht/fp8", "ll/fp32", "ll/fp8"):
        assert f"moe layer {mode}" in out
