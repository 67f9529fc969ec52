"""Launchers and ``chip_smoke.py`` on the CPU: config cuts, mesh and cache
placement, the compile-cache directory, and both smoke phases at a tiny
size (the chip runs them at full width)."""
import dataclasses
import functools
import gc
import importlib.util
import os
import re
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
from repro.models import model_zoo as Z

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


# the layer scopes (jax.named_scope) of the one-chip path and of the EP path
ONE_CHIP_SCOPES = {"cast", "embed", "attention", "moe.router", "moe.experts",
                   "moe.shared", "lm_head"}
EP_SCOPES = {"moe.router", "moe.dispatch", "moe.experts", "moe.combine"}


def _scopes(hlo: str) -> set:
    return {part for path in re.findall(r'op_name="([^"]*)"', hlo)
            for part in path.split("/")} & (ONE_CHIP_SCOPES | EP_SCOPES)


def _tiny_shared():
    cfg = _tiny()
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, d_shared=64))


def _served(cfg, batch=2, prompt=6, gen=4):
    params = serve.init_params(cfg, None, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt), 0,
                                 cfg.vocab_size)
    prefill, step = serve.compile_steps(
        cfg, None, params, serve.new_cache(cfg, None, batch, prompt + gen),
        prompts)
    return params, prompts, prefill, step


def test_compile_steps_names_the_programs_and_scopes_each_layer():
    """The step programs carry every one-chip scope but ``cast``: the
    weights come cast by their own program, which carries ``cast``."""
    _, _, prefill, step = _served(_tiny_shared())
    assert prefill.weights is step.weights
    for program, name, scopes in (
            (prefill, "jit_prefill", ONE_CHIP_SCOPES - {"cast"}),
            (step, "jit_decode_step", ONE_CHIP_SCOPES - {"cast"}),
            (step.weights.cast, "jit_serve_weights", {"cast"})):
        hlo = program.as_text()
        assert hlo.startswith(f"HloModule {name},")
        assert _scopes(hlo) == scopes


def _casting_steps(cfg):
    """The model-step programs jitted on the f32 masters: each call casts
    the masters it is given."""
    return (jax.jit(functools.partial(Z.prefill, cfg, moe_mode="ht")),
            jax.jit(functools.partial(Z.decode_step, cfg, moe_mode="ll")))


@pytest.mark.parametrize("batched", [True, False])
def test_served_weights_give_the_casting_steps_tokens_and_logits_bitwise(batched):
    """Serving from the copy cast once changes no arithmetic: in bf16, the
    tokens and logits equal, bit for bit, those of programs that cast the
    masters in every call."""
    cfg = _tiny()
    assert cfg.dtype == "bfloat16"
    params, prompts, prefill, step = _served(cfg)
    ref_prefill, ref_step = _casting_steps(cfg)
    got, want = (serve.generate(cfg, pre if batched else None, st, params,
                                serve.new_cache(cfg, None, 2, 10), prompts, 4)
                 for pre, st in ((prefill, step), (ref_prefill, ref_step)))
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert step.weights.casts == 1


def test_served_weights_are_cast_once_per_master_tree():
    cfg = _tiny()
    params, prompts, prefill, step = _served(cfg)

    def run(params):
        return serve.generate(cfg, prefill, step, params,
                              serve.new_cache(cfg, None, 2, 10), prompts, 4)

    first = run(params)
    run(params)
    assert step.weights.casts == 1
    served = step.weights(params)
    assert step.weights.casts == 1
    assert served["embed"].dtype == jax.numpy.bfloat16
    assert served["final_ln"].dtype == jax.numpy.float32   # norms stay f32
    # a new tree of the same leaves is the same masters; new leaves are not
    assert step.weights(dict(params)) is served
    again = run(jax.tree.map(lambda x: x + 0, params))
    assert step.weights.casts == 2
    np.testing.assert_array_equal(np.asarray(first[1]), np.asarray(again[1]))


def test_f32_compute_serves_the_masters_as_they_are():
    cfg = dataclasses.replace(_tiny(), dtype="float32")
    params, prompts, prefill, step = _served(cfg)
    assert step.weights.cast is None
    assert step.weights(params) is params
    serve.generate(cfg, prefill, step, params,
                   serve.new_cache(cfg, None, 2, 10), prompts, 4)
    assert step.weights.casts == 0


def _repro_spans(logdir) -> list:
    """(name, start_ns, end_ns, stats) of the host spans named repro.* in
    the one trace under ``logdir``, in order of start."""
    from jax.profiler import ProfileData

    found = list(Path(logdir).glob("**/*.xplane.pb"))
    assert len(found) == 1
    pd = ProfileData.from_file(str(found[0]))
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for plane in pd.planes if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("repro.")), key=lambda s: s[1])


@pytest.mark.parametrize("batched", [True, False])
def test_generate_emits_serve_spans_in_order(batched, tmp_path):
    """On fresh programs the first call casts the masters, inside the
    prompt's span; a second call with the same masters casts nothing."""
    cfg = _tiny()
    S, gen = 6, 4
    params, prompts, prefill, step = _served(cfg, prompt=S, gen=gen)

    def collecting_step(*args):
        gc.collect()
        return step(*args)

    for call in (0, 1):
        logdir = tmp_path / f"call{call}"
        with jax.profiler.trace(str(logdir)):
            tokens, logits = serve.generate(
                cfg, prefill if batched else None, collecting_step, params,
                serve.new_cache(cfg, None, 2, S + gen), prompts, gen)
            jax.block_until_ready((tokens, logits))
        spans = _repro_spans(logdir)
        serve_spans = [s for s in spans if s[0] != "repro.host.gc"]
        prompt_steps = [] if batched else ["repro.serve.step"] * S
        cast = ["repro.serve.cast"] if call == 0 else []
        # the cast starts inside the call that needs it: the prefill, or
        # the prompt's first step
        assert [s[0] for s in serve_spans] == (
            ["repro.serve.prefill", *prompt_steps[:1], *cast,
             *prompt_steps[1:], "repro.serve.sample"]
            + ["repro.serve.step", "repro.serve.sample"] * (gen - 1)
            + ["repro.serve.stack"])
        casts = [s for s in serve_spans if s[0] == "repro.serve.cast"]
        serve_spans = [s for s in serve_spans if s[0] != "repro.serve.cast"]
        steps = [s for s in serve_spans if s[0] == "repro.serve.step"]
        assert [s[3]["step"] for s in steps] == list(
            range(S if batched else 0, S + gen - 1))
        prefill_span = serve_spans[0]
        for _, lo, hi, _ in steps[:len(prompt_steps)] + casts:
            assert prefill_span[1] <= lo and hi <= prefill_span[2]
        if casts and not batched:
            assert steps[0][1] <= casts[0][1] and casts[0][2] <= steps[0][2]
        # each step's collection shows as a repro.host.gc span inside its
        # step
        collections = [s for s in spans if s[0] == "repro.host.gc"]
        for _, lo, hi, _ in steps:
            assert any(lo <= c[1] and c[2] <= hi for c in collections)
    assert step.weights.casts == 1


def test_served_weights_keep_their_shardings_on_a_mesh(dist_runner):
    """On a (1, 4) mesh the served copy keeps each master leaf's sharding,
    and the prompt through the sharded step gives the tokens and logits of
    a step that casts the masters in every call, bit for bit, with one
    cast."""
    out = dist_runner(textwrap.dedent("""
        from functools import partial
        import jax, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.distributed.sharding import make_dist_ctx
        from repro.launch import serve
        from repro.launch.mesh import make_bench_mesh
        from repro.models import model_zoo as Z

        cfg = reduced_config(get_config("moonshot_v1_16b_a3b"), n_layers=2,
                             d_model=64, n_experts=8, vocab=512)
        dist = make_dist_ctx(cfg, make_bench_mesh(4, model=4))
        params = serve.init_params(cfg, dist, jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 4), 0,
                                     cfg.vocab_size)
        prefill, step = serve.compile_steps(
            cfg, dist, params, serve.new_cache(cfg, dist, 4, 8), prompts)
        assert prefill is None
        served = step.weights(params)
        for m, s in zip(jax.tree.leaves(params), jax.tree.leaves(served)):
            assert s.sharding == m.sharding, (m.sharding, s.sharding)
        ref_step = jax.jit(partial(Z.decode_step, cfg, dist=dist,
                                   moe_mode="ll"))
        got, want = (serve.generate(cfg, None, st, params,
                                    serve.new_cache(cfg, dist, 4, 8),
                                    prompts, 4) for st in (step, ref_step))
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert step.weights.casts == 1
        print("MESH-OK")
    """), n_devices=4, timeout=600)
    assert "MESH-OK" in out


def test_generate_removes_its_gc_callback_on_return_and_on_error():
    cfg = _tiny()
    params, prompts, prefill, step = _served(cfg)
    before = list(gc.callbacks)
    seen = []

    def watched_step(*args):
        seen.append(len(gc.callbacks))
        return step(*args)

    serve.generate(cfg, prefill, watched_step, params,
                   serve.new_cache(cfg, None, 2, 10), prompts, 4)
    assert seen and set(seen) == {len(before) + 1}
    assert gc.callbacks == before

    def failing_step(*args):
        raise RuntimeError("step failed")

    with pytest.raises(RuntimeError, match="step failed"):
        serve.generate(cfg, prefill, failing_step, params,
                       serve.new_cache(cfg, None, 2, 10), prompts, 4)
    assert gc.callbacks == before


def test_ep_path_scopes_its_exchanges(dist_runner):
    """On a (1, 4) mesh the MoE layer runs the EP island: every all-to-all
    lies under moe.dispatch or moe.combine, in LL, HT and two-level HT."""
    out = dist_runner(textwrap.dedent("""
        import dataclasses, re
        from functools import partial
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.configs import get_config, reduced_config
        from repro.core.ep import EPSpec, dispatch_combine_ht
        from repro.core.moe import moe_apply, moe_init
        from repro.distributed.sharding import make_dist_ctx
        from repro.kernels.ref import grouped_swiglu_ref
        from repro.launch.mesh import make_bench_mesh

        def paths(hlo):
            return re.findall(r'op_name="([^"]*)"', hlo)

        def a2a_paths(hlo):
            return [m for line in hlo.splitlines() if " all-to-all(" in line
                    for m in re.findall(r'op_name="([^"]*)"', line)]

        cfg = reduced_config(get_config("moonshot_v1_16b_a3b"), n_layers=2,
                             d_model=64, n_experts=8, vocab=512)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, d_shared=64))
        mesh = make_bench_mesh(4, model=4)
        dist = make_dist_ctx(cfg, mesh)
        p = moe_init(cfg, jax.random.PRNGKey(0))
        x = jnp.ones((4, 16, cfg.d_model), jnp.float32)
        for mode in ("ll", "ht"):
            with jax.set_mesh(mesh):
                hlo = jax.jit(partial(moe_apply, cfg, dist, mode=mode)).lower(
                    p, x).compile().as_text()
            scopes = {part for path in paths(hlo) for part in path.split("/")}
            want = {"moe.router", "moe.dispatch", "moe.experts",
                    "moe.combine", "moe.shared"}
            assert want <= scopes, (mode, want - scopes)
            a2a = a2a_paths(hlo)
            assert a2a and all("/moe.dispatch/" in a or "/moe.combine/" in a
                               for a in a2a), (mode, a2a)
            print(f"SCOPES-{mode}-OK")

        mesh2 = jax.make_mesh((2, 2), ("pod", "model"),
                              axis_types=(AxisType.Auto,) * 2)
        E, K, D, F, T = 8, 2, 16, 24, 16
        spec = EPSpec(axes=("pod", "model"), sizes=(2, 2), n_experts=E,
                      top_k=K, capacity_factor=4.0, dtype=jnp.float32)
        w = jnp.ones((E, D, F)), jnp.ones((E, D, F)), jnp.ones((E, F, D))

        def island(x, ti, tw, wg, wu, wd):
            return dispatch_combine_ht(
                spec, x, ti, tw,
                lambda t: grouped_swiglu_ref(t, wg, wu, wd)).out

        ax, ep = ("pod", "model"), P(("pod", "model"), None, None)
        hlo = jax.jit(jax.shard_map(
            island, mesh=mesh2, in_specs=(P(ax), P(ax), P(ax), ep, ep, ep),
            out_specs=P(ax), check_vma=False)).lower(
            jnp.ones((T, D)), jnp.zeros((T, K), jnp.int32),
            jnp.ones((T, K)), *w).compile().as_text()
        a2a = a2a_paths(hlo)
        assert len(a2a) >= 6 and all(
            "/moe.dispatch/" in a or "/moe.combine/" in a for a in a2a), a2a
        print("SCOPES-two-level-OK")
    """), n_devices=4, timeout=600)
    for tag in ("ll", "ht", "two-level"):
        assert f"SCOPES-{tag}-OK" in out
