"""Proof that the MoE serving path runs on a TPU at Moonlight-16B-A3B widths.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # expert parallelism over four chips

One chip: ``moonshot_v1_16b_a3b`` at its published widths (d_model 2048,
16 heads x 128, 64 routed experts top-6, d_expert 1408), cut to 2 layers
and a 20,480-token vocabulary, serves 4 requests of 256 prompt tokens and
32 generated tokens each through ``repro.launch.serve`` (one batched
prefill, 31 decode steps).  The generated logits are checked finite and
the token ids in range.  On a small input, prefill and decode through the
KV cache are checked against one forward pass without it.

Four chips: the same config on a (data=1, model=4) mesh, 16 experts per
chip placed by ``param_shardings``.  (a) One MoE layer at 1024 tokens
through ``moe_apply`` in HT and LL mode with fp32 and fp8 wire against the
dense ``mode="ref"`` layer on one chip; (b) decode steps of the sharded
serve path against the one-chip decode step.  No other phase runs.

The checks that compare two implementations of the whole model (cache
against full forward, mesh against one chip) run in f32 at the highest
matmul precision: in bf16 their numerics differ by bf16 roundings, which
flip near-tied top-6 routing choices of the random router in the second
layer and change whole expert outputs.

Every phase runs in this one process.  Weights are random, from ``--seed``.
Without a TPU the script exits non-zero and prints no result.  The last
line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH, LAYERS, VOCAB = "moonshot_v1_16b_a3b", 2, 20_480
BATCH, PROMPT, GEN = 4, 256, 32
LAYER_TOKENS, DECODE_STEPS = 1024, 4
CHECK_BATCH, CHECK_PROMPT, CHECK_GEN = 2, 16, 4
# max|got - ref| / max|ref|: bf16 compute against the f32-accumulated
# dense path, the documented fp8-wire bound (DESIGN.md §14), and two f32
# implementations at the highest matmul precision
TOL = {"bf16": 5e-2, "fp8": 0.2, "f32": 1e-3}


def log(msg: str) -> None:
    print(msg, flush=True)


def relerr(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def f32(cfg):
    import dataclasses
    return dataclasses.replace(cfg, dtype="float32")


def cache_check(cfg, params, *, batch: int = CHECK_BATCH,
                prompt: int = CHECK_PROMPT, gen: int = CHECK_GEN,
                seed: int = 0) -> None:
    """Prefill + decode through the KV cache against one forward pass
    over the same tokens without it, in f32 at the highest precision."""
    import jax
    import jax.numpy as jnp

    from repro.launch import serve
    from repro.models import model_zoo as Z

    cfg = f32(cfg)

    @jax.jit
    def full_logits(params, toks):
        x, _ = Z.forward(cfg, params, toks)
        return (x @ Z.lm_head_weight(cfg, params)).astype(jnp.float32)

    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (batch, prompt), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        prefill, step = serve.compile_steps(
            cfg, None, params,
            serve.new_cache(cfg, None, batch, prompt + gen), prompts)
        tokens, logits = serve.generate(
            cfg, prefill, step, params,
            serve.new_cache(cfg, None, batch, prompt + gen), prompts, gen)
        seq = jnp.concatenate([prompts, tokens[:, :-1]], axis=1)
        ref = full_logits(params, seq)[:, prompt - 1:, :cfg.vocab_size]
    err = relerr(logits[..., :cfg.vocab_size], ref)
    log(f"f32 cached decode vs full forward ({batch} x ({prompt} + {gen}) "
        f"tokens): relerr {err:.3e} (tol {TOL['f32']})")
    check(err <= TOL["f32"], "cached decode matches the full forward")


def one_chip(cfg, *, batch: int = BATCH, prompt: int = PROMPT,
             gen: int = GEN, seed: int = 0) -> None:
    """The serve path on the default device, then :func:`cache_check`."""
    import jax
    import jax.numpy as jnp

    from repro.core.backend import get_backend
    from repro.core.moe import moe_path
    from repro.kernels import ops as kops
    from repro.launch import serve

    be = get_backend(cfg.moe.ep_backend)
    log(f"kernel mode: {kops.platform_mode()}")
    log(f"moe path: prefill {moe_path(None, 'ht', be)}, "
        f"decode {moe_path(None, 'll', be)} (no EP mesh on one chip)")
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    params = jax.block_until_ready(serve.init_params(cfg, None, key))
    log(f"init params: {time.perf_counter() - t0:.3f} s")
    prompts = jax.random.randint(key, (batch, prompt), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    prefill, step = serve.compile_steps(
        cfg, None, params, serve.new_cache(cfg, None, batch, prompt + gen),
        prompts)
    log(f"compile prefill + decode step: {time.perf_counter() - t0:.3f} s")
    for name, c in (("prefill", prefill), ("decode step", step)):
        ma = c.memory_analysis()
        log(f"{name} program: {c.as_text().count('tpu_custom_call')} pallas "
            f"calls, argument bytes {ma.argument_size_in_bytes}, temp bytes "
            f"{ma.temp_size_in_bytes}, output bytes {ma.output_size_in_bytes}")

    def run():
        out = serve.generate(cfg, prefill, step, params,
                             serve.new_cache(cfg, None, batch, prompt + gen),
                             prompts, gen)
        return jax.block_until_ready(out)

    run()                                   # warm-up: first dispatches
    t0 = time.perf_counter()
    tokens, logits = run()
    dt = time.perf_counter() - t0
    log(f"generate: {tokens.size} tokens ({batch} requests x {gen}) after a "
        f"{prompt}-token prompt in {dt:.4f} s after warm-up "
        f"({tokens.size / dt:.1f} tok/s)")
    log(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")

    check(tokens.shape == (batch, gen), f"tokens shape {tokens.shape}")
    lo, hi = int(tokens.min()), int(tokens.max())
    log(f"token ids in [{lo}, {hi}], vocab {cfg.vocab_size}")
    check(0 <= lo and hi < cfg.vocab_size, "token ids inside [0, vocab)")
    finite = bool(jnp.isfinite(logits).all())
    log(f"logits {tuple(logits.shape)} finite: {finite}")
    check(finite, "finite logits")
    del logits
    cache_check(cfg, params, seed=seed)


def four_chips(cfg, *, tokens: int = LAYER_TOKENS, batch: int = BATCH,
               steps: int = DECODE_STEPS, seed: int = 0) -> None:
    """EP over a (data=1, model=4) mesh against the dense one-chip path."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.moe import moe_apply, moe_init, padded_experts_static
    from repro.distributed.sharding import make_dist_ctx, param_shardings
    from repro.kernels import ops as kops
    from repro.launch import serve
    from repro.launch.mesh import make_bench_mesh
    from repro.models import model_zoo as Z

    mesh = make_bench_mesh(4, model=4)
    dist = make_dist_ctx(cfg, mesh)
    log(f"mesh {dict(mesh.shape)}, kernel mode {kops.platform_mode()}, "
        f"{padded_experts_static(cfg) // 4} experts per chip")
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)

    # (a) one MoE layer, EP modes x wire dtypes vs the dense layer
    p1 = jax.jit(lambda k: Z.cast_params(moe_init(cfg, k),
                                         jnp.dtype(cfg.dtype)))(k1)
    p4 = jax.device_put(p1, param_shardings(cfg, dist, {"moe": p1})["moe"])
    x1 = jax.random.normal(k2, (batch, tokens // batch, cfg.d_model),
                           jnp.dtype(cfg.dtype))
    x4 = jax.device_put(x1, NamedSharding(mesh, P("data", "model", None)))
    y_ref, _ = jax.jit(partial(moe_apply, cfg, None, mode="ref"))(p1, x1)
    for mode in ("ht", "ll"):
        for wire in ("fp32", "fp8"):
            cw = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, wire_dtype=wire))
            f = jax.jit(partial(moe_apply, cw, dist, mode=mode)).lower(
                p4, x4).compile()
            n_kernels = f.as_text().count("tpu_custom_call")
            y, aux = f(p4, x4)
            err = relerr(y, y_ref)
            tol = TOL["fp8" if wire == "fp8" else "bf16"]
            log(f"moe layer {mode}/{wire}: relerr vs ref {err:.6f} (tol "
                f"{tol}), dropped {float(aux['dropped']):.6f}, "
                f"{n_kernels} pallas calls")
            check(err <= tol, f"{mode}/{wire} layer within tolerance")
    del p1, p4

    # (b) decode steps of the sharded serve path vs the one-chip step, f32
    cfg = f32(cfg)
    params1 = serve.init_params(cfg, None, k3)
    params4 = jax.device_put(params1, param_shardings(cfg, dist, params1))
    max_len = 4 * steps       # the cache sequence splits over 4 chips
    toks = jax.random.randint(k3, (batch, max_len), 0, cfg.vocab_size)
    cache1 = serve.new_cache(cfg, None, batch, max_len)
    cache4 = serve.new_cache(cfg, dist, batch, max_len)
    errs = []
    with jax.default_matmul_precision("highest"):
        _, step1 = serve.compile_steps(cfg, None, params1, cache1, toks)
        _, step4 = serve.compile_steps(cfg, dist, params4, cache4, toks)
        log(f"sharded f32 decode step: "
            f"{step4.as_text().count('tpu_custom_call')} pallas calls")
        for t in range(steps):
            tok = toks[:, t:t + 1]
            l1, cache1 = step1(params1, cache1, tok, jnp.int32(t))
            l4, cache4 = step4(params4, cache4, tok, jnp.int32(t))
            errs.append(relerr(l4[:, :cfg.vocab_size],
                               l1[:, :cfg.vocab_size]))
    log(f"f32 mesh decode vs one chip, {steps} steps: relerr "
        f"{', '.join(f'{e:.3e}' for e in errs)} (tol {TOL['f32']})")
    check(max(errs) <= TOL["f32"], "mesh decode logits match one chip")
    for d in jax.devices()[:4]:
        log(f"peak_bytes_in_use {d}: {peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    full = get_config(ARCH)
    cfg = serve.build_config(ARCH, layers=LAYERS, vocab=VOCAB)
    log(f"config {ARCH}: d_model {cfg.d_model}, heads {cfg.n_heads} x "
        f"{cfg.head_dim_} (kv {cfg.n_kv_heads}), experts "
        f"{cfg.moe.n_experts} top-{cfg.moe.top_k}, d_expert "
        f"{cfg.moe.d_expert}; cut: layers {full.n_layers} -> {cfg.n_layers},"
        f" vocab {full.vocab_size} -> {cfg.vocab_size}")
    log(f"device: {devices[0].device_kind} x {len(devices)}")
    if args.chips == 4:
        four_chips(cfg, seed=args.seed)
    else:
        one_chip(cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
