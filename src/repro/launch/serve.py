"""Serving launcher: batched prefill + decode loop with the LL EP mode.

  PYTHONPATH=src python -m repro.launch.serve --arch moonshot_v1_16b_a3b \
      --layers 2 --vocab 20480 --batch 4 --prompt-len 256 --gen 32

Without ``--reduced``, ``--layers``/``--vocab`` cut only the depth and the
vocabulary of the published config; its widths stay.  ``--reduced`` runs
the tiny same-family config (widths cut too) used on the CPU.

Prefill is ONE batched forward pass (``model_zoo.prefill``) that fills the
KV cache for the whole prompt, then decode proceeds token-at-a-time in LL
mode — the prefill/decode split the EP-native serving engine
(``repro.serving``) schedules continuously.  With ``--mesh local`` the
parameters and the KV cache are placed with their mesh shardings and the
prompt runs through the (sharded-cache) decode step.  ``--ep-backend``/
``--wire-dtype`` mirror ``launch/train.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import ModelConfig, cut_config, get_config, reduced_config
from repro.distributed.sharding import (DistCtx, cache_pspecs, make_dist_ctx,
                                        param_shardings)
from repro.launch.tracing import gc_spans, span
from repro.models import model_zoo as Z


def build_config(arch: str, *, reduced: bool = False,
                 layers: Optional[int] = None, d_model: int = 128,
                 vocab: Optional[int] = None, ep_backend: str = "",
                 wire_dtype: str = "") -> ModelConfig:
    """The served config: ``reduced`` cuts widths too (CPU smoke size);
    otherwise only depth and vocabulary are cut, where given."""
    cfg = get_config(arch)
    if reduced:
        cfg = reduced_config(cfg, n_layers=layers or 2, d_model=d_model,
                             vocab=vocab or 512)
    else:
        cfg = cut_config(cfg, n_layers=layers, vocab=vocab)
    moe_over = {}
    if ep_backend:
        moe_over["ep_backend"] = ep_backend
    if wire_dtype:
        moe_over["wire_dtype"] = wire_dtype
    if moe_over:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, **moe_over))
    return cfg


def init_params(cfg: ModelConfig, dist: Optional[DistCtx], key) -> dict:
    """Random parameters from ``key``; on a mesh each leaf is created with
    its ``param_shardings`` placement (experts split over the EP axes)."""
    init = partial(Z.init_params, cfg)
    if dist is None:
        return jax.jit(init)(key)
    shardings = param_shardings(cfg, dist, jax.eval_shape(init, key))
    return jax.jit(init, out_shardings=shardings)(key)


def new_cache(cfg: ModelConfig, dist: Optional[DistCtx], batch: int,
              max_len: int) -> dict:
    """An empty KV cache in the compute dtype; on a mesh placed by
    ``cache_pspecs``."""
    init = partial(Z.init_cache, cfg, batch, max_len, jnp.dtype(cfg.dtype))
    if dist is None:
        return jax.jit(init)()
    specs = cache_pspecs(cfg, dist, jax.eval_shape(init), batch)
    return jax.jit(init, out_shardings=jax.tree.map(
        lambda s: NamedSharding(dist.mesh, s), specs))()


class ServedWeights:
    """The weights the model-step programs read: the caller's master tree
    cast to the compute dtype (``model_zoo.cast_params``) by ``cast``, once
    per master tree.

    A call with the same master leaves as the last (by identity) returns
    the kept copy; other leaves run ``cast`` once, inside a host span
    ``repro.serve.cast``, and count in ``casts``.  Where the cast would
    change no leaf (masters already in the compute dtype), ``cast`` is None
    and the masters are served as they are.  The masters' leaves are held
    as long as the copy."""

    def __init__(self, cast):
        self.cast = cast
        self.casts = 0
        self._masters = None        # (leaves, treedef) of the last masters
        self._served = None

    def __call__(self, params: dict) -> dict:
        if self.cast is None:
            return params
        leaves, tree = jax.tree.flatten(params)
        last = self._masters
        if last is None or tree != last[1] or any(
                a is not b for a, b in zip(leaves, last[0])):
            with span("repro.serve.cast"):
                self._served = self.cast(params)
            self._masters = leaves, tree
            self.casts += 1
        return self._served


@dataclasses.dataclass(frozen=True)
class ServedProgram:
    """A compiled model-step program (``compiled``) called with the master
    weights: ``weights`` resolves them to the served copy it takes."""
    compiled: object
    weights: ServedWeights

    def __call__(self, params: dict, *args):
        return self.compiled(self.weights(params), *args)

    def as_text(self) -> str:
        return self.compiled.as_text()

    def memory_analysis(self):
        return self.compiled.memory_analysis()


def compile_steps(cfg: ModelConfig, dist: Optional[DistCtx], params: dict,
                  cache: dict, prompts):
    """Ahead-of-time compiled ``(prefill, step)`` for these shapes, called
    as ``prefill(params, cache, prompts)`` and ``step(params, cache,
    tokens, pos)`` with the master weights ``params``.

    The programs take the served weights, the masters cast to the compute
    dtype by a third program, ``jit_serve_weights`` (each leaf keeps its
    sharding), run once per master tree and kept by the
    :class:`ServedWeights` both share; dropping both frees the copy.
    ``prefill`` is None where the prompt goes through the decode step (a
    model-axis mesh shards the cache; mamba stacks)."""
    dtype = jnp.dtype(cfg.dtype)

    # named functions, so that the programs read jit_serve_weights,
    # jit_decode_step and jit_prefill in a profiler trace
    def serve_weights(params):
        return Z.cast_params(params, dtype)

    def decode_step(params, cache, tokens, pos):
        return Z.decode_step(cfg, params, cache, tokens, pos, dist=dist,
                             moe_mode="ll")

    def prefill(params, cache, tokens):
        return Z.prefill(cfg, params, cache, tokens, moe_mode="ht")

    shapes = jax.eval_shape(serve_weights, params)
    cast = None
    if any(a.dtype != b.dtype for a, b in zip(jax.tree.leaves(params),
                                             jax.tree.leaves(shapes))):
        shardings = jax.tree.map(lambda x: x.sharding, params)
        cast = jax.jit(serve_weights, out_shardings=shardings).lower(
            params).compile()
        params = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)
    weights = ServedWeights(cast)
    step = jax.jit(decode_step, donate_argnums=(1,)).lower(
        params, cache, prompts[:, :1], jnp.int32(0)).compile()
    step = ServedProgram(step, weights)
    if cfg.mamba.enabled or (dist is not None and dist.model_axis is not None):
        return None, step
    return ServedProgram(jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, prompts).compile(), weights), step


@partial(jax.jit, static_argnums=1)
def _greedy(logits, vocab: int):
    return jnp.argmax(logits[:, :vocab], axis=-1)[:, None].astype(jnp.int32)


def generate(cfg: ModelConfig, prefill, step, params: dict, cache: dict,
             prompts, gen: int):
    """Greedy generation of ``gen`` tokens after ``prompts`` (B, S), with
    ``prefill`` and ``step`` from :func:`compile_steps`, called with the
    master weights ``params``: the programs take the served bf16 copy, cast
    on the first call with a new master tree and kept after it.

    Returns ``(tokens (B, gen) int32, logits (B, gen, V_pad) f32)``: row
    ``i`` of the logits is the distribution token ``i`` was drawn from.
    ``cache`` is donated.  Host spans (``launch/tracing.py``):
    ``repro.serve.prefill`` round the prompt, ``repro.serve.cast`` round a
    cast of the masters (inside the first call that needs it),
    ``repro.serve.step`` round each decode-step call, ``repro.serve.sample``
    round each greedy pick, ``repro.serve.stack`` round the final stacking,
    ``repro.host.gc`` round each garbage collection."""
    S = prompts.shape[1]
    with gc_spans():
        with span("repro.serve.prefill"):
            if prefill is not None:
                logits, cache = prefill(params, cache, prompts)
            else:
                for t in range(S):
                    with span("repro.serve.step", step=t):
                        logits, cache = step(params, cache,
                                             prompts[:, t:t + 1],
                                             jnp.int32(t))
        logits_all = [logits]
        with span("repro.serve.sample"):
            tok = _greedy(logits, cfg.vocab_size)
        tokens = [tok]
        for t in range(S, S + gen - 1):
            with span("repro.serve.step", step=t):
                logits, cache = step(params, cache, tok, jnp.int32(t))
            logits_all.append(logits)
            with span("repro.serve.sample"):
                tok = _greedy(logits, cfg.vocab_size)
            tokens.append(tok)
        with span("repro.serve.stack"):
            return (jnp.concatenate(tokens, axis=1),
                    jnp.stack(logits_all, axis=1))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth cut (default: published, or 2 if --reduced)")
    ap.add_argument("--d-model", type=int, default=128,
                    help="width of the --reduced config")
    ap.add_argument("--vocab", type=int, default=None,
                    help="vocabulary cut (default: published, or 512 if "
                         "--reduced)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="none", choices=["none", "local"])
    ap.add_argument("--local-model-axis", type=int, default=4)
    ap.add_argument("--ep-backend", default="",
                    help="EP transport backend (e.g. jax_collectives, "
                         "simulated_rdma); default: the config's choice")
    ap.add_argument("--wire-dtype", default="",
                    choices=["", "fp32", "fp8", "int8"],
                    help="dispatch wire payload dtype (DESIGN §14)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_bench_mesh

    enable_compile_cache()
    cfg = build_config(args.arch, reduced=args.reduced, layers=args.layers,
                       d_model=args.d_model, vocab=args.vocab,
                       ep_backend=args.ep_backend,
                       wire_dtype=args.wire_dtype)
    dist = None
    if args.mesh == "local":
        mesh = make_bench_mesh(len(jax.devices()), model=args.local_model_axis)
        dist = make_dist_ctx(cfg, mesh)

    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, dist, key)
    B, max_len = args.batch, args.prompt_len + args.gen
    prompts = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    prefill, step = compile_steps(cfg, dist, params,
                                  new_cache(cfg, dist, B, max_len), prompts)
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    tokens, _ = generate(cfg, prefill, step, params,
                         new_cache(cfg, dist, B, max_len), prompts, args.gen)
    tokens = jax.block_until_ready(tokens)
    dt = time.perf_counter() - t0
    total = tokens.size
    print(f"[serve] compiled in {t_compile:.2f}s; generated {total} tokens "
          f"in {dt:.2f}s ({total / dt:.1f} tok/s), first sequence: "
          f"{tokens[0, :8].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
