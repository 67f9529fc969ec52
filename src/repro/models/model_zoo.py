"""Model zoo: init/forward/loss/decode for every assigned architecture.

Layers repeat with a static ``period`` (1 for uniform stacks, 8 for jamba);
parameters for each slot in the period are stacked over ``n_periods`` and the
forward pass is a single ``lax.scan`` over periods (small HLO, fast 512-way
SPMD compiles).  VLM/audio frontends are stubs: precomputed prefix embeddings
arrive via ``input_specs`` and are prepended to the embedded token stream.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell
from repro.core.moe import padded_experts_static
from repro.distributed.sharding import DistCtx, scan_period
from repro.models import blocks as B
from repro.models.layers import rmsnorm, rmsnorm_init

Array = jax.Array


# ------------------------------------------------------------------ init --
def init_params(cfg: ModelConfig, key: Array) -> dict:
    period, n_periods = scan_period(cfg)
    keys = jax.random.split(key, period + 2)
    vp = cfg.padded_vocab()
    d = cfg.d_model
    params: dict = {
        "embed": jax.random.normal(keys[-1], (vp, d), jnp.float32) * 0.02,
        "final_ln": rmsnorm_init(d),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[-2], (d, vp), jnp.float32) / math.sqrt(d)

    def stack_slot(s: int):
        ks = jax.random.split(keys[s], n_periods)
        ps = [B.block_init(cfg, s, ks[i]) for i in range(n_periods)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)

    params["blocks"] = {f"slot{s}": stack_slot(s) for s in range(period)}
    return params


def cast_params(params: dict, dtype) -> dict:
    """Cast float params to compute dtype (norm scales stay fp32)."""
    def f(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if x.dtype == jnp.float32 and not any(
                t in name for t in ("ln", "norm", "A_log", "dt_b", "router",
                                    "D", "conv_b")):
            return x.astype(dtype)
        return x
    with jax.named_scope("cast"):
        return jax.tree_util.tree_map_with_path(f, params)


# --------------------------------------------------------------- forward --
def forward(cfg: ModelConfig, params: dict, tokens: Array,
            prefix_embeds: Optional[Array] = None, *,
            dist: Optional[DistCtx] = None, moe_mode: str = "ht",
            moe_chunks: int = 1, causal_skip: bool = False,
            unroll: bool = False, sp_islands: bool = False,
            remat_policy: str = "full",
            moe_backend=None) -> tuple[Array, dict]:
    """tokens (B, S_txt) [+ prefix (B, S_pre, D)] -> hidden (B, S, D), aux.

    ``moe_backend``: name or EPBackend instance shared by every MoE layer
    (the persistent-session path registers transport state once per step;
    host-backend instances require ``unroll=True`` outside jit)."""
    period, n_periods = scan_period(cfg)
    x = B.vocab_embed(dist, params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    if dist is not None:
        x = dist.constraint(x, dist.batch_axes, dist.seq_axis, None)
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (x.shape[0], S))

    def period_body(x, slot_params):
        aux_l = {}
        aux_loss = jnp.float32(0.0)
        dropped = jnp.float32(0.0)
        for s in range(period):
            x, aux = B.block_apply(cfg, dist, slot_params[f"slot{s}"], x,
                                   positions, moe_mode=moe_mode,
                                   moe_chunks=moe_chunks,
                                   causal_skip=causal_skip,
                                   sp_islands=sp_islands,
                                   moe_backend=moe_backend)
            aux_loss = aux_loss + aux.get("aux_loss", jnp.float32(0.0))
            dropped = dropped + aux.get("dropped", jnp.float32(0.0))
            if "load" in aux:
                aux_l[f"slot{s}"] = aux["load"]
        return x, {"aux_loss": aux_loss, "dropped": dropped, "loads": aux_l}

    body = period_body
    if cfg.remat:
        policy = {"full": None,
                  "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                  }[remat_policy]
        body = jax.checkpoint(period_body, prevent_cse=False, policy=policy)
    if unroll:
        # python-loop over periods (used by the dry-run cost extrapolation:
        # XLA cost_analysis counts a while body once, so truncated models
        # are compiled scan-free and extrapolated; see launch/dryrun.py)
        auxes = []
        for i in range(n_periods):
            slot_i = jax.tree.map(lambda a: a[i], params["blocks"])
            x, a = body(x, slot_i)
            auxes.append(a)
        aux_s = jax.tree.map(lambda *xs: jnp.stack(xs), *auxes)
    else:
        x, aux_s = lax.scan(body, x, params["blocks"])
    aux = {"aux_loss": aux_s["aux_loss"].sum(),
           "dropped": aux_s["dropped"].mean() if cfg.moe.enabled else jnp.float32(0.0),
           "loads": aux_s["loads"]}  # per slot: (n_periods, E) expert loads
    with jax.named_scope("lm_head"):
        x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    return x, aux


def lm_head_weight(cfg: ModelConfig, params: dict) -> Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def loss_fn(cfg: ModelConfig, params: dict, tokens: Array, labels: Array,
            prefix_embeds: Optional[Array] = None, *,
            dist: Optional[DistCtx] = None, moe_mode: str = "ht",
            moe_chunks: int = 1, causal_skip: bool = False,
            loss_chunk: int = 2048, unroll: bool = False,
            sp_islands: bool = False,
            remat_policy: str = "full",
            moe_backend=None) -> tuple[Array, dict]:
    """Next-token cross entropy with a vocab-parallel, seq-chunked head."""
    dtype = jnp.dtype(cfg.dtype)
    x, aux = forward(cfg, cast_params(params, dtype), tokens, prefix_embeds,
                     dist=dist, moe_mode=moe_mode, moe_chunks=moe_chunks,
                     causal_skip=causal_skip, unroll=unroll,
                     sp_islands=sp_islands, remat_policy=remat_policy,
                     moe_backend=moe_backend)
    head = lm_head_weight(cfg, params).astype(dtype)
    if prefix_embeds is not None:  # prefix positions carry no label
        x = x[:, prefix_embeds.shape[1]:]
    total, count = _chunked_xent(cfg, dist, x, head, labels, loss_chunk)
    loss = total / jnp.maximum(count, 1.0) + aux["aux_loss"]
    metrics = {"xent": total / jnp.maximum(count, 1.0),
               "aux_loss": aux["aux_loss"], "dropped": aux["dropped"],
               "loads": jax.lax.stop_gradient(aux["loads"])}
    return loss, metrics


def _chunked_xent(cfg: ModelConfig, dist: Optional[DistCtx], x: Array,
                  head: Array, labels: Array, chunk: int):
    Bsz, S, D = x.shape
    V = head.shape[1]
    chunk = min(chunk, S)
    n_chunks = -(-S // chunk)
    total = jnp.float32(0.0)
    count = jnp.float32(0.0)
    for c in range(n_chunks):
        sl = slice(c * chunk, min((c + 1) * chunk, S))
        xc, yc = x[:, sl], labels[:, sl]
        if dist is not None and dist.model_axis:
            t, n = _xent_island(dist, xc, head, yc, cfg.vocab_size)
        else:
            logits = (xc @ head).astype(jnp.float32)
            logits = jnp.where(jnp.arange(V)[None, None] < cfg.vocab_size,
                               logits, -jnp.inf)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
            ok = (yc >= 0).astype(jnp.float32)
            t = ((lse - gold) * ok).sum()
            n = ok.sum()
        total += t
        count += n
    return total, count


def _xent_island(dist: DistCtx, xc: Array, head: Array, yc: Array,
                 vocab_real: int):
    """Vocab-parallel cross entropy: head (D, V/model) local per shard."""
    mesh, m, bd = dist.mesh, dist.model_axis, dist.batch_axes
    V_local = head.shape[1] // mesh.shape[m]

    def island(x_l, h_l, y_l):
        start = lax.axis_index(m) * V_local
        logits = (x_l @ h_l).astype(jnp.float32)          # (B_l, Sc, V_l)
        vmask = (start + jnp.arange(V_local)) < vocab_real
        logits = jnp.where(vmask[None, None], logits, -jnp.inf)
        # stability max is gradient-free (lse grad == softmax either way);
        # pmax has no JVP rule, so it must see a symbolic-zero tangent:
        # stop_gradient goes INSIDE the pmax.
        mx = lax.pmax(lax.stop_gradient(logits.max(-1)), m)
        se = lax.psum(jnp.exp(logits - mx[..., None]).sum(-1), m)
        lse = mx + jnp.log(se)
        idx = y_l - start
        ok_v = (idx >= 0) & (idx < V_local)
        gold_l = jnp.take_along_axis(logits, jnp.clip(idx, 0, V_local - 1)[..., None],
                                     axis=-1)[..., 0]
        gold = lax.psum(jnp.where(ok_v, gold_l, 0.0), m)
        ok = (y_l >= 0).astype(jnp.float32)
        t = lax.psum(((lse - gold) * ok).sum(), (m,) + tuple(bd))
        n = lax.psum(ok.sum(), (m,) + tuple(bd))
        return t, n

    return jax.shard_map(island, mesh=mesh,
                         in_specs=(P(bd, None, None), P(None, m), P(bd, None)),
                         out_specs=(P(), P()), check_vma=False)(xc, head, yc)


# ---------------------------------------------------------------- decode --
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    period, n_periods = scan_period(cfg)

    def stack_slot(s: int):
        c = B.block_init_cache(cfg, s, batch, max_len, dtype)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_periods,) + x.shape).copy(),
            c)

    return {f"slot{s}": stack_slot(s) for s in range(period)}


def prefill(cfg: ModelConfig, params: dict, cache: dict, tokens: Array,
            *, dist: Optional[DistCtx] = None, moe_mode: str = "ht",
            unroll: bool = False) -> tuple[Array, dict]:
    """Batched prompt prefill: ONE forward pass over tokens (B, S) that
    fills ``cache[:, :S]`` for every attention layer and returns the
    last-position logits (B, V_pad) — the single-pass replacement for S
    ``decode_step`` calls.  Local-cache path (no model-axis sharding) and
    attention-only stacks; mamba archs keep the per-token loop."""
    assert dist is None or dist.model_axis is None, \
        "batched prefill is the local-cache path; sharded caches decode"
    assert not cfg.mamba.enabled, "mamba prefill goes through decode_step"
    period, n_periods = scan_period(cfg)
    dtype = jnp.dtype(cfg.dtype)
    cparams = cast_params(params, dtype)
    x = B.vocab_embed(dist, cparams["embed"], tokens)
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (x.shape[0], S))

    def period_body(x, scanned):
        slot_params, slot_cache = scanned
        new_cache = {}
        for s in range(period):
            x, c2, _ = B.block_prefill(cfg, dist, slot_params[f"slot{s}"], x,
                                       slot_cache[f"slot{s}"], positions,
                                       moe_mode=moe_mode)
            new_cache[f"slot{s}"] = c2
        return x, new_cache

    if unroll:
        caches = []
        for i in range(n_periods):
            sl = jax.tree.map(lambda a: a[i], (cparams["blocks"], cache))
            x, c2 = period_body(x, sl)
            caches.append(c2)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
    else:
        x, new_cache = lax.scan(period_body, x, (cparams["blocks"], cache))
    with jax.named_scope("lm_head"):
        x = rmsnorm(x, cparams["final_ln"], cfg.norm_eps)
        head = lm_head_weight(cfg, cparams)
        logits = (x[:, -1] @ head).astype(jnp.float32)
        if dist is not None:
            logits = dist.constraint(logits, dist.batch_axes, dist.model_axis)
    return logits, new_cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict, tokens: Array,
                pos, *, dist: Optional[DistCtx] = None,
                moe_mode: str = "ll", unroll: bool = False) -> tuple[Array, dict]:
    """One decode step: tokens (B, 1) at position ``pos`` (same for batch).

    Returns (logits (B, V_pad), new_cache).
    """
    period, n_periods = scan_period(cfg)
    dtype = jnp.dtype(cfg.dtype)
    cparams = cast_params(params, dtype)
    x = B.vocab_embed(dist, cparams["embed"], tokens)
    if dist is not None:
        from repro.distributed.sharding import effective_batch_axes
        x = dist.constraint(x, effective_batch_axes(dist, x.shape[0]),
                            None, None)

    def period_body(x, scanned):
        slot_params, slot_cache = scanned
        new_cache = {}
        for s in range(period):
            x, c2, _ = B.block_decode(cfg, dist, slot_params[f"slot{s}"], x,
                                      slot_cache[f"slot{s}"], pos,
                                      moe_mode=moe_mode)
            new_cache[f"slot{s}"] = c2
        return x, new_cache

    if unroll:
        caches = []
        for i in range(n_periods):
            sl = jax.tree.map(lambda a: a[i], (cparams["blocks"], cache))
            x, c2 = period_body(x, sl)
            caches.append(c2)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
    else:
        x, new_cache = lax.scan(period_body, x, (cparams["blocks"], cache))
    with jax.named_scope("lm_head"):
        x = rmsnorm(x, cparams["final_ln"], cfg.norm_eps)
        head = lm_head_weight(cfg, cparams)
        logits = (x[:, 0] @ head).astype(jnp.float32)
        if dist is not None:
            logits = dist.constraint(logits, dist.batch_axes, dist.model_axis)
    return logits, new_cache
