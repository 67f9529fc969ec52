"""Transformer/Mamba blocks with mesh-aware sharding constraints, plus the
distributed decode-attention and vocab-parallel embedding islands.

Layout contract (DESIGN.md §4): the residual stream between blocks is
``P(batch_axes, "model", None)`` — batch over data axes, sequence over the
model axis (sequence parallelism).  Attention/MLP gather the sequence and
reduce-scatter it back (Megatron-style SP); the MoE island consumes tokens
in-place (EP needs no gather); Mamba gathers the sequence and keeps d_inner
on "model".
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional  # noqa: F401

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.moe import moe_apply, moe_init
from repro.distributed.sharding import DistCtx
from repro.models import mamba as mamba_mod
from repro.models.layers import (AttnParams, KVCache, MLPParams, _qkv,
                                 apply_rope, attention, attn_init,
                                 decode_attention_local, decode_qkv,
                                 flash_attention_blocked, mlp_init, rmsnorm,
                                 rmsnorm_init, swiglu)

Array = jax.Array


def _c(dist: Optional[DistCtx], x: Array, *spec):
    return dist.constraint(x, *spec) if dist is not None else x


def block_init(cfg: ModelConfig, layer_slot: int, key: Array) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict = {"ln1": rmsnorm_init(cfg.d_model), "ln2": rmsnorm_init(cfg.d_model)}
    if cfg.is_attn_layer(layer_slot):
        ap = attn_init(cfg, k1)
        p["attn"] = {k: v for k, v in ap._asdict().items() if v is not None}
    elif cfg.mamba.enabled:
        p["mamba"] = mamba_mod.mamba_init(cfg, k1)
    if cfg.is_moe_layer(layer_slot):
        p["moe"] = moe_init(cfg, k2)
    elif cfg.d_ff:
        p["mlp"] = dict(mlp_init(cfg.d_model, cfg.d_ff, k3)._asdict())
    return p


def _attn_params(cfg: ModelConfig, d: dict) -> AttnParams:
    return AttnParams(wq=d["wq"], wk=d["wk"], wv=d["wv"], wo=d["wo"],
                      bq=d.get("bq"), bk=d.get("bk"), bv=d.get("bv"),
                      q_norm=d.get("q_norm"), k_norm=d.get("k_norm"))


def _mixer_scope(p: dict) -> str:
    """The layer scope of a block's token mixer (``ln1`` through its
    output projection)."""
    return "mamba" if "mamba" in p else "attention"


def block_apply(cfg: ModelConfig, dist: Optional[DistCtx], p: dict, x: Array,
                positions: Array, *, moe_mode: str = "ht",
                moe_chunks: int = 1, causal_skip: bool = False,
                sp_islands: bool = False,
                moe_backend=None) -> tuple[Array, dict]:
    """x: (B, S, D) residual (sharded P(bd, model, None)) -> (x', aux).

    ``sp_islands``: route attention/MLP through explicit shard_map islands
    (manual Megatron TP+SP: all-gather(seq) fwd / reduce-scatter bwd) instead
    of GSPMD constraint transitions — see EXPERIMENTS.md §Perf.

    ``moe_backend``: a backend name or :class:`EPBackend` instance handed to
    :func:`moe_apply` — a model passes one instance to ALL its blocks for
    the persistent-session path (registration once per step, DESIGN §16).
    """
    aux = {}
    bd = dist.batch_axes if dist else None
    use_islands = sp_islands and _islands_ok(cfg, dist, x)
    with jax.named_scope(_mixer_scope(p)):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if "attn" in p:
            if use_islands:
                h = _attention_island(cfg, dist, p["attn"], h, positions,
                                      causal_skip=causal_skip)
            else:
                h = _c(dist, h, bd, None, None)          # gather seq (SP)
                h = attention(cfg, _attn_params(cfg, p["attn"]), h,
                              positions, causal_skip=causal_skip)
                h = _c(dist, h, bd, dist.seq_axis if dist else None, None)
        elif "mamba" in p:
            h = _c(dist, h, bd, None, None)
            h = mamba_mod.mamba_apply(cfg, p["mamba"], h)
            h = _c(dist, h, bd, dist.seq_axis if dist else None, None)
    x = x + h

    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        h, aux = moe_apply(cfg, dist, p["moe"], h, mode=moe_mode,
                           chunks=moe_chunks, backend=moe_backend)
    elif "mlp" in p:
        if use_islands:
            h = _mlp_island(cfg, dist, p["mlp"], h)
        else:
            h = _c(dist, h, bd, None, None)
            h = swiglu(MLPParams(**{k: p["mlp"][k]
                                    for k in ("w_gate", "w_up", "w_down")}), h)
            h = _c(dist, h, bd, dist.seq_axis if dist else None, None)
    else:
        h = jnp.zeros_like(h)
    return x + h, aux


def _islands_ok(cfg: ModelConfig, dist: Optional[DistCtx], x: Array) -> bool:
    if dist is None or dist.model_axis is None:
        return False
    msz = dist.mesh.shape[dist.model_axis]
    import math as _m
    bsz = _m.prod(dist.mesh.shape[a] for a in dist.batch_axes)
    return (x.shape[1] % msz == 0 and x.shape[0] % bsz == 0
            and (cfg.attention_free or cfg.n_heads % msz == 0)
            and (not cfg.d_ff or cfg.d_ff % msz == 0))


def _attention_island(cfg: ModelConfig, dist: DistCtx, pa: dict, x: Array,
                      positions: Array, *, causal_skip: bool) -> Array:
    """Manual TP+SP attention: all-gather(seq) -> local-head attention ->
    reduce-scatter(seq).  Autodiff through shard_map transposes the
    collectives minimally (gather^T = psum_scatter)."""
    mesh, m, bd = dist.mesh, dist.model_axis, dist.batch_axes
    msz = mesh.shape[m]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    H_l = H // msz
    kv_sharded = Hkv % msz == 0
    rep = H // Hkv

    def island(x_l, pos_l, wq, wk, wv, wo, bq, bk, bv, qn, kn):
        xg = lax.all_gather(x_l, m, axis=1, tiled=True)   # (B_l, S, D)
        pos = lax.all_gather(pos_l, m, axis=1, tiled=True)
        dt = xg.dtype
        midx = lax.axis_index(m)
        q = jnp.einsum("bsd,dhk->bshk", xg, wq.astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", xg, wk.astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", xg, wv.astype(dt))
        if bq is not None:
            q = q + bq.astype(dt)
            k = k + bk.astype(dt)
            v = v + bv.astype(dt)
        if qn is not None:
            q = rmsnorm(q, qn, cfg.norm_eps)
            k = rmsnorm(k, kn, cfg.norm_eps)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        if not kv_sharded:
            # local q heads [midx*H_l, (midx+1)*H_l) need kv heads
            # [midx*H_l//rep, ...): gather the aligned slice dynamically
            kv_per_shard = max(1, H_l // rep)
            start = (midx * H_l) // rep
            k = lax.dynamic_slice_in_dim(k, start, kv_per_shard, axis=2)
            v = lax.dynamic_slice_in_dim(v, start, kv_per_shard, axis=2)
            rep_l = H_l // kv_per_shard
        else:
            rep_l = rep
        S = xg.shape[1]
        blk = min(512, S)
        o = flash_attention_blocked(q, k, v, causal=True, q_block=blk,
                                    kv_block=blk, causal_skip=causal_skip)
        y = jnp.einsum("bshk,hkd->bsd", o, wo.astype(dt))  # partial over m
        return lax.psum_scatter(y, m, scatter_dimension=1, tiled=True)

    qspec = P(None, m, None)
    kvspec = P(None, m, None) if kv_sharded else P(None, None, None)
    bspec_q = P(m, None)
    bspec_kv = P(m, None) if kv_sharded else P(None, None)
    args = [x, positions, pa["wq"], pa["wk"], pa["wv"], pa["wo"],
            pa.get("bq"), pa.get("bk"), pa.get("bv"),
            pa.get("q_norm"), pa.get("k_norm")]
    in_specs = [P(bd, m, None), P(bd, m), qspec, kvspec, kvspec,
                P(m, None, None), bspec_q, bspec_kv, bspec_kv,
                P(None), P(None)]
    # drop None args (optional biases/norms) — shard_map needs real arrays
    keep = [i for i, a in enumerate(args) if a is not None]
    none_mask = [a is None for a in args]

    def wrapper(*present):
        full = []
        it = iter(present)
        for is_none in none_mask:
            full.append(None if is_none else next(it))
        return island(*full)

    return jax.shard_map(wrapper, mesh=mesh,
                         in_specs=tuple(in_specs[i] for i in keep),
                         out_specs=P(bd, m, None),
                         check_vma=False)(*[args[i] for i in keep])


def _mlp_island(cfg: ModelConfig, dist: DistCtx, pm: dict, x: Array) -> Array:
    """Manual TP+SP SwiGLU MLP island."""
    mesh, m, bd = dist.mesh, dist.model_axis, dist.batch_axes

    def island(x_l, wg, wu, wd):
        xg = lax.all_gather(x_l, m, axis=1, tiled=True)   # (B_l, S, D)
        dt = xg.dtype
        h = jax.nn.silu(xg @ wg.astype(dt)) * (xg @ wu.astype(dt))
        y = h @ wd.astype(dt)                             # partial over m
        return lax.psum_scatter(y, m, scatter_dimension=1, tiled=True)

    return jax.shard_map(
        island, mesh=mesh,
        in_specs=(P(bd, m, None), P(None, m), P(None, m), P(m, None)),
        out_specs=P(bd, m, None), check_vma=False)(
        x, pm["w_gate"], pm["w_up"], pm["w_down"])


# ------------------------------------------------------------ decode path --
class BlockCache(NamedTuple):
    """Per-layer decode state: exactly one of (kv, mamba) is meaningful."""
    k: Array
    v: Array
    conv: Array
    ssm: Array


def block_init_cache(cfg: ModelConfig, layer_slot: int, batch: int,
                     max_len: int, dtype=jnp.bfloat16) -> BlockCache:
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    if cfg.is_attn_layer(layer_slot):
        z = jnp.zeros((batch, max_len, hkv, hd), dtype)
        return BlockCache(k=z, v=z, conv=jnp.zeros((batch, 1, 1), dtype),
                          ssm=jnp.zeros((batch, 1, 1), jnp.float32))
    mc = mamba_mod.mamba_init_cache(cfg, batch, dtype)
    return BlockCache(k=jnp.zeros((batch, 1, 1, 1), dtype),
                      v=jnp.zeros((batch, 1, 1, 1), dtype),
                      conv=mc.conv, ssm=mc.ssm)


def _decode_attn_dist(dist: DistCtx, q, k_new, v_new, cache: BlockCache,
                      pos) -> tuple[Array, BlockCache]:
    """Split-sequence (flash-decoding) attention over the sharded KV cache.

    Global shapes: q (B,1,H,hd); k_new/v_new (B,1,Hkv,hd); cache.k/v
    (B, S_max, Hkv, hd) sharded P(bd_eff, seq_axes, None, None), where
    seq_axes = model axis plus any batch axes idled by a tiny decode batch
    (long_500k shards its 512k cache over every axis; DESIGN.md §4).
    """
    from repro.distributed.sharding import (cache_seq_axes,
                                            effective_batch_axes)
    mesh = dist.mesh
    Bg = q.shape[0]
    bd = effective_batch_axes(dist, Bg)
    seq_axes = cache_seq_axes(dist, Bg)
    n_seq_shards = math.prod(mesh.shape[a] for a in seq_axes)
    S_max = cache.k.shape[1]
    S_local = S_max // n_seq_shards

    def island(q_l, kn, vn, kc, vc, pos):
        idx = jnp.int32(0)
        for a in seq_axes:
            idx = idx * mesh.shape[a] + lax.axis_index(a)
        start = idx * S_local
        loc = jnp.clip(pos - start, 0, S_local - 1)
        in_rng = (pos >= start) & (pos < start + S_local)
        kc2 = lax.dynamic_update_slice_in_dim(kc, kn.astype(kc.dtype), loc, 1)
        vc2 = lax.dynamic_update_slice_in_dim(vc, vn.astype(vc.dtype), loc, 1)
        kc = jnp.where(in_rng, kc2, kc)
        vc = jnp.where(in_rng, vc2, vc)
        part = decode_attention_local(q_l, kc, vc, pos, start=start)
        mx = lax.pmax(part.m, seq_axes)
        c = jnp.exp(jnp.where(jnp.isneginf(part.m), -jnp.inf, part.m - mx))
        o = lax.psum(part.o * c[..., None], seq_axes)
        l = lax.psum(part.l * c, seq_axes)
        o = o / jnp.maximum(l, 1e-9)[..., None]
        return o.astype(q_l.dtype), kc, vc

    sq = seq_axes if len(seq_axes) > 1 else (seq_axes[0] if seq_axes else None)
    o, k2, v2 = jax.shard_map(
        island, mesh=mesh,
        in_specs=(P(bd, None, None, None), P(bd, None, None, None),
                  P(bd, None, None, None), P(bd, sq, None, None),
                  P(bd, sq, None, None), P()),
        out_specs=(P(bd, None, None, None), P(bd, sq, None, None),
                   P(bd, sq, None, None)),
        check_vma=False)(q, k_new, v_new, cache.k, cache.v,
                         jnp.asarray(pos, jnp.int32))
    return o, cache._replace(k=k2, v=v2)


def block_decode(cfg: ModelConfig, dist: Optional[DistCtx], p: dict,
                 x: Array, cache: BlockCache, pos,
                 *, moe_mode: str = "ll") -> tuple[Array, BlockCache, dict]:
    """One-token decode: x (B, 1, D)."""
    aux = {}
    with jax.named_scope(_mixer_scope(p)):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if "attn" in p:
            ap = _attn_params(cfg, p["attn"])
            q, k_new, v_new = decode_qkv(cfg, ap, h, pos)
            if dist is not None and dist.model_axis:
                o, cache = _decode_attn_dist(dist, q, k_new, v_new, cache,
                                             pos)
            else:
                kc = lax.dynamic_update_slice_in_dim(
                    cache.k, k_new.astype(cache.k.dtype), pos, 1)
                vc = lax.dynamic_update_slice_in_dim(
                    cache.v, v_new.astype(cache.v.dtype), pos, 1)
                cache = cache._replace(k=kc, v=vc)
                part = decode_attention_local(q, kc, vc, pos)
                l = jnp.where(part.l == 0, 1.0, part.l)
                o = (part.o / l[..., None]).astype(h.dtype)
            h = jnp.einsum("bshk,hkd->bsd", o, ap.wo.astype(h.dtype))
        elif "mamba" in p:
            mc = mamba_mod.MambaCache(conv=cache.conv, ssm=cache.ssm)
            h, mc = mamba_mod.mamba_decode_step(cfg, p["mamba"], h, mc)
            cache = cache._replace(conv=mc.conv, ssm=mc.ssm)
    x = x + h

    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        h, aux = moe_apply(cfg, dist, p["moe"], h, mode=moe_mode)
    elif "mlp" in p:
        h = swiglu(MLPParams(**{k: p["mlp"][k]
                                for k in ("w_gate", "w_up", "w_down")}), h)
    else:
        h = jnp.zeros_like(h)
    return x + h, cache, aux


def block_prefill(cfg: ModelConfig, dist: Optional[DistCtx], p: dict,
                  x: Array, cache: BlockCache, positions: Array,
                  *, moe_mode: str = "ht",
                  moe_chunks: int = 1) -> tuple[Array, BlockCache, dict]:
    """Batched prompt prefill: x (B, S, D) -> (x', cache', aux).

    Causal attention over the whole prompt while the projected k/v land in
    ``cache[:, :S]`` in ONE ``dynamic_update_slice`` — the batched
    replacement for S ``block_decode`` calls (the serving launcher's old
    placeholder).  Local-cache path only: a model-axis mesh shards the
    cache over chips (``_decode_attn_dist``), where prefill stays with the
    distributed decode loop.
    """
    aux = {}
    with jax.named_scope("attention"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if "attn" in p:
            ap = _attn_params(cfg, p["attn"])
            q, k_new, v_new = _qkv(cfg, ap, h, positions)
            S = x.shape[1]
            blk = min(512, S)
            o = flash_attention_blocked(q, k_new, v_new, causal=True,
                                        q_block=blk, kv_block=blk)
            kc = lax.dynamic_update_slice_in_dim(
                cache.k, k_new.astype(cache.k.dtype), 0, 1)
            vc = lax.dynamic_update_slice_in_dim(
                cache.v, v_new.astype(cache.v.dtype), 0, 1)
            cache = cache._replace(k=kc, v=vc)
            h = jnp.einsum("bshk,hkd->bsd", o, ap.wo.astype(h.dtype))
        elif "mamba" in p:
            raise NotImplementedError(
                "batched prefill needs the post-prompt recurrent state; "
                "mamba layers prefill through the per-token decode loop")
    x = x + h

    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        h, aux = moe_apply(cfg, dist, p["moe"], h, mode=moe_mode,
                           chunks=moe_chunks)
    elif "mlp" in p:
        h = swiglu(MLPParams(**{k: p["mlp"][k]
                                for k in ("w_gate", "w_up", "w_down")}), h)
    else:
        h = jnp.zeros_like(h)
    return x + h, cache, aux


# ---------------------------------------------- vocab-parallel embedding --
def vocab_embed(dist: Optional[DistCtx], embed: Array, tokens: Array) -> Array:
    """tokens (B, S) -> (B, S, D); embed (V_pad, D) sharded P("model", None)."""
    with jax.named_scope("embed"):
        return _vocab_embed(dist, embed, tokens)


def _vocab_embed(dist: Optional[DistCtx], embed: Array,
                 tokens: Array) -> Array:
    if dist is None or dist.model_axis is None:
        return jnp.take(embed, tokens, axis=0)
    from repro.distributed.sharding import effective_batch_axes
    mesh, m = dist.mesh, dist.model_axis
    Bg, S = tokens.shape
    bd = effective_batch_axes(dist, Bg)
    sq = m if (S > 1 and S % mesh.shape[m] == 0) else None
    V_local = embed.shape[0] // mesh.shape[m]

    def island(emb_l, tok_l):
        # tokens are seq-sharded over the same axis as the vocab slices:
        # gather the (tiny, int) token ids, look up against the local vocab
        # slice, then reduce-scatter the partial embeddings back to the
        # seq-sharded layout (Megatron vocab-parallel embedding).
        if sq is not None:
            tok_all = lax.all_gather(tok_l, m, axis=1, tiled=True)  # (B_l, S)
        else:
            tok_all = tok_l
        start = lax.axis_index(m) * V_local
        idx = tok_all - start
        ok = (idx >= 0) & (idx < V_local)
        got = jnp.take(emb_l, jnp.clip(idx, 0, V_local - 1), axis=0)
        got = jnp.where(ok[..., None], got, 0)
        if sq is not None:
            return lax.psum_scatter(got, m, scatter_dimension=1, tiled=True)
        return lax.psum(got, m)

    return jax.shard_map(island, mesh=mesh,
                         in_specs=(P(m, None), P(bd, sq)),
                         out_specs=P(bd, sq, None),
                         check_vma=False)(embed, tokens)
