"""MoE FFN layer: router + UCCL-EP dispatch/combine + grouped expert SwiGLU
(+ optional always-on shared experts which bypass dispatch, qwen2-moe style).

The expert-parallel path runs inside one ``shard_map`` island over the full
mesh; without a mesh (CPU smoke tests) it falls back to the dense oracle.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, _round_up
from repro.core import plan as planlib
from repro.core.backend import get_backend
from repro.core.ep import EPSpec, moe_ref
from repro.core.routing import RouterParams, route, router_init
from repro.distributed.sharding import DistCtx
from repro.kernels import ops as kops
from repro.models.layers import MLPParams, mlp_init, swiglu

Array = jax.Array


def padded_experts_static(cfg: ModelConfig) -> int:
    """Mesh-independent padded expert count (divisible by EP16 and, when the
    model has >=32 experts, by EP32) so checkpoints are mesh-portable."""
    e = cfg.moe.n_experts
    return _round_up(e, 32) if e >= 32 else _round_up(e, 16)


def moe_init(cfg: ModelConfig, key: Array) -> dict:
    m = cfg.moe
    e_pad = padded_experts_static(cfg)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    d, f = cfg.d_model, m.d_expert
    s, so = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    r = router_init(d, e_pad, k1, m.router_aux_free_bias)
    out = {
        "router_w": r.w,
        "w_gate": jax.random.normal(k2, (e_pad, d, f), jnp.float32) * s,
        "w_up": jax.random.normal(k3, (e_pad, d, f), jnp.float32) * s,
        "w_down": jax.random.normal(k4, (e_pad, f, d), jnp.float32) * so,
    }
    if r.bias is not None:
        out["router_b"] = r.bias
    if m.d_shared:
        out["shared"] = dict(mlp_init(d, m.d_shared, k5)._asdict())
    return out


def _expert_fn(wg, wu, wd):
    """Occupancy-carrying expert_fn: ``fn(tokens, counts)`` applies the
    grouped SwiGLU skipping rows beyond each bucket's occupied count, and
    ``fn.fused`` is the fully fused gather->FFN->scatter hot path the HT
    local compute uses (no (E, C, D) buffer materialization).

    EP dispatch buffers pad with exact zeros (scratch-row gathers), and
    swiglu(0) == 0 — ``zero_padded=True`` lets the jnp "ref" path skip the
    (pure-overhead) occupancy mask while the kernel paths use counts to
    skip the padding's MXU flops (the whole point of the contract)."""
    def fn(tokens, counts=None):  # (E_local, C, D)
        return kops.grouped_swiglu(tokens, wg, wu, wd, counts,
                                   zero_padded=True)

    def fused(x_ext, src_of_slot, w_slot, counts=None):
        return kops.gather_swiglu_scatter(x_ext, src_of_slot, w_slot,
                                          wg, wu, wd, counts,
                                          zero_padded=True)
    fn.fused = fused
    return fn


def make_ep_spec(cfg: ModelConfig, dist: DistCtx, *, mode: str,
                 chunks: int = 1, dtype=jnp.bfloat16) -> EPSpec:
    sizes = tuple(dist.mesh.shape[a] for a in dist.ep_axes)
    cf = (cfg.moe.ll_capacity_factor if mode == "ll"
          else cfg.moe.capacity_factor)
    return EPSpec(axes=tuple(dist.ep_axes), sizes=sizes,
                  n_experts=padded_experts_static(cfg), top_k=cfg.moe.top_k,
                  capacity_factor=cf, chunks=chunks, dtype=dtype,
                  mode=("ll" if mode == "ll" else "ht"),
                  wire_dtype=getattr(cfg.moe, "wire_dtype", "fp32"))


def moe_path(dist: Optional[DistCtx], mode: str, ep_backend) -> str:
    """Which compute path :func:`moe_apply` takes: ``"host"`` (a host
    transport backend, outside jit), ``"dense"`` (the :func:`moe_ref`
    oracle: no EP mesh, or ``mode="ref"``) or ``"ep"`` (the shard_map
    dispatch/combine island with the grouped expert kernels)."""
    if not ep_backend.jit_compatible and mode != "ref":
        return "host"
    if dist is None or not dist.ep_axes or mode == "ref":
        return "dense"
    return "ep"


def moe_apply(cfg: ModelConfig, dist: Optional[DistCtx], p: dict, x: Array,
              *, mode: str = "ht", chunks: int = 1,
              backend=None) -> tuple[Array, dict]:
    """x: (B, S, D) -> (y, aux).  mode: "ht" | "ll" | "ref".

    ``backend`` (default ``cfg.moe.ep_backend``) selects the EP transport
    from the :mod:`repro.core.backend` registry — a registered name, or an
    :class:`~repro.core.backend.EPBackend` *instance* (the persistent-
    session path: a model passes ONE backend object to all its MoE layers
    so guard tables/buckets/proxies register once per step, DESIGN §16).
    ``simulated_rdma`` is a host-side reference path (numpy over the
    transport substrate) — valid outside ``jit`` only, for protocol
    cross-checks and debugging.
    """
    B, S, D = x.shape
    mcfg = cfg.moe
    e_pad = p["w_gate"].shape[0]
    rparams = RouterParams(w=p["router_w"], bias=p.get("router_b"))
    # fail loud on unknown names (get_backend raises), never fall back
    be = backend if backend is not None else mcfg.ep_backend
    ep_be = get_backend(be) if isinstance(be, str) else be

    path = moe_path(dist, mode, ep_be)
    if path == "host":
        y, aux = _moe_host_sim(cfg, dist, rparams, p, x, mode, ep_be)
    elif path == "dense":
        t = x.reshape(-1, D)
        with jax.named_scope("moe.router"):
            rout = route(mcfg, rparams, t, mcfg.n_experts)
            load = planlib.expert_load(rout.top_idx, e_pad)
            # imbalance over the REAL experts only: padded slots never
            # receive tokens and would dilute the mean (4 real in 16
            # padded -> 4x)
            aux = {"aux_loss": rout.aux_loss, "dropped": jnp.float32(0.0),
                   "load": load,
                   "imbalance": planlib.load_imbalance(load[:mcfg.n_experts])}
        with jax.named_scope("moe.experts"):
            y = moe_ref(t, rout.top_idx, rout.top_w, p["w_gate"], p["w_up"],
                        p["w_down"])
        y = y.reshape(B, S, D)
    else:
        y, aux = _moe_dist(cfg, dist, rparams, p, x, mode, chunks, ep_be)

    if mcfg.d_shared and "shared" in p:
        with jax.named_scope("moe.shared"):
            sh = MLPParams(**{k: p["shared"][k]
                              for k in ("w_gate", "w_up", "w_down")})
            y = y + swiglu(sh, x)
    return y, aux


def _moe_host_sim(cfg: ModelConfig, dist: Optional[DistCtx],
                  rparams: RouterParams, p: dict, x: Array,
                  mode: str, ep_be) -> tuple[Array, dict]:
    """Host-backend path: run the MoE layer's dispatch/combine on concrete
    numpy arrays (e.g. the simulated-RDMA substrate; outside jit only)."""
    import numpy as np

    from repro.core.transport.ep_executor import np_grouped_swiglu

    B, S, D = x.shape
    mcfg = cfg.moe
    t = x.reshape(-1, D)
    rout = route(mcfg, rparams, t, mcfg.n_experts)
    e_pad = p["w_gate"].shape[0]
    if dist is not None and dist.ep_axes:
        spec = make_ep_spec(cfg, dist, mode=mode, dtype=x.dtype)
    else:
        degree = max(d for d in (1, 2, 4) if (B * S) % d == 0
                     and e_pad % d == 0)
        spec = EPSpec(axes=("sim",), sizes=(degree,), n_experts=e_pad,
                      top_k=mcfg.top_k, mode=mode,
                      wire_dtype=getattr(mcfg, "wire_dtype", "fp32"))
    wg, wu, wd = (np.asarray(p[k], np.float32)
                  for k in ("w_gate", "w_up", "w_down"))
    res = ep_be.dispatch_combine(
        spec, np.asarray(t, np.float32), np.asarray(rout.top_idx),
        np.asarray(rout.top_w, np.float32),
        lambda toks, counts=None: np_grouped_swiglu(toks, wg, wu, wd,
                                                    counts=counts))
    load = planlib.expert_load(rout.top_idx, e_pad)
    # with a replicated placement the backend's *physical*-slot stat is the
    # truth; without one, report over the real (unpadded) logical experts
    if getattr(spec, "placement", None) is not None:
        imb = jnp.float32(res.aux["imbalance"])
    else:
        imb = planlib.load_imbalance(load[:mcfg.n_experts])
    aux = {"aux_loss": rout.aux_loss,
           "dropped": jnp.float32(res.aux["dropped"]),
           "load": load, "imbalance": imb}
    return jnp.asarray(res.out, x.dtype).reshape(B, S, D), aux


def _moe_dist(cfg: ModelConfig, dist: DistCtx, rparams: RouterParams, p: dict,
              x: Array, mode: str, chunks: int, ep_backend) -> tuple[Array,
                                                                     dict]:
    mesh = dist.mesh
    all_axes = tuple(mesh.axis_names)
    mcfg = cfg.moe
    spec = make_ep_spec(cfg, dist, mode=mode, chunks=chunks, dtype=x.dtype)
    eps = spec.experts_per_shard
    nshards = math.prod(mesh.shape[a] for a in all_axes)

    from repro.distributed.sharding import effective_batch_axes
    Bg, Sg, _ = x.shape
    bd = effective_batch_axes(dist, Bg)
    sq = (dist.seq_axis if (Sg > 1 and dist.seq_axis
                            and Sg % mesh.shape[dist.seq_axis] == 0) else None)
    ep_spec_p = tuple(dist.ep_axes) if len(dist.ep_axes) > 1 else dist.ep_axes[0]
    x_spec = P(bd, sq, None)

    def island(x_l, rw, rb, wg, wu, wd):
        Bl, Sl, D = x_l.shape
        t = x_l.reshape(-1, D)
        with jax.named_scope("moe.router"):
            rout = route(mcfg, RouterParams(rw, rb), t, mcfg.n_experts)
        # the backend scopes its exchanges moe.dispatch and moe.combine
        with jax.named_scope("moe.experts"):
            res = ep_backend.dispatch_combine(spec, t, rout.top_idx,
                                              rout.top_w,
                                              _expert_fn(wg, wu, wd))
        y = res.out.reshape(Bl, Sl, D)
        denom = jnp.float32(nshards)
        # global load via the shared helper (one definition for all three
        # moe branches); imbalance is max/mean physical-slot load — with
        # the identity placement the logical counts ARE the physical ones
        load_g = jax.lax.psum(
            planlib.expert_load(rout.top_idx, spec.n_experts), all_axes)
        aux = {
            "aux_loss": jax.lax.psum(rout.aux_loss, all_axes) / denom,
            "dropped": jax.lax.psum(res.aux["dropped"], all_axes) / denom,
            "occupancy": jax.lax.psum(
                jnp.float32(res.aux.get("occupancy", 0.0)), all_axes) / denom,
            "load": load_g,
            "imbalance": planlib.load_imbalance(load_g[:mcfg.n_experts]),
        }
        return y, aux

    rb = rparams.bias
    if rb is None:
        rb = jnp.zeros((spec.n_experts,), jnp.float32)
    out_specs = (x_spec, {"aux_loss": P(), "dropped": P(), "occupancy": P(),
                          "load": P(), "imbalance": P()})
    y, aux = jax.shard_map(
        island, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(None),
                  P(ep_spec_p, None, None), P(ep_spec_p, None, None),
                  P(ep_spec_p, None, None)),
        out_specs=out_specs, check_vma=False,
    )(x, rparams.w, rb, p["w_gate"], p["w_up"], p["w_down"])
    return y, aux
