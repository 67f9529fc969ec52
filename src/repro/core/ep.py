"""UCCL-EP expert-parallel dispatch/combine, adapted natively to TPU meshes.

Two modes, mirroring the paper (§3.3):

- **LL (low latency)**: one-shot capacity-bucketed ``all_to_all`` per choice
  (token, expert).  No synchronisation between transfers; used for decode.

- **HT (high throughput)**: chunked dispatch with **token deduplication** and
  **hierarchical reduce**.  A token routed to multiple experts inside the same
  destination *group* (a pod on the 2-level mesh, a shard on the 1-level mesh)
  crosses that group boundary exactly once, carrying its expert list as
  metadata (the paper's TransferCmd payload); expert outputs are partially
  reduced inside the group and exactly one combined vector returns per
  (token, group) — the paper's intra-node reduce + single inter-node return.

All functions below run INSIDE ``shard_map`` — they see per-shard arrays and
use ``jax.lax`` collectives over the EP mesh axes.  ``repro.core.moe`` wraps
them; pure-jnp oracles live in :func:`moe_ref` for tests.

Routing *decisions* (slot assignment, counts, capacity masks, dedup tables)
come from the shared plan layer in :mod:`repro.core.plan`; this module only
implements their *execution* over jax collectives (payload packing, a2a,
grouped FFN, combine).  The simulated-RDMA transport executor consumes the
same plans, so the two backends cannot drift (DESIGN.md §8).

Shapes are static (XLA): capacity-bucketed buffers with overflow *drops*,
which are counted and returned (the paper's incast/congestion concern maps to
capacity pressure here; see DESIGN.md §6).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import plan as planlib

Array = jax.Array

NEG = jnp.int32(-1)


@dataclass(frozen=True)
class EPSpec:
    """Static description of the expert-parallel layout."""

    axes: tuple[str, ...]        # mesh axes carrying experts, outer->inner
    sizes: tuple[int, ...]       # sizes of those axes
    n_experts: int               # padded expert count
    top_k: int
    capacity_factor: float = 2.0
    chunks: int = 1              # HT pipeline chunks
    dtype: jnp.dtype = jnp.bfloat16
    mode: str = "ht"             # "ll" (decode) | "ht" (train/prefill)
    # dispatch-payload wire dtype: "fp32" (passthrough: tokens cross in
    # ``dtype``) | "fp8" | "int8" (block-quantized, inline per-128-feature
    # fp32 scales; dequantized to fp32 at the receiver — DESIGN.md §14).
    # Compression applies to dispatch only; combine returns and all
    # accumulation stay full precision.
    wire_dtype: str = "fp32"
    # replicated expert placement: phys->logical slot table as a hashable
    # tuple (``Placement.key()``), length = physical slot count.  None (or
    # the identity table) keeps today's single-placement layout bit-for-bit;
    # otherwise routing splits each logical expert's tokens across its
    # replicas deterministically (plan.split_to_physical) and every
    # downstream structure — a2a buckets, guard tables, fence counts,
    # ret_pos — sizes from ``n_physical``.  ``n_experts`` stays the LOGICAL
    # (router-space) count.
    placement: Optional[tuple[int, ...]] = None

    @property
    def degree(self) -> int:
        return math.prod(self.sizes)

    @property
    def experts_per_shard(self) -> int:
        assert self.n_experts % self.degree == 0
        return self.n_experts // self.degree

    @property
    def n_physical(self) -> int:
        """Physical expert-slot count (== n_experts without replication)."""
        return len(self.placement) if self.placement is not None \
            else self.n_experts

    @property
    def physical_per_shard(self) -> int:
        assert self.n_physical % self.degree == 0
        return self.n_physical // self.degree

    def placement_obj(self) -> Optional[planlib.Placement]:
        """Materialized Placement, or None for the identity layout (the
        replicas=1 contract: identity tables take the exact legacy path)."""
        if self.placement is None:
            return None
        pl = planlib.placement_from_table(
            np.asarray(self.placement, np.int32))
        return None if pl.is_identity else pl

    @property
    def two_level(self) -> bool:
        return len(self.axes) == 2

    def flat_axis(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]


class DispatchResult(NamedTuple):
    out: Array          # (T, D) combined expert outputs
    aux: dict           # {"dropped": scalar fraction, "occupancy": ..., ...}


# occupancy-carrying expert_fn contract dispatch: ``fn(tokens, counts)``
# where counts are the per-expert (or per-bucket, shape (E_local, B))
# occupied row counts; legacy single-argument callables still work
_call_expert_fn = planlib.call_expert_fn


def _cap(n: float, cf: float, hard_max: int, multiple: int = 8) -> int:
    c = int(math.ceil(n * cf / multiple)) * multiple
    # floor of 32 slots: tiny per-shard token counts (decode, smoke tests)
    # have large load fluctuations relative to the mean; 32 rows cost ~nothing
    floor = min(hard_max, 32)
    return max(floor, min(c, hard_max))


# ================================================= wire-dtype dispatch ====
def _wire_qdtype(wire_dtype: str):
    return jnp.float8_e4m3fn if wire_dtype == "fp8" else jnp.int8


def _quantized_a2a(spec: EPSpec, x_ext_f32: Array, src_of_slot: Array,
                   counts: Optional[Array], axis, P: int) -> Array:
    """Dispatch payloads cross the wire block-quantized (DESIGN.md §14).

    Fused gather->quantize (kernels.gather_quantize) from the fp32 source,
    a2a of the quantized bytes plus the inline per-block fp32 scales, then
    dequantize-on-receive back to fp32.  Empty slots gather the scratch zero
    row and decode to exact zeros, preserving the ``zero_padded`` contract.
    fp8 payloads cross bitcast to uint8: the *wire* carries raw bytes, and
    narrow-float collectives aren't portable across backends.
    """
    from repro.kernels import ops as kops
    n = src_of_slot.shape[0]
    D = x_ext_f32.shape[1]
    q, sc = kops.gather_quantize(x_ext_f32, src_of_slot, counts,
                                 wire_dtype=spec.wire_dtype)
    nb = sc.shape[1]
    per = n // P
    qb = lax.bitcast_convert_type(q, jnp.uint8).reshape(P, per, D)
    qr = lax.all_to_all(qb, axis, split_axis=0, concat_axis=0, tiled=True)
    sr = lax.all_to_all(sc.reshape(P, per, nb), axis, split_axis=0,
                        concat_axis=0, tiled=True)
    qw = lax.bitcast_convert_type(qr.reshape(n, D),
                                  _wire_qdtype(spec.wire_dtype))
    return kops.dequantize_tokens(qw, sr.reshape(n, nb))      # (n, D) fp32


def _wire_dispatch_a2a(spec: EPSpec, x: Array, plan: "_GroupPlan", axis,
                       G: int, C: int) -> Array:
    """Token-payload a2a for one dedup'd group dispatch, in the wire dtype.

    fp32 passthrough sends ``plan.send_x`` as-is (tokens cross in
    ``spec.dtype``); compressed modes re-gather from the fp32 source via
    ``plan.src_of_slot`` so the quantize fuses with the packing gather.
    Metadata (expert ids, combine weights) always crosses uncompressed.
    """
    if spec.wire_dtype == "fp32":
        return lax.all_to_all(plan.send_x, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    D = x.shape[1]
    xf = jnp.concatenate([x.astype(jnp.float32),
                          jnp.zeros((1, D), jnp.float32)], axis=0)
    rows = _quantized_a2a(spec, xf, plan.src_of_slot, None, axis, G)
    return rows.astype(spec.dtype).reshape(G, C, D)


# =========================================================== LL mode ======
def dispatch_combine_ll(spec: EPSpec, x: Array, top_idx: Array, top_w: Array,
                        expert_fn: Callable[[Array], Array],
                        capacity: Optional[int] = None) -> DispatchResult:
    """One-shot per-choice dispatch -> grouped expert FFN -> combine.

    x: (T, D); top_idx/top_w: (T, K).  expert_fn maps (E_local, C_in, D) ->
    (E_local, C_in, D) applying local expert i to row block i — under a
    replicated ``spec.placement`` the row blocks are PHYSICAL slots (the
    caller gathers weights through ``phys_to_logical``).
    """
    T, D = x.shape
    K = spec.top_k
    pl_obj = spec.placement_obj()
    if pl_obj is not None:
        top_idx = planlib.split_to_physical(pl_obj, top_idx)
    E, P, eps = spec.n_physical, spec.degree, spec.physical_per_shard
    # hard_max is T*K, not T: routing tables may send a token to the same
    # expert more than once (e.g. random tables in tests)
    C = capacity or _cap(T * K / E, spec.capacity_factor, hard_max=T * K)

    with jax.named_scope("moe.dispatch"):
        pl = planlib.make_plan(top_idx, E, C)
        flat_e = top_idx.reshape(-1)                       # (T*K,)
        valid, rank = pl.valid.reshape(-1), pl.rank.reshape(-1)
        keep = pl.keep.reshape(-1)
        # overflow -> scratch
        slot = planlib.flat_slots(flat_e, rank, keep, C, E)

        # index-indirection packing (scatter ids, gather payloads; §Perf O2)
        rows = jnp.arange(T * K, dtype=jnp.int32) // K
        src_of_slot = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
            rows, mode="drop")[:-1]
        # a2a over the (flattened) EP axes: expert e lives on flat shard
        # e // eps.
        if spec.wire_dtype == "fp32":
            x_ext = jnp.concatenate([x.astype(spec.dtype),
                                     jnp.zeros((1, D), spec.dtype)], axis=0)
            send = x_ext[src_of_slot].reshape(P, eps * C, D)
            recv = lax.all_to_all(send, spec.flat_axis(), split_axis=0,
                                  concat_axis=0, tiled=True)  # (P, eps*C, D)
        else:
            # compressed wire: quantize from the full-precision source (not
            # the already-narrowed spec.dtype), dequantize to fp32 at the
            # receiver
            xf_ext = jnp.concatenate([x.astype(jnp.float32),
                                      jnp.zeros((1, D), jnp.float32)], axis=0)
            deq = _quantized_a2a(spec, xf_ext, src_of_slot,
                                 jnp.minimum(pl.counts, C), spec.flat_axis(),
                                 P)
            recv = deq.astype(spec.dtype).reshape(P, eps * C, D)
        recv = recv.reshape(P, eps, C, D).transpose(1, 0, 2, 3).reshape(
            eps, P * C, D)

        # occupancy exchange: each source's per-(dest expert) occupied
        # counts — the same metadata the paper's completion fences carry — so
        # the expert kernel can skip the capacity padding (§Perf:
        # occupancy-aware compute).  recv bucket layout is (local expert,
        # source bucket): counts (eps, P).
        cnt_send = jnp.minimum(pl.counts, C).reshape(P, eps)
        cnt_recv = lax.all_to_all(cnt_send, spec.flat_axis(), split_axis=0,
                                  concat_axis=0, tiled=True)       # (P, eps)
    out_e = _call_expert_fn(expert_fn, recv, cnt_recv.T)  # (eps, P*C, D)

    with jax.named_scope("moe.combine"):
        back = out_e.reshape(eps, P, C, D).transpose(1, 0, 2, 3).reshape(
            P, eps * C, D)
        back = lax.all_to_all(back, spec.flat_axis(), split_axis=0,
                              concat_axis=0, tiled=True)
        back = back.reshape(E * C, D)

        # combine: weighted fp32 segment-sum over the T*K kept choices — no
        # (T, K, D) fp32 materialization + einsum, and no touching the
        # (mostly padded) E*C slot space: each choice gathers its slot's row
        # and scatter-adds into its token (dropped choices add 0 via the
        # scratch row)
        w_flat = jnp.where(keep, top_w.reshape(-1).astype(jnp.float32), 0.0)
        contrib = back[jnp.where(keep, flat_e * C + rank, 0)].astype(
            jnp.float32) * w_flat[:, None]
        out = jnp.zeros((T + 1, D), jnp.float32).at[
            jnp.where(keep, rows, T)].add(contrib)[:-1]
    dropped = pl.n_dropped / jnp.maximum(valid.sum(), 1)
    occupancy = jnp.minimum(pl.counts, C).sum() / (E * C)
    # global per-physical-slot load + imbalance (max/mean): the one stat the
    # online re-placer and the benchmarks both read (DESIGN.md §15)
    load_phys = lax.psum(pl.counts, spec.flat_axis())
    return DispatchResult(out.astype(x.dtype),
                          {"dropped": dropped, "occupancy": occupancy,
                           "load_phys": load_phys,
                           "imbalance": planlib.load_imbalance(load_phys)})


# =========================================================== HT mode ======
class _GroupPlan(NamedTuple):
    """Source-side bookkeeping of one dedup'd group dispatch."""

    send_x: Array       # (G, C, D) token payloads
    send_eid: Array     # (G, C, K) expert ids local to the dest group (-1 pad)
    send_w: Array       # (G, C, K) combine weights
    src_of_slot: Array  # (G*C,) source token row per slot (T for empty) —
                        # drives both payload packing and the combine scatter
    dropped: Array      # scalar count


def _dedup_group_dispatch(x: Array, eid: Array, w: Array, group_of: Array,
                          n_groups: int, C: int, dtype) -> _GroupPlan:
    """Deduplicate choices per (token, group); bucket entries by group.

    x: (T, D); eid: (T, K) expert ids *within the group's namespace* (-1 pad);
    w: (T, K); group_of: (T, K) destination group per choice (-1 for pad).
    """
    T, K = eid.shape
    D = x.shape[1]
    valid = eid >= 0
    # dedup + (token, group) entry table from the shared plan layer
    first, entry_valid, rank_tg, keep_tg, dropped = planlib.dedup_entry_table(
        group_of, valid, n_groups, C)
    # pack entries by index-indirection: scatter row ids, gather payloads
    # once per (t, g) — no (T, G, D) value materialisation (§Perf O2)
    slot_tg = planlib.flat_slots(jnp.arange(n_groups)[None], rank_tg, keep_tg,
                                 C, n_groups)
    src_rows = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None],
                                (T, n_groups))
    src_of_slot = jnp.full((n_groups * C + 1,), T, jnp.int32).at[slot_tg].set(
        src_rows, mode="drop")[:-1]
    x_ext = jnp.concatenate([x.astype(dtype), jnp.zeros((1, D), dtype)],
                            axis=0)
    send_x = x_ext[src_of_slot].reshape(n_groups, C, D)
    # metadata: k-th choice rides on its (t,g) entry
    slot_choice = jnp.where(valid, jnp.take_along_axis(
        slot_tg, jnp.where(valid, group_of, 0), axis=1), n_groups * C)
    kpos = jnp.broadcast_to(jnp.arange(K)[None], (T, K))
    send_eid = jnp.full((n_groups * C + 1, K), NEG, jnp.int32).at[
        slot_choice, kpos].set(jnp.where(valid, eid, NEG), mode="drop")[:-1]
    send_w = jnp.zeros((n_groups * C + 1, K), jnp.float32).at[
        slot_choice, kpos].set(jnp.where(valid, w.astype(jnp.float32), 0.0),
                               mode="drop")[:-1]
    return _GroupPlan(send_x, send_eid.reshape(n_groups, C, K),
                      send_w.reshape(n_groups, C, K), src_of_slot, dropped)


def _expert_apply(spec: EPSpec, x_in: Array, eid: Array, w: Array,
                  expert_fn: Callable[[Array], Array], cf: float,
                  n_tokens_hint: int):
    """Final-level compute: entries (N, D) each with <=K local expert ids.

    Buckets (entry, choice) pairs per local expert, applies the grouped FFN,
    and returns the *weighted partial sum per entry* (the intra-node reduce).

    Capacity is sized from the REAL expected load (``n_tokens_hint`` source
    tokens x K choices, balanced across experts) — not from the padded recv
    row count N, which is mostly capacity padding; invalid rows (eid = -1)
    consume no slots.

    HBM-traffic note (§Perf O2): packing is *index-indirection* — row ids
    are scattered (4-byte ints), payloads move through ONE gather into the
    (eps, Ce, D) buffer, and the combine is a weighted scatter-add of the
    expert outputs.  This avoids materialising (N·K, D) value scatters and
    the padded (N, K, D) fp32 gather of the naive formulation (~8x traffic).
    """
    N, D = x_in.shape
    K = eid.shape[1]
    eps = spec.physical_per_shard
    Ce = _cap(n_tokens_hint * K / eps, cf, hard_max=N * K)
    pl = planlib.make_plan(eid, eps, Ce)
    flat_e = eid.reshape(-1)
    valid, rank, keep = (pl.valid.reshape(-1), pl.rank.reshape(-1),
                         pl.keep.reshape(-1))
    slot = planlib.flat_slots(flat_e, rank, keep, Ce, eps)
    rows = jnp.arange(N * K, dtype=jnp.int32) // K          # choice -> entry
    # index scatter (ints) + payload gather
    ent_of_slot = jnp.full((eps * Ce + 1,), N, jnp.int32).at[slot].set(
        rows, mode="drop")[:-1]
    x_ext = jnp.concatenate([x_in.astype(spec.dtype),
                             jnp.zeros((1, D), spec.dtype)], axis=0)
    w_of_slot = jnp.zeros((eps * Ce + 1,), jnp.float32).at[slot].set(
        w.reshape(-1).astype(jnp.float32), mode="drop")[:-1]
    counts = jnp.minimum(pl.counts, Ce)       # occupied prefix per expert
    occupancy = counts.sum() / (eps * Ce)
    fused = getattr(expert_fn, "fused", None)
    if fused is not None:
        # fully fused gather -> expert SwiGLU -> weighted fp32 scatter-add:
        # neither the (eps, Ce, D) gather buffer nor the expert-output
        # intermediate is materialized (kernels.gather_swiglu_scatter)
        part = fused(x_ext, ent_of_slot, w_of_slot, counts)
    else:
        buf = x_ext[ent_of_slot]
        out_e = _call_expert_fn(expert_fn, buf.reshape(eps, Ce, D),
                                counts).reshape(eps * Ce, D)
        # weighted scatter-add back per entry (intra-node reduce)
        part = jnp.zeros((N + 1, D), jnp.float32).at[
            jnp.where(w_of_slot != 0, ent_of_slot, N)].add(
            out_e.astype(jnp.float32) * w_of_slot[:, None], mode="drop")[:-1]
    return part, (valid & ~keep).sum(), occupancy


def _combine_scatter(plan: _GroupPlan, ret: Array, T: int) -> Array:
    """ret: (G, C, D) returned partials; scatter-add entries back per token.

    Empty slots carry zero partials and point at the scratch row T, so one
    unmasked fp32 scatter-add replaces the old (T, G, D) gather + where +
    sum materialization (§Perf: scatter-based combine)."""
    G, C, D = ret.shape
    out = jnp.zeros((T + 1, D), jnp.float32).at[plan.src_of_slot].add(
        ret.reshape(G * C, D).astype(jnp.float32))
    return out[:-1]


def dispatch_combine_ht(spec: EPSpec, x: Array, top_idx: Array, top_w: Array,
                        expert_fn: Callable[[Array], Array]) -> DispatchResult:
    """Chunked + dedup'd + hierarchical dispatch/combine (paper HT mode)."""
    T, D = x.shape
    pl_obj = spec.placement_obj()
    if pl_obj is not None:
        # one replica split for the whole table (not per chunk), matching
        # the substrate's per-source round-robin semantics
        top_idx = planlib.split_to_physical(pl_obj, top_idx)
    n_chunks = planlib.effective_chunks(T, spec.chunks)
    Tc = T // n_chunks
    outs, drops, total = [], jnp.int32(0), jnp.int32(0)
    occs = []
    for c in range(n_chunks):
        sl = slice(c * Tc, (c + 1) * Tc)
        o, d, occ = _ht_one_chunk(spec, x[sl], top_idx[sl], top_w[sl],
                                  expert_fn)
        outs.append(o)
        occs.append(occ)
        drops += d
        total += Tc * spec.top_k
    out = jnp.concatenate(outs, axis=0) if n_chunks > 1 else outs[0]
    load_phys = lax.psum(
        planlib.group_counts(top_idx.reshape(-1), spec.n_physical,
                             (top_idx >= 0).reshape(-1)), spec.flat_axis())
    return DispatchResult(out.astype(x.dtype),
                          {"dropped": drops / jnp.maximum(total, 1),
                           "occupancy": sum(occs) / n_chunks,
                           "chunks": n_chunks,
                           "load_phys": load_phys,
                           "imbalance": planlib.load_imbalance(load_phys)})


def _ht_one_chunk(spec: EPSpec, x: Array, top_idx: Array, top_w: Array,
                  expert_fn) -> tuple[Array, Array, Array]:
    # top_idx is already PHYSICAL here (dispatch_combine_ht splits replicas
    # once up front); all bucketing below runs in the physical slot space
    T, D = x.shape
    K = spec.top_k
    E, eps = spec.n_physical, spec.physical_per_shard
    cf = spec.capacity_factor
    valid = top_idx >= 0

    if not spec.two_level:
        # one-level: groups are the EP shards themselves (dedup at shard level)
        P = spec.degree
        group_of = jnp.where(valid, top_idx // eps, -1)
        eid_local = jnp.where(valid, top_idx % eps, NEG)
        frac = 1.0 - (1.0 - 1.0 / P) ** K
        C = _cap(T * frac, cf, hard_max=T)
        with jax.named_scope("moe.dispatch"):
            plan = _dedup_group_dispatch(x, eid_local, top_w, group_of, P, C,
                                         spec.dtype)
            rx = _wire_dispatch_a2a(spec, x, plan, spec.axes[0], P, C)
            re = lax.all_to_all(plan.send_eid, spec.axes[0], 0, 0,
                                tiled=True)
            rw = lax.all_to_all(plan.send_w, spec.axes[0], 0, 0, tiled=True)
        part, d2, occ = _expert_apply(spec, rx.reshape(P * C, D),
                                      re.reshape(P * C, K),
                                      rw.reshape(P * C, K),
                                      expert_fn, cf, n_tokens_hint=T)
        with jax.named_scope("moe.combine"):
            ret = lax.all_to_all(part.reshape(P, C, D).astype(spec.dtype),
                                 spec.axes[0], 0, 0, tiled=True)
            out = _combine_scatter(plan, ret.astype(jnp.float32), T)
        return out, plan.dropped + d2, occ

    # ---- two-level: outer = pod (RDMA domain), inner = model (ICI domain) --
    ax_o, ax_i = spec.axes
    Po, Pi = spec.sizes
    e_per_pod = E // Po
    pod_of = jnp.where(valid, top_idx // e_per_pod, -1)
    eid_in_pod = jnp.where(valid, top_idx % e_per_pod, NEG)
    frac_o = 1.0 - (1.0 - 1.0 / Po) ** K
    C1 = _cap(T * frac_o, cf, hard_max=T)
    N2 = Po * C1
    frac_i = 1.0 - (1.0 - 1.0 / Pi) ** K
    C2 = _cap(N2 * frac_i, cf, hard_max=N2)
    with jax.named_scope("moe.dispatch"):
        plan1 = _dedup_group_dispatch(x, eid_in_pod, top_w, pod_of, Po, C1,
                                      spec.dtype)
        # inter-pod a2a (same-rail: inner index unchanged), tokens cross once
        rx = _wire_dispatch_a2a(spec, x, plan1, ax_o, Po, C1)  # (Po, C1, D)
        re = lax.all_to_all(plan1.send_eid, ax_o, 0, 0, tiled=True)
        rw = lax.all_to_all(plan1.send_w, ax_o, 0, 0, tiled=True)
        x2 = rx.reshape(N2, D)
        e2 = re.reshape(N2, K)                 # expert ids within my pod
        w2 = rw.reshape(N2, K)
        # intra-pod forwarding: group by inner shard (NVLink-domain
        # distribution)
        v2 = e2 >= 0
        grp2 = jnp.where(v2, e2 // eps, -1)
        eid2 = jnp.where(v2, e2 % eps, NEG)
        plan2 = _dedup_group_dispatch(x2, eid2, w2, grp2, Pi, C2, spec.dtype)
        rx2 = _wire_dispatch_a2a(spec, x2, plan2, ax_i, Pi, C2)
        re2 = lax.all_to_all(plan2.send_eid, ax_i, 0, 0, tiled=True)
        rw2 = lax.all_to_all(plan2.send_w, ax_i, 0, 0, tiled=True)
    part, d3, occ = _expert_apply(spec, rx2.reshape(Pi * C2, D),
                                  re2.reshape(Pi * C2, K),
                                  rw2.reshape(Pi * C2, K),
                                  expert_fn, cf, n_tokens_hint=T)
    with jax.named_scope("moe.combine"):
        # hierarchical combine A: return partials intra-pod, reduce per
        # (t, pod)
        ret2 = lax.all_to_all(part.reshape(Pi, C2, D).astype(spec.dtype),
                              ax_i, 0, 0, tiled=True)
        red2 = _combine_scatter(plan2, ret2.astype(jnp.float32), N2)
        # hierarchical combine B: ONE vector per (token, pod) crosses pods
        # back
        ret1 = lax.all_to_all(red2.reshape(Po, C1, D).astype(spec.dtype),
                              ax_o, 0, 0, tiled=True)
        out = _combine_scatter(plan1, ret1.astype(jnp.float32), T)
    return out, plan1.dropped + plan2.dropped + d3, occ


# ====================================================== reference oracle ==
def moe_ref(x: Array, top_idx: Array, top_w: Array, w_gate: Array, w_up: Array,
            w_down: Array) -> Array:
    """Dense per-token MoE oracle: no parallelism, no capacity drops.

    x: (T, D); top_idx/top_w: (T, K); w_*: (E, D, F) / (E, F, D).
    """
    E = w_gate.shape[0]
    oh = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)        # (T, K, E)
    w_e = jnp.einsum("tke,tk->te", oh, top_w.astype(jnp.float32))
    xf = x.astype(jnp.float32)
    g = jnp.einsum("td,edf->tef", xf, w_gate.astype(jnp.float32))
    u = jnp.einsum("td,edf->tef", xf, w_up.astype(jnp.float32))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, w_down.astype(jnp.float32))
    return jnp.einsum("ted,te->td", y, w_e).astype(x.dtype)
