"""Plain reference of the served model, in float32 at the highest matmul
precision, and its lower-precision control.

It imports nothing of the program.  It reads the sizes from the
configuration file and the weights from the benchmark's own parameter tree
(``bench.weights``), whose layout is the program's: ``embed`` (V, D),
``lm_head`` (D, V), ``final_ln``, and per layer, stacked over layers under
``blocks/slot0``: ``ln1``, ``ln2``, ``attn/{wq, wk, wv}`` (D, H, hd),
``attn/wo`` (H, hd, D), optional ``attn/{bq, bk, bv}``, ``moe/router_w``
(D, E), optional ``moe/router_b``, ``moe/{w_gate, w_up}`` (E, D, F),
``moe/w_down`` (E, F, D) and optional ``moe/shared/{w_gate, w_up, w_down}``.

The architecture is the one the program runs, which departs from the
published one where the configuration's ``deviations`` say so (its
``as_run`` values; :func:`sizes` refuses others): pre-norm
RMSNorm blocks; multi-head attention with RoPE (rotate-half over the whole
head) and a causal softmax; an MoE layer whose router takes a softmax over
the real experts, picks the top k by logit plus the selection bias, and
weights them by their probabilities (renormalised over the k where
``norm_topk_prob`` says so); SwiGLU experts;
an ungated shared SwiGLU expert; a final RMSNorm and an untied LM head.

``precision="fp8"`` is the control: every matmul operand (weights and
activations) rounded to float8 e4m3 with a per-tensor absmax scale, products
accumulated in float32.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
FP8_MAX = 448.0
TOKENS_PER_MOE_CHUNK = 256
TOKENS_PER_BLOCK = 4096


def _round(x, precision: str):
    """``x`` as the given precision holds it, back in float32."""
    if precision == "f32":
        return x
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(eq, a, b, precision):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      precision=HI)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (R, L, H, hd), pos (L,): rotate-half RoPE over the whole head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv             # (L, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, wg, wu, wd, precision):
    g = _mm("td,df->tf", x, wg, precision)
    u = _mm("td,df->tf", x, wu, precision)
    return _mm("tf,fd->td", jax.nn.silu(g) * u, wd, precision)


def _moe(sz, p, h, precision):
    """h (T, D) -> routed experts' sum plus the shared expert."""
    T, D = h.shape
    E = p["router_w"].shape[-1]
    logits = _mm("td,de->te", h, p["router_w"], precision)
    real = jnp.arange(E) < sz["experts"]
    logits = jnp.where(real[None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    sel = logits + p["router_b"][None] if "router_b" in p else logits
    _, idx = lax.top_k(sel, sz["top_k"])
    w = jnp.take_along_axis(probs, idx, axis=-1)
    if sz["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    dense_w = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], idx].add(w)

    def chunk(args):
        hc, wc = args
        g = _mm("td,edf->tef", hc, p["w_gate"], precision)
        u = _mm("td,edf->tef", hc, p["w_up"], precision)
        y = _mm("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"], precision)
        return jnp.einsum("ted,te->td", y, wc, precision=HI)

    n = TOKENS_PER_MOE_CHUNK
    pad = (-T) % n
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, n, D)
    wp = jnp.pad(dense_w, ((0, pad), (0, 0))).reshape(-1, n, E)
    out = lax.map(chunk, (hp, wp)).reshape(-1, D)[:T]
    if "shared" in p:
        s = p["shared"]
        out = out + _swiglu(h, s["w_gate"], s["w_up"], s["w_down"], precision)
    return out


@partial(jax.jit, static_argnames=("sz", "precision", "first"))
def _layer(blocks, i, x, *, sz, precision, first=0):
    """Layer ``i`` over x (R, L, D): attention, then the MoE layer, for the
    positions ``first ..`` (every position attends to all before it)."""
    p = jax.tree.map(lambda a: a[i], blocks)
    sz = dict(sz)
    R, L, D = x.shape
    a = p["attn"]
    h = _rms(x, p["ln1"], sz["eps"])
    q = _mm("rld,dhk->rlhk", h[:, first:], a["wq"], precision)
    k = _mm("rld,dhk->rlhk", h, a["wk"], precision)
    v = _mm("rld,dhk->rlhk", h, a["wv"], precision)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    pos = jnp.arange(L)
    q = _rope(q, pos[first:], sz["theta"])
    k = _rope(k, pos, sz["theta"])
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = _mm("rqhk,rshk->rhqs", q, k, precision) / math.sqrt(q.shape[-1])
    s = jnp.where(pos[first:, None] >= pos[None, :], s, -jnp.inf)
    o = _mm("rhqs,rshk->rqhk", jax.nn.softmax(s, axis=-1), v, precision)
    x = x[:, first:] + _mm("rlhk,hkd->rld", o, a["wo"], precision)
    h = _rms(x, p["ln2"], sz["eps"])
    n = L - first
    return x + _moe(sz, p["moe"], h.reshape(R * n, D), precision).reshape(
        R, n, D)


@partial(jax.jit, static_argnames=("precision",))
def _head(params, x, eps, *, precision):
    h = _rms(x, params["final_ln"], eps)
    return _mm("rld,dv->rlv", h, params["lm_head"], precision)


@partial(jax.jit, static_argnames=("precision",))
def _embed(embed, tokens, *, precision):
    return _round(jnp.take(embed, tokens, axis=0), precision)


def sizes(config: dict) -> tuple:
    """The numbers the reference reads from a configuration file, as a
    hashable tuple of pairs: the top-level keys, with the file's ``as_run``
    values (what the program runs in place of the source's) over them."""
    c = {**config, **config.get("as_run", {})}
    # the architecture implemented here, which the file has to name
    if (c.get("scoring_func", "softmax") != "softmax"
            or c.get("routed_scaling_factor", 1.0) != 1.0
            or c.get("first_k_dense_replace", 0) != 0):
        raise ValueError(f"{config['name']}: the reference runs softmax "
                         "routing without a routed scale on MoE layers only")
    experts = c.get("n_routed_experts", c.get("num_experts"))
    return tuple(sorted({
        "layers": int(c["num_hidden_layers"]),
        "experts": int(experts),
        "top_k": int(c["num_experts_per_tok"]),
        "norm_topk": bool(c["norm_topk_prob"]),
        "theta": float(c["rope_theta"]),
        "eps": float(c["rms_norm_eps"]),
        "vocab": int(c["vocab_size"]),
    }.items()))


def logits(config: dict, params: dict, seqs, first: int,
           precision: str = "f32"):
    """Reference logits at positions ``first ..`` of each row of ``seqs``
    (R, L) int32: an array (R, L - first, vocab) float32 on the host.
    Rows go through in blocks of at most ``TOKENS_PER_BLOCK`` tokens."""
    sz = sizes(config)
    d = dict(sz)
    seqs = np.asarray(seqs, np.int32)
    R, L = seqs.shape
    per = max(1, TOKENS_PER_BLOCK // L)
    blocks = params["blocks"]["slot0"]
    out = []
    for r0 in range(0, R, per):
        x = _embed(params["embed"], jnp.asarray(seqs[r0:r0 + per]),
                   precision=precision)
        for i in range(d["layers"]):
            # the last layer is needed only where logits are read
            last = first if i == d["layers"] - 1 else 0
            x = _layer(blocks, jnp.int32(i), x, sz=sz, precision=precision,
                       first=last)
        y = _head(params, x, d["eps"], precision=precision)
        out.append(np.asarray(y[..., :d["vocab"]]))
    return np.concatenate(out, axis=0)
