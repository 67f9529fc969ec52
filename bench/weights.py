"""Random weights and prompts from ``--seed``, made by the benchmark on the
device, so that the program and the plain reference read the same numbers
and the reference takes nothing the program made.

The weights follow the program's parameter layout (its tree and shapes, as
``jax.eval_shape`` of its initialiser gives them) and are drawn here, in
one jitted call, in the dtype they are served from (float32 masters):
norm scales 1, biases N(0, 0.02^2), the embedding N(0, 1) and every other
matrix N(0, 1/fan_in).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

_ONES = ("ln1", "ln2", "final_ln", "q_norm", "k_norm")
_BIASES = ("bq", "bk", "bv", "router_b")


def seed_key(seed: int):
    """A PRNG key for any whole number up to 2**64: seeds that differ in
    their upper 32 bits give different keys."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", None) or getattr(last, "name", last))


def _fan_in(name: str, shape) -> int:
    if name in ("wq", "wk", "wv"):            # (..., D, H, hd)
        return shape[-3]
    if name == "wo":                          # (..., H, hd, D)
        return shape[-3] * shape[-2]
    return shape[-2]                          # (..., fan_in, fan_out)


def _draw(shapes, key):
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, s), k in zip(leaves, keys):
        name = _leaf_name(path)
        if name in _ONES:
            out.append(jnp.ones(s.shape, s.dtype))
        elif name in _BIASES:
            out.append(0.02 * jax.random.normal(k, s.shape, s.dtype))
        elif name == "embed":
            out.append(jax.random.normal(k, s.shape, s.dtype))
        else:
            scale = 1.0 / math.sqrt(_fan_in(name, s.shape))
            out.append(scale * jax.random.normal(k, s.shape, s.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def make_params(cfg, seed: int):
    """The program's parameter tree for ``cfg``, drawn from ``seed`` on the
    device."""
    from repro.models import model_zoo as Z

    key = seed_key(seed)
    shapes = jax.eval_shape(partial(Z.init_params, cfg), key)
    return jax.jit(partial(_draw, shapes))(key)


@partial(jax.jit, static_argnums=(2, 3, 4))
def prompts(key, round_index, batch: int, length: int, vocab: int):
    """Round ``round_index``'s prompts: (batch, length) token ids drawn
    uniformly from the vocabulary."""
    k = jax.random.fold_in(key, round_index)
    return jax.random.randint(k, (batch, length), 0, vocab, jnp.int32)
