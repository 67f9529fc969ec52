"""Compile the main-path Pallas kernels for a described TPU v5e at the
widths of ``moonshot_v1_16b_a3b`` (d_model 2048, d_expert 1408, 64 experts
top-6, 16 heads x 128) with 16 experts per chip, as on a four-chip EP mesh.

Nothing runs: the TPU compiler, which is installed without a chip,
refuses what interpret mode accepts — blocks off the (8, 128) tiling,
loads Mosaic cannot lower, more VMEM than the scoped limit.  The topology
is described inside a fixture (never at import), so under several pytest
workers only the worker given this file loads the TPU library.

The decode step of the benchmark's ``moonlight-2l`` configuration is
compiled whole, to hold its layer scopes (``jax.named_scope``) where the
benchmark's per-layer readers look for them.
"""
import os
import re
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.grouped_matmul import (gather_swiglu_scatter_pallas,
                                          grouped_swiglu_pallas)
from repro.kernels.quantize_pack import dequantize_pallas, gather_quantize_pallas

D, F, E_LOCAL, E, HEADS, HEAD_DIM = 2048, 1408, 16, 64, 16, 128
BF16, F32, I32, FP8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.float8_e4m3fn


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2; the persistent compilation cache
    is off meanwhile (entries written without a chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes, **kw):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


_W = [((E_LOCAL, D, F), BF16), ((E_LOCAL, D, F), BF16),
      ((E_LOCAL, F, D), BF16)]


@pytest.mark.parametrize("rows,counts,dtype", [
    (192, (E_LOCAL,), BF16),        # HT receive buckets, 1024-token prefill
    (384, (E_LOCAL, 4), BF16),      # LL receive: 4 source buckets of 96
    (96, (E_LOCAL, 4), BF16),       # LL decode: 4 source buckets of 24
    (96, (E_LOCAL, 4), F32),        # the same in f32 (f32 weight blocks)
])
def test_grouped_swiglu_compiles(one_chip, rows, counts, dtype):
    w = [(shape, dtype) for shape, _ in _W]
    _compile(one_chip, grouped_swiglu_pallas, ((E_LOCAL, rows, D), dtype),
             *w, (counts, I32))


@pytest.mark.parametrize("tokens,slots", [
    (17, 32),                 # decode: 16 entries + the scratch row
    (1025, 192),              # prefill: 1024 entries + the scratch row
])
def test_gather_swiglu_scatter_compiles(one_chip, tokens, slots):
    n = E_LOCAL * slots
    _compile(one_chip, gather_swiglu_scatter_pallas, ((tokens, D), BF16),
             ((n,), I32), ((n,), F32), *_W, ((E_LOCAL,), I32))


@pytest.mark.parametrize("tokens,slots,counted", [
    (257, E * 96, True),      # LL prefill: 256 tokens, 64 buckets of 96
    (257, 4 * 256, False),    # HT prefill: 4 dedup'd groups of 256
    (5, E * 24, True),        # LL decode: 4 tokens, 64 buckets of 24
])
def test_gather_quantize_fp8_compiles(one_chip, tokens, slots, counted):
    shapes = [((tokens, D), F32), ((slots,), I32)]
    if counted:
        shapes.append(((E,), I32))
    _compile(one_chip, gather_quantize_pallas, *shapes, wire_dtype="fp8")


def test_dequantize_fp8_compiles(one_chip):
    _compile(one_chip, dequantize_pallas, ((E * 96, D), FP8),
             ((E * 96, D // 128), F32))


def test_decode_attention_compiles(one_chip):
    kv = ((4, 4096, HEADS, HEAD_DIM), BF16)
    _compile(one_chip, decode_attention_pallas, ((4, HEADS, HEAD_DIM), BF16),
             kv, kv, ((), I32))


# result type and dims, opcode and JAX path of an HLO instruction
HLO_OP = re.compile(r"= (\w+)\[([\d,]*)\]\S* ([\w-]+)\(.*op_name=\"([^\"]*)\"")


def test_decode_step_keeps_casts_and_expert_dots_in_their_scopes(one_chip):
    """Every convert of a stacked expert weight lies under ``cast``, every
    dot of the routed experts under ``moe.experts`` (batch 8, so that no
    token dim reads as E or F)."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import cells
    from repro.models import model_zoo as Z

    cfg = cells.model_config(cells.resolve("moonlight.decode").config)
    assert (cfg.d_model, cfg.moe.d_expert, cfg.moe.n_experts) == (D, F, E)

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(partial(Z.init_params, cfg),
                                   jax.random.PRNGKey(0)))
    cache = shapes(jax.eval_shape(partial(Z.init_cache, cfg, 8, 16, BF16)))
    tokens, pos = shapes((jax.ShapeDtypeStruct((8, 1), I32),
                          jax.ShapeDtypeStruct((), I32)))

    def decode_step(params, cache, tokens, pos):
        return Z.decode_step(cfg, params, cache, tokens, pos, moe_mode="ll")

    hlo = jax.jit(decode_step).lower(params, cache, tokens,
                                     pos).compile().as_text()
    casts, dots = [], []
    for dtype, dims, opcode, path in HLO_OP.findall(hlo):
        dims = tuple(int(d) for d in dims.split(",") if d)
        if opcode == "convert" and dims[-3:] in ((E, D, F), (E, F, D)):
            casts.append((dtype, path))
        if opcode in ("dot", "convolution") and E in dims and (
                F in dims or D in dims):
            dots.append(path)
    assert len(casts) == 3 and all(
        dt == "bf16" and "/cast/" in p for dt, p in casts), casts
    assert len(dots) >= 3 and all("/moe.experts/" in p for p in dots), dots
