"""Ops-level kernel mode parity (ISSUE 3): the ``repro.kernels.ops``
wrappers must produce the same numbers in "ref" (jnp oracle) and
"interpret" (Pallas kernel body on CPU) modes, including the new
occupancy-aware counts contract — and the EP dispatch paths must deliver
counts to the expert kernels and still match the dense oracle when the
kernel bodies (not the jnp refs) execute.

Each test names its mode with the ``mode=`` argument; without one the
wrappers take :func:`repro.kernels.ops.platform_mode` (Pallas on the TPU,
the refs elsewhere).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.core.ep import EPSpec, dispatch_combine_ht, dispatch_combine_ll, moe_ref
from repro.kernels import ops as kops


def _problem(seed, e, t, d, f, k):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, d), jnp.float32)
    ti = jax.random.randint(ks[1], (t, k), 0, e).astype(jnp.int32)
    tw = jax.nn.softmax(jax.random.normal(ks[2], (t, k)), -1)
    wg = jax.random.normal(ks[3], (e, d, f)) * 0.2
    wu = jax.random.normal(ks[4], (e, d, f)) * 0.2
    wd = jax.random.normal(ks[5], (e, f, d)) * 0.2
    return x, ti, tw, wg, wu, wd


@pytest.mark.parametrize("counts", [None, (5, 0, 20, 1)])
def test_ops_grouped_swiglu_mode_parity(counts):
    e, c, d, f = 4, 20, 16, 13
    x, _, _, wg, wu, wd = _problem(0, e, e * c, d, f, 1)
    x = x[:e * c].reshape(e, c, d)
    cnt = None if counts is None else jnp.asarray(counts, jnp.int32)
    ref = kops.grouped_swiglu(x, wg, wu, wd, cnt, mode="ref")
    got = kops.grouped_swiglu(x, wg, wu, wd, cnt, mode="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_ops_grouped_matmul_mode_parity():
    g, m, k, n = 3, 20, 13, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(ks[0], (g, m, k), jnp.float32)
    w = jax.random.normal(ks[1], (g, k, n), jnp.float32)
    cnt = jnp.array([7, 0, 20], jnp.int32)
    ref = kops.grouped_matmul(x, w, cnt, mode="ref")
    got = kops.grouped_matmul(x, w, cnt, mode="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_ops_gather_swiglu_scatter_mode_parity():
    e, c, d, f, t = 3, 12, 16, 19, 9
    _, _, _, wg, wu, wd = _problem(2, e, t, d, f, 1)
    x_ext = jnp.concatenate([jax.random.normal(jax.random.PRNGKey(3), (t, d)),
                             jnp.zeros((1, d))], 0)
    rng = np.random.default_rng(0)
    cnt = jnp.array([4, 0, 12], jnp.int32)
    src = np.full((e * c,), t, np.int32)
    wsl = np.zeros((e * c,), np.float32)
    for g in range(e):
        for r in range(int(cnt[g])):
            src[g * c + r] = rng.integers(0, t)
            wsl[g * c + r] = rng.random() + 0.1
    args = (x_ext, jnp.asarray(src), jnp.asarray(wsl), wg, wu, wd, cnt)
    ref = kops.gather_swiglu_scatter(*args, mode="ref")
    got = kops.gather_swiglu_scatter(*args, mode="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("wdt", ["fp8", "int8"])
def test_ops_gather_quantize_mode_parity(wdt):
    """The fused routing-gather -> block-quantize -> scale-pack kernel
    (ISSUE 6 wire codec) in interpret mode is bit-identical to the jnp
    ref, and dequantize round-trips identically in both modes."""
    e, c, d, t = 3, 10, 200, 9
    rng = np.random.default_rng(8)
    x_ext = jnp.asarray(np.concatenate(
        [rng.standard_normal((t, d)).astype(np.float32),
         np.zeros((1, d), np.float32)], 0))
    counts = rng.integers(0, c + 1, e).astype(np.int32)
    src = np.full((e * c,), t, np.int32)
    for g in range(e):
        src[g * c:g * c + counts[g]] = rng.integers(0, t, counts[g])
    args = (x_ext, jnp.asarray(src), jnp.asarray(counts))
    qr, sr = kops.gather_quantize(*args, wire_dtype=wdt, mode="ref")
    qi, si = kops.gather_quantize(*args, wire_dtype=wdt, mode="interpret")
    np.testing.assert_array_equal(
        np.ascontiguousarray(np.asarray(qr)).view(np.uint8),
        np.ascontiguousarray(np.asarray(qi)).view(np.uint8))
    np.testing.assert_array_equal(np.asarray(sr), np.asarray(si))
    np.testing.assert_array_equal(
        np.asarray(kops.dequantize_tokens(qr, sr, mode="ref")),
        np.asarray(kops.dequantize_tokens(qi, si, mode="interpret")))


def test_platform_mode_follows_backend():
    """The default kernel mode is derived from the platform, not from an
    environment variable: the jnp refs everywhere but the TPU."""
    want = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert kops.platform_mode() == want
    assert kops._mode(None) == want
    assert kops._mode("interpret") == "interpret"


def _mesh11():
    return jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))


@pytest.mark.parametrize("mode", ["ll", "ht"])
def test_dispatch_delivers_counts_to_expert_fn(mode):
    """Both dispatch paths hand plan-derived occupied counts to expert_fn
    (the occupancy contract), and the result matches the dense oracle."""
    e, k, t, d, f = 8, 2, 32, 16, 24
    x, ti, tw, wg, wu, wd = _problem(4, e, t, d, f, k)
    spec = EPSpec(axes=("model",), sizes=(1,), n_experts=e, top_k=k,
                  capacity_factor=8.0, dtype=jnp.float32)
    seen = []

    def expert_fn(tokens, counts=None):
        seen.append(counts is not None)
        assert counts is not None
        return kops.grouped_swiglu(tokens, wg, wu, wd, counts, mode="ref")

    fn = dispatch_combine_ll if mode == "ll" else dispatch_combine_ht

    def island(x, ti, tw):
        r = fn(spec, x, ti, tw, expert_fn)
        return r.out, r.aux["dropped"], r.aux["occupancy"]

    out, dropped, occ = jax.jit(jax.shard_map(
        island, mesh=_mesh11(), in_specs=(P(), P(), P()),
        out_specs=(P(), P(), P()), check_vma=False))(x, ti, tw)
    assert seen and all(seen)
    assert float(dropped) == 0.0
    assert 0.0 < float(occ) <= 1.0
    ref = moe_ref(x, ti, tw, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("kernel_mode", ["ref", "interpret"])
def test_moe_layer_kernel_mode_equivalence(kernel_mode, monkeypatch):
    """The MoE layer through kops mode dispatch on a one-device EP mesh:
    with the platform's default mode steered to interpret, the kernel
    bodies (occupancy-aware grouped SwiGLU + fused gather/scatter) must
    reproduce the dense ref-mode layer output."""
    from repro.configs import get_config, reduced_config
    from repro.core.moe import moe_apply, moe_init
    from repro.distributed.sharding import make_dist_ctx

    monkeypatch.setattr(kops, "platform_mode", lambda: kernel_mode)
    cfg = reduced_config(get_config("qwen2_moe_a2_7b"), n_layers=2,
                         d_model=32, n_experts=4)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    p = moe_init(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32), jnp.float32)
    y_ref, _ = moe_apply(cfg, None, p, x, mode="ref")
    dist = make_dist_ctx(cfg, mesh)
    y, aux = jax.jit(lambda p, x: moe_apply(cfg, dist, p, x, mode="ht",
                                            backend="jax_collectives"))(p, x)
    assert float(aux["dropped"]) == 0.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-5)


def test_ht_chunk_degradation_surfaced():
    """T % chunks != 0 degrades to the largest divisor (not 1) and surfaces
    the effective chunk count in aux."""
    e, k, t, d, f = 4, 2, 30, 8, 12
    x, ti, tw, wg, wu, wd = _problem(7, e, t, d, f, k)
    spec = EPSpec(axes=("model",), sizes=(1,), n_experts=e, top_k=k,
                  capacity_factor=8.0, chunks=4, dtype=jnp.float32)

    def island(x, ti, tw):
        r = dispatch_combine_ht(
            spec, x, ti, tw,
            lambda tk, c=None: kops.grouped_swiglu(tk, wg, wu, wd, c,
                                                   mode="ref"))
        return r.out, r.aux["dropped"]

    out, dropped = jax.jit(jax.shard_map(
        island, mesh=_mesh11(), in_specs=(P(), P(), P()),
        out_specs=(P(), P()), check_vma=False))(x, ti, tw)
    # aux["chunks"] is static metadata: probe it outside jit
    from repro.core.plan import effective_chunks
    assert effective_chunks(30, 4) == 3
    assert effective_chunks(32, 4) == 4
    assert effective_chunks(31, 4) == 1
    assert effective_chunks(30, 1) == 1
    ref = moe_ref(x, ti, tw, wg, wu, wd)
    assert float(dropped) == 0.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-5)
