"""Low-precision wire dispatch (ISSUE 6): codec, quantize-pack kernels,
and loss parity of compressed dispatch against the dense fp32 oracles.

Tolerance notes (documented contract, DESIGN.md §14):

- **fp8-e4m3**: 3 mantissa bits -> worst-case relative quantization error
  of 2^-4 = 6.25% per element *of its block's absmax* (plus the fp32->fp16
  pre-rounding, which is negligible at these magnitudes).  After the
  expert FFN and the weighted combine, empirical end-to-end error stays
  under 5% of the output range; the tests pin 20% as a loud-failure bound.
- **int8**: symmetric 8-bit -> <= 1/254 of block absmax per element
  (~0.4%); end-to-end bound pinned at 5% of output range.
- **fp32**: passthrough, bit-exact.

Parity between the numpy codec, the jnp ref, and the Pallas kernel bodies
is *bit-exact* by construction: the wire rounding contract is fp32 ->
fp16 -> fp8-e4m3 (RTNE at both steps) and scales are computed as
``absmax * (1/qmax)`` with a pre-rounded fp32 reciprocal in every dialect
(XLA strength-reduces division-by-constant to a reciprocal multiply;
doing it explicitly keeps numpy and XLA on the same floats).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import WIRE_BLOCK, wire_layout
from repro.core.transport.codec import (WIRE_DTYPES, dequantize_blocked,
                                        get_codec, quantize_blocked)
from repro.kernels import ops as kops
from repro.kernels.quantize_pack import (gather_quantize_pallas,
                                         gather_quantize_ref)

# end-to-end loss-parity bounds vs the dense fp32 oracle (see module doc)
E2E_TOL = {"fp32": 0.0, "fp8": 0.2, "int8": 0.05}
# elementwise roundtrip bounds relative to each block's absmax
RT_TOL = {"fp8": 0.0625 + 1e-3, "int8": 1.0 / 254 + 1e-4}


# ================================================================ codec ==
def test_wire_layout_math():
    assert wire_layout(1024, "fp32").token_bytes == 4096
    wl = wire_layout(1024, "fp8")
    assert (wl.token_bytes, wl.q_bytes, wl.n_blocks) == (1024 + 32, 1024, 8)
    wl = wire_layout(200, "int8")    # ragged last block
    assert (wl.token_bytes, wl.n_blocks) == (200 + 8, 2)
    assert wire_layout(8, "fp8").token_bytes == 12
    with pytest.raises(ValueError):
        wire_layout(8, "fp16")


@pytest.mark.parametrize("wdt", ["fp8", "int8"])
@pytest.mark.parametrize("d", [8, 128, 200, 1024])
def test_quantize_roundtrip_bounded(wdt, d):
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((16, d)) * 10 ** rng.uniform(
        -2, 2, (16, 1))).astype(np.float32)
    q, s = quantize_blocked(x, wdt)
    y = dequantize_blocked(q, s)
    nb = -(-d // WIRE_BLOCK)
    pad = nb * WIRE_BLOCK - d
    xb = np.pad(x, ((0, 0), (0, pad))).reshape(16, nb, WIRE_BLOCK)
    absmax = np.abs(xb).max(-1)                       # (16, nb)
    err = np.abs(np.pad(y, ((0, 0), (0, pad))).reshape(16, nb, WIRE_BLOCK)
                 - xb).max(-1)
    assert (err <= RT_TOL[wdt] * np.maximum(absmax, 1e-30)).all()


def test_quantize_zero_rows_exact():
    x = np.zeros((4, 200), np.float32)
    for wdt in ("fp8", "int8"):
        q, s = quantize_blocked(x, wdt)
        assert (np.asarray(q, np.float32) == 0).all()
        np.testing.assert_array_equal(dequantize_blocked(q, s), x)


@pytest.mark.parametrize("wdt", ["fp8", "int8"])
@pytest.mark.parametrize("d", [8, 128, 200, 1024])
def test_quantize_np_jnp_bit_parity(wdt, d):
    """The numpy codec (substrate) and the jnp ref (jax path) must agree
    bit-for-bit — the wire bytes are the protocol, not an approximation."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((32, d)).astype(np.float32)
    qn, sn = quantize_blocked(x, wdt)
    qj, sj = quantize_blocked(jnp.asarray(x), wdt)
    np.testing.assert_array_equal(
        np.ascontiguousarray(qn).view(np.uint8),
        np.ascontiguousarray(np.asarray(qj)).view(np.uint8))
    np.testing.assert_array_equal(sn, np.asarray(sj))
    np.testing.assert_array_equal(
        dequantize_blocked(qn, sn),
        np.asarray(dequantize_blocked(qj, sj)))


@pytest.mark.parametrize("wdt", WIRE_DTYPES)
@pytest.mark.parametrize("d", [8, 200, 1024])
def test_codec_encode_decode_roundtrip(wdt, d):
    codec = get_codec(wdt)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((8, d)).astype(np.float32)
    buf = codec.encode(x)
    assert buf.dtype == np.uint8
    assert buf.shape == (8, codec.wire_bytes(d))
    assert codec.wire_bytes(d) == wire_layout(d, wdt).token_bytes
    y = codec.decode(buf, d)
    if wdt == "fp32":
        np.testing.assert_array_equal(y, x)
    else:
        q, s = quantize_blocked(x, wdt)
        np.testing.assert_array_equal(y, dequantize_blocked(q, s))


def test_get_codec_unknown():
    with pytest.raises(ValueError):
        get_codec("fp16")


# =============================================================== kernels ==
def _gq_problem(seed, e, c, d, t):
    rng = np.random.default_rng(seed)
    x_ext = np.concatenate([rng.standard_normal((t, d)).astype(np.float32),
                            np.zeros((1, d), np.float32)], 0)
    counts = rng.integers(0, c + 1, e).astype(np.int32)
    src = np.full((e * c,), t, np.int32)
    for g in range(e):
        src[g * c:g * c + counts[g]] = rng.integers(0, t, counts[g])
    return x_ext, src, counts


@pytest.mark.parametrize("wdt", ["fp8", "int8"])
@pytest.mark.parametrize("e,c,d,t", [(4, 6, 200, 11), (2, 16, 128, 9)])
def test_gather_quantize_kernel_parity(wdt, e, c, d, t):
    """Pallas kernel (interpret mode) == jnp ref == numpy codec, bit-exact,
    including occupancy zeroing of unoccupied slots."""
    x_ext, src, counts = _gq_problem(3, e, c, d, t)
    qr, sr = gather_quantize_ref(x_ext, src, counts, wire_dtype=wdt)
    qk, sk = gather_quantize_pallas(jnp.asarray(x_ext), jnp.asarray(src),
                                    jnp.asarray(counts), wire_dtype=wdt,
                                    bm=8, interpret=True)
    np.testing.assert_array_equal(
        np.ascontiguousarray(qr).view(np.uint8),
        np.ascontiguousarray(np.asarray(qk)).view(np.uint8))
    np.testing.assert_array_equal(sr, np.asarray(sk))
    # unoccupied slots are exact zeros with zero scales
    occ = np.zeros((e * c,), bool)
    for g in range(e):
        occ[g * c:g * c + counts[g]] = True
    assert (np.asarray(qk, np.float32)[~occ] == 0).all()
    assert (np.asarray(sk)[~occ] == 0).all()


def test_kernel_f16_rounding_matches_cast():
    """The kernel's f16 step (bit arithmetic in f32, since Mosaic has no
    f16 on every TPU) equals the codec's f32 -> f16 cast over the clipped
    fp8 range, ties included, and the fp8 bytes that follow are equal."""
    from repro.kernels.quantize_pack import _round_to_f16

    rng = np.random.default_rng(3)
    y = rng.uniform(-448, 448, 200_000).astype(np.float32)
    small = (rng.standard_normal(50_000) * 1e-3).astype(np.float32)
    # f16 ties: halfway between neighbouring f16 values, both parities
    h = rng.uniform(-448, 448, 50_000).astype(np.float16).astype(np.float32)
    ties = (h.view(np.uint32) | np.uint32(0x1000)).view(np.float32)
    y = np.concatenate([y, small, ties, np.float32([0.0, -0.0, 448, -448])])
    got = np.asarray(_round_to_f16(jnp.asarray(y)))
    want = y.astype(np.float16).astype(np.float32)
    normal = np.abs(y) >= 2.0 ** -14
    np.testing.assert_array_equal(got[normal], want[normal])
    f8 = jnp.float8_e4m3fn
    np.testing.assert_array_equal(
        np.asarray(jnp.asarray(got).astype(f8)).view(np.uint8),
        np.asarray(jnp.asarray(want).astype(f8)).view(np.uint8))


def test_ops_gather_quantize_mode_parity():
    """The ops-level wrapper: ref and interpret modes agree bit-for-bit,
    and dequantize_tokens round-trips both."""
    x_ext, src, counts = _gq_problem(4, 3, 8, 200, 7)
    for wdt in ("fp8", "int8"):
        qr, sr = kops.gather_quantize(jnp.asarray(x_ext), jnp.asarray(src),
                                      jnp.asarray(counts), wire_dtype=wdt,
                                      mode="ref")
        qi, si = kops.gather_quantize(jnp.asarray(x_ext), jnp.asarray(src),
                                      jnp.asarray(counts), wire_dtype=wdt,
                                      mode="interpret")
        np.testing.assert_array_equal(
            np.ascontiguousarray(np.asarray(qr)).view(np.uint8),
            np.ascontiguousarray(np.asarray(qi)).view(np.uint8))
        np.testing.assert_array_equal(np.asarray(sr), np.asarray(si))
        yr = kops.dequantize_tokens(qr, sr, mode="ref")
        yi = kops.dequantize_tokens(qi, si, mode="interpret")
        np.testing.assert_array_equal(np.asarray(yr), np.asarray(yi))


def test_kernel_bytes_match_codec_encode():
    """The kernel's packed output is byte-identical to codec.encode of the
    gathered rows — the substrate and jax paths put the SAME bytes on the
    wire (modulo layout: kernel returns (q, scales) planes, codec packs
    rows; compare after packing)."""
    d = 200
    x_ext, src, counts = _gq_problem(5, 2, 8, d, 9)
    for wdt in ("fp8", "int8"):
        codec = get_codec(wdt)
        q, s = gather_quantize_ref(x_ext, src, counts, wire_dtype=wdt)
        wl = wire_layout(d, wdt)
        packed = np.zeros((q.shape[0], wl.token_bytes), np.uint8)
        packed[:, :wl.q_bytes] = np.ascontiguousarray(q).view(np.uint8)
        packed[:, wl.q_bytes:] = np.ascontiguousarray(s).view(np.uint8)
        buf = x_ext[src]
        occ = np.zeros((len(src),), bool)
        for g in range(2):
            occ[g * 8:g * 8 + counts[g]] = True
        buf = np.where(occ[:, None], buf, 0.0).astype(np.float32)
        np.testing.assert_array_equal(packed, codec.encode(buf))


# ====================================================== loss parity (e2e) ==
def _substrate_case(proto, wdt, seed=0, d=64):
    from repro.core.transport import EPWorld, NetConfig
    rng = np.random.default_rng(seed)
    R, eps, K, F, Tl = 2, 4, 2, 16, 8
    E = R * eps
    x = rng.standard_normal((R, Tl, d)).astype(np.float32)
    ti = rng.integers(0, E, size=(R, Tl, K)).astype(np.int32)
    tw = rng.random((R, Tl, K)).astype(np.float32)
    tw /= tw.sum(-1, keepdims=True)
    wg = (rng.standard_normal((E, d, F)) * 0.2).astype(np.float32)
    wu = (rng.standard_normal((E, d, F)) * 0.2).astype(np.float32)
    wd = (rng.standard_normal((E, F, d)) * 0.2).astype(np.float32)
    w = EPWorld(n_ranks=R, n_experts=E, top_k=K, d=d, f=F, capacity=Tl * K,
                net_cfg=NetConfig(mode="srd", seed=seed), wire_dtype=wdt)
    out = (w.run(x, ti, tw, wg, wu, wd) if proto == "ll"
           else w.run_ht(x, ti, tw, wg, wu, wd, n_chunks=2))
    ref = EPWorld.oracle(x, ti, tw, wg, wu, wd)
    return out, ref, w


@pytest.mark.parametrize("proto", ["ll", "ht"])
@pytest.mark.parametrize("wdt", WIRE_DTYPES)
def test_substrate_loss_parity(proto, wdt):
    """Compressed dispatch through the full transport substrate vs the
    dense fp32 oracle, within the documented tolerance for the dtype."""
    out, ref, _ = _substrate_case(proto, wdt)
    if wdt == "fp32":
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
    else:
        err = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err <= E2E_TOL[wdt], f"{proto}/{wdt} relerr {err:.4f}"


@pytest.mark.parametrize("wdt", ["fp8", "int8"])
def test_substrate_compression_reduces_payload(wdt):
    """Honest wire accounting: the compressed run's dispatch payload bytes
    are the fp32 run's scaled by wb/4d (exactly — same message schedule)."""
    d = 64
    _, _, w32 = _substrate_case("ll", "fp32", d=d)
    _, _, wq = _substrate_case("ll", wdt, d=d)
    p32 = w32.timeline["dispatch_payload_bytes"]
    pq = wq.timeline["dispatch_payload_bytes"]
    wb = wire_layout(d, wdt).token_bytes
    assert p32 > 0 and pq * 4 * d == p32 * wb
    assert wq.timeline["dispatch_wire_bytes"] > pq


@pytest.mark.parametrize("mode", ["ll", "ht"])
@pytest.mark.parametrize("wdt", ["fp8", "int8"])
def test_jax_dispatch_loss_parity(mode, wdt):
    """jax-collectives compressed dispatch vs moe_ref (single-shard mesh:
    collectives degenerate, quantize/dequantize still on the path)."""
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.core.ep import (EPSpec, dispatch_combine_ht,
                               dispatch_combine_ll, moe_ref)
    from repro.kernels.ref import grouped_swiglu_ref
    t, d, f, e, k = 32, 200, 24, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (t, d))
    ti = jax.random.randint(ks[1], (t, k), 0, e).astype(jnp.int32)
    tw = jax.nn.softmax(jax.random.normal(ks[2], (t, k)), -1)
    wg = jax.random.normal(ks[3], (e, d, f)) * 0.2
    wu = jax.random.normal(ks[4], (e, d, f)) * 0.2
    wd = jax.random.normal(ks[5], (e, f, d)) * 0.2
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    spec = EPSpec(axes=("model",), sizes=(1,), n_experts=e, top_k=k,
                  capacity_factor=8.0, dtype=jnp.float32, wire_dtype=wdt,
                  chunks=2 if mode == "ht" else 1)
    fn = dispatch_combine_ll if mode == "ll" else dispatch_combine_ht

    def island(x, ti, tw, wg, wu, wd):
        r = fn(spec, x, ti, tw, lambda tk: grouped_swiglu_ref(tk, wg, wu, wd))
        return r.out, r.aux["dropped"]

    out, dropped = jax.jit(jax.shard_map(
        island, mesh=mesh, in_specs=(P(),) * 6, out_specs=(P(), P()),
        check_vma=False))(x, ti, tw, wg, wu, wd)
    assert float(dropped) == 0.0
    ref = np.asarray(moe_ref(x, ti, tw, wg, wu, wd))
    err = np.abs(np.asarray(out) - ref).max() / (np.abs(ref).max() + 1e-9)
    assert err <= E2E_TOL[wdt], f"{mode}/{wdt} relerr {err:.4f}"


def test_distributed_compression_delegates_to_codec():
    """distributed.compression is a thin wrapper over the transport codec
    (one quantizer in the repo): its int8 chunks must round-trip through
    the same blocked math."""
    from repro.distributed.compression import BLOCK, dequantize, quantize
    rng = np.random.default_rng(6)
    g = rng.standard_normal(1000).astype(np.float32)
    c = quantize(jnp.asarray(g))
    y = np.asarray(dequantize(c, g.size))
    nb = -(-g.size // BLOCK)
    xb = np.pad(g, (0, nb * BLOCK - g.size)).reshape(nb, BLOCK)
    q, s = quantize_blocked(xb, "int8", block=BLOCK)
    np.testing.assert_array_equal(np.asarray(c.q), np.asarray(q))
    np.testing.assert_array_equal(np.asarray(c.scale), np.asarray(s[:, 0]))
    err = np.abs(y - g).max()
    assert err <= np.abs(xb).max() / 100


@pytest.mark.parametrize("wdt", ["fp8", "int8"])
def test_moe_apply_wire_dtype_reaches_backend(wdt):
    """Config seam regression: ``cfg.moe.wire_dtype`` must reach the EPSpec
    on the no-dist simulated path (it was silently dropped once).  The
    compressed run must differ from fp32 (compression actually engaged)
    while staying within the documented tolerance of the ref oracle."""
    import dataclasses

    from repro.configs import get_config, reduced_config
    from repro.core.moe import moe_apply, moe_init

    cfg = reduced_config(get_config("qwen2_moe_a2_7b"), n_layers=2,
                         d_model=256, n_experts=4)
    p = moe_init(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 256), jnp.float32)
    y_ref, _ = moe_apply(cfg, None, p, x, mode="ref")
    cfg_q = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, wire_dtype=wdt))
    y_q, _ = moe_apply(cfg_q, None, p, x, mode="ll",
                       backend="simulated_rdma")
    scale = float(jnp.max(jnp.abs(y_ref))) + 1e-9
    err = float(jnp.max(jnp.abs(y_q - y_ref))) / scale
    assert 0.0 < err <= E2E_TOL[wdt], f"{wdt} relerr {err:.4f}"
