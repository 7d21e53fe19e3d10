"""Width-tiled TrIM conv2d + arbitrary-scale fixed-point requant
(DESIGN.md §4): parity vs the oracles for partial tiles, strided halo
columns, the VMEM auto-pick, and bit-exact multiplier+shift rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import ExecutionPolicy
from repro.kernels import ref
from repro.kernels.ops import trim_conv2d
from repro.kernels.requant import (requant_mult_shift, requant_ref_int64,
                                   scale_to_mult_shift)
from repro.kernels.trim_conv2d import (VMEM_BUDGET_BYTES, pick_tile_w,
                                       trim_conv2d_pallas)

#: Pallas everywhere (interpret mode on CPU) — the old force-pallas mode.
PALLAS = ExecutionPolicy(substrate="pallas")


# ---------------------------------------------------------------------------
# width tiling: parity vs ref.py
# ---------------------------------------------------------------------------

TILED_CASES = [
    # (H, W, K, stride, tile_w)  — W_O deliberately not a TW multiple
    (6, 30, 3, 1, 8),            # 30 = 3*8 + 6 partial tail
    (9, 29, 3, 2, 4),            # halo columns with stride 2 (K > S)
    (11, 29, 5, 1, 6),           # K=5: 4 halo columns
    (13, 27, 5, 2, 5),           # K=5 stride 2: 3 halo columns
    (8, 21, 3, 1, 7),            # exact multiple (no partial tail)
    (6, 17, 1, 1, 4),            # K=1: no halo at all
]


@pytest.mark.parametrize("case", TILED_CASES, ids=str)
def test_conv2d_width_tiled_float(case):
    H, W, K, stride, tw = case
    key = jax.random.PRNGKey(sum(case))
    x = jax.random.normal(key, (1, H, W, 4), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, K, 4, 8),
                          jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 2), (8,), jnp.float32)
    out = trim_conv2d_pallas(x, w, stride=stride, tile_w=tw, bias=b,
                             relu=True, tile_h=4, block_c=4, block_f=8,
                             interpret=True)
    want = jnp.maximum(ref.conv2d_ref(x, w, stride=stride) + b, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", TILED_CASES[:4], ids=str)
def test_conv2d_width_tiled_int_exact(case):
    """uint8 x int8 -> int32 stays bit-exact through the tiled path."""
    H, W, K, stride, tw = case
    key = jax.random.PRNGKey(sum(case))
    x = jax.random.randint(key, (1, H, W, 4), 0, 255, jnp.uint8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (K, K, 4, 8),
                           -127, 127, jnp.int8)
    out = trim_conv2d_pallas(x, w, stride=stride, tile_w=tw, tile_h=4,
                             block_c=4, block_f=8, interpret=True)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.conv2d_ref(x, w, stride=stride)))


@pytest.mark.parametrize("stride,W", [(1, 512), (2, 1023)], ids=str)
def test_conv2d_wide_512(stride, W):
    """Acceptance: W_O = 512 through the Pallas path with TW < W_O —
    int8 bitwise and fp32 within tolerance, stride 1 and 2."""
    key = jax.random.PRNGKey(stride)
    H = 4 if stride == 1 else 5
    xi = jax.random.randint(key, (1, H, W, 4), 0, 255, jnp.uint8)
    wi = jax.random.randint(jax.random.fold_in(key, 1), (3, 3, 4, 8),
                            -127, 127, jnp.int8)
    W_O = (W + 2 - 3) // stride + 1
    assert W_O == 512
    out = trim_conv2d_pallas(xi, wi, stride=stride, tile_w=128, tile_h=4,
                             block_c=4, block_f=8, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.conv2d_ref(xi, wi, stride=stride)))
    xf = (xi.astype(jnp.float32) / 255.0) - 0.5
    wf = wi.astype(jnp.float32) / 127.0
    outf = trim_conv2d_pallas(xf, wf, stride=stride, tile_w=128, tile_h=4,
                              block_c=4, block_f=8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(outf), np.asarray(ref.conv2d_ref(xf, wf, stride=stride)),
        rtol=2e-5, atol=2e-5)


def test_conv2d_vmem_budget_forces_tiling():
    """A tight VMEM budget must trigger the auto-pick (TW < W_O) and stay
    correct; the kernel is the only thing that changes, not the math."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (1, 6, 64, 4), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 4, 8),
                          jnp.float32)
    tw = pick_tile_w(64, K=3, TH=4, Cb=4, Fb=8, vmem_budget=16384)
    assert tw < 64
    out = trim_conv2d_pallas(x, w, tile_h=4, block_c=4, block_f=8,
                             vmem_budget=16384, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.conv2d_ref(x, w)),
                               rtol=2e-5, atol=2e-5)


def test_pick_tile_w_paper_shapes_single_block():
    """Acceptance: the VGG-16 / AlexNet shapes keep the degenerate
    single-block layout (n_wt == 1) under the default VMEM budget."""
    # VGG-16 widest layer: 224x224, C/F blocks of 128, f32.
    assert pick_tile_w(224, K=3, TH=8, Cb=128, Fb=128) == 224
    # AlexNet CL1: 227x227x3, K=11 stride 4, which the kernel runs folded:
    # ceil(11/4) = 3 taps per axis over 4*4*3 = 48 channels.
    assert pick_tile_w(55, K=3, TH=8, Cb=48, Fb=96) == 55
    # A genuinely wide map must tile under the same default budget.
    assert pick_tile_w(2048, K=3, TH=8, Cb=128, Fb=128) < 2048
    assert VMEM_BUDGET_BYTES <= 16 * 2 ** 20


def test_ops_tile_w_dispatch_parity():
    """tile_w threads through the public ops dispatcher (CPU oracle vs
    pallas-policy width-tiled kernel agree)."""
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (1, 8, 26, 4))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 4, 8))
    a = trim_conv2d(x, w, tile_w=8)
    b = trim_conv2d(x, w, tile_w=8, policy=PALLAS)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# arbitrary-scale requant: bit-exact fixed-point rounding
# ---------------------------------------------------------------------------


def test_requant_mult_shift_matches_int64_oracle():
    """The int32-only hi/lo-split requant == the int64 oracle over the
    full int32 accumulator range, for every shift regime."""
    rng = np.random.default_rng(0)
    acc = np.concatenate([
        rng.integers(-2 ** 31, 2 ** 31, 4096, dtype=np.int64),
        np.array([0, 1, -1, 2 ** 31 - 1, -2 ** 31, 65535, -65536],
                 np.int64)]).astype(np.int32)
    for s in [1, 2, 8, 15, 16, 17, 20, 24, 31]:
        for m in [1, 3, 255, 16384, 32767]:
            got = np.asarray(requant_mult_shift(jnp.asarray(acc), m, s),
                             np.int64)
            np.testing.assert_array_equal(got, requant_ref_int64(acc, m, s),
                                          err_msg=f"m={m} s={s}")


def test_requant_fp32_scale_oracle_bit_exact():
    """Fixed-point (mult, shift) from an fp32 scale reproduces
    clip(floor(acc * scale + 0.5)) bit-exactly — the scale m*2^-s is
    representable exactly, so the float oracle and the integer datapath
    must agree on every element."""
    rng = np.random.default_rng(1)
    scales = np.float32(rng.uniform(1e-6, 200.0, 16))
    m, s = scale_to_mult_shift(scales)
    acc = rng.integers(-10 ** 8, 10 ** 8, (3, 5, 7, 16),
                       dtype=np.int64).astype(np.int32)
    got = np.asarray(requant_mult_shift(jnp.asarray(acc), jnp.asarray(m),
                                        jnp.asarray(s)), np.int64)
    exact_scale = m.astype(np.float64) / np.exp2(s.astype(np.float64))
    want = np.clip(np.floor(acc.astype(np.float64) * exact_scale + 0.5),
                   0, 255).astype(np.int64)
    np.testing.assert_array_equal(got, want)
    # and the encoded scale is within 2^-14 relative of the requested one
    np.testing.assert_allclose(exact_scale, scales, rtol=2.0 ** -14)


@pytest.mark.parametrize("tiled", [False, True], ids=["single", "tiled"])
def test_conv2d_fused_requant_mult_shift(tiled):
    """Fused multiplier+shift requant in the kernel flush == unfused
    int64 oracle, bitwise, per-channel, with and without width tiling."""
    key = jax.random.PRNGKey(5)
    x = jax.random.randint(key, (1, 10, 22, 4), 0, 255, jnp.uint8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (3, 3, 4, 8),
                           -127, 127, jnp.int8)
    rng = np.random.default_rng(2)
    m = rng.integers(8192, 32767, 8).astype(np.int32)
    s = rng.integers(14, 24, 8).astype(np.int32)
    out = trim_conv2d_pallas(x, w, stride=2, relu=True,
                             requant=(jnp.asarray(m), jnp.asarray(s)),
                             tile_w=4 if tiled else None,
                             tile_h=4, block_c=4, block_f=8, interpret=True)
    assert out.dtype == jnp.uint8
    psum = np.maximum(np.asarray(ref.conv2d_ref(x, w, stride=2)), 0)
    np.testing.assert_array_equal(np.asarray(out, np.int64),
                                  requant_ref_int64(psum, m, s))


def test_ops_requant_cpu_pallas_bitwise():
    """The jnp fallback epilogue and the fused kernel produce identical
    uint8 (the dispatcher is substrate-transparent for the int8 path)."""
    key = jax.random.PRNGKey(6)
    x = jax.random.randint(key, (1, 12, 12, 4), 0, 255, jnp.uint8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (3, 3, 4, 8),
                           -127, 127, jnp.int8)
    rq = (jnp.full((8,), 21000, jnp.int32), jnp.full((8,), 19, jnp.int32))
    a = trim_conv2d(x, w, None, rq, relu=True)
    b = trim_conv2d(x, w, None, rq, relu=True, policy=PALLAS)
    assert a.dtype == b.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ops_requant_grouped():
    """Grouped conv (AlexNet two-tower) slices per-channel requant arrays
    onto the right filter groups."""
    key = jax.random.PRNGKey(7)
    x = jax.random.randint(key, (1, 8, 8, 8), 0, 255, jnp.uint8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (3, 3, 4, 6),
                           -127, 127, jnp.int8)
    rng = np.random.default_rng(3)
    m = jnp.asarray(rng.integers(8192, 32767, 6).astype(np.int32))
    s = jnp.asarray(rng.integers(14, 22, 6).astype(np.int32))
    a = trim_conv2d(x, w, None, (m, s), groups=2, relu=True)
    b = trim_conv2d(x, w, None, (m, s), groups=2, relu=True,
                    policy=PALLAS)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cnn_int8_arbitrary_requant_fused():
    """Model-level: calibrate_requant pairs drive the fully-fused int8
    forward; parity vs an explicit unfused recomputation, bitwise."""
    from repro.configs import CNN_SMOKES
    from repro.nn.conv import (calibrate_requant, cnn_forward_int8,
                               init_cnn, max_pool2x2, quantize_cnn)
    cfg = CNN_SMOKES["vgg16"]
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    qp, _ = quantize_cnn(params, cfg)
    u8 = jax.random.randint(jax.random.PRNGKey(1), (1, 16, 16, 3), 0, 255,
                            jnp.uint8)
    pairs = calibrate_requant(qp, u8, cfg)
    assert len(pairs) == len(cfg.layers) - 1
    fused = cnn_forward_int8(qp, u8, cfg, requant=pairs)
    # unfused replay through the oracle conv + shared requant helper
    x = u8
    for i, l in enumerate(cfg.layers):
        w = qp["conv"][i]["kernel"]
        psum = jnp.maximum(ref.conv2d_ref(x, w, stride=l.stride,
                                          padding=l.padding), 0)
        if i == len(cfg.layers) - 1:
            want = psum
            break
        m, s = pairs[i]
        x = requant_mult_shift(psum, m, s).astype(jnp.uint8)
        if i in cfg.pool_after:
            x = max_pool2x2(x)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(want))


def test_cnn_int8_per_tensor_calibration():
    """per_channel=False emits scalar-per-layer pairs that still run the
    fused path end to end."""
    from repro.configs import CNN_SMOKES
    from repro.nn.conv import (calibrate_requant, cnn_forward_int8,
                               init_cnn, quantize_cnn)
    cfg = CNN_SMOKES["alexnet"]
    params = init_cnn(jax.random.PRNGKey(2), cfg)
    qp, _ = quantize_cnn(params, cfg)
    u8 = jax.random.randint(jax.random.PRNGKey(3), (1, 19, 19, 3), 0, 255,
                            jnp.uint8)
    pairs = calibrate_requant(qp, u8, cfg, per_channel=False)
    out = cnn_forward_int8(qp, u8, cfg, requant=pairs)
    assert out.dtype == jnp.int32
