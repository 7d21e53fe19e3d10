"""Pallas kernel validation: shape/dtype sweeps + hypothesis properties,
interpret=True (the kernel body executes on CPU) vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.engine import ExecutionPolicy
from repro.kernels import ref
from repro.kernels.ops import trim_conv2d
from repro.kernels.trim_conv1d import trim_conv1d_pallas
from repro.kernels.trim_conv2d import trim_conv2d_pallas
from repro.kernels.trim_matmul import trim_matmul_pallas

#: Pallas everywhere (interpret mode on CPU) — the old force-pallas mode.
PALLAS = ExecutionPolicy(substrate="pallas")
#: Same, with the FPGA-faithful strided-layer decimation replay (§V).
PALLAS_HW = ExecutionPolicy(substrate="pallas", emulate_hw=True)


# ---------------------------------------------------------------------------
# conv2d — the TrIM kernel
# ---------------------------------------------------------------------------

CONV2D_CASES = [
    # (N, H, W, C, K, F, tile_h, bc, bf)
    (1, 8, 8, 4, 3, 8, 4, 4, 8),
    (2, 16, 20, 8, 3, 16, 8, 8, 16),
    (1, 13, 13, 3, 3, 5, 4, 3, 5),       # odd sizes force padding
    (1, 12, 12, 4, 5, 8, 4, 4, 8),       # K=5
    (1, 9, 9, 2, 1, 4, 4, 2, 4),         # K=1 degenerate
    (2, 24, 24, 16, 3, 32, 8, 16, 32),
]


@pytest.mark.parametrize("case", CONV2D_CASES, ids=str)
def test_conv2d_float_sweep(case):
    N, H, W, C, K, F, th, bc, bf = case
    key = jax.random.PRNGKey(sum(case))
    x = jax.random.normal(key, (N, H, W, C), jnp.float32)
    w = jax.random.normal(key, (K, K, C, F), jnp.float32)
    out = trim_conv2d_pallas(x, w, tile_h=th, block_c=bc, block_f=bf,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.conv2d_ref(x, w)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", CONV2D_CASES[:4], ids=str)
def test_conv2d_int_exact(case):
    """The paper's integer datapath: uint8 x int8 -> int32, bit-exact."""
    N, H, W, C, K, F, th, bc, bf = case
    key = jax.random.PRNGKey(sum(case))
    x = jax.random.randint(key, (N, H, W, C), 0, 255, jnp.uint8)
    w = jax.random.randint(key, (K, K, C, F), -127, 127, jnp.int8)
    out = trim_conv2d_pallas(x, w, tile_h=th, block_c=bc, block_f=bf,
                             interpret=True)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.conv2d_ref(x, w)))


def test_conv2d_bf16_accumulates_f32():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 8, 8, 64), jnp.bfloat16)
    w = jax.random.normal(key, (3, 3, 64, 8), jnp.bfloat16)
    out = trim_conv2d_pallas(x, w, tile_h=4, block_c=64, block_f=8,
                             interpret=True)
    want = ref.conv2d_ref(x.astype(jnp.float32), w.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_conv2d_stride_decimation():
    """Striding = stride-1 sweep + decimation (the hardware's behaviour)."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (1, 16, 16, 4))
    w = jax.random.normal(key, (3, 3, 4, 8))
    out = trim_conv2d(x, w, stride=2, policy=PALLAS)
    want = ref.conv2d_ref(x, w, stride=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# conv1d — the Mamba short-conv kernel
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(B=st.integers(1, 3), L=st.integers(1, 70), D=st.integers(1, 40),
       K=st.integers(1, 6), tile=st.sampled_from([8, 16, 32]))
def test_conv1d_property(B, L, D, K, tile):
    key = jax.random.PRNGKey(B * 1000 + L * 10 + D + K)
    x = jax.random.normal(key, (B, L, D), jnp.float32)
    w = jax.random.normal(key, (K, D), jnp.float32)
    out = trim_conv1d_pallas(x, w, tile_l=tile, block_d=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.conv1d_causal_ref(x, w)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# matmul — the K=1 degenerate TrIM (weight-stationary blocked)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(M=st.integers(1, 200), K=st.integers(1, 120), N=st.integers(1, 150),
       bm=st.sampled_from([16, 32, 64]), bk=st.sampled_from([16, 64]))
def test_matmul_property(M, K, N, bm, bk):
    key = jax.random.PRNGKey(M + K * 7 + N * 13)
    a = jax.random.normal(key, (M, K), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
    out = trim_matmul_pallas(a, b, block_m=bm, block_n=32, block_k=bk,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=2e-4, atol=2e-4)


def test_matmul_int8_exact():
    key = jax.random.PRNGKey(3)
    a = jax.random.randint(key, (64, 96), -127, 127, jnp.int8)
    b = jax.random.randint(key, (96, 48), -127, 127, jnp.int8)
    out = trim_matmul_pallas(a, b, block_m=32, block_n=32, block_k=32,
                             interpret=True)
    want = ref.matmul_ref(a, b)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_ops_cpu_fallback_matches_pallas():
    """ops.* dispatches to the oracle on CPU; the pallas policy must
    agree."""
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (1, 10, 10, 4))
    w = jax.random.normal(key, (3, 3, 4, 8))
    a = trim_conv2d(x, w)
    b = trim_conv2d(x, w, policy=PALLAS)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention — the §Perf memory-term kernel
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, H, Sq, D, bq, bk, causal)
    (2, 3, 64, 16, 16, 16, True),
    (1, 2, 33, 8, 16, 8, True),      # ragged seq vs blocks
    (2, 2, 40, 16, 16, 16, False),
    (1, 1, 128, 32, 64, 32, True),
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=str)
def test_flash_pallas_sweep(case):
    from repro.kernels.flash_attention import (flash_attention_pallas,
                                               flash_attention_ref)
    B, H, S, D, bq, bk, causal = case
    key = jax.random.PRNGKey(sum(case))
    q = jax.random.normal(key, (B, H, S, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, D))
    o = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                               block_k=bk, interpret=True)
    r = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


def test_flash_pallas_kv_length():
    from repro.kernels.flash_attention import (flash_attention_pallas,
                                               flash_attention_ref)
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (1, 2, 16, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 16, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 16, 8))
    o = flash_attention_pallas(q, k, v, causal=False, kv_length=9,
                               block_q=8, block_k=8, interpret=True)
    r = flash_attention_ref(q, k, v, causal=False, kv_length=9)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


def test_flash_pallas_bf16():
    from repro.kernels.flash_attention import (flash_attention_pallas,
                                               flash_attention_ref)
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (1, 2, 32, 16), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 32, 16),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 32, 16),
                          jnp.bfloat16)
    o = flash_attention_pallas(q, k, v, causal=True, block_q=16, block_k=16,
                               interpret=True)
    r = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_conv2d_grouped():
    """Grouped conv (AlexNet's two-tower CL2/4/5): per-group Pallas calls
    == lax grouped-conv oracle."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (1, 10, 10, 8))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 4, 6))
    a = trim_conv2d(x, w, groups=2)
    b = trim_conv2d(x, w, groups=2, policy=PALLAS)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# stride-aware fused conv2d (DESIGN.md §2): parity vs the oracle for
# stride x kernel x dtype x epilogue, computing only the strided outputs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("K", [1, 3])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_conv2d_strided_float(stride, K, fused):
    key = jax.random.PRNGKey(stride * 10 + K)
    x = jax.random.normal(key, (2, 13, 13, 4), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, K, 4, 8),
                          jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 2), (8,), jnp.float32)
    out = trim_conv2d_pallas(x, w, stride=stride,
                             bias=b if fused else None, relu=fused,
                             tile_h=4, block_c=4, block_f=8, interpret=True)
    want = ref.conv2d_ref(x, w, stride=stride)
    if fused:
        want = jnp.maximum(want + b, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("K", [1, 3])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_conv2d_strided_int_exact(stride, K, fused):
    """uint8 x int8 -> int32 stays bit-exact through the strided kernel,
    with and without the fused bias/ReLU epilogue."""
    key = jax.random.PRNGKey(stride * 100 + K)
    x = jax.random.randint(key, (1, 13, 13, 4), 0, 255, jnp.uint8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (K, K, 4, 8),
                           -127, 127, jnp.int8)
    b = jax.random.randint(jax.random.fold_in(key, 2), (8,),
                           -1000, 1000, jnp.int32)
    out = trim_conv2d_pallas(x, w, stride=stride,
                             bias=b if fused else None, relu=fused,
                             tile_h=4, block_c=4, block_f=8, interpret=True)
    want = ref.conv2d_ref(x, w, stride=stride)
    if fused:
        want = jnp.maximum(want + b, 0)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_conv2d_fused_requant_uint8():
    """Fused power-of-two requantization (the engine's output stage) returns
    uint8 bit-identical to the unfused relu >> shift >> clip pipeline."""
    key = jax.random.PRNGKey(5)
    x = jax.random.randint(key, (1, 12, 12, 4), 0, 255, jnp.uint8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (3, 3, 4, 8),
                           -127, 127, jnp.int8)
    out = trim_conv2d_pallas(x, w, stride=2, relu=True, requant_shift=9,
                             tile_h=4, block_c=4, block_f=8, interpret=True)
    want = jnp.clip(jnp.right_shift(
        jnp.maximum(ref.conv2d_ref(x, w, stride=2), 0), 9), 0, 255)
    assert out.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(want, np.uint8))


def test_conv2d_alexnet_cl1_shape():
    """AlexNet CL1 structure (K=11, stride 4, no padding) on a reduced map:
    the hard case for the halo/index-map math (K >> stride)."""
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (1, 23, 23, 3), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (11, 11, 3, 8),
                          jnp.float32)
    out = trim_conv2d_pallas(x, w, stride=4, padding=0, tile_h=2,
                             block_c=3, block_f=8, interpret=True)
    want = ref.conv2d_ref(x, w, stride=4, padding=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_conv2d_emulate_hw_matches_fused():
    """The FPGA-faithful decimation schedule (§V) and the stride-aware
    kernel agree: same outputs, different work."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (1, 16, 16, 4))
    w = jax.random.normal(key, (3, 3, 4, 8))
    b = jax.random.normal(jax.random.fold_in(key, 1), (8,))
    hw = trim_conv2d(x, w, b, stride=2, relu=True, policy=PALLAS_HW)
    fused = trim_conv2d(x, w, b, stride=2, relu=True, policy=PALLAS)
    want = jnp.maximum(ref.conv2d_ref(x, w, stride=2) + b, 0)
    np.testing.assert_allclose(np.asarray(hw), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_conv2d_grouped_fused_bias():
    """Grouped conv (AlexNet two-tower) with the fused epilogue: per-group
    bias slices land on the right filters."""
    key = jax.random.PRNGKey(8)
    x = jax.random.normal(key, (1, 10, 10, 8))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 4, 6))
    b = jax.random.normal(jax.random.fold_in(key, 2), (6,))
    a = trim_conv2d(x, w, b, groups=2, relu=True)
    p = trim_conv2d(x, w, b, groups=2, relu=True, policy=PALLAS)
    np.testing.assert_allclose(np.asarray(a), np.asarray(p), rtol=2e-5,
                               atol=2e-5)


def test_cnn_int8_fused_requant_parity():
    """Calibrated fused-requant int8 forward == dynamic-shift forward,
    bit-exact (the whole epilogue moves into the kernel flush)."""
    from repro.configs import CNN_SMOKES
    from repro.nn.conv import (calibrate_requant_shifts, cnn_forward_int8,
                               init_cnn, quantize_cnn)
    cfg = CNN_SMOKES["vgg16"]
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    qp, _ = quantize_cnn(params, cfg)
    u8 = jax.random.randint(jax.random.PRNGKey(1), (1, 16, 16, 3), 0, 255,
                            jnp.uint8)
    dyn = cnn_forward_int8(qp, u8, cfg)
    shifts = calibrate_requant_shifts(qp, u8, cfg)
    fused = cnn_forward_int8(qp, u8, cfg, requant_shifts=shifts)
    np.testing.assert_array_equal(np.asarray(dyn), np.asarray(fused))


def test_conv2d_halo_taller_than_block():
    """Regression: K - stride > tile_h * stride (e.g. K=11 stride 1 with the
    default tile_h, or tiny maps where H_O < K) must auto-grow the row block
    instead of slicing past the assembled tile."""
    key = jax.random.PRNGKey(12)
    x = jax.random.normal(key, (1, 16, 16, 3), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (11, 11, 3, 4),
                          jnp.float32)
    out = trim_conv2d_pallas(x, w, padding=0, tile_h=8, block_c=3,
                             block_f=4, interpret=True)  # halo 10 > RB 8
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.conv2d_ref(x, w, padding=0)),
        rtol=2e-5, atol=2e-5)
    # tiny map: H_O = 1 forces TH = 1 < K - 1
    x2 = jax.random.normal(key, (1, 3, 3, 2), jnp.float32)
    w2 = jax.random.normal(jax.random.fold_in(key, 2), (3, 3, 2, 4),
                           jnp.float32)
    out2 = trim_conv2d_pallas(x2, w2, padding=0, tile_h=8, block_c=2,
                              block_f=4, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out2), np.asarray(ref.conv2d_ref(x2, w2, padding=0)),
        rtol=2e-5, atol=2e-5)
    # the emulate_hw decimate arm on an AlexNet-CL1-like layer hits the
    # stride-1 sweep with the default tile_h
    x3 = jax.random.normal(key, (1, 23, 23, 3))
    w3 = jax.random.normal(jax.random.fold_in(key, 3), (11, 11, 3, 4))
    hw = trim_conv2d(x3, w3, stride=4, padding=0, policy=PALLAS_HW)
    np.testing.assert_allclose(
        np.asarray(hw), np.asarray(ref.conv2d_ref(x3, w3, stride=4,
                                                  padding=0)),
        rtol=2e-5, atol=2e-5)


def test_cnn_int8_grouped_layers():
    """Regression: the int8 datapath derives groups from the running channel
    count (AlexNet two-tower layers), incl. the calibrated fused path."""
    from repro.core.trim.model import ConvLayerSpec
    from repro.nn.conv import (CNNConfig, calibrate_requant_shifts,
                               cnn_forward_int8)
    cfg = CNNConfig(
        "two-tower-smoke",
        layers=(ConvLayerSpec("CL1", 8, 8, 3, 4, 8),
                ConvLayerSpec("CL2", 8, 8, 3, 4, 8)),   # 8 chans / M=4 -> 2
        pool_after=(), classifier=(8,), n_classes=4, input_hw=(8, 8))
    key = jax.random.PRNGKey(13)
    qp = {"conv": [
        {"kernel": jax.random.randint(key, (3, 3, 4, 8), -127, 127,
                                      jnp.int8)},
        {"kernel": jax.random.randint(jax.random.fold_in(key, 1),
                                      (3, 3, 4, 8), -127, 127, jnp.int8)}]}
    u8 = jax.random.randint(jax.random.fold_in(key, 2), (1, 8, 8, 4), 0,
                            255, jnp.uint8)
    dyn = cnn_forward_int8(qp, u8, cfg)
    assert dyn.dtype == jnp.int32 and dyn.shape == (1, 8, 8, 8)
    shifts = calibrate_requant_shifts(qp, u8, cfg)
    fused = cnn_forward_int8(qp, u8, cfg, requant_shifts=shifts)
    np.testing.assert_array_equal(np.asarray(dyn), np.asarray(fused))
