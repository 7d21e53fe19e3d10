"""The TrIM conv kernels compile for a TPU v5e chip — no chip needed.

The TPU compiler is installed with jaxlib and compiles for a *described*
topology (``jax.experimental.topologies``), so the chip's Mosaic lowering
refusals (strided value slices, int32 matmul operands, misaligned blocks,
VMEM overruns) fail here, in tier-1, instead of on the chip.  Shapes are
the paper's own layers at a serving bucket's batch size.  Nothing runs:
results are checked in interpret mode by the other kernel tests, and by
the integer-exactness test at the bottom of this file.

The topology is described inside a fixture (never at import), so every
test worker collects the same tests and only the one running this file
loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ref
from repro.kernels.trim_conv2d import trim_conv2d_pallas
from repro.kernels.trim_conv2d_vjp import trim_conv2d_wgrad_pallas

N = 8  # the largest serving bucket chip_smoke.py uses


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A described-topology compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _grouped_int8(x, w, m, s):
    """AlexNet's two-tower layer the way the dispatcher runs it: one
    kernel call per group, fused ReLU + per-channel requant."""
    G = 2
    cg, fg = x.shape[-1] // G, w.shape[-1] // G
    return jnp.concatenate([
        trim_conv2d_pallas(x[..., g * cg:(g + 1) * cg],
                           w[..., g * fg:(g + 1) * fg], padding=1, relu=True,
                           requant=(m[g * fg:(g + 1) * fg],
                                    s[g * fg:(g + 1) * fg]))
        for g in range(G)], axis=-1)


# name -> (fn, [(shape, dtype), ...])
CASES = {
    "vgg16_cl1_f32_fwd": (
        lambda x, w, b: trim_conv2d_pallas(x, w, bias=b, relu=True),
        [((N, 224, 224, 3), jnp.float32), ((3, 3, 3, 64), jnp.float32),
         ((64,), jnp.float32)]),
    "vgg16_cl2_f32_fwd": (
        lambda x, w, b: trim_conv2d_pallas(x, w, bias=b, relu=True),
        [((N, 224, 224, 64), jnp.float32), ((3, 3, 64, 64), jnp.float32),
         ((64,), jnp.float32)]),
    "alexnet_cl1_s4_f32_fwd": (
        lambda x, w, b: trim_conv2d_pallas(x, w, stride=4, padding=0,
                                           bias=b, relu=True),
        [((N, 227, 227, 3), jnp.float32), ((11, 11, 3, 96), jnp.float32),
         ((96,), jnp.float32)]),
    "alexnet_cl1_s4_f32_wgrad": (
        lambda x, g: trim_conv2d_wgrad_pallas(x, g, K=11, stride=4,
                                              padding=0),
        [((N, 227, 227, 3), jnp.float32), ((N, 55, 55, 96), jnp.float32)]),
    "vgg16_cl2_int8_requant": (
        lambda x, w, m, s: trim_conv2d_pallas(x, w, relu=True,
                                              requant=(m, s)),
        [((N, 224, 224, 64), jnp.uint8), ((3, 3, 64, 64), jnp.int8),
         ((64,), jnp.int32), ((64,), jnp.int32)]),
    "alexnet_cl5_grouped_int8": (
        _grouped_int8,
        [((N, 13, 13, 384), jnp.uint8), ((3, 3, 192, 256), jnp.int8),
         ((256,), jnp.int32), ((256,), jnp.int32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = CASES[case]
    sds = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
           for shape, dt in args]
    compiled = jax.jit(fn).lower(*sds).compile()
    # The Mosaic kernel itself is in the program (not an interpret-mode
    # emulation of it).
    assert "tpu_custom_call" in compiled.as_text()


#: The serving heads at published widths: VGG-16's 7x7x512 and AlexNet's
#: 6x6x256 features into 4096-4096-1000.
HEAD_K = {"vgg16": 7 * 7 * 512, "alexnet": 6 * 6 * 256}

#: Operations that move or name values but compute none.
MOVES = {"parameter", "constant", "bitcast", "copy", "dynamic-update-slice",
         "dynamic-slice", "tuple", "get-tuple-element"}


def _head_hlo(one_chip, arch, n):
    from repro.engine.execute import serve_head

    dims = (HEAD_K[arch], 4096, 4096, 1000)
    fc = [{"kernel": jax.ShapeDtypeStruct((k, f), jnp.float32, sharding=one_chip),
           "bias": jax.ShapeDtypeStruct((f,), jnp.float32, sharding=one_chip)}
          for k, f in zip(dims, dims[1:])]
    x = jax.ShapeDtypeStruct((n, dims[0]), jnp.float32, sharding=one_chip)
    return jax.jit(serve_head).lower(fc, x).compile().as_text()


def _head_products(hlo):
    """Each fusion that holds a head product: its result shape and memory
    space, the tiling the compiler chose (``window_config`` without its
    cycle estimate), and the product and the arithmetic after it as
    (shape, operation, precision).  Together they decide the bits of the
    product and of its epilogue (bias, ReLU)."""
    comps, body = {}, None
    for line in hlo.splitlines():
        if line.startswith("%") and line.rstrip().endswith("{"):
            comps[line.split()[0][1:]] = body = []
        elif line.startswith("}"):
            body = None
        elif body is not None:
            body.append(line)
    out = []
    for line in hlo.splitlines():
        called = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
        ops = []
        for l in comps.get(called.group(1), []) if called else []:
            op = re.search(r"= (\w+\[[\d,]*\])\S* ([\w-]+)\(", l)
            prec = re.search(r"operand_precision=\{(\w+)", l)
            if op:
                ops.append((*op.groups(), prec and prec.group(1)))
        at = [i for i, (_, op, _) in enumerate(ops) if op in ("convolution", "dot")]
        if at:
            tiling = re.search(r'"window_config":\{[^}]*\}', line)
            out.append((re.search(r"= (.+?) fusion\(", line).group(1),
                        re.sub(r'"estimated_cycles":"\d+",', "", tiling.group(0)) if tiling else None,
                        [o for o in ops[at[0]:] if o[1] not in MOVES]))
    return sorted(out, key=repr)


@pytest.fixture(scope="module")
def head_one_block(one_chip):
    from repro.engine.execute import HEAD_ROWS

    return {arch: _head_products(_head_hlo(one_chip, arch, HEAD_ROWS)) for arch in HEAD_K}


@pytest.mark.parametrize("arch", sorted(HEAD_K))
def test_serve_head_block_on_v5e(head_one_block, arch):
    """One block of the serving head compiles for a v5e as three
    full-precision products of HEAD_ROWS rows."""
    from repro.engine.execute import HEAD_ROWS

    assert sorted(p[2][0] for p in head_one_block[arch]) == sorted(
        (f"f32[{HEAD_ROWS},{f}]", "convolution", "highest") for f in (1000, 4096, 4096))


@pytest.mark.parametrize("arch,n", [("vgg16", 1), ("vgg16", 4), ("vgg16", 70), ("alexnet", 1)])
def test_serve_head_is_one_computation_at_every_bucket_on_v5e(one_chip, head_one_block, arch, n):
    """At every bucket size the v5e compiler emits the same head products,
    tiled the same way, with the same epilogues, as for one full block,
    so an image's logits carry the same bits in every bucket.  Loops over
    blocks appear only past HEAD_ROWS images."""
    from repro.engine.execute import HEAD_ROWS

    hlo = _head_hlo(one_chip, arch, n)
    assert _head_products(hlo) == head_one_block[arch]
    assert ("while" in hlo) == (n > HEAD_ROWS)


@pytest.mark.parametrize("stride,K", [(1, 3), (4, 11)])
def test_int_tap_dot_worst_case_exact(stride, K):
    """The integer path's bf16 x bf16 -> f32 tap dot at the largest
    magnitudes uint8 x int8 can reach (255 x -128 on every one of Cb=128
    channels, stride-folded or not) stays bit-exact against the int32
    oracle."""
    C, F = 128, 8
    x = jnp.full((1, 15, 15, C), 255, jnp.uint8)
    w = jnp.full((K, K, C, F), -128, jnp.int8)
    # a few smaller entries so a dropped or duplicated tap cannot cancel
    x = x.at[0, ::3, ::2, ::5].set(7)
    w = w.at[::2, 1::3, ::7, 1].set(127)
    out = trim_conv2d_pallas(x, w, stride=stride, block_c=128,
                             interpret=True)
    want = ref.conv2d_ref(x, w, stride=stride)
    assert out.dtype == jnp.int32
    assert int(np.abs(np.asarray(want)).max()) > 1 << 24
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
