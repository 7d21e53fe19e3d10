"""TrIM matmul — the degenerate K=1 case of the paper's dataflow, i.e. a
weight-stationary blocked matmul with single-fetch input broadcast and a
VMEM psum accumulator over the contraction grid axis.

This is the building block the LM layers share with the conv engine: the
paper's TrIM Core (P_M-channel contraction on stationary kernels) IS a
blocked matmul when K=1, and its Engine (P_N cores on broadcast inputs) is
the N-block grid axis whose input index_map is N-independent.

a (M, K) @ b (K, N) -> (M, N); f32/bf16 (f32 accum) or int8 (int32 accum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=acc_ref.dtype)

    @pl.when(ki == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def trim_matmul_pallas(a: jax.Array, b: jax.Array, *, block_m: int = 256,
                       block_n: int = 256, block_k: int = 512,
                       out_dtype=None, interpret: bool = False) -> jax.Array:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    integer = jnp.issubdtype(a.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else jnp.float32
    if out_dtype is None:
        out_dtype = jnp.int32 if integer else a.dtype

    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    gm, gn, gk = -(-M // bm), -(-N // bn), -(-K // bk)
    a_p = jnp.pad(a, ((0, gm * bm - M), (0, gk * bk - K)))
    b_p = jnp.pad(b, ((0, gk * bk - K), (0, gn * bn - N)))

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),   # N-independent
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),   # M-stationary
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(a_p, b_p)
    return out[:M, :N]
