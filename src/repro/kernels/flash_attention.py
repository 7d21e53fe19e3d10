"""Flash attention as a Pallas TPU kernel — the §Perf answer to the
dominant memory term of the train/prefill cells.

The XLA-visible streaming attention (nn.attention.flash_attention)
necessarily materializes the (Sq, Sk) score tensor block-by-block in HBM
(two dots can't fuse in HLO), which makes attention bytes scale as
B*H*Sq*Sk*4 — the dominant roofline memory term at seq 4k-32k. This kernel
keeps the running (m, l, acc) statistics in VMEM scratch across the kv-block
grid axis, so HBM traffic drops to q+k+v+o (the flash-attention guarantee).

Grid: (B*H, n_q_blocks, n_kv_blocks), kv innermost. Causal blocks that are
fully masked are skipped with pl.when (their DMA is still scheduled by the
pipeline — on TPU the win comes from the revolving-buffer reuse, the skip
saves the MXU work).

Shapes: q (B, H, Sq, D), k/v (B, H, Sk, D) -> o (B, H, Sq, D). The block
layout wants D and the block sizes MXU-aligned (D multiple of 128 ideally;
interpret mode accepts anything).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, n_kv: int, causal: bool, scale: float,
                  kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    if causal:
        # skip blocks entirely above the diagonal
        run = (ki * bk) <= (qi * bq + bq - 1)
    else:
        run = ki >= 0

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...][:, 0]                         # (bq,)
        l_prev = l_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

    @pl.when(ki == n_kv - 1)
    def _flush():
        l = l_ref[...][:, 0]
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, block_q: int = 512,
                           block_k: int = 512,
                           kv_length: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """q (B, H, Sq, D), k/v (B, H, Sk, D) -> (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    kv_len = Sk if kv_length is None else kv_length
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - Sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0)))
    qp = qp.reshape(B * H, nq * bq, D)
    kp = kp.reshape(B * H, nk * bk, D)
    vp = vp.reshape(B * H, nk * bk, D)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv=nk, causal=causal,
        scale=D ** -0.5, kv_len=kv_len)
    scratch = [
        pltpu.VMEM((bq, D), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
    ]
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, nq * bq, D), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(qp, kp, vp)
    return out.reshape(B, H, nq * bq, D)[:, :, :Sq]


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        kv_length: Optional[int] = None) -> jax.Array:
    """Naive oracle: full-softmax attention, f32."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= k_pos[None, :] <= (jnp.arange(Sq)[:, None] + (Sk - Sq))
    if kv_length is not None:
        mask &= (k_pos < kv_length)[None, :]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
