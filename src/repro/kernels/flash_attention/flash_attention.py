"""Flash attention Pallas TPU kernel (causal / sliding-window GQA).

Online-softmax accumulation over KV blocks: grid = (B, H, nQ, nK) with the
KV axis as the innermost ("arbitrary") dimension so the per-(b,h,qblock)
running max / denominator / accumulator live in VMEM scratch across KV
iterations. Block shapes are MXU-aligned (BQ x D and BK x D tiles, D is the
lane dimension, BQ/BK multiples of the 128 MXU edge at production sizes;
tests also sweep smaller toy tiles).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..interpret import pallas_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, bq: int, bk: int, causal: bool, window: int, q_offset: int,
    n_kv_blocks: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (BK, D)
    d = q.shape[-1]
    s = jnp.dot(q, k.T) * (d ** -0.5)            # (BQ, BK)

    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    k_idx = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= k_idx <= q_idx
    if window:
        mask &= k_idx > q_idx - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,        # (B, H, Sq, D)
    k: jax.Array,        # (B, G, T, D)
    v: jax.Array,        # (B, G, T, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, H, Sq, D = q.shape
    G, T = k.shape[1], k.shape[2]
    R = H // G
    bq = min(block_q, Sq)
    bk = min(block_k, T)
    assert Sq % bq == 0 and T % bk == 0, (Sq, bq, T, bk)
    nq, nk = Sq // bq, T // bk

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, window=window,
        q_offset=q_offset, n_kv_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, R=R: (b, h // R, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, R=R: (b, h // R, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),     # running max
            pltpu.VMEM((bq,), jnp.float32),     # running denominator
            pltpu.VMEM((bq, D), jnp.float32),   # output accumulator
        ],
        interpret=pallas_interpret(interpret),
    )(q, k, v)
