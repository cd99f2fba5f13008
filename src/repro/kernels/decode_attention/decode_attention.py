"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

Decode is memory-bound (the whole KV cache streams HBM->VMEM once per
step); the kernel blocks the cache's T axis as the innermost grid dimension
with online-softmax scratch carried across KV blocks, so VMEM holds only
(BK x D) tiles of K/V plus the (R x D) accumulator per (batch, kv-head).
Queries are grouped per KV head (GQA): the q block is the (R, D) bundle of
R = H/G query heads sharing one KV head — the MXU sees an (R x D) x
(D x BK) matmul per tile instead of R vector products.
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


def _decode_kernel(
    kvlen_ref, q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, bk: int, n_kv_blocks: int,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = pl.program_id(0)
    kv_len = kvlen_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)           # (R, D)
    k = k_ref[0, 0].astype(jnp.float32)           # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)           # (BK, D)
    d = q.shape[-1]
    s = jnp.dot(q, k.T) * (d ** -0.5)             # (R, BK)
    t_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(t_idx < kv_len, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(j == n_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,          # (B, H, D)
    k: jax.Array,          # (B, G, T, D)
    v: jax.Array,          # (B, G, T, D)
    kv_len: jax.Array,     # (B,) int32 valid lengths
    *,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, H, D = q.shape
    G, T = k.shape[1], k.shape[2]
    R = H // G
    bk = min(block_k, T)
    assert T % bk == 0
    nk = T // bk
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))

    qg = q.reshape(B, G, R, D)
    kernel = functools.partial(_decode_kernel, bk=bk, n_kv_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, G, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # kv_len (scalar prefetch)
            pl.BlockSpec((1, 1, R, D), lambda b, g, j: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, g, j: (b, g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, R, D), lambda b, g, j: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, R, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((R,), jnp.float32),
            pltpu.VMEM((R,), jnp.float32),
            pltpu.VMEM((R, D), jnp.float32),
        ],
        interpret=pallas_interpret(interpret),
    )(kv_len, qg, k, v)
    return out.reshape(B, H, D)
