"""Intra-chunk SSD Pallas TPU kernel (Mamba2 SSD, arXiv:2405.21060).

The SSD dual form makes the intra-chunk computation three MXU matmuls per
(chunk, head): the (Q x N)x(N x Q) C.B^T Gram matrix, the masked-decay
(Q x Q)x(Q x P) output matmul, and the (N x Q)x(Q x P) state reduction.
This kernel fuses them for one chunk block with all operands resident in
VMEM — grid = (heads*batch, n_chunks), each step touching (Q,P)+(2*Q,N)
inputs. The sequential inter-chunk recurrence is composed outside
(ops.py), mirroring how the paper's transfer engine splits bulk work
(chunks) from a cheap serial combine.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..interpret import pallas_interpret


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, s_ref, d_ref):
    x = x_ref[0, 0].astype(jnp.float32)     # (Q, P)
    a = a_ref[0, 0].astype(jnp.float32)     # (Q,)
    B = b_ref[0, 0].astype(jnp.float32)     # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)     # (Q, N)
    Q = x.shape[0]

    cs = jnp.cumsum(a)
    diff = cs[:, None] - cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)

    scores = jnp.dot(C, B.T) * L                          # (Q, Q)
    y_ref[0, 0] = jnp.dot(scores, x).astype(y_ref.dtype)  # (Q, P)

    decay_states = jnp.exp(cs[-1] - cs)                   # (Q,)
    bw = B * decay_states[:, None]                        # (Q, N)
    s_ref[0, 0] = jnp.dot(bw.T, x).transpose(1, 0).astype(s_ref.dtype)
    d_ref[0, 0] = jnp.exp(cs).astype(d_ref.dtype)


def ssd_chunk(
    xbar: jax.Array,     # (BH, nc, Q, P)  batch*heads fused leading dim
    a: jax.Array,        # (BH, nc, Q)
    B: jax.Array,        # (BH, nc, Q, N)
    C: jax.Array,        # (BH, nc, Q, N)
    *,
    interpret: Optional[bool] = None,
):
    """Returns (y_diag (BH,nc,Q,P), states (BH,nc,P,N), out_decay (BH,nc,Q))."""
    BH, nc, Q, P = xbar.shape
    N = B.shape[-1]
    return pl.pallas_call(
        _ssd_kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, c: (i, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda i, c: (i, c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, Q, P), xbar.dtype),
            jax.ShapeDtypeStruct((BH, nc, P, N), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, Q), jnp.float32),
        ],
        interpret=pallas_interpret(interpret),
    )(xbar, a, B, C)
