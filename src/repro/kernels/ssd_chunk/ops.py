"""Jit'd wrapper composing the intra-chunk kernel with the inter-chunk
scan: a drop-in alternative to ``models.ssm.ssd_chunked`` for g=1."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .ref import ssd_chunk_ref
from .ssd_chunk import ssd_chunk


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret", "use_kernel")
)
def ssd_op(
    xbar: jax.Array,     # (b, l, h, p)
    a: jax.Array,        # (b, l, h)
    B: jax.Array,        # (b, l, 1, n) — single B/C group
    C: jax.Array,        # (b, l, 1, n)
    *,
    chunk: int,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
):
    """Returns (y (b,l,h,p), final_state (b,h,p,n))."""
    b, l, h, p = xbar.shape
    n = B.shape[-1]
    assert l % chunk == 0
    nc = l // chunk

    # fuse (b, h) and broadcast B/C over heads
    xc = xbar.reshape(b, nc, chunk, h, p).transpose(0, 3, 1, 2, 4)
    xc = xc.reshape(b * h, nc, chunk, p)
    ac = a.reshape(b, nc, chunk, h).transpose(0, 3, 1, 2).reshape(
        b * h, nc, chunk
    )
    Bb = jnp.broadcast_to(
        B.reshape(b, 1, nc, chunk, n), (b, h, nc, chunk, n)
    ).reshape(b * h, nc, chunk, n)
    Cb = jnp.broadcast_to(
        C.reshape(b, 1, nc, chunk, n), (b, h, nc, chunk, n)
    ).reshape(b * h, nc, chunk, n)

    if use_kernel:
        y_diag, states, out_decay = ssd_chunk(
            xc, ac, Bb, Cb, interpret=interpret
        )
    else:
        y_diag, states, out_decay = jax.vmap(ssd_chunk_ref)(xc, ac, Bb, Cb)

    # inter-chunk recurrence
    chunk_decay = out_decay[:, :, -1]                    # (bh, nc)

    def step(s, inp):
        dec, st = inp
        s_new = s * dec[:, None, None] + st
        return s_new, s

    s0 = jnp.zeros((b * h, p, n), jnp.float32)
    final, prev = jax.lax.scan(
        step,
        s0,
        (chunk_decay.transpose(1, 0), states.transpose(1, 0, 2, 3)),
    )
    prev = prev.transpose(1, 0, 2, 3)                    # (bh, nc, p, n)

    y_off = jnp.einsum(
        "icqn,icpn,icq->icqp", Cb.astype(jnp.float32), prev,
        out_decay,
    )
    y = (y_diag.astype(jnp.float32) + y_off).reshape(b, h, nc, chunk, p)
    y = y.transpose(0, 2, 3, 1, 4).reshape(b, l, h, p).astype(xbar.dtype)
    final = final.reshape(b, h, p, n).astype(xbar.dtype)
    return y, final
