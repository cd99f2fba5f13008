"""Jit'd public wrapper for the flash-attention kernel, accepting the
model's (B, S, H, D) layout."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .ref import flash_attention_ref


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k",
                     "interpret", "use_kernel"),
)
def flash_attention_op(
    q: jax.Array,          # (B, S, H, D) — model layout
    k: jax.Array,          # (B, T, G, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> jax.Array:
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    fn = flash_attention if use_kernel else flash_attention_ref
    kw = dict(causal=causal, window=window, q_offset=q_offset)
    if use_kernel:
        kw.update(block_q=block_q, block_k=block_k, interpret=interpret)
    out = fn(qt, kt, vt, **kw)
    return out.transpose(0, 2, 1, 3)
