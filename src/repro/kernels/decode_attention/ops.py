"""Jit'd wrapper for flash-decode, accepting the model's cache layout
(B, T, G, D) and (B, 1, H, D) single-token queries."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention
from .ref import decode_attention_ref


@functools.partial(
    jax.jit, static_argnames=("block_k", "interpret", "use_kernel")
)
def decode_attention_op(
    q: jax.Array,         # (B, 1, H, D) model layout
    k_cache: jax.Array,   # (B, T, G, D)
    v_cache: jax.Array,
    kv_len: jax.Array,
    *,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> jax.Array:
    qq = q[:, 0]
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)
    if use_kernel:
        out = decode_attention(qq, kt, vt, kv_len, block_k=block_k,
                               interpret=interpret)
    else:
        out = decode_attention_ref(qq, kt, vt, kv_len)
    return out[:, None]
