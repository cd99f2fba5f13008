"""Jit'd wrapper: assemble a flat payload from out-of-order landed chunks."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .ref import relay_assemble_ref
from .relay_copy import relay_assemble


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def relay_assemble_op(
    staged: jax.Array,
    perm: jax.Array,
    *,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> jax.Array:
    if use_kernel:
        return relay_assemble(staged, perm, interpret=interpret)
    return relay_assemble_ref(staged, perm)
