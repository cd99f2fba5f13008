"""A position-weighted checksum of an array's bits, on the chip and in
plain numpy: any changed, lost or moved element changes it.

    sum_i bits(x_i) * (i * GOLDEN + 1)  modulo 2**32

with ``i`` the element's index in row-major order. The chip's version
builds ``i`` from one iota per axis, so no leaf is reshaped (on a TPU a
reshape is a copy) and the whole sum is one fused reduction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN = np.uint32(2654435761)      # odd: the weights i * GOLDEN + 1 differ
_BITS = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


@jax.jit
def checksum(x: jax.Array) -> jax.Array:
    bits = jax.lax.bitcast_convert_type(x, _BITS[x.dtype.itemsize])
    i = jnp.zeros(x.shape, jnp.uint32)
    for axis, n in enumerate(x.shape):
        i = i * jnp.uint32(n) + jax.lax.broadcasted_iota(jnp.uint32, x.shape,
                                                         axis)
    return jnp.sum(bits.astype(jnp.uint32) * (i * GOLDEN + 1),
                   dtype=jnp.uint32)


def host_checksums(doc: np.ndarray, block_tokens: int,
                   blocks: int) -> np.ndarray:
    """The checksum of each prefix of ``doc`` (16-bit elements) of up to
    ``blocks`` whole blocks of ``block_tokens`` rows: entry b is that of the
    first b + 1 blocks. Plain numpy over the host copy, block by block; it
    uses that the weights of block b are those of block 0 plus
    b * block_elems * GOLDEN."""
    bits = doc.reshape(doc.shape[0], -1).view(np.uint16)
    per = block_tokens * bits.shape[1]
    w0 = np.arange(per, dtype=np.uint32) * GOLDEN + np.uint32(1)
    out, total = [], np.uint32(0)
    with np.errstate(over="ignore"):
        for b in range(blocks):
            x = bits[b * block_tokens:(b + 1) * block_tokens].reshape(-1) \
                .astype(np.uint32)
            s1 = np.sum(x * w0, dtype=np.uint32)
            s0 = np.sum(x, dtype=np.uint32)
            shift = np.uint32((b * per) % (1 << 32)) * GOLDEN
            total = np.uint32(total + s1 + shift * s0)
            out.append(total)
    return np.asarray(out, np.uint32)
