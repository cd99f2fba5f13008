"""Relay-copy Pallas TPU kernel: streaming assembly of multipath chunks.

TPU adaptation of the paper's dual-pipeline relay (Fig 6): on H20 two
relay streams ping-pong so the PCIe hop of chunk i+1 overlaps the NVLink
hop of chunk i. On TPU the same overlap is exactly what a Pallas grid
pipeline provides: with a (n_chunks,) grid, the DMA bringing block i+1
HBM->VMEM runs while block i is being written out — hardware double
buffering with zero manual orchestration.

Micro-tasks land out of logical order (whichever path drains first), so
assembly is a permutation gather: the landing-order -> logical-order map is
scalar-prefetched (SMEM) and consumed by the input index_map, i.e. the DMA
engine itself performs the scatter/gather — no compute-core shuffling.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..interpret import pallas_interpret


def _copy_kernel(perm_ref, staged_ref, out_ref):
    out_ref[...] = staged_ref[...]


def relay_assemble(
    staged: jax.Array,    # (n_chunks, chunk_elems) rows in landing order
    perm: jax.Array,      # (n_chunks,) perm[i] = staged row of logical chunk i
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    n_chunks, chunk_elems = staged.shape
    # Each block is one whole chunk, viewed as (rows, lanes) so that the
    # block's last two dimensions equal the array's, as the TPU's tiling
    # requires: lane-dense rows of 128 when the chunk divides into them,
    # else a single row.
    lanes = 128 if chunk_elems % 128 == 0 else chunk_elems
    rows = chunk_elems // lanes
    block = (1, rows, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec(block, lambda i, perm_ref: (perm_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec(block, lambda i, perm_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_chunks, rows, lanes), staged.dtype),
        interpret=pallas_interpret(interpret),
    )(jnp.asarray(perm, jnp.int32), staged.reshape(n_chunks, rows, lanes))
    return out.reshape(n_chunks, chunk_elems)
