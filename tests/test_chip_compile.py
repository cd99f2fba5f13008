"""Compile the main path's kernels and the served decode step for one
described TPU v5e chip, at qwen3-4b widths.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (block shapes off the tiling, too much VMEM, too much HBM). The
topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import PAPER_MODELS
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.relay_copy import relay_assemble

QWEN3_4B = PAPER_MODELS["qwen3-4b"]
H, G, D = QWEN3_4B.n_heads, QWEN3_4B.n_kv_heads, QWEN3_4B.hd


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static
    ).compile()


def test_flash_attention_compiles_qwen3_4b(one_chip):
    S = 2048
    q = _sds((1, H, S, D), jnp.bfloat16, one_chip)
    kv = _sds((1, G, S, D), jnp.bfloat16, one_chip)
    fn = functools.partial(
        flash_attention, block_q=128, block_k=128, interpret=False
    )
    compiled = _compile(fn, q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_t4096(one_chip):
    B, T = 2, 4096
    q = _sds((B, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, G, T, D), jnp.bfloat16, one_chip)
    kv_len = _sds((B,), jnp.int32, one_chip)
    fn = functools.partial(decode_attention, interpret=False)
    compiled = _compile(fn, q, kv, kv, kv_len)
    assert "tpu_custom_call" in compiled.as_text()


def test_relay_assemble_compiles_1mib_bf16_chunks(one_chip):
    n_chunks, chunk_elems = 8, (1 << 20) // 2
    staged = _sds((n_chunks, chunk_elems), jnp.bfloat16, one_chip)
    perm = _sds((n_chunks,), jnp.int32, one_chip)
    fn = functools.partial(relay_assemble, interpret=False)
    compiled = _compile(fn, staged, perm)
    assert "tpu_custom_call" in compiled.as_text()


def test_served_decode_step_compiles_one_layer_qwen3_4b(one_chip):
    """The server's own jitted decode step, one layer deep at full width,
    against a 4096-token cache. Decode attention is XLA here; no kernel."""
    from repro.models.init import abstract_params
    from repro.models.transformer import init_caches
    from repro.serving.engine import jit_decode_step

    cfg = dataclasses.replace(QWEN3_4B, n_layers=1)
    place = lambda tree: jax.tree.map(
        lambda l: _sds(l.shape, l.dtype, one_chip), tree
    )
    params = place(abstract_params(cfg))
    caches = place(jax.eval_shape(lambda: init_caches(cfg, 1, 4096)))
    token = _sds((1,), jnp.int32, one_chip)
    cache_len = _sds((), jnp.int32, one_chip)
    compiled = jit_decode_step.lower(
        params, token, caches, cache_len, cfg
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 2 * cfg.vocab * cfg.d_model
