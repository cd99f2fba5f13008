"""Attention: GQA self-attention (full / sliding-window), decode with
linear or ring-buffer KV caches, and cross-attention to frontend
embeddings (VLM patches / audio conditioning frames).

All math runs grouped (B, S, G, H/G, D) so GQA never materializes repeated
KV heads; softmax accumulates in fp32.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import BATCH, MODEL, mesh_axis_sizes, rope, shard

NEG_INF = -1e30


def shard_kv_cache(x: jax.Array) -> jax.Array:
    """KV cache (B, T, G, D): heads over `model` when divisible; otherwise
    the sequence axis goes there when the kv_seq_shard perf option is on
    (must agree with distributed.sharding.cache_pspec or XLA inserts
    full-cache reshards every layer)."""
    sizes = mesh_axis_sizes()
    m = sizes.get("model", 1)
    if m > 1 and x.shape[2] % m != 0:
        from ..distributed.sharding import OPT

        if OPT["kv_seq_shard"]:
            return shard(x, BATCH, MODEL, None, None)
    return shard(x, BATCH, None, MODEL, None)


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------
def qkv_proj(params: Dict, x: jax.Array, cfg) -> Tuple[jax.Array, ...]:
    B, S, _ = x.shape
    H, G, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].reshape(cfg.d_model, H, D))
    k = jnp.einsum("bsd,dgk->bsgk", x, params["wk"].reshape(cfg.d_model, G, D))
    v = jnp.einsum("bsd,dgk->bsgk", x, params["wv"].reshape(cfg.d_model, G, D))
    if cfg.qkv_bias:
        q = q + params["bq"].reshape(H, D)
        k = k + params["bk"].reshape(G, D)
        v = v + params["bv"].reshape(G, D)
    q = shard(q, BATCH, None, MODEL, None)
    k = shard(k, BATCH, None, MODEL, None)
    v = shard(v, BATCH, None, MODEL, None)
    return q, k, v


def out_proj(params: Dict, o: jax.Array, cfg) -> jax.Array:
    B, S = o.shape[:2]
    return jnp.einsum(
        "bshk,hkd->bsd", o, params["wo"].reshape(cfg.n_heads, cfg.hd, cfg.d_model)
    )


# ---------------------------------------------------------------------------
# Core grouped attention
# ---------------------------------------------------------------------------
def gqa(
    q: jax.Array,                 # (B, Sq, H, D)
    k: jax.Array,                 # (B, T, G, D)
    v: jax.Array,                 # (B, T, G, D)
    mask: Optional[jax.Array],    # broadcastable to (B, 1, 1, Sq, T)
) -> jax.Array:
    B, Sq, H, D = q.shape
    G = k.shape[2]
    R = H // G
    qg = q.reshape(B, Sq, G, R, D)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qg, k).astype(jnp.float32)
    scores = scores * (D ** -0.5)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bgrst,btgd->bsgrd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, H, D)


def causal_mask(
    q_pos: jax.Array, kv_pos: jax.Array, window: int = 0
) -> jax.Array:
    """(Sq, T) -> broadcast (1, 1, 1, Sq, T). Window 0 = unlimited."""
    m = kv_pos[None, :] <= q_pos[:, None]
    if window:
        m &= kv_pos[None, :] > q_pos[:, None] - window
    return m[None, None, None]


# ---------------------------------------------------------------------------
# Train / prefill self-attention
# ---------------------------------------------------------------------------
def self_attention(
    params: Dict,
    x: jax.Array,
    cfg,
    *,
    window: int = 0,
    return_cache: bool = False,
):
    B, S, _ = x.shape
    pos = jnp.arange(S)
    q, k, v = qkv_proj(params, x, cfg)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    if getattr(cfg, "attn_impl", "xla") == "pallas":
        from ..kernels.flash_attention.ops import flash_attention_op

        bq = max(16, min(128, S))
        while S % bq:
            bq //= 2
        o = flash_attention_op(
            q, k, v, causal=True, window=window, block_q=bq, block_k=bq,
        )
    else:
        mask = causal_mask(pos, pos, window)
        o = gqa(q, k, v, mask)
    y = out_proj(params, o, cfg)
    if return_cache:
        return y, {"k": k, "v": v}
    return y


# ---------------------------------------------------------------------------
# Decode self-attention with KV cache
# ---------------------------------------------------------------------------
def init_kv_cache(cfg, batch: int, max_len: int, window: int = 0) -> Dict:
    """Linear cache (window=0) or ring buffer of size ``window``."""
    W = window if window else max_len
    shape = (batch, W, cfg.n_kv_heads, cfg.hd)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def _ring_kv_positions(cache_len: jax.Array, W: int) -> jax.Array:
    """Absolute position stored in each ring slot after writing position
    ``cache_len`` at slot ``cache_len % W``. Slots not yet written map to
    negative positions (masked out)."""
    s = jnp.arange(W)
    return cache_len - ((cache_len - s) % W)


def decode_self_attention(
    params: Dict,
    x: jax.Array,              # (B, 1, d) — the new token's hidden state
    cache: Dict,               # {"k","v"}: (B, W, G, D)
    cache_len: jax.Array,      # scalar int32: tokens already in the cache
    cfg,
    *,
    window: int = 0,
) -> Tuple[jax.Array, Dict]:
    B = x.shape[0]
    W = cache["k"].shape[1]
    q, k_new, v_new = qkv_proj(params, x, cfg)
    q = rope(q, cache_len[None] if cache_len.ndim == 0 else cache_len,
             cfg.rope_theta)
    k_new = rope(k_new, jnp.full((1,), 0, jnp.int32) + cache_len,
                 cfg.rope_theta)
    slot = cache_len % W if window else cache_len
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1
    )
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1
    )
    k_cache = shard_kv_cache(k_cache)
    v_cache = shard_kv_cache(v_cache)
    if window:
        kv_pos = _ring_kv_positions(cache_len, W)
        valid = kv_pos >= 0
    else:
        kv_pos = jnp.arange(W)
        valid = kv_pos <= cache_len
    mask = valid[None, None, None, None, :]
    o = gqa(q, k_cache, v_cache, mask)
    y = out_proj(params, o, cfg)
    return y, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# Cross-attention (modality frontend consumption; no causal mask, no RoPE)
# ---------------------------------------------------------------------------
def cross_attention(
    params: Dict,
    x: jax.Array,           # (B, S, d) decoder states
    frontend: jax.Array,    # (B, F, d) precomputed patch/frame embeddings
    cfg,
) -> jax.Array:
    H, G, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum(
        "bsd,dhk->bshk", x, params["wq"].reshape(cfg.d_model, H, D)
    )
    k = jnp.einsum(
        "bfd,dgk->bfgk", frontend, params["wk_cross"].reshape(cfg.d_model, G, D)
    )
    v = jnp.einsum(
        "bfd,dgk->bfgk", frontend, params["wv_cross"].reshape(cfg.d_model, G, D)
    )
    q = shard(q, BATCH, None, MODEL, None)
    o = gqa(q, k, v, mask=None)
    return out_proj(params, o, cfg)
