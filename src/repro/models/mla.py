"""Multi-head latent attention (DeepSeek-V2's MLA) with a latent KV cache.

Per token, a layer projects the hidden state to a latent ``c``
(``kv_lora_rank`` values, RMS-normalised) and one roped key ``k_rope``
(``qk_rope_head_dim`` values shared by every head). The cache holds those
two and never the per-head keys and values:

* **prefill** decompresses per-head keys (``qk_nope_head_dim``) and values
  (``v_head_dim``) from ``c`` with ``w_kv_b`` and attends causally over
  blocks of queries with an online softmax, so no S x S score tensor is
  ever built;
* **decode** attends in the latent space: the key half of ``w_kv_b`` is
  absorbed into the query and its value half is applied after the
  weighted sum of latents.

Rope is YaRN's (``yarn_inv_freq``) on the rope dims, whose interleaved
pairs ``(x[2i], x[2i+1])`` rotate together, as the published modelling
code lays them out (it permutes them to ``[evens, odds]`` before
``rotate_half``; the output keeps that order, in q and k alike).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import rms_norm

NEG_INF = -1e30
Q_BLOCK = 512           # prefill attends over blocks of this many queries


# ---------------------------------------------------------------------------
# YaRN rope (DeepseekV2YarnRotaryEmbedding)
# ---------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations: float, dim: int, base: float,
                    max_len: int) -> float:
    return (dim * math.log(max_len / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def yarn_inv_freq(cfg) -> np.ndarray:
    """Inverse frequencies of the rope dims: interpolated (divided by the
    factor) below the correction range of ``rope_beta_slow`` rotations,
    extrapolated (as trained) above that of ``rope_beta_fast``, blended by
    a linear ramp between them."""
    dim, base, f = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / f
    low = max(math.floor(_correction_dim(
        cfg.rope_beta_fast, dim, base, cfg.rope_original_max_len)), 0)
    high = min(math.ceil(_correction_dim(
        cfg.rope_beta_slow, dim, base, cfg.rope_original_max_len)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp               # 1: the dim keeps its trained frequency
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def softmax_scale(cfg) -> float:
    """``(qk_nope + qk_rope)^-0.5``, times YaRN's attention scale squared
    where ``rope_mscale_all_dim`` is set."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def yarn_rope(x: jax.Array, positions: jax.Array, cfg) -> jax.Array:
    """x: (B, S, H, dim) rope dims; positions: (S,). Returns the rotated
    dims in the published order ``[evens, odds]``."""
    cos_sin_scale = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                     / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))
    cos = (jnp.cos(ang) * cos_sin_scale)[:, None, :]
    sin = (jnp.sin(ang) * cos_sin_scale)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------
def _project(p: Dict, x: jax.Array, cfg, positions: jax.Array):
    """q's nope and roped parts (B, S, H, .), the normalised latent
    (B, S, r) and the roped shared key (B, S, dr)."""
    B, S, _ = x.shape
    H, dn = cfg.n_heads, cfg.qk_nope_head_dim
    r = cfg.kv_lora_rank
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"]).reshape(B, S, H, -1)
    q_rope = yarn_rope(q[..., dn:], positions, cfg)
    kv = jnp.einsum("bsd,dk->bsk", x, p["w_kv_a"])
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = yarn_rope(kv[..., None, r:], positions, cfg)[:, :, 0]
    return q[..., :dn], q_rope, c, k_rope


def _out(p: Dict, o: jax.Array) -> jax.Array:
    B, S = o.shape[:2]
    return jnp.einsum("bsk,kd->bsd", o.reshape(B, S, -1), p["wo"])


# ---------------------------------------------------------------------------
# Prefill / train: decompressed keys and values
# ---------------------------------------------------------------------------
def _causal_blocks(q, k, v, scale: float, block: int) -> jax.Array:
    """Causal softmax attention (B, S, H, D) x (B, S, H, D) -> (B, S, H, Dv),
    one block of queries at a time against the key blocks at or before
    it, with an online softmax in float32."""
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    n = -(-S // block)

    def blocks(a):
        a = jnp.pad(a, ((0, 0), (0, n * block - S), (0, 0), (0, 0)))
        return a.reshape(B, n, block, H, -1).transpose(1, 0, 3, 2, 4)

    qb, kb, vb = blocks(q), blocks(k), blocks(v)     # (n, B, H, block, .)
    offs = jnp.arange(block)

    def query_block(i):
        q_i = qb[i]

        def key_block(j, carry):
            m, l, acc = carry
            s = jnp.einsum("bhqd,bhkd->bhqk", q_i, kb[j],
                           preferred_element_type=jnp.float32) * scale
            causal = (j * block + offs)[None, :] <= (i * block + offs)[:, None]
            s = jnp.where(causal, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            e = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", e.astype(v.dtype), vb[j],
                preferred_element_type=jnp.float32)
            return m_new, l * corr + e.sum(-1), acc

        init = (jnp.full((B, H, block), NEG_INF, jnp.float32),
                jnp.zeros((B, H, block), jnp.float32),
                jnp.zeros((B, H, block, Dv), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, i + 1, key_block, init)
        return (acc / l[..., None]).astype(v.dtype)

    out = jax.lax.map(query_block, jnp.arange(n))   # (n, B, H, block, Dv)
    return out.transpose(1, 0, 3, 2, 4).reshape(B, n * block, H, Dv)[:, :S]


def _causal_full(q, k, v, scale: float) -> jax.Array:
    """The same attention with every score at once: differentiable (the
    blocked form's loop has a traced bound), for training."""
    S = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def mla_attention(p: Dict, x: jax.Array, cfg, *,
                  return_cache: bool = False):
    """Self-attention over the whole sequence ``x`` (B, S, d). With
    ``return_cache`` (prefill) attends in blocks of queries and also
    returns the latent cache entries ``{"c", "k_rope"}`` of every
    position; otherwise (training) attends in one differentiable piece."""
    B, S, _ = x.shape
    H, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_rope, c, k_rope = _project(p, x, cfg, jnp.arange(S))
    kv = jnp.einsum("bsr,rk->bsk", c, p["w_kv_b"]).reshape(B, S, H, dn + dv)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope[:, :, None], (B, S, H, k_rope.shape[-1]))], -1)
    v = kv[..., dn:]
    scale = softmax_scale(cfg)
    if return_cache:
        y = _out(p, _causal_blocks(q, k, v, scale, Q_BLOCK))
        return y, {"c": c, "k_rope": k_rope}
    return _out(p, _causal_full(q, k, v, scale))


# ---------------------------------------------------------------------------
# Decode: attention in the latent space
# ---------------------------------------------------------------------------
def init_mla_cache(cfg, batch: int, max_len: int) -> Dict:
    """The latent cache of one layer: ``kv_lora_rank + qk_rope_head_dim``
    values per token."""
    return {
        "c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cfg.dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim),
                            cfg.dtype),
    }


def mla_decode(p: Dict, x: jax.Array, cache: Dict, cache_len: jax.Array,
               cfg) -> Tuple[jax.Array, Dict]:
    """One new token (B, 1, d) at position ``cache_len`` against the
    latent cache; returns its output and the cache with it written."""
    H, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    q_nope, q_rope, c, k_rope = _project(p, x, cfg, cache_len[None])
    cache = {
        "c": jax.lax.dynamic_update_slice_in_dim(
            cache["c"], c.astype(cache["c"].dtype), cache_len, axis=1),
        "k_rope": jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), cache_len,
            axis=1),
    }
    w = p["w_kv_b"].reshape(r, H, dn + dv)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w[..., :dn])
    s = (jnp.einsum("bshr,btr->bhst", q_lat, cache["c"],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bshk,btk->bhst", q_rope, cache["k_rope"],
                      preferred_element_type=jnp.float32))
    s = s * softmax_scale(cfg)
    valid = jnp.arange(cache["c"].shape[1]) <= cache_len
    prob = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", prob.astype(x.dtype), cache["c"])
    o = jnp.einsum("bshr,rhv->bshv", o_lat, w[..., dn:])
    return _out(p, o), cache
