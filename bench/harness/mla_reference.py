"""The plain reference of a latent-attention MoE decoder (DeepSeek-V2's
``DeepseekV2ForCausalLM``): its forward pass in float32, layer by layer.

It follows the published modelling code: pre-norm RMSNorm; multi-head
latent attention in its decompressed form (q without LoRA; ``kv_a`` split
into the latent, which is RMS-normalised and expanded per head by
``kv_b`` into keys and values, and one roped key shared by every head);
YaRN rotary embedding as ``DeepseekV2YarnRotaryEmbedding`` computes it,
applied after the interleaved pairs are permuted (``view(d/2, 2)
.transpose``) as ``apply_rotary_pos_emb`` does; the softmax scale times
YaRN's mscale squared; SiLU-gated MLPs; for MoE layers a softmax router
over every expert in float32, the top-k weights renormalised only where
``norm_topk_prob`` and times ``routed_scaling_factor``, every token's
chosen experts computed exactly (nothing dropped), plus the shared
experts; a final RMSNorm and an untied output head. It imports nothing of
the program, and reads the weights that ``mla_model.make_flat`` makes
again from the seed.

Routing is decided per token on the device; the tokens of each expert are
then gathered and run through it together (exactly those tokens, padded
with rows that add nothing so that few shapes compile).

``precision="fp8"`` is the control: every matrix product of a weight
takes its operands rounded to float8 (e4m3, a scale per row of
activations and per output column of weights), the step below the
bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .mla_model import Shapes

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512           # attention is taken over blocks of queries
ROW_BUCKET = 1024       # an expert's tokens are padded to a multiple of this
E4M3_MAX = 448.0


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """x (S, k) @ w (k, n) in float32, or with float8 operands."""
    x, w = x.astype(F32), w.astype(F32)
    if precision == "fp8":
        x, w = _fp8(x, axis=-1), _fp8(w, axis=0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


# ---------------------------------------------------------------------------
# YaRN, as DeepseekV2YarnRotaryEmbedding
# ---------------------------------------------------------------------------
def yarn_get_mscale(scale: float = 1, mscale: float = 1) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_find_correction_dim(num_rotations, dim, base, max_position):
    return (dim * math.log(max_position / (num_rotations * 2 * math.pi))) \
        / (2 * math.log(base))


def yarn_find_correction_range(low_rot, high_rot, dim, base, max_position):
    low = math.floor(yarn_find_correction_dim(low_rot, dim, base,
                                              max_position))
    high = math.ceil(yarn_find_correction_dim(high_rot, dim, base,
                                              max_position))
    return max(low, 0), min(high, dim - 1)


def yarn_linear_ramp_mask(lo, hi, dim):
    if lo == hi:
        hi += 0.001
    return np.clip((np.arange(dim, dtype=np.float32) - lo) / (hi - lo), 0, 1)


def yarn_inv_freq(s: Shapes) -> np.ndarray:
    dim, base = s.qk_rope_head_dim, s.rope_theta
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (s.rope_factor * base ** (
        np.arange(0, dim, 2, dtype=np.float32) / dim))
    low, high = yarn_find_correction_range(
        s.rope_beta_fast, s.rope_beta_slow, dim, base,
        s.rope_original_max_len)
    inv_freq_mask = 1.0 - yarn_linear_ramp_mask(low, high, dim // 2)
    return (freq_inter * (1 - inv_freq_mask)
            + freq_extra * inv_freq_mask).astype(np.float32)


def softmax_scale(s: Shapes) -> float:
    scale = (s.qk_nope_head_dim + s.qk_rope_head_dim) ** -0.5
    if s.rope_mscale_all_dim:
        m = yarn_get_mscale(s.rope_factor, s.rope_mscale_all_dim)
        scale = scale * m * m
    return scale


def _cos_sin(s: Shapes, pos):
    freqs = pos[:, None].astype(F32) * jnp.asarray(yarn_inv_freq(s))
    emb = jnp.concatenate([freqs, freqs], -1)
    m = yarn_get_mscale(s.rope_factor, s.rope_mscale) \
        / yarn_get_mscale(s.rope_factor, s.rope_mscale_all_dim)
    return jnp.cos(emb) * m, jnp.sin(emb) * m


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], -1)


def _apply_rope(x, cos, sin):
    """x: (S, H, d) -> the published order: pairs permuted, then rotated."""
    S, H, d = x.shape
    x = x.reshape(S, H, d // 2, 2).transpose(0, 1, 3, 2).reshape(S, H, d)
    return x * cos[:, None] + _rotate_half(x) * sin[:, None]


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention(x: jax.Array, w: Dict[str, jax.Array], s: Shapes,
               precision: str) -> jax.Array:
    """x + MLA(RMSNorm(x)), over the whole sequence, decompressed."""
    S = x.shape[0]
    H, dn, dr, dv = s.n_heads, s.qk_nope_head_dim, s.qk_rope_head_dim, \
        s.v_head_dim
    r = s.kv_lora_rank
    h = _rms(x, w["ln1"], s.norm_eps)
    q = _mm(h, w["wq"], precision).reshape(S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    ckv = _mm(h, w["w_kv_a"], precision)
    c, k_pe = ckv[:, :r], ckv[:, None, r:]
    kv = _mm(_rms(c, w["kv_norm"], s.norm_eps), w["w_kv_b"],
             precision).reshape(S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    pos = jnp.arange(S)
    cos, sin = _cos_sin(s, pos)
    q_pe, k_pe = _apply_rope(q_pe, cos, sin), _apply_rope(k_pe, cos, sin)
    qs = jnp.concatenate([q_nope, q_pe], -1)
    ks = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    scale = softmax_scale(s)

    def block(lo):
        qb = jax.lax.dynamic_slice_in_dim(qs, lo, Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, ks, precision=HI) * scale
        causal = pos[None, :] <= (lo + jnp.arange(Q_BLOCK))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, S, Q_BLOCK)).reshape(S, H * dv)
    return x + _mm(o, w["wo"], precision)


def _mlp(h, g, u, d, precision):
    return _mm(jax.nn.silu(_mm(h, g, precision)) * _mm(h, u, precision), d,
               precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_mlp(x, w, s: Shapes, precision: str):
    h = _rms(x, w["ln2"], s.norm_eps)
    return x + _mlp(h, w["w_gate"], w["w_up"], w["w_down"], precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _route(x, w, s: Shapes, precision: str):
    """The normed input, the shared experts' output, and each token's
    experts and weights: softmax over every expert in float32."""
    h = _rms(x, w["ln2"], s.norm_eps)
    shared = _mlp(h, w["ws_gate"], w["ws_up"], w["ws_down"], precision)
    probs = jax.nn.softmax(jnp.dot(h, w["router"].astype(F32),
                                   precision=HI), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, s.top_k)
    if s.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return h, shared, top_i, top_p * s.routed_scaling_factor


@functools.partial(jax.jit, static_argnums=(5,))
def _routed(y, h, take, scale, w, precision: str):
    """y + each expert's weighted output for its tokens: expert e runs the
    rows ``take[e]`` of ``h`` (an index past the end pads: it reads zeros
    and adds nothing), scaled by ``scale[e]``."""
    def expert(e, y):
        rows = jnp.take(h, take[e], axis=0, mode="fill", fill_value=0)
        out = _mlp(rows, w["w_gate"][e], w["w_up"][e], w["w_down"][e],
                   precision)
        return y.at[take[e]].add(out * scale[e][:, None], mode="drop")

    return jax.lax.fori_loop(0, take.shape[0], expert, y)


def _moe(x, w, s: Shapes, precision: str) -> jax.Array:
    """x + shared experts + each token's routed experts, weighted. Which
    tokens each expert takes is read on the host; every expert then runs
    its tokens, padded to the largest count (a multiple of ``ROW_BUCKET``)."""
    h, shared, top_i, top_p = _route(x, w, s, precision)
    idx, wts = np.asarray(top_i), np.asarray(top_p)
    S, E = idx.shape[0], s.n_experts
    counts = np.bincount(idx.reshape(-1), minlength=E)
    n = max(1, -(-int(counts.max()) // ROW_BUCKET)) * ROW_BUCKET
    take = np.full((E, n), S, np.int32)
    scale = np.zeros((E, n), np.float32)
    for e in range(E):
        rows, slot = np.nonzero(idx == e)
        take[e, :len(rows)] = rows
        scale[e, :len(rows)] = wts[rows, slot]
    return _routed(x + shared, h, jnp.asarray(take), jnp.asarray(scale), w,
                   precision)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, ln_f, head, s: Shapes, precision: str):
    return _mm(_rms(x, ln_f, s.norm_eps), head, precision)


def _layer_weights(weights, kind: str, i: int) -> Dict[str, jax.Array]:
    pre = kind + "."
    return {k[len(pre):]: v[i] for k, v in weights.items()
            if k.startswith(pre)}


def logits_at(weights: Dict[str, jax.Array], s: Shapes,
              sequences: Sequence[np.ndarray], positions: Sequence[np.ndarray],
              precision: str = "f32", pad_to: int = 4096) -> List[np.ndarray]:
    """Float32 logits (rows of ``positions[i]``) of each sequence. A sequence
    is padded at its end to a multiple of ``pad_to`` (of ``Q_BLOCK``), which
    changes no earlier position under a causal mask and bounds the shapes
    compiled. ``weights`` is the flat layout of ``mla_model.make_flat``."""
    out = []
    for seq, pos in zip(sequences, positions):
        n = -(-len(seq) // pad_to) * pad_to
        toks = np.zeros(n, np.int32)
        toks[:len(seq)] = seq
        x = weights["embedding"][jnp.asarray(toks)].astype(F32)
        for i in range(s.first_k_dense):
            w = _layer_weights(weights, "lead", i)
            x = _dense_mlp(_attention(x, w, s, precision), w, s, precision)
        for i in range(s.n_moe):
            w = _layer_weights(weights, "moe", i)
            x = _moe(_attention(x, w, s, precision), w, s, precision)
        rows = x[jnp.asarray(np.asarray(pos, np.int32))]
        out.append(np.asarray(_head(rows, weights["ln_f"], weights["head"],
                                    s, precision)))
    return out
