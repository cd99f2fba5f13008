"""The plain reference: a decoder's forward pass in float32, layer by layer.

It follows the published description of a dense decoder of the Qwen
families (pre-norm RMSNorm, rotary positions on the first and second half
of each head, grouped-query softmax attention with an optional bias on the
q/k/v projections, a SiLU-gated MLP, a final RMSNorm and an output head
that may be the embedding). It imports nothing of the program, and reads
the weights that ``model.make_weights`` makes again from the seed.

``precision="fp8"`` is the control: every matrix product takes its operands
rounded to float8 (e4m3, a scale per row of activations and per output
column of weights), the step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .model import Shapes

F32 = jnp.float32
Q_BLOCK = 512           # attention is taken over blocks of queries
E4M3_MAX = 448.0
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "bq", "bk", "bv", "wo", "ln2",
                "w_gate", "w_up", "w_down")


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """x (S, k) @ w (k, n) in float32, or with float8 operands."""
    x, w = x.astype(F32), w.astype(F32)
    if precision == "fp8":
        x, w = _fp8(x, axis=-1), _fp8(w, axis=0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x: jax.Array, w: Dict[str, jax.Array], s: Shapes,
           precision: str) -> jax.Array:
    S = x.shape[0]
    H, G, D = s.n_heads, s.n_kv_heads, s.head_dim
    h = _rms(x, w["ln1"], s.norm_eps)
    q, k, v = (_mm(h, w[n], precision) for n in ("wq", "wk", "wv"))
    if s.qkv_bias:
        q, k, v = q + w["bq"].astype(F32), k + w["bk"].astype(F32), \
            v + w["bv"].astype(F32)
    pos = jnp.arange(S)
    q = _rope(q.reshape(S, H, D), pos, s.rope_theta)
    k = _rope(k.reshape(S, G, D), pos, s.rope_theta)
    v = v.reshape(S, G, D)
    k = jnp.repeat(k, H // G, axis=1)
    v = jnp.repeat(v, H // G, axis=1)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k,
                        precision=jax.lax.Precision.HIGHEST) * D ** -0.5
        causal = pos[None, :] <= (lo + jnp.arange(qb.shape[0]))[:, None]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v,
                               precision=jax.lax.Precision.HIGHEST))
    o = jnp.concatenate(outs, 0).reshape(S, H * D)
    x = x + _mm(o, w["wo"], precision)
    h = _rms(x, w["ln2"], s.norm_eps)
    g = _mm(h, w["w_gate"], precision)
    u = _mm(h, w["w_up"], precision)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], precision)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, ln_f, head, s: Shapes, precision: str):
    h = _rms(x, ln_f, s.norm_eps)
    return _mm(h, head.T if s.tie_embeddings else head, precision)


def logits_at(weights: Dict[str, jax.Array], s: Shapes,
              sequences: Sequence[np.ndarray], positions: Sequence[np.ndarray],
              precision: str = "f32", pad_to: int = 256) -> List[np.ndarray]:
    """Float32 logits (rows of ``positions[i]``) of each sequence. A sequence
    is padded at its end to a multiple of ``pad_to``, which changes no
    earlier position under a causal mask and bounds the shapes compiled.
    ``weights`` is the flat layout of ``model.flat_layout``."""
    layers = [k for k in LAYER_LEAVES if k in weights]
    head = weights["embedding"] if s.tie_embeddings else weights["head"]
    out = []
    for seq, pos in zip(sequences, positions):
        n = -(-len(seq) // pad_to) * pad_to
        toks = np.zeros(n, np.int32)
        toks[:len(seq)] = seq
        x = weights["embedding"][jnp.asarray(toks)].astype(F32)
        for i in range(s.n_layers):
            x = _layer(x, {k: weights[k][i] for k in layers}, s, precision)
        rows = x[jnp.asarray(np.asarray(pos, np.int32))]
        out.append(np.asarray(_head(rows, weights["ln_f"], head, s, precision)))
    return out
