"""Expert-parallel Mixture-of-Experts channel mixer.

Training: scatter-based dispatch (no GShard dense dispatch tensors):
tokens are scattered into per-expert capacity buffers with positions
derived from a cumulative count, experts run as a batched einsum over the
expert axis (sharded over the ``model`` mesh axis = expert parallelism),
and outputs are gathered back with router-probability weighting. Top-k
routing with capacity dropping and the standard load-balance auxiliary
loss.

Serving (``dropless=True``, prefill and decode): no token is dropped. The
token-expert assignments are sorted by expert and run as grouped matmuls
(``jax.lax.ragged_dot``); where there are fewer assignments than experts
(a decode step) each chosen expert is sliced out of its stack instead, so
only they are read.

Shared experts (``n_shared_experts``) are one gated MLP every token runs.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .layers import BATCH, MODEL, shard


def router(params: Dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Returns (top-k weights, top-k expert indices, probs): softmax over
    the experts in float32, the top-k probabilities renormalised where
    ``cfg.norm_topk_prob``, times ``cfg.routed_scaling_factor``."""
    logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), params["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.clip(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    top_p = top_p * cfg.routed_scaling_factor
    return top_p.astype(x.dtype), top_i, probs


def load_balance_loss(probs: jax.Array, top_i: jax.Array, n_experts: int):
    """Switch-style aux loss: E * sum_e f_e * P_e."""
    sel = jax.nn.one_hot(top_i, n_experts, dtype=jnp.float32)  # (B,S,k,E)
    frac_tokens = jnp.mean(jnp.mean(sel, axis=2), axis=(0, 1))  # (E,), sums to 1
    mean_prob = jnp.mean(probs, axis=(0, 1))                    # (E,)
    return n_experts * jnp.sum(frac_tokens * mean_prob)


def shared_experts(params: Dict, x: jax.Array) -> jax.Array:
    """The shared experts, one gated MLP that every token runs."""
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, params["ws_gate"]))
    h = h * jnp.einsum("bsd,df->bsf", x, params["ws_up"])
    return jnp.einsum("bsf,fd->bsd", h, params["ws_down"])


def _routed_dropless(params: Dict, x: jax.Array, top_p: jax.Array,
                     top_i: jax.Array, E: int) -> jax.Array:
    """Every (token, expert) assignment, none dropped: (N, d) tokens with
    their (N, K) weights and experts -> (N, d)."""
    N, d = x.shape
    K = top_i.shape[-1]
    flat_i = top_i.reshape(-1)
    if N * K < E:
        # Fewer assignments than experts (a decode step): each chosen
        # expert is sliced out of its stack, so that only it is read (a
        # gather of the stack copies every expert of the layer first).
        def chosen(w):
            return jnp.stack([jax.lax.dynamic_index_in_dim(w, flat_i[a], 0,
                                                           keepdims=False)
                              for a in range(N * K)])

        xs = jnp.repeat(x, K, axis=0)
        g = jnp.einsum("nd,ndf->nf", xs, chosen(params["w_gate"]))
        u = jnp.einsum("nd,ndf->nf", xs, chosen(params["w_up"]))
        out = jnp.einsum("nf,nfd->nd", jax.nn.silu(g) * u,
                         chosen(params["w_down"]))
    else:
        order = jnp.argsort(flat_i)
        sizes = jnp.bincount(flat_i, length=E).astype(jnp.int32)
        xs = x[order // K]
        g = jax.lax.ragged_dot(xs, params["w_gate"], sizes)
        u = jax.lax.ragged_dot(xs, params["w_up"], sizes)
        out_sorted = jax.lax.ragged_dot(jax.nn.silu(g) * u,
                                        params["w_down"], sizes)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        out = out_sorted[back]
    out = out.reshape(N, K, d) * top_p[..., None]
    return jnp.sum(out.astype(jnp.float32), axis=1).astype(x.dtype)


def moe_ffn(
    params: Dict, x: jax.Array, cfg, *, return_aux: bool = False,
    dropless: bool = False,
):
    """x: (B, S, d). Training: each batch row is a dispatch group with its
    own capacity C = ceil(S * top_k / E * capacity_factor). ``dropless``
    (serving) drops no token, and its aux output is the number of
    assignments to each expert, (E,) int32, in place of the loss."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    top_p, top_i, probs = router(params, x, cfg)

    if dropless:
        y = _routed_dropless(params, x.reshape(B * S, d),
                             top_p.reshape(B * S, K), top_i.reshape(B * S, K),
                             E).reshape(B, S, d)
        if cfg.n_shared_experts:
            y = y + shared_experts(params, x)
        if return_aux:
            return y, jnp.bincount(top_i.reshape(-1), length=E).astype(
                jnp.int32)
        return y

    C = max(1, int((S * K / E) * cfg.capacity_factor + 0.9999))
    C = min(C, S * K)

    # Position of each (token, k) assignment within its expert's buffer:
    # running count of prior assignments to the same expert in this group.
    sel = jax.nn.one_hot(top_i, E, dtype=jnp.int32)          # (B, S, K, E)
    flat = sel.reshape(B, S * K, E)
    pos_flat = jnp.cumsum(flat, axis=1) - flat               # prior count
    pos = jnp.sum(pos_flat.reshape(B, S, K, E) * sel, axis=-1)  # (B, S, K)
    keep = (pos < C).astype(x.dtype)                         # capacity drop
    pos_c = jnp.minimum(pos, C - 1)

    # Scatter tokens into (B, E, C, d) expert buffers.
    bidx = jnp.arange(B)[:, None, None]                      # (B,1,1)
    contrib = x[:, :, None, :] * keep[..., None]             # (B, S, K, d)
    buffers = jnp.zeros((B, E, C, d), x.dtype).at[
        bidx, top_i, pos_c
    ].add(contrib)
    buffers = shard(buffers, BATCH, MODEL, None, None)

    # Batched expert FFN (SwiGLU), expert axis sharded over `model`.
    h_gate = jnp.einsum("becd,edf->becf", buffers, params["w_gate"])
    h_up = jnp.einsum("becd,edf->becf", buffers, params["w_up"])
    h = jax.nn.silu(h_gate) * h_up
    h = shard(h, BATCH, MODEL, None, None)
    out_buf = jnp.einsum("becf,efd->becd", h, params["w_down"])
    out_buf = shard(out_buf, BATCH, MODEL, None, None)

    # Gather back to token order with router weighting.
    gathered = out_buf[bidx, top_i, pos_c]                   # (B, S, K, d)
    y = jnp.sum(gathered * (top_p * keep)[..., None], axis=2)
    if cfg.n_shared_experts:
        y = y + shared_experts(params, x)
    y = shard(y, BATCH, None, None)
    if return_aux:
        return y, load_balance_loss(probs, top_i, E)
    return y
