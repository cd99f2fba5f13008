"""Expert-parallel MoE with explicit all-to-all (shard_map).

The pjit scatter-dispatch formulation (moe.py) is correct but XLA's SPMD
partitioner lowers the (B, E, C, d) buffer construction as full-buffer
all-reduces — ~730 GB/device/step on olmoe train_4k (§Perf hillclimb B).
The communication-optimal schedule is the classic expert-parallel
all-to-all: tokens are sequence-sharded over the ``model`` axis, each
shard routes locally, exchanges per-expert capacity buffers with a single
all_to_all, runs its local experts, and all_to_alls back. Predicted
volume: B*S*k*cf*d*2 bytes/device/layer (~167 MB for olmoe) instead of
full-buffer all-reduces — a ~40x reduction.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import _current_mesh, mesh_axis_sizes
from .moe import moe_ffn


def _batch_axes(sizes) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in sizes)


def moe_ffn_ep(params: Dict, x: jax.Array, cfg, *, return_aux: bool = False):
    """Drop-in for moe_ffn; falls back when no model axis / E not
    divisible, or for a router this exchange does not implement (shared
    experts, top-k weights not renormalised). x: (B, S, d)."""
    sizes = mesh_axis_sizes()
    m = sizes.get("model", 1)
    E, K = cfg.n_experts, cfg.top_k
    if m == 1 or E % m != 0 or cfg.n_shared_experts \
            or not cfg.norm_topk_prob or cfg.routed_scaling_factor != 1:
        return moe_ffn(params, x, cfg, return_aux=return_aux)

    mesh = _current_mesh()
    B, S, d = x.shape
    S_pad = math.ceil(S / m) * m
    if S_pad != S:
        x = jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0)))
    ba = _batch_axes(sizes)
    x_spec = P(ba if ba else None, "model", None)
    E_loc = E // m
    cf = cfg.capacity_factor

    def local(router, wg, wu, wd, xl):
        Bl, Sl, _ = xl.shape
        N = Bl * Sl
        xt = xl.reshape(N, d)
        logits = jnp.einsum(
            "nd,de->ne", xt.astype(jnp.float32), router.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, K)
        top_p = (top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)
                 ).astype(xt.dtype)
        C = max(1, int(N * K / E * cf + 0.9999))
        C = min(C, N * K)

        sel = jax.nn.one_hot(top_i, E, dtype=jnp.int32)        # (N,K,E)
        flat = sel.reshape(N * K, E)
        pos_flat = jnp.cumsum(flat, axis=0) - flat
        pos = jnp.sum(pos_flat.reshape(N, K, E) * sel, axis=-1)  # (N,K)
        keep = (pos < C).astype(xt.dtype)
        pos_c = jnp.minimum(pos, C - 1)

        buf = jnp.zeros((E, C, d), xt.dtype).at[top_i, pos_c].add(
            xt[:, None, :] * keep[..., None]
        )
        # exchange: shard-major expert order — shard j owns experts
        # [j*E_loc, (j+1)*E_loc)
        sent = jax.lax.all_to_all(
            buf.reshape(m, E_loc, C, d), "model",
            split_axis=0, concat_axis=0, tiled=False,
        )                                                       # (m,E_loc,C,d)
        hg = jnp.einsum("mecd,edf->mecf", sent, wg)
        hu = jnp.einsum("mecd,edf->mecf", sent, wu)
        h = jax.nn.silu(hg) * hu
        out = jnp.einsum("mecf,efd->mecd", h, wd)
        back = jax.lax.all_to_all(
            out, "model", split_axis=0, concat_axis=0, tiled=False
        ).reshape(E, C, d)
        y = back[top_i, pos_c]                                  # (N,K,d)
        y = jnp.sum(y * (top_p * keep)[..., None], axis=1)
        y = y.reshape(Bl, Sl, d)

        # load-balance loss, averaged over every mesh axis
        fr = jnp.mean(
            jax.nn.one_hot(top_i, E, dtype=jnp.float32), axis=(0, 1)
        )
        mp = jnp.mean(probs, axis=0)
        axes_all = ("model",) + ba
        fr = jax.lax.pmean(fr, axes_all)
        mp = jax.lax.pmean(mp, axes_all)
        aux = E * jnp.sum(fr * mp)
        return y, aux

    y, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"], params["w_down"], x)
    if S_pad != S:
        y = y[:, :S]
    if return_aux:
        return y, aux
    return y
