"""Layer-stack composition: scan over super-blocks of ``cfg.period``
layers (MaxText-style stacked params — keeps HLO size and compile time
independent of depth), supporting heterogeneous interleaves (hybrid
attn:ssm, MoE cadence, VLM cross-attention cadence). Leading dense layers
(``cfg.first_k_dense``) run once ahead of the scan, each with its own
parameters (``params["lead"]``) and cache entry.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (
    cross_attention,
    decode_self_attention,
    init_kv_cache,
    self_attention,
)
from .layers import BATCH, MODEL, rms_norm, shard
from .mla import init_mla_cache, mla_attention, mla_decode
from .moe import moe_ffn
from .ssm import init_ssm_state, mamba_block, mamba_block_decode


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------
def _layer_cache(cfg, mixer: str, batch: int, max_len: int, window: int):
    if mixer == "attn":
        return init_kv_cache(cfg, batch, max_len, window)
    if mixer == "mla":     # no window: the latent cache holds every position
        return init_mla_cache(cfg, batch, max_len)
    if mixer == "ssm":
        return init_ssm_state(cfg, batch)
    return {}              # cross_attn has no mutable state


def init_caches(cfg, batch: int, max_len: int, window: int = 0):
    """Per-period-position cache pytrees with a leading n_periods axis;
    with leading dense layers, ``{"lead": [one per layer], "blocks": ...}``."""
    caches: List = [
        jax.tree.map(
            lambda a: jnp.zeros((cfg.n_periods,) + a.shape, a.dtype),
            _layer_cache(cfg, mixer, batch, max_len, window),
        )
        for mixer, _ in cfg.layer_plan()
    ]
    if cfg.first_k_dense:
        lead = [_layer_cache(cfg, mixer, batch, max_len, window)
                for mixer, _ in cfg.lead_plan()]
        return {"lead": lead, "blocks": caches}
    return caches


# ---------------------------------------------------------------------------
# One super-block (cfg.period layers)
# ---------------------------------------------------------------------------
def super_block(
    params_period: List[Dict],
    x: jax.Array,
    cfg,
    *,
    mode: str,                       # train | prefill | decode
    frontend: Optional[jax.Array],
    caches: Optional[List],
    cache_len: Optional[jax.Array],
    window: int,
    plan: Optional[Tuple[Tuple[str, str], ...]] = None,
):
    """``plan`` (default ``cfg.layer_plan()``) names each layer's mixer
    and channel. The third result is the MoE load-balance loss in training;
    in prefill and decode, where MoE layers drop no token, it is the number
    of assignments to each expert, (MoE layers, n_experts) int32, when the
    plan has MoE layers."""
    new_caches: List = []
    aux = jnp.zeros((), jnp.float32)
    routing: List = []
    for j, (mixer, channel) in enumerate(plan or cfg.layer_plan()):
        p = params_period[j]
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        cache_j = caches[j] if caches is not None else None
        if mixer == "mla":
            if mode == "decode":
                y, new_cache = mla_decode(p, h, cache_j, cache_len, cfg)
            elif mode == "prefill":
                y, fresh = mla_attention(p, h, cfg, return_cache=True)
                new_cache = jax.tree.map(
                    lambda c, f: jax.lax.dynamic_update_slice_in_dim(
                        c, f.astype(c.dtype), 0, axis=1),
                    cache_j, fresh)
            else:
                y = mla_attention(p, h, cfg)
                new_cache = cache_j
        elif mixer == "attn":
            if mode == "decode":
                y, new_cache = decode_self_attention(
                    p, h, cache_j, cache_len, cfg, window=window
                )
            elif mode == "prefill":
                y, kv = self_attention(
                    p, h, cfg, window=window, return_cache=True
                )

                # Write prefix KV into the cache. For a ring buffer
                # (window mode) only the last W positions survive, placed
                # at slot = position % W so decode continues seamlessly.
                def _write(c, fresh):
                    fresh = fresh.astype(c.dtype)
                    S, W = fresh.shape[1], c.shape[1]
                    if window and S >= W:
                        return jnp.roll(fresh[:, S - W:], S % W, axis=1)
                    return jax.lax.dynamic_update_slice_in_dim(
                        c, fresh, 0, axis=1
                    )

                new_cache = jax.tree.map(_write, cache_j, kv)
            else:
                y = self_attention(p, h, cfg, window=window)
                new_cache = cache_j
        elif mixer == "ssm":
            if mode == "decode":
                y, new_cache = mamba_block_decode(p, h, cache_j, cfg)
            elif mode == "prefill":
                y, state = mamba_block(p, h, cfg, return_state=True)
                new_cache = jax.tree.map(
                    lambda c, s: s.astype(c.dtype), cache_j, state
                )
            else:
                y = mamba_block(p, h, cfg)
                new_cache = cache_j
        else:  # cross_attn
            y = cross_attention(p, h, frontend, cfg)
            new_cache = cache_j if cache_j is not None else {}
        x = x + y
        x = shard(x, BATCH, None, None)
        if channel != "none":
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            if channel == "moe":
                if mode == "train":
                    if cfg.moe_ep:
                        from .moe_ep import moe_ffn_ep

                        y2, aux_j = moe_ffn_ep(p, h2, cfg, return_aux=True)
                    else:
                        y2, aux_j = moe_ffn(p, h2, cfg, return_aux=True)
                    aux = aux + aux_j
                else:
                    y2, counts = moe_ffn(p, h2, cfg, return_aux=True,
                                         dropless=True)
                    routing.append(counts)
            else:
                from .layers import mlp_forward

                y2 = mlp_forward(p, h2, cfg.mlp)
            x = x + y2
            x = shard(x, BATCH, None, None)
        new_caches.append(new_cache)
    return x, new_caches, (jnp.stack(routing) if routing else aux)


# ---------------------------------------------------------------------------
# Full stack: scan over periods
# ---------------------------------------------------------------------------
def run_blocks(
    params: Dict,
    x: jax.Array,
    cfg,
    *,
    mode: str = "train",
    frontend: Optional[jax.Array] = None,
    caches: Optional[List] = None,
    cache_len: Optional[jax.Array] = None,
    window: int = 0,
    remat: bool = False,
):
    """Returns (hidden, new_caches, aux): ``aux`` is the load-balance loss
    in training, and in prefill and decode of a MoE model the assignments
    to each expert, (MoE layers, n_experts) int32 (``super_block``)."""
    blocks = params["blocks"]           # leaves: (n_periods, ...)
    have_caches = caches is not None
    lead_new = None
    if cfg.first_k_dense:
        lead_caches = caches["lead"] if have_caches else None
        caches = caches["blocks"] if have_caches else None
        lead = functools.partial(
            super_block, cfg=cfg, mode=mode, frontend=frontend,
            caches=lead_caches, cache_len=cache_len, window=window,
            plan=cfg.lead_plan(),
        )
        if remat:
            lead = jax.checkpoint(lead)
        x, lead_new, _ = lead(params["lead"], x)

    def body(carry_x, per):
        params_period, caches_period = per
        out, new_caches, aux = super_block(
            params_period, carry_x, cfg,
            mode=mode, frontend=frontend,
            caches=caches_period if have_caches else None,
            cache_len=cache_len, window=window,
        )
        return out, (new_caches if have_caches else 0, aux)

    if remat:
        policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if cfg.remat_policy == "dots" else None
        )
        body = jax.checkpoint(body, policy=policy)

    def done(x, new_caches, auxs):
        aux = (auxs.reshape(-1, auxs.shape[-1]) if auxs.ndim > 1
               else jnp.sum(auxs))
        if not have_caches:
            return x, None, aux
        if cfg.first_k_dense:
            new_caches = {"lead": lead_new, "blocks": new_caches}
        return x, new_caches, aux

    xs = (blocks, caches if have_caches else jnp.zeros((cfg.n_periods,)))
    if cfg.scan_layers:
        x, (new_caches, auxs) = jax.lax.scan(body, x, xs)
        return done(x, new_caches, auxs)

    # Unrolled: accurate XLA cost analysis (scan bodies are counted once
    # by the cost model); used by the dry-run.
    news, auxs = [], []
    for i in range(cfg.n_periods):
        per = jax.tree.map(lambda a: a[i], xs)
        x, (nc, aux) = body(x, per)
        news.append(nc)
        auxs.append(aux)
    new_caches = (jax.tree.map(lambda *ls: jnp.stack(ls), *news)
                  if have_caches else None)
    return done(x, new_caches, jnp.stack(auxs))
