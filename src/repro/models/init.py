"""Parameter initialization (stacked-per-period layout for layer scanning).

``init_params`` returns the real pytree (used by smoke tests, examples,
training); ``abstract_params`` returns ShapeDtypeStructs via ``eval_shape``
so the multi-pod dry-run never allocates memory.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp


def _dense(rng, shape, dtype, scale=None):
    scale = scale if scale is not None else shape[0] ** -0.5
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


def init_layer(rng, cfg, mixer: str, channel: str, d_ff: int = 0) -> Dict:
    """One layer's leaves; ``d_ff`` overrides the MLP width (a leading
    dense layer's)."""
    d, dt = cfg.d_model, cfg.dtype
    H, G, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(rng, 24)
    p: Dict = {"ln1": jnp.ones((d,), dt)}
    if mixer == "mla":
        dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        r, dv = cfg.kv_lora_rank, cfg.v_head_dim
        p["wq"] = _dense(ks[0], (d, H * (dn + dr)), dt)
        p["w_kv_a"] = _dense(ks[1], (d, r + dr), dt)
        p["kv_norm"] = jnp.ones((r,), dt)
        p["w_kv_b"] = _dense(ks[2], (r, H * (dn + dv)), dt)
        p["wo"] = _dense(ks[3], (H * dv, d), dt)
    elif mixer in ("attn", "cross_attn"):
        p["wq"] = _dense(ks[0], (d, H * D), dt)
        p["wo"] = _dense(ks[3], (H * D, d), dt)
        if mixer == "attn":
            p["wk"] = _dense(ks[1], (d, G * D), dt)
            p["wv"] = _dense(ks[2], (d, G * D), dt)
            if cfg.qkv_bias:
                p["bq"] = jnp.zeros((H * D,), dt)
                p["bk"] = jnp.zeros((G * D,), dt)
                p["bv"] = jnp.zeros((G * D,), dt)
        else:
            p["wk_cross"] = _dense(ks[1], (d, G * D), dt)
            p["wv_cross"] = _dense(ks[2], (d, G * D), dt)
    elif mixer == "ssm":
        di = cfg.ssm_d_inner
        gn = cfg.ssm_groups * cfg.ssm_state
        h = cfg.ssm_heads
        w = cfg.conv_width
        p["w_z"] = _dense(ks[4], (d, di), dt)
        p["w_x"] = _dense(ks[5], (d, di), dt)
        p["w_B"] = _dense(ks[6], (d, gn), dt)
        p["w_C"] = _dense(ks[7], (d, gn), dt)
        p["w_dt"] = _dense(ks[8], (d, h), dt)
        p["conv_x_w"] = _dense(ks[9], (w, di), dt, scale=w ** -0.5)
        p["conv_x_b"] = jnp.zeros((di,), dt)
        p["conv_B_w"] = _dense(ks[10], (w, gn), dt, scale=w ** -0.5)
        p["conv_B_b"] = jnp.zeros((gn,), dt)
        p["conv_C_w"] = _dense(ks[11], (w, gn), dt, scale=w ** -0.5)
        p["conv_C_b"] = jnp.zeros((gn,), dt)
        p["dt_bias"] = jnp.full((h,), 0.5, dt)
        p["A_log"] = jnp.zeros((h,), jnp.float32)
        p["D"] = jnp.ones((h,), dt)
        p["norm"] = jnp.ones((di,), dt)
        p["w_out"] = _dense(ks[12], (di, d), dt)
    if channel == "mlp":
        p["ln2"] = jnp.ones((d,), dt)
        p["w_gate"] = _dense(ks[13], (d, d_ff), dt)
        p["w_up"] = _dense(ks[14], (d, d_ff), dt)
        p["w_down"] = _dense(ks[15], (d_ff, d), dt)
    elif channel == "moe":
        E = cfg.n_experts
        p["ln2"] = jnp.ones((d,), dt)
        p["router"] = _dense(ks[16], (d, E), jnp.float32)
        p["w_gate"] = _dense(ks[17], (E, d, cfg.d_ff), dt)
        p["w_up"] = _dense(ks[18], (E, d, cfg.d_ff), dt)
        p["w_down"] = _dense(ks[19], (E, cfg.d_ff, d), dt)
        if cfg.n_shared_experts:
            f = cfg.n_shared_experts * cfg.d_ff
            p["ws_gate"] = _dense(ks[20], (d, f), dt)
            p["ws_up"] = _dense(ks[21], (d, f), dt)
            p["ws_down"] = _dense(ks[22], (f, d), dt)
    return p


def init_period(rng, cfg) -> List[Dict]:
    plan = cfg.layer_plan()
    keys = jax.random.split(rng, len(plan))
    return [
        init_layer(k, cfg, mixer, channel)
        for k, (mixer, channel) in zip(keys, plan)
    ]


def init_params(rng, cfg) -> Dict:
    k_embed, k_head, k_blocks = jax.random.split(rng, 3)
    params: Dict = {
        "embedding": _dense(k_embed, (cfg.vocab, cfg.d_model), cfg.dtype,
                            scale=0.02),
        "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = _dense(k_head, (cfg.d_model, cfg.vocab), cfg.dtype)
    if cfg.first_k_dense:
        params["lead"] = [
            init_layer(k, cfg, mixer, channel, d_ff=cfg.dense_d_ff)
            for k, (mixer, channel) in zip(
                jax.random.split(jax.random.fold_in(rng, 3), cfg.first_k_dense),
                cfg.lead_plan())
        ]
    # vmap over periods stacks every leaf with a leading n_periods axis
    params["blocks"] = jax.vmap(lambda k: init_period(k, cfg))(
        jax.random.split(k_blocks, cfg.n_periods)
    )
    return params


def abstract_params(cfg):
    """Parameter ShapeDtypeStructs without allocating (for the dry-run)."""
    return jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)
    )


def param_bytes(cfg) -> int:
    tree = abstract_params(cfg)
    return sum(
        int(jnp.prod(jnp.array(l.shape))) * l.dtype.itemsize
        for l in jax.tree.leaves(tree)
    )
