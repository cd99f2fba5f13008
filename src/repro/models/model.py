"""LM wrapper: embeddings -> scanned blocks -> head; train / prefill /
decode entry points used by the launcher, serving engine and dry-run."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import BATCH, MODEL, cross_entropy_loss, embed, rms_norm, shard, unembed
from .transformer import init_caches, run_blocks


def forward(
    params: Dict,
    tokens: Optional[jax.Array],
    cfg,
    *,
    mode: str = "train",
    inputs_embeds: Optional[jax.Array] = None,
    frontend: Optional[jax.Array] = None,
    caches: Optional[List] = None,
    cache_len: Optional[jax.Array] = None,
    window: int = 0,
    remat: bool = False,
    last_only: bool = False,
):
    """Returns (logits, new_caches, aux) (``run_blocks``'s aux).

    ``inputs_embeds`` replaces token embedding for audio frontends
    (precomputed frame embeddings — the stubbed modality carve-out);
    ``frontend`` feeds cross-attention layers (VLM patch embeddings).
    ``last_only`` runs the final norm and the head at the last position.
    """
    if inputs_embeds is not None:
        x = inputs_embeds.astype(cfg.dtype)
    else:
        x = embed(params, tokens)
    x = shard(x, BATCH, None, None)
    x, new_caches, aux = run_blocks(
        params, x, cfg, mode=mode, frontend=frontend, caches=caches,
        cache_len=cache_len, window=window, remat=remat,
    )
    if last_only:
        x = x[:, -1:]
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params, x)
    logits = shard(logits, BATCH, None, MODEL)
    return logits, new_caches, aux


# ---------------------------------------------------------------------------
def loss_fn(
    params: Dict,
    batch: Dict,
    cfg,
    *,
    aux_weight: float = 0.01,
    remat: bool = True,
):
    logits, _, aux = forward(
        params, batch["tokens"], cfg, mode="train",
        frontend=batch.get("frontend"),
        inputs_embeds=batch.get("inputs_embeds"),
        remat=remat,
    )
    ce = cross_entropy_loss(logits, batch["labels"], batch.get("mask"))
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


def prefill(
    params: Dict,
    tokens: jax.Array,
    cfg,
    *,
    max_len: int,
    window: int = 0,
    frontend: Optional[jax.Array] = None,
    inputs_embeds: Optional[jax.Array] = None,
    routing: bool = False,
):
    """Process a prompt, returning (last-position logits, caches, length),
    and with ``routing`` (a MoE model) also the number of assignments to
    each expert, (MoE layers, n_experts) int32. The output head runs at
    the last position only."""
    B, S = (
        tokens.shape if tokens is not None else inputs_embeds.shape[:2]
    )
    caches = init_caches(cfg, B, max_len, window)
    logits, caches, aux = forward(
        params, tokens, cfg, mode="prefill", caches=caches,
        cache_len=jnp.zeros((), jnp.int32), window=window,
        frontend=frontend, inputs_embeds=inputs_embeds, last_only=True,
    )
    out = (logits[:, 0], caches, jnp.array(S, jnp.int32))
    return out + (aux,) if routing else out


def decode_step(
    params: Dict,
    token: jax.Array,            # (B,) or (B,1) token ids
    caches: List,
    cache_len: jax.Array,        # scalar int32
    cfg,
    *,
    window: int = 0,
    frontend: Optional[jax.Array] = None,
    inputs_embeds: Optional[jax.Array] = None,
):
    """One decode step: returns (logits (B, vocab), new caches)."""
    if token is not None and token.ndim == 1:
        token = token[:, None]
    logits, new_caches, _ = forward(
        params, token, cfg, mode="decode", caches=caches,
        cache_len=cache_len, window=window, frontend=frontend,
        inputs_embeds=inputs_embeds,
    )
    return logits[:, 0], new_caches
