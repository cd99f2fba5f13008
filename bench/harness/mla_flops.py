"""Operations and bytes that a latent-attention MoE model (``mla_model``)
needs, computed from shapes.

Counted as multiply-adds times two. A prefill token runs every dense
matrix of its layers, the router, its ``top_k`` routed experts and the
shared experts; causal attention needs the pairs at or below the diagonal
only, at the decompressed widths (q·k over ``qk_nope + qk_rope``, the
weighted sum over ``v_head_dim``); the output head runs at the last
position only. A decode step needs to read every weight but the embedding
table (one row of it) and the routed experts no token chose: the shared
experts and ``top_k`` routed experts per MoE layer, plus the latent cache
of its context. Work an implementation does beyond that (masked scores,
the cache beyond the context, experts read and not used) is not counted,
so it shows as a lower share of the peak.
"""
from __future__ import annotations

from .mla_model import Shapes


def _attn_params(s: Shapes) -> int:
    return sum(a * b for a, b in (shp for shp in s.attn_shapes().values()
                                  if len(shp) == 2))


def token_matmul_params(s: Shapes) -> int:
    """Weights a prefill token multiplies, over all layers."""
    d, f = s.d_model, s.expert_d_ff
    dense = _attn_params(s) + 3 * d * s.dense_d_ff
    moe = (_attn_params(s) + d * s.n_experts
           + 3 * d * f * (s.top_k + s.n_shared_experts))
    return s.first_k_dense * dense + s.n_moe * moe


def prefill_flops(s: Shapes, tokens: int) -> int:
    linear = 2 * tokens * token_matmul_params(s)
    pairs = tokens * (tokens + 1) // 2
    width = s.qk_nope_head_dim + s.qk_rope_head_dim + s.v_head_dim
    attention = 2 * s.n_layers * s.n_heads * width * pairs
    head = 2 * s.d_model * s.vocab
    return linear + attention + head


def decode_weight_bytes(s: Shapes) -> int:
    """The weights one decode step must read."""
    unread = s.n_moe * (s.n_experts - s.top_k) * s.expert_bytes() \
        + (s.vocab - 1) * s.d_model * s.dtype.itemsize
    return s.weight_bytes() - unread


def decode_bytes(s: Shapes, context: int) -> int:
    """Bytes one decode step must read: its weights and the latent cache of
    ``context`` tokens."""
    return decode_weight_bytes(s) + context * s.kv_bytes_per_token()
