"""Operations and bytes that the algorithm needs, computed from shapes.

Counted as multiply-adds times two. Causal attention needs the pairs at or
below the diagonal only; a prefill needs the output head at its last
position only. Work that an implementation does beyond that (masked
scores, logits of every prompt position) is not counted, so it shows as a
lower share of the peak.
"""
from __future__ import annotations

from .model import Shapes


def layer_matmul_params(s: Shapes) -> int:
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    return s.d_model * (q + 2 * kv) + q * s.d_model + 3 * s.d_model * s.d_ff


def prefill_flops(s: Shapes, tokens: int) -> int:
    linear = 2 * tokens * s.n_layers * layer_matmul_params(s)
    pairs = tokens * (tokens + 1) // 2
    attention = 4 * s.n_layers * s.n_heads * s.head_dim * pairs
    head = 2 * s.d_model * s.vocab
    return linear + attention + head


def decode_bytes(s: Shapes, context: int) -> int:
    """Bytes one decode step must read: every weight once and the cache of
    ``context`` tokens."""
    return s.weight_bytes() + context * s.kv_bytes_per_token()
