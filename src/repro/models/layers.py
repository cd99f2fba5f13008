"""Common neural-net building blocks (pure JAX, pjit-compatible).

Sharding is expressed through ``shard(x, ...)`` constraints that no-op when
no mesh is active (CPU smoke tests) and bind to whatever subset of the
production axes ("pod", "data", "model") the active mesh defines.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

# Logical batch axes: sharded over pod+data when present.
BATCH: Tuple[str, ...] = ("pod", "data")
MODEL = "model"


def _current_mesh():
    """The mesh governing this trace, as set by ``jax.set_mesh`` (how the
    launchers and the dry-run provide it), or None."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def active_mesh_axes() -> frozenset:
    m = _current_mesh()
    return frozenset(m.axis_names) if m is not None else frozenset()


def mesh_axis_sizes() -> dict:
    m = _current_mesh()
    return dict(m.shape) if m is not None else {}


def pspec(*spec: Axis, dims: Optional[Sequence[int]] = None) -> P:
    """Build a PartitionSpec keeping only axes the active mesh defines and
    (when ``dims`` is given) only where the dimension is divisible by the
    mesh-axis size — e.g. 56 attention heads cannot shard 16 ways, and 8 KV
    heads on a 16-way model axis stay replicated (Megatron GQA rule)."""
    sizes = mesh_axis_sizes()

    def filt(e: Axis, dim: Optional[int]):
        if e is None:
            return None
        if isinstance(e, str):
            e = (e,)
        t = tuple(a for a in e if a in sizes)
        if not t:
            return None
        total = 1
        for a in t:
            total *= sizes[a]
        if dim is not None and dim % total != 0:
            return None
        return t if len(t) > 1 else t[0]

    if dims is None:
        dims = [None] * len(spec)
    return P(*[filt(e, d) for e, d in zip(spec, dims)])


def shard(x: jax.Array, *spec: Axis) -> jax.Array:
    """Apply a sharding constraint if a mesh is active; no-op otherwise.
    Drops axes that don't divide the corresponding dimension."""
    if not mesh_axis_sizes():
        return x
    return jax.lax.with_sharding_constraint(
        x, pspec(*spec, dims=x.shape)
    )


# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def rope(
    x: jax.Array, positions: jax.Array, theta: float = 10_000.0
) -> jax.Array:
    """Rotary position embedding. x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def mlp_forward(params: dict, x: jax.Array, kind: str = "swiglu") -> jax.Array:
    """Gated MLP: SwiGLU (llama-family) or GeGLU (gemma)."""
    h_gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
    h_up = jnp.einsum("...d,df->...f", x, params["w_up"])
    act = jax.nn.gelu(h_gate) if kind == "geglu" else jax.nn.silu(h_gate)
    h = shard(act * h_up, BATCH, None, MODEL)
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


def embed(params: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["embedding"], tokens, axis=0)


def unembed(params: dict, x: jax.Array) -> jax.Array:
    w = params.get("head", params["embedding"])
    if w.shape[0] != x.shape[-1]:
        return jnp.einsum("...d,vd->...v", x, w)
    return jnp.einsum("...d,dv->...v", x, w)


def cross_entropy_loss(
    logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
