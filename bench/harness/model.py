"""A configuration file's sizes, and the seeded weights made from them.

The configuration files keep the source's own keys (``hidden_size``,
``num_hidden_layers``, ...). ``Shapes`` reads them, and ``program_config``
turns them into the program's ``ModelConfig``. The weights are made here,
from the seed, on the device in one jitted call and in the type they are
served in; the reference makes them again the same way after the window,
so it takes nothing that the program made.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .generator import rng_for


@dataclasses.dataclass(frozen=True)
class Shapes:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tie_embeddings: bool
    rope_theta: float
    norm_eps: float
    dtype: Any

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Shapes":
        c = config
        heads = c["num_attention_heads"]
        return cls(
            n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"],
            n_heads=heads,
            n_kv_heads=c.get("num_key_value_heads", heads),
            head_dim=c.get("head_dim") or c.get("kv_channels")
            or c["hidden_size"] // heads,
            # Qwen (v1) counts both halves of its gated MLP in
            # intermediate_size; its file gives the width of one.
            d_ff=c.get("ffn_hidden_size", c["intermediate_size"]),
            vocab=c["vocab_size"],
            qkv_bias=bool(c.get("qkv_bias", c.get("attention_bias", False))),
            tie_embeddings=bool(c.get("tie_word_embeddings", False)),
            rope_theta=float(c.get("rope_theta", c.get("rotary_emb_base",
                                                       10_000.0))),
            norm_eps=float(c.get("rms_norm_eps",
                                 c.get("layer_norm_epsilon", 1e-6))),
            dtype=jnp.dtype(c.get("torch_dtype", "bfloat16")),
        )

    def weight_bytes(self) -> int:
        return sum(int(np.prod(s)) for s in self.leaf_shapes().values()) \
            * self.dtype.itemsize

    def kv_bytes_per_token(self) -> int:
        return 2 * self.n_layers * self.n_kv_heads * self.head_dim \
            * self.dtype.itemsize

    def leaf_shapes(self) -> Dict[str, tuple]:
        d, L = self.d_model, self.n_layers
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        shapes = {
            "embedding": (self.vocab, d), "ln_f": (d,),
            "ln1": (L, d), "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
            "wo": (L, q, d), "ln2": (L, d), "w_gate": (L, d, self.d_ff),
            "w_up": (L, d, self.d_ff), "w_down": (L, self.d_ff, d),
        }
        if self.qkv_bias:
            shapes.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
        if not self.tie_embeddings:
            shapes["head"] = (d, self.vocab)
        return shapes


def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    s = Shapes.of(config)
    return ModelConfig(
        name=config["name"], family="dense", n_layers=s.n_layers,
        d_model=s.d_model, n_heads=s.n_heads, n_kv_heads=s.n_kv_heads,
        head_dim=s.head_dim, d_ff=s.d_ff, vocab=s.vocab, qkv_bias=s.qkv_bias,
        tie_embeddings=s.tie_embeddings, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, dtype=s.dtype, source=config["source"],
    )


def _key(seed: int) -> jax.Array:
    words = rng_for(seed, 2).integers(0, 1 << 32, 2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _init(key, s: Shapes) -> Dict[str, jax.Array]:
    """Random weights with the scales of a trained model's statistics:
    matrices at 1/sqrt(fan-in), embeddings at 0.02, norm scales near 1 and
    biases near 0, so that no term is a no-op a fault could hide behind."""
    names = sorted(s.leaf_shapes())
    keys = dict(zip(names, jax.random.split(key, len(names))))
    out = {}
    for name, shape in s.leaf_shapes().items():
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name in ("ln1", "ln2", "ln_f"):
            w = 1.0 + 0.1 * z
        elif name in ("bq", "bk", "bv"):
            w = 0.1 * z
        elif name == "embedding":
            w = 0.02 * z if not s.tie_embeddings else z * s.d_model ** -0.5
        else:
            w = z * shape[-2] ** -0.5
        out[name] = w.astype(s.dtype)
    return out


def make_weights(config: Dict[str, Any], seed: int, device=None):
    """The weights in the program's layout (stacked per layer under
    ``blocks``), made on ``device`` in one jitted call."""
    s = Shapes.of(config)
    device = device or jax.devices()[0]
    flat = jax.jit(_init, static_argnums=1,
                   out_shardings=jax.sharding.SingleDeviceSharding(device))(
        _key(seed), s)
    return to_program_layout(flat)


def to_program_layout(flat: Dict[str, jax.Array]) -> Dict[str, Any]:
    top = ("embedding", "ln_f", "head")
    tree = {k: flat[k] for k in top if k in flat}
    tree["blocks"] = [{k: v for k, v in flat.items() if k not in top}]
    return tree


def flat_layout(tree: Dict[str, Any]) -> Dict[str, jax.Array]:
    flat = {k: v for k, v in tree.items() if k != "blocks"}
    flat.update(tree["blocks"][0])
    return flat
