"""A latent-attention MoE configuration's sizes (the DeepSeek-V2 family), and
the seeded weights made from them.

The configuration file keeps the source's own keys (``kv_lora_rank``,
``first_k_dense_replace``, ``n_routed_experts``, ``rope_scaling``, ...).
``Shapes`` reads them, and ``program_config`` turns them into the
program's ``ModelConfig``. The weights are made here, from the seed, on the
device, in the type they are served in; the reference makes them again the
same way after the window, so it takes nothing that the program made.

Flat names: ``embedding``, ``ln_f``, ``head``, and per layer kind
``lead.<leaf>`` (the leading dense layers) and ``moe.<leaf>`` (the MoE
layers), each with a leading axis over its layers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .generator import rng_for

NORMS = ("ln1", "ln2", "kv_norm", "ln_f")


@dataclasses.dataclass(frozen=True)
class Shapes:
    n_layers: int
    first_k_dense: int
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    dense_d_ff: int
    expert_d_ff: int
    n_experts: int
    n_shared_experts: int
    top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    vocab: int
    rope_theta: float
    rope_factor: float
    rope_original_max_len: int
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale: float
    rope_mscale_all_dim: float
    norm_eps: float
    dtype: Any

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Shapes":
        c = config
        rs = c["rope_scaling"]
        # What this family's code supports: no q LoRA, an MoE layer after
        # every leading dense one, greedy softmax routing, YaRN, an untied
        # head.
        unsupported = {
            "q_lora_rank": c.get("q_lora_rank") is not None,
            "moe_layer_freq": c.get("moe_layer_freq", 1) != 1,
            "scoring_func": c.get("scoring_func", "softmax") != "softmax",
            "topk_method": c.get("topk_method", "greedy") != "greedy",
            "tie_word_embeddings": bool(c.get("tie_word_embeddings")),
            "rope_scaling": rs.get("type") != "yarn",
        }
        if any(unsupported.values()):
            raise ValueError("unsupported settings: " + ", ".join(
                k for k, bad in unsupported.items() if bad))
        return cls(
            n_layers=c["num_hidden_layers"],
            first_k_dense=c["first_k_dense_replace"],
            d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"],
            dense_d_ff=c["intermediate_size"],
            expert_d_ff=c["moe_intermediate_size"],
            n_experts=c["n_routed_experts"],
            n_shared_experts=c["n_shared_experts"],
            top_k=c["num_experts_per_tok"],
            norm_topk_prob=bool(c["norm_topk_prob"]),
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            rope_factor=float(rs["factor"]),
            rope_original_max_len=int(rs["original_max_position_embeddings"]),
            rope_beta_fast=float(rs["beta_fast"]),
            rope_beta_slow=float(rs["beta_slow"]),
            rope_mscale=float(rs["mscale"]),
            rope_mscale_all_dim=float(rs["mscale_all_dim"]),
            norm_eps=float(c["rms_norm_eps"]),
            dtype=jnp.dtype(c.get("torch_dtype", "bfloat16")),
        )

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_k_dense

    def attn_shapes(self) -> Dict[str, tuple]:
        d, H, r = self.d_model, self.n_heads, self.kv_lora_rank
        dn, dr, dv = self.qk_nope_head_dim, self.qk_rope_head_dim, \
            self.v_head_dim
        return {"ln1": (d,), "wq": (d, H * (dn + dr)), "w_kv_a": (d, r + dr),
                "kv_norm": (r,), "w_kv_b": (r, H * (dn + dv)),
                "wo": (H * dv, d), "ln2": (d,)}

    def leaf_shapes(self) -> Dict[str, tuple]:
        d, E, f = self.d_model, self.n_experts, self.expert_d_ff
        fs, fd = self.n_shared_experts * f, self.dense_d_ff
        lead = dict(self.attn_shapes(), w_gate=(d, fd), w_up=(d, fd),
                    w_down=(fd, d))
        moe = dict(self.attn_shapes(), router=(d, E), w_gate=(E, d, f),
                   w_up=(E, d, f), w_down=(E, f, d), ws_gate=(d, fs),
                   ws_up=(d, fs), ws_down=(fs, d))
        shapes = {"embedding": (self.vocab, d), "ln_f": (d,),
                  "head": (d, self.vocab)}
        shapes.update({f"lead.{k}": (self.first_k_dense,) + s
                       for k, s in lead.items()})
        shapes.update({f"moe.{k}": (self.n_moe,) + s for k, s in moe.items()})
        return shapes

    def weight_bytes(self) -> int:
        return sum(int(np.prod(s)) for s in self.leaf_shapes().values()) \
            * self.dtype.itemsize

    def expert_bytes(self) -> int:
        """Bytes of one routed expert (its three matrices)."""
        return 3 * self.d_model * self.expert_d_ff * self.dtype.itemsize

    def kv_bytes_per_token(self) -> int:
        """The latent cache: ``kv_lora_rank + qk_rope_head_dim`` per layer."""
        return self.n_layers * (self.kv_lora_rank + self.qk_rope_head_dim) \
            * self.dtype.itemsize


def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file. Built before
    anything else, so a program without latent attention fails here."""
    from repro.configs.base import ModelConfig

    s = Shapes.of(config)
    return ModelConfig(
        name=config["name"], family="moe", n_layers=s.n_layers,
        d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=config["num_key_value_heads"], d_ff=s.expert_d_ff,
        vocab=s.vocab, n_experts=s.n_experts, top_k=s.top_k,
        n_shared_experts=s.n_shared_experts,
        norm_topk_prob=s.norm_topk_prob,
        routed_scaling_factor=s.routed_scaling_factor,
        first_k_dense=s.first_k_dense, dense_d_ff=s.dense_d_ff,
        kv_lora_rank=s.kv_lora_rank, qk_nope_head_dim=s.qk_nope_head_dim,
        qk_rope_head_dim=s.qk_rope_head_dim, v_head_dim=s.v_head_dim,
        rope_theta=s.rope_theta, rope_factor=s.rope_factor,
        rope_original_max_len=s.rope_original_max_len,
        rope_beta_fast=s.rope_beta_fast, rope_beta_slow=s.rope_beta_slow,
        rope_mscale=s.rope_mscale, rope_mscale_all_dim=s.rope_mscale_all_dim,
        norm_eps=s.norm_eps, dtype=s.dtype, source=config["source"],
    )


def _key(seed: int) -> jax.Array:
    words = rng_for(seed, 2).integers(0, 1 << 32, 2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _leaf(key, name: str, shape: tuple, dtype) -> jax.Array:
    """One leaf with the scales of a trained model's statistics: matrices
    at 1/sqrt(fan-in), the embedding at 0.02, norm scales near 1, so that
    no term is a no-op a fault could hide behind. Drawn in the served type,
    so that no float32 copy of a large leaf exists."""
    z = jax.random.normal(key, shape, dtype)
    base = name.rsplit(".", 1)[-1]
    if base in NORMS:
        return (1.0 + 0.1 * z).astype(dtype)
    if base == "embedding":
        return (0.02 * z).astype(dtype)
    return (z * shape[-2] ** -0.5).astype(dtype)


def make_flat(config: Dict[str, Any], seed: int, device=None):
    """The flat weights, made on ``device`` one leaf per jitted call."""
    s = Shapes.of(config)
    device = device or jax.devices()[0]
    shapes = s.leaf_shapes()
    names = sorted(shapes)
    keys = dict(zip(names, jax.random.split(_key(seed), len(names))))
    out = {}
    for name in names:
        make = jax.jit(_leaf, static_argnums=(1, 2, 3),
                       out_shardings=jax.sharding.SingleDeviceSharding(device))
        out[name] = make(keys[name], name, shapes[name], s.dtype)
    return out


def to_program_layout(flat: Dict[str, jax.Array]) -> Dict[str, Any]:
    """The program's tree: ``lead`` a list of per-layer dicts, ``blocks`` one
    period (one MoE layer) stacked over the MoE layers."""
    tree = {k: flat[k] for k in ("embedding", "ln_f", "head")}
    lead = {k[5:]: v for k, v in flat.items() if k.startswith("lead.")}
    n = next(iter(lead.values())).shape[0]
    tree["lead"] = [{k: v[i] for k, v in lead.items()} for i in range(n)]
    tree["blocks"] = [{k[4:]: v for k, v in flat.items()
                       if k.startswith("moe.")}]
    return tree


def make_weights(config: Dict[str, Any], seed: int, device=None):
    """The weights in the program's layout, made on ``device``."""
    return to_program_layout(make_flat(config, seed, device))
