"""Per-architecture smoke tests: REDUCED variant of each assigned family
(<=2 super-blocks, d_model<=512, <=4 experts) runs one forward/train step
and one serve step on CPU; asserts output shapes and no NaNs.

The FULL configs are exercised only via the dry-run (ShapeDtypeStruct,
no allocation) — see tests/test_dryrun.py and launch/dryrun.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, INPUT_SHAPES, get_config
from repro.models import decode_step, init_params, loss_fn, prefill
from repro.training import AdamWConfig, TrainConfig, make_train_step, init_adamw

ALL_ARCHS = sorted(ARCHS)

# Archs whose reduced train step still exceeds ~30 s on CI hardware; the
# fast tier skips them (the slow tier and the forward/serve smokes keep
# covering the family).
SLOW_TRAIN_ARCHS = {"jamba-1.5-large-398b"}
TRAIN_ARCH_PARAMS = [
    pytest.param(a, marks=pytest.mark.slow) if a in SLOW_TRAIN_ARCHS else a
    for a in ALL_ARCHS
]


def _batch(cfg, B=2, S=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab),
    }
    batch["labels"] = batch["tokens"]
    if cfg.cross_attn_every:
        batch["frontend"] = jax.random.normal(
            ks[1], (B, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype
        )
    if cfg.family == "audio":
        # stubbed codec frontend: precomputed frame embeddings
        batch["inputs_embeds"] = jax.random.normal(
            ks[2], (B, S, cfg.d_model), cfg.dtype
        )
    return batch


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_reduced_config_invariants(name):
    cfg = get_config(name).reduced()
    # leading dense layers run once, ahead of the scanned super-blocks
    assert cfg.n_layers - cfg.first_k_dense <= 2 * cfg.period
    assert cfg.d_model <= 512
    assert cfg.n_experts <= 4
    assert cfg.family == ARCHS[name].family
    # reduced keeps the structural plan of the family
    assert len(cfg.layer_plan()) == cfg.period


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_forward_shapes_and_finite(name):
    cfg = get_config(name).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    batch = _batch(cfg, B, S)
    from repro.models import forward

    logits, _, aux = forward(
        params, batch["tokens"], cfg, mode="train",
        frontend=batch.get("frontend"),
        inputs_embeds=batch.get("inputs_embeds"),
    )
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("name", TRAIN_ARCH_PARAMS)
def test_one_train_step(name):
    cfg = dataclasses.replace(get_config(name).reduced(), dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_adamw(params)
    step = make_train_step(
        cfg, TrainConfig(remat=False, opt=AdamWConfig(lr=1e-3))
    )
    batch = {k: jnp.asarray(v) for k, v in _batch(cfg).items()}
    new_params, new_opt, metrics = jax.jit(step)(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually changed
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        params, new_params,
    )
    assert max(jax.tree.leaves(moved)) > 0
    assert int(new_opt.step) == 1


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_one_serve_step(name):
    cfg = get_config(name).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    kw = {}
    if "frontend" in batch:
        kw["frontend"] = batch["frontend"]
    logits, caches, clen = prefill(
        params, batch["tokens"], cfg, max_len=S + 4, **kw
    )
    assert logits.shape == (B, cfg.vocab)
    tok = jnp.argmax(logits, -1)
    logits2, caches = decode_step(params, tok, caches, clen, cfg, **kw)
    assert logits2.shape == (B, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits2.astype(jnp.float32))))


@pytest.mark.parametrize("shape_name", sorted(INPUT_SHAPES))
def test_input_specs_cover_all_archs(shape_name):
    """input_specs builds abstract inputs for every (arch, shape) without
    allocating."""
    from repro.launch.specs import input_specs

    shape = INPUT_SHAPES[shape_name]
    for name in ALL_ARCHS:
        cfg = get_config(name, shape=shape_name)
        specs = input_specs(cfg, shape)
        leaves = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
        )
        assert leaves, (name, shape_name)
        assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)


def test_long_500k_window_applied_to_dense_families():
    for name in ALL_ARCHS:
        cfg = get_config(name, shape="long_500k")
        if cfg.family in ("ssm", "hybrid"):
            assert cfg.attn_window == 0   # sub-quadratic natively
        else:
            assert cfg.attn_window > 0    # sliding-window carve-in


def test_param_counts_match_published_scale():
    """Sanity: derived parameter counts are in the right ballpark of the
    published model sizes."""
    expect = {
        "gemma-7b": (7e9, 10e9),
        "qwen2-72b": (65e9, 80e9),
        "tinyllama-1.1b": (0.9e9, 1.3e9),
        "yi-34b": (30e9, 38e9),
        "olmoe-1b-7b": (6e9, 8e9),
        "mamba2-370m": (0.3e9, 0.5e9),
        "jamba-1.5-large-398b": (330e9, 420e9),
        "llama4-maverick-400b-a17b": (350e9, 450e9),
        "llama-3.2-vision-90b": (80e9, 100e9),
        "musicgen-large": (2.5e9, 4.5e9),
    }
    for name, (lo, hi) in expect.items():
        n = ARCHS[name].param_count()
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B outside [{lo/1e9}, {hi/1e9}]"
