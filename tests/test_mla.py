"""DeepSeek-V2-Lite's block on the served path: multi-head latent attention
with a latent KV cache, YaRN rope, a leading dense layer, dropless routed
and shared experts. Compared on the CPU with the benchmark's plain float32
reference (``bench/harness/mla_reference.py``) at the reduced size, on
seeded random weights.

Tolerances: the program in float32 and the reference differ only by the
order of float32 sums (blocked online softmax against one softmax, the
absorbed against the decompressed product, grouped against per-token
expert sums), about 1e-6 of logits of size about 1; ``ATOL`` leaves a
margin of two orders of magnitude over that, and the same program in
bfloat16 (8 bits of mantissa, 3e-3 relative) lies beyond it, which a case
below asserts.
"""
import dataclasses
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, PAPER_MODELS, get_config
from repro.models import decode_step, forward, init_params, prefill
from repro.models.mla import (
    init_mla_cache,
    mla_attention,
    mla_decode,
    softmax_scale,
    yarn_inv_freq,
)
from repro.models.moe import moe_ffn
from repro.models.transformer import init_caches
from repro.serving import FunctionalServer
from repro.serving.kv_cache import kv_bytes_per_token

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
from harness import mla_model, mla_reference  # noqa: E402

ATOL = 1e-4


def _reduced_config_file(cfg):
    """The reduced ``deepseek-v2-lite`` as a configuration file in the
    source's own keys, as the benchmark's reference reads it."""
    return {
        "name": cfg.name, "source": cfg.source,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.first_k_dense,
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": None,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "intermediate_size": cfg.dense_d_ff,
        "moe_intermediate_size": cfg.d_ff, "n_routed_experts": cfg.n_experts,
        "n_shared_experts": cfg.n_shared_experts,
        "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "vocab_size": cfg.vocab, "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "type": "yarn", "factor": cfg.rope_factor,
            "original_max_position_embeddings": cfg.rope_original_max_len,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "mscale": cfg.rope_mscale,
            "mscale_all_dim": cfg.rope_mscale_all_dim},
        "rms_norm_eps": cfg.norm_eps, "torch_dtype": "float32",
    }


@pytest.fixture(scope="module")
def small():
    """The reduced configuration, its file, the reference's weights and
    the same weights in the program's layout (float32)."""
    cfg = get_config("deepseek-v2-lite").reduced()
    conf = _reduced_config_file(cfg)
    flat = mla_model.make_flat(conf, seed=11)
    return cfg, conf, flat, mla_model.to_program_layout(flat)


def test_reduced_keeps_the_structure(small):
    cfg, conf, _, _ = small
    assert cfg.lead_plan() == (("mla", "mlp"),)
    assert cfg.layer_plan() == (("mla", "moe"),)
    assert cfg.n_periods == 2 and cfg.n_layers == 3
    assert cfg.n_shared_experts == 2 and not cfg.norm_topk_prob
    assert cfg.kv_lora_rank and cfg.qk_rope_head_dim and cfg.v_head_dim
    # the benchmark's config file builds the same program configuration
    built = mla_model.program_config(conf)
    keys = ("n_layers", "first_k_dense", "d_model", "n_heads", "d_ff",
            "dense_d_ff", "vocab", "n_experts", "top_k", "n_shared_experts",
            "norm_topk_prob", "routed_scaling_factor", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "rope_factor", "rope_original_max_len",
            "rope_beta_fast", "rope_beta_slow", "rope_mscale",
            "rope_mscale_all_dim", "norm_eps")
    assert {k: getattr(built, k) for k in keys} \
        == {k: getattr(cfg, k) for k in keys}


@pytest.mark.parametrize("dtype,within", [(jnp.float32, True),
                                          (jnp.bfloat16, False)])
def test_prefill_then_latent_decode_equals_reference(small, dtype, within):
    """(a) The served path's prefill, then decode through the latent cache,
    against the reference's full forward pass, on logits. In bfloat16 the
    same program lies beyond the tolerance."""
    cfg, conf, flat, tree = small
    cfg = dataclasses.replace(cfg, dtype=dtype)
    params = jax.tree.map(lambda a: a.astype(dtype), tree)
    seq = np.random.default_rng(3).integers(0, cfg.vocab, 37).astype(np.int32)
    S = 30
    logits, caches, n, routing = prefill(params, jnp.asarray(seq[None, :S]),
                                         cfg, max_len=48, routing=True)
    got = [np.asarray(logits[0], np.float32)]
    for i in range(S, len(seq) - 1):
        logits, caches = decode_step(params, jnp.asarray(seq[i:i + 1]),
                                     caches, n, cfg)
        n = n + 1
        got.append(np.asarray(logits[0], np.float32))
    with jax.default_matmul_precision("highest"):
        ref = mla_reference.logits_at(
            flat, mla_model.Shapes.of(conf), [seq[:-1]],
            [np.arange(S - 1, len(seq) - 1)], pad_to=mla_reference.Q_BLOCK)[0]
    err = float(np.max(np.abs(np.stack(got) - ref)))
    assert (err <= ATOL) is within, err
    # every MoE layer counted each prompt token's top-k assignments
    assert routing.shape == (cfg.n_periods, cfg.n_experts)
    assert np.all(np.asarray(routing).sum(-1) == S * cfg.top_k)


def test_absorbed_decode_equals_decompressed_attention(small):
    """(b) The last position attended in the latent space (key half of
    ``w_kv_b`` absorbed into q, value half after the sum) equals the
    decompressed attention's output there."""
    cfg, _, _, tree = small
    p = tree["lead"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 21, cfg.d_model))
    full, fresh = mla_attention(p, x, cfg, return_cache=True)
    cache = init_mla_cache(cfg, 2, 32)
    cache = jax.tree.map(
        lambda c, f: jax.lax.dynamic_update_slice_in_dim(c, f[:, :20], 0, 1),
        cache, fresh)
    y, cache = mla_decode(p, x[:, 20:], cache, jnp.asarray(20), cfg)
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(full[:, 20]),
                               atol=1e-5)
    # the decode wrote the latent and the roped key its prefill would have
    np.testing.assert_allclose(np.asarray(cache["c"][:, 20]),
                               np.asarray(fresh["c"][:, 20]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(cache["k_rope"][:, 20]),
                               np.asarray(fresh["k_rope"][:, 20]), atol=1e-6)
    # the blocked prefill equals attention with every score at once
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(mla_attention(p, x, cfg)), atol=1e-5)


def _per_token_moe(p, x, cfg):
    """Every token through its own top-k experts, weights not
    renormalised, plus the shared experts: the published layer."""
    probs = jax.nn.softmax(x @ p["router"], -1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p * cfg.routed_scaling_factor
    silu = jax.nn.silu
    out = []
    for t in range(x.shape[0]):
        y = silu(x[t] @ p["ws_gate"]) * (x[t] @ p["ws_up"]) @ p["ws_down"]
        for k in range(cfg.top_k):
            e = int(top_i[t, k])
            h = silu(x[t] @ p["w_gate"][e]) * (x[t] @ p["w_up"][e])
            y = y + top_p[t, k] * (h @ p["w_down"][e])
        out.append(y)
    return jnp.stack(out)


@pytest.mark.parametrize("tokens", [1, 24])
@pytest.mark.parametrize("skewed", [False, True])
def test_dropless_moe_equals_per_token_routing(small, tokens, skewed):
    """(c) The serving MoE (grouped over sorted assignments, or the chosen
    experts gathered for one token) with its shared experts equals
    per-token routing, also when the router sends most tokens to one
    expert, where the training path's capacity drops tokens."""
    cfg, _, _, tree = small
    p = {k: v[0] for k, v in tree["blocks"][0].items()}
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, cfg.d_model))
    if skewed:
        p = dict(p, router=p["router"].at[:, 0].add(
            5.0 * jnp.sign(x.mean(0))))
    y, counts = moe_ffn(p, x[None], cfg, return_aux=True, dropless=True)
    ref = _per_token_moe(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(ref), atol=1e-5)
    assert int(counts.sum()) == tokens * cfg.top_k
    if skewed and tokens > 1:
        assert int(counts[0]) > 0.8 * tokens          # most go to expert 0
        capped = moe_ffn(p, x[None], cfg)              # the training path
        assert float(jnp.max(jnp.abs(capped[0] - ref))) > 1e-2


def _published_yarn(dim=64, base=10000.0, factor=40.0, orig=4096,
                    beta_fast=32, beta_slow=1):
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies, written out."""
    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp), (low, high)


def test_yarn_matches_published_formulas():
    """(d) At the published widths, without weights: the blended
    frequencies (correction dims 10 and 23 of 32) and the softmax scale
    192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2."""
    cfg = get_config("deepseek-v2-lite")
    want, (low, high) = _published_yarn()
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(yarn_inv_freq(cfg), want, rtol=1e-6)
    scale = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert softmax_scale(cfg) == pytest.approx(scale, rel=1e-12)
    assert softmax_scale(cfg) == pytest.approx(0.1147, abs=5e-5)
    assert cfg.param_count() == 15_706_373_632


@pytest.mark.parametrize("name", ["qwen3-4b", "qwen-7b-chat"])
def test_dense_configurations_untouched(name):
    """(e) No leading layer and no latent cache for the dense decoders,
    and their reduced prefill-then-decode still equals the full forward
    pass (the prefill's head now runs at the last position only)."""
    full = PAPER_MODELS[name]
    assert full.lead_plan() == () and full.layer_plan() == (("attn", "mlp"),)
    assert not full.uses_mla and full.first_k_dense == 0
    cfg = dataclasses.replace(full.reduced(), dtype=jnp.float32)
    caches = jax.eval_shape(lambda: init_caches(cfg, 1, 16))
    assert isinstance(caches, list) and set(caches[0]) == {"k", "v"}
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert "lead" not in params
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 19), 0, cfg.vocab)
    ref, _, _ = forward(params, toks, cfg, mode="train")
    logits, caches, n = prefill(params, toks[:, :16], cfg, max_len=20)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, 15]),
                               atol=2e-4)
    for i in range(16, 19):
        logits, caches = decode_step(params, toks[:, i], caches, n, cfg)
        n = n + 1
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref[:, i]), atol=3e-4)


def test_kv_bytes_per_token_counts_the_latent():
    """The stage of the benchmark (9 layers) holds 9 x (512 + 64) x 2 B;
    every other configuration keeps 2 x KV heads x head size per attention
    layer, as before."""
    conf = json.loads((ROOT / "bench" / "configs"
                       / "deepseek-v2-lite-l9.json").read_text())
    assert kv_bytes_per_token(mla_model.program_config(conf)) == 10_368
    assert kv_bytes_per_token(get_config("deepseek-v2-lite")) == 27 * 576 * 2
    for name, cfg in {**ARCHS, **PAPER_MODELS}.items():
        if cfg.uses_mla:
            continue
        attn = sum(m == "attn" for m, _ in cfg.layer_plan()) * cfg.n_periods
        assert kv_bytes_per_token(cfg) == 2 * cfg.n_kv_heads * cfg.hd \
            * attn * 2, name


def test_served_by_the_functional_server(tmp_path):
    """The reduced model through ``FunctionalServer`` (``jit_prefill``,
    ``jit_decode_step``, the scheduler and KV manager of every model):
    under a profiler session the prefill's routing and the KV manager's
    offloads are counted, with the latent's bytes per token."""
    from repro.obs import SPAN_METRICS

    cfg = get_config("deepseek-v2-lite").reduced()
    srv = FunctionalServer(cfg, max_running=2, device_budget_tokens=1024,
                           max_len=64, page_size=16)
    prompts = np.random.default_rng(5).integers(0, cfg.vocab, (2, 40))
    names = ("moe.assignments", "moe.assignments_max", "kv.offload_tokens",
             "kv.offload_bytes")
    total = lambda: {n: SPAN_METRICS.counter(n + ".calls").total()
                     for n in names}
    before = total()
    with jax.profiler.trace(str(tmp_path)):
        for p in prompts:
            srv.submit(p, max_new_tokens=4)
        done = srv.run_until_done()
    added = {n: total()[n] - before[n] for n in names}
    assert len(done) == 2 and all(len(r.generated) == 4 for r in done)
    assert added["moe.assignments"] == 2 * 40 * cfg.top_k * cfg.n_periods
    assert 40 * cfg.top_k / cfg.n_experts * 2 * cfg.n_periods \
        <= added["moe.assignments_max"] <= added["moe.assignments"]
    assert added["kv.offload_tokens"] == 2 * (40 + 3)
    assert added["kv.offload_bytes"] \
        == added["kv.offload_tokens"] * kv_bytes_per_token(cfg)


def test_count_adds_n_only_under_a_session(tmp_path):
    from repro.obs import SPAN_METRICS, count, recording

    name = "test_mla.count_probe"
    assert not recording()
    count(name, 7)
    assert name + ".calls" not in SPAN_METRICS
    with jax.profiler.trace(str(tmp_path)):
        assert recording()
        count(name, 7)
        count(name)
    assert SPAN_METRICS.counter(name + ".calls").total() == 8
