"""The latent-attention MoE cell's check on a tiny cell of the same layout:
it passes on the program, fails with the float8 control in the program's
place and with one flipped bit in one routed expert's weights, and a
program without latent attention fails at set-up before any weight is
made. Also the family's shapes, operations and bytes against counts made
by hand."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, add_cell
from harness import mla_flops, mla_model, mla_reference, runner

SEED = 2 ** 31 + 41
CELL = "tinymla.longdoc"

TINY_MLA = {
    "name": "tinymla", "source": "a three-layer decoder of the DeepSeek-V2 layout",
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "n_shared_experts": 2, "num_experts_per_tok": 3,
    "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "moe_layer_freq": 1, "scoring_func": "softmax", "topk_method": "greedy",
    "vocab_size": 512, "tie_word_embeddings": False, "rope_theta": 10000.0,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096},
    "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16", "reduced": [],
}
TINY_LONGDOC = (
    {"driver": "serve_mla", "rate_per_s": 20,
     "prefix": {"tokens": {"grid": [64, 128, 64]},
                "uses": {"values": [2, 3]},
                "reuse_gap_s": {"exponential": 0.1}},
     "suffix_tokens": {"values": [32]},
     "new_tokens": {"lognormal": {"median": 4, "sigma": 0.5},
                    "min": 2, "max": 8}},
    {"max_running": 3, "max_len": 256, "check_tokens": 20,
     "limits": {"served_logit_gap": 0.1}},
    "deepseek-v2-lite-l9.longdoc", 1)


@pytest.fixture(scope="module")
def root(tiny_root):
    if not (tiny_root / "bench" / "cells" / f"{CELL}.json").exists():
        add_cell(tiny_root, CELL, *TINY_LONGDOC, config=TINY_MLA)
    return tiny_root


def _run(root, control=False, traced=False):
    return runner.execute(root, CELL, SEED, 1.0, traced, require_chip=False,
                          control=control)[0]


def test_program_passes_and_control_fails(root):
    """The program's served tokens are within the limit; the float8
    reference in its place is not."""
    result = _run(root, control=True)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 4
    gap = result["checks"]["served_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert result["control"]["served_logit_gap"] > gap["limit"]
    assert result["control_correct"] is False


def test_flipped_bit_in_one_routed_expert(root, monkeypatch):
    """One bit of one weight of one routed expert, flipped in the served
    weights alone (the reference makes them again): not correct."""
    from repro.serving import FunctionalServer

    init = FunctionalServer.__init__

    def flipped(self, cfg, params=None, **kw):
        w = params["blocks"][0]["w_down"]
        bits = jax.lax.bitcast_convert_type(w, jnp.uint16)
        # the top exponent bit of expert 0's first weight in every MoE layer
        bits = bits.at[:, 0, 0, 0].set(bits[:, 0, 0, 0] ^ np.uint16(0x4000))
        params["blocks"][0]["w_down"] = jax.lax.bitcast_convert_type(
            bits, w.dtype)
        init(self, cfg, params=params, **kw)

    monkeypatch.setattr(FunctionalServer, "__init__", flipped)
    assert not _run(root)["correct"]


def test_traced_run_reads_the_program_counters(root, monkeypatch):
    """A traced run's readers see its own window's counts (the registry is
    the process's, so earlier traced runs of this process are set aside)."""
    import repro.obs
    from repro.obs import MetricsRegistry, spans

    fresh = MetricsRegistry()
    monkeypatch.setattr(spans, "SPAN_METRICS", fresh)
    monkeypatch.setattr(repro.obs, "SPAN_METRICS", fresh)
    result = _run(root, traced=True)
    assert result["correct"]
    m = result["metrics"]
    s = mla_model.Shapes.of(TINY_MLA)
    assert m["kv.bytes_per_token"]["value"] == s.kv_bytes_per_token() \
        == 3 * (32 + 8) * 2
    peak = m["moe.expert_load_peak"]["value"]
    assert 1.0 <= peak <= s.n_experts / s.top_k


def test_program_without_latent_attention_fails_at_once(root, monkeypatch):
    """A ``ModelConfig`` that has no latent-attention fields (an older
    program) refuses the configuration before any weight is made."""
    import repro.configs.base as base

    fields = {f.name for f in dataclasses.fields(base.ModelConfig)}
    older = dataclasses.make_dataclass(
        "ModelConfig", [(f.name, f.type, dataclasses.field(default=f.default))
                        for f in dataclasses.fields(base.ModelConfig)
                        if f.name in fields - {"kv_lora_rank",
                                               "first_k_dense"}],
        frozen=True)
    made = []
    monkeypatch.setattr(base, "ModelConfig", older)
    monkeypatch.setattr(mla_model, "make_flat",
                        lambda *a, **k: made.append(1))
    with pytest.raises(TypeError):
        _run(root)
    assert not made


def _shapes(name):
    return mla_model.Shapes.of(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


def test_deepseek_v2_lite_l9_counts():
    s = _shapes("deepseek-v2-lite-l9")
    # MLA: q 2048x3072, kv_a 2048x576, kv_b 512x4096, o 2048x2048
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    expert = 3 * 2048 * 1408
    params = (2 * 102400 * 2048 + 2048
              + attn + 2 * 2048 + 512 + 3 * 2048 * 10944
              + 8 * (attn + 2 * 2048 + 512 + 2048 * 64 + 64 * expert
                     + 2 * expert))
    assert params == 5_179_222_528
    assert s.weight_bytes() == 2 * params == 10_358_445_056
    assert s.kv_bytes_per_token() == 10_368
    per_token = (attn + 3 * 2048 * 10944) + 8 * (attn + 2048 * 64
                                                 + 8 * expert)
    assert mla_flops.token_matmul_params(s) == per_token
    S = 8192
    want = (2 * S * per_token + 2 * 9 * 16 * 320 * S * (S + 1) // 2
            + 2 * 2048 * 102400)
    assert mla_flops.prefill_flops(s, S) == want
    # a decode step reads one embedding row and 6 + 2 shared experts a layer
    read = 2 * (params - 102399 * 2048 - 8 * 58 * expert)
    assert mla_flops.decode_weight_bytes(s) == read
    assert 1.90e9 < read < 1.92e9
    assert mla_flops.decode_bytes(s, 100) == read + 100 * 10_368


def test_reference_yarn_matches_published_values():
    """At the published widths: the softmax scale 192^-0.5 * (0.1 * 0.707
    * ln 40 + 1)^2, and frequencies that keep the trained ones in the
    fastest dims and divide by 40 in the slowest."""
    s = _shapes("deepseek-v2-lite-l9")
    assert mla_reference.softmax_scale(s) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
    assert mla_reference.softmax_scale(s) == pytest.approx(0.1147, abs=1e-4)
    inv = mla_reference.yarn_inv_freq(s)
    trained = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:10], trained[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[-8:], trained[-8:] / 40, rtol=1e-6)
