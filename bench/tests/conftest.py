"""CPU tests of the benchmark harness. Run them by path from the root of
the checkout:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They use four virtual CPU devices (for the relayed fetch) and tiny
configurations written next to copies of the real cells' files.
"""
import json
import os
import pathlib
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import pytest  # noqa: E402

TINY = {
    "name": "tiny", "source": "a two-layer decoder of the Qwen layout",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
    "vocab_size": 512, "tie_word_embeddings": False, "qkv_bias": True,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16",
    "reduced": [],
}
# (cell, traffic file, cell file, the real cell whose metrics it takes)
TINY_CELLS = {
    "tiny.switch": (
        {"driver": "switch", "items": 4, "suffix_tokens": {"values": [64]},
         "new_tokens": {"values": [4]}},
        {"max_running": 1, "max_len": 128,
         "limits": {"weights_wrong": 0, "served_logit_gap": 0.08}},
        "qwen3-4b.switch", 1),
    "tiny.docqa": (
        {"driver": "serve", "rate_per_s": 20,
         "prefix": {"tokens": {"grid": [32, 96, 32]},
                    "uses": {"values": [2, 3]},
                    "reuse_gap_s": {"exponential": 0.1}},
         "suffix_tokens": {"values": [32]},
         "new_tokens": {"lognormal": {"median": 4, "sigma": 0.5},
                        "min": 2, "max": 8}},
        {"max_running": 3, "max_len": 256, "check_tokens": 20,
         "limits": {"served_logit_gap": 0.08}},
        "qwen-7b-chat-l16.docqa", 1),
    "tiny.fetch.relay4": (
        {"driver": "fetch", "items": 8,
         "prefix": {"groups": 2, "document_tokens": 2048, "block_tokens": 256,
                    "tokens": {"lognormal": {"median": 512, "sigma": 0.6},
                               "min": 256, "max": 2048, "multiple": 256}}},
        {"trace_seconds": 1, "limits": {"fetches_wrong": 0}},
        "qwen-7b-chat-l16.fetch.relay4", 4),
}


def add_cell(root: pathlib.Path, name, traffic, settings, like, chips,
             config=TINY):
    """Add one cell to the benchmark at ``root`` by new files and new
    entries only: a configuration, a traffic mix, the cell's settings, and
    the cell in ``BENCHMARK.json``, reporting what the cell ``like`` does."""
    cfg_name, traffic_name = name.split(".", 1)
    bench = root / "bench"
    cfg_file = bench / "configs" / f"{config['name']}.json"
    if not cfg_file.exists():
        cfg_file.write_text(json.dumps(config))
    (bench / "traffic" / f"{traffic_name}.json").write_text(json.dumps(traffic))
    (bench / "cells" / f"{name}.json").write_text(json.dumps(settings))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    if not any(c["name"] == config["name"] for c in spec["configs"]):
        spec["configs"].append({"name": config["name"],
                                "source": config["source"],
                                "file": f"bench/configs/{config['name']}.json",
                                "reduced": [], "why": "CPU test"})
    spec["workloads"].append({"name": name, "config": cfg_name,
                              "traffic": traffic_name, "chips": chips,
                              "why": "CPU test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    """A copy of the benchmark with the tiny cells added by files alone."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, (traffic, settings, like, chips) in TINY_CELLS.items():
        add_cell(root, name, traffic, settings, like, chips)
    return root


@pytest.fixture(scope="session", autouse=True)
def compile_cache_off():
    """Keep these runs out of the checkout's compile cache."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
