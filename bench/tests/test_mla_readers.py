"""The latent-attention MoE cell's readers: a known answer on a hand-filled
registry or trace, and nothing on an empty one."""
import json
import types

import pytest

from conftest import BENCH
from harness import mla_flops, mla_model, spec
from harness.trace import Trace

CONFIG = json.loads((BENCH / "configs" / "deepseek-v2-lite-l9.json").read_text())
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PLANE = "/device:TPU:0"


def _read(name, run):
    return spec.metric_reader(spec.BENCH, name)(run)


def _registry(**calls):
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    for name, n in calls.items():
        reg.counter(name.replace("__", ".") + ".calls").inc(n)
    return reg


def _run(trace=None, records=None):
    cell = types.SimpleNamespace(config=CONFIG)
    return types.SimpleNamespace(cell=cell, trace=trace, peaks=PEAKS,
                                 records=records or {})


def test_program_counters(monkeypatch):
    import repro.obs

    monkeypatch.setattr(repro.obs, "SPAN_METRICS", _registry(**{
        "kv__offload_tokens": 300, "kv__offload_bytes": 300 * 10_368,
        "moe__assignments": 8 * 6 * 1000, "moe__assignments_max": 8 * 150}))
    assert _read("kv.bytes_per_token", _run()) == 10_368
    # the busiest expert of each layer took 150 of 6,000 assignments, 1.6x
    # the mean of 6,000 / 64
    assert _read("moe.expert_load_peak", _run()) == pytest.approx(1.6)


def test_program_counters_absent(monkeypatch):
    import repro.obs

    monkeypatch.setattr(repro.obs, "SPAN_METRICS", _registry())
    for name in ("kv.bytes_per_token", "moe.expert_load_peak"):
        assert _read(name, _run()) is None
    monkeypatch.delattr(repro.obs, "SPAN_METRICS")
    for name in ("kv.bytes_per_token", "moe.expert_load_peak"):
        assert _read(name, _run()) is None


def _trace():
    """A 10 s window: two prefills of 0.5 s and three decode steps of 5 ms,
    busy 1.015 s."""
    s = 1e9
    runs = [("jit_prefill", 1 * s, 1.5 * s), ("jit_prefill", 3 * s, 3.5 * s),
            ("jit_decode_step", 4 * s, 4.005 * s),
            ("jit_decode_step", 5 * s, 5.005 * s),
            ("jit_decode_step", 6 * s, 6.005 * s)]
    return Trace(ops={PLANE: [("fusion", a, b) for _, a, b in runs]},
                 modules={PLANE: runs}, host=[("window", 0.0, 10 * s)])


def test_device_trace_readers():
    shapes = mla_model.Shapes.of(CONFIG)
    records = {
        "prefill_tokens": [8448, 4352],
        "requests": [{"prompt": [0] * 8448, "generated": [1, 2, 3]},
                     {"prompt": [0] * 4352, "generated": [1, 2]}],
    }
    run = _run(_trace(), records)
    flops = mla_flops.prefill_flops(shapes, 8448) \
        + mla_flops.prefill_flops(shapes, 4352)
    assert _read("moe.prefill_mfu", run) == pytest.approx(
        100 * flops / (1.0 * 197e12))
    need = sum(mla_flops.decode_bytes(shapes, c) for c in (8449, 8450, 4353))
    assert _read("moe.decode_hbm_share", run) == pytest.approx(
        100 * need / (0.015 * 819e9))
    assert _read("device.idle_share.longdoc", run) == pytest.approx(
        100 * (1 - 1.015 / 10))


def test_device_trace_readers_without_a_trace():
    for name in ("moe.prefill_mfu", "moe.decode_hbm_share",
                 "device.idle_share.longdoc"):
        assert _read(name, _run(None, {"prefill_tokens": [1],
                                       "requests": []})) is None
