"""Serving substrate: KV accounting, prefix cache + offload round trips,
scheduler preemption, weight sleep/wake, latency-model bands vs paper."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import PAPER_MODELS, get_config
from repro.core import MMAConfig, make_functional_engine, make_sim_engine
from repro.models import init_params
from repro.serving import (
    FunctionalServer,
    KVCacheManager,
    LatencyModel,
    Request,
    Scheduler,
    WeightManager,
    kv_bytes_per_token,
)


def test_kv_bytes_per_token_qwen7b_matches_paper():
    """Paper §5.2.1: 64k-token Qwen-7B-Chat cache = 17.5 GB (fp8 KV)."""
    cfg = PAPER_MODELS["qwen-7b-chat"]
    gb = 65_536 * kv_bytes_per_token(cfg, dtype_size=1) / (1 << 30)
    assert 14 <= gb <= 19


def test_kv_manager_accounting_and_fetch():
    cfg = get_config("tinyllama-1.1b").reduced()
    eng, world, _ = make_sim_engine()
    kv = KVCacheManager(cfg, eng, device_budget_bytes=10 << 20,
                        page_size=16)
    toks = np.arange(64, dtype=np.int32)
    assert kv.can_admit(64)
    kv.admit(64)
    used = kv.device_bytes
    assert used == 64 * kv.bytes_per_token
    key, task = kv.offload(toks)
    world.run()
    assert kv.device_bytes == 0
    hit, task, _ = kv.fetch(toks)
    world.run()
    assert hit == 64
    assert kv.device_bytes == used
    # diverging tokens don't hit
    other = toks.copy()
    other[0] += 1
    hit2, _, _ = kv.fetch(other)
    assert hit2 == 0


def test_scheduler_preemption_and_resume():
    cfg = get_config("tinyllama-1.1b").reduced()
    eng, world, _ = make_sim_engine()
    budget_tokens = 100
    kv = KVCacheManager(
        cfg, eng, device_budget_bytes=budget_tokens * kv_bytes_per_token(cfg)
    )
    sched = Scheduler(kv, max_running=4)
    r1 = Request(tokens=np.arange(40), max_new_tokens=10)
    r2 = Request(tokens=np.arange(40), max_new_tokens=10)
    r3 = Request(tokens=np.arange(30), max_new_tokens=10)
    for r in (r1, r2, r3):
        sched.submit(r)
    admitted = sched.schedule()
    assert [r.req_id for r in admitted] == [r1.req_id, r2.req_id]  # budget
    # preempt frees budget for r3
    victim = sched.preempt_one()
    assert victim is r2
    admitted2 = sched.schedule()
    assert r3 in admitted2 or r2 in admitted2
    sched.finish(r1 if r1 in sched.running else sched.running[0])
    admitted3 = sched.schedule()
    assert sched.has_work()


def test_functional_server_prefix_hit_on_repeat():
    cfg = get_config("tinyllama-1.1b").reduced()
    srv = FunctionalServer(cfg, max_running=1, device_budget_tokens=2048,
                           max_len=128, page_size=16)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=64)
    r1 = srv.submit(prompt, max_new_tokens=3)
    srv.run_until_done()
    r2 = srv.submit(prompt, max_new_tokens=3)
    srv.run_until_done()
    assert r1.hit_tokens == 0
    assert r2.hit_tokens >= 48          # page-aligned prefix of 64
    # determinism: same prompt, same weights -> same generation
    assert r1.generated == r2.generated
    kinds = [k for k, _ in srv.transfer_log]
    assert "offload" in kinds and "fetch" in kinds


def test_weight_manager_sim_latencies_in_paper_band():
    """Qwen3-32B switching ~2.3-2.5x faster with MMA (paper Fig 13)."""
    cfg = PAPER_MODELS["qwen3-32b"]
    base = LatencyModel(cfg, use_mma=False).model_switch()
    mma = LatencyModel(cfg, use_mma=True).model_switch()
    for b, m in zip(base, mma):
        assert 2.0 < b / m < 2.7


def test_ttft_speedup_band_and_fetch_share():
    cfg = PAPER_MODELS["qwen-7b-chat"]
    tb = LatencyModel(cfg, use_mma=False).ttft(65_536)
    tm = LatencyModel(cfg, use_mma=True).ttft(65_536)
    assert 0.6 <= tb.fetch_fraction <= 0.75     # paper: up to 70%
    assert 1.9 <= tb.ttft_s / tm.ttft_s <= 2.5  # paper: 2.38x at 64k


def test_weight_manager_functional_roundtrip_exact():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    before = jax.tree.map(np.asarray, params)
    eng = make_functional_engine(
        config=MMAConfig(chunk_bytes=1 << 17, fallback_bytes=0)
    )

    class DeviceOnlyLeaf:
        """Knows its size; copying it to the host fails the test."""

        def __init__(self, leaf):
            self.nbytes = leaf.nbytes

        def __array__(self, *args, **kwargs):
            raise AssertionError("WeightManager copied a leaf D2H to size it")

    sized = WeightManager(eng, params=jax.tree.map(DeviceOnlyLeaf, params))
    assert sized.nbytes == sum(l.nbytes for l in jax.tree.leaves(before))
    wm = WeightManager(eng, params=params)
    wm.sleep()
    assert wm.params is None and wm.state == "asleep"
    with pytest.raises(AssertionError):
        wm.sleep()   # double sleep is a bug
    wm.wake()
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(wm.params)):
        assert np.array_equal(a, np.asarray(b))


def test_server_serves_woken_weights_without_compiling():
    """A sleep/wake of the served weights through the functional engine
    keeps the jitted model's executables: the woken weights match what the
    server held (same device, committed), generate the same tokens, and a
    sleep drops the server's reference first."""
    from repro.launch.compile_cache import CompileCounter

    cfg = get_config("tinyllama-1.1b").reduced()
    srv = FunctionalServer(cfg, max_running=1, device_budget_tokens=2048,
                           max_len=128, page_size=16)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab, size=32)
    first = srv.submit(prompt, max_new_tokens=3)
    srv.run_until_done()
    wm = WeightManager(
        make_functional_engine(
            config=MMAConfig(chunk_bytes=1 << 16, fallback_bytes=0)
        ),
        params=srv.release_params(),
    )
    assert srv.params is None
    with pytest.raises(RuntimeError, match="weights released"):
        srv.step()
    wm.sleep()
    wm.wake()
    srv.params = wm.params
    counter = CompileCounter()
    try:
        again = srv.submit(prompt, max_new_tokens=3)
        srv.run_until_done()
    finally:
        counter.close()
    assert again.generated == first.generated
    assert counter.built == 0


def test_model_switch_pair():
    cfg = get_config("tinyllama-1.1b").reduced()
    eng = make_functional_engine(
        config=MMAConfig(chunk_bytes=1 << 17, fallback_bytes=0)
    )
    a = WeightManager(eng, params=init_params(jax.random.PRNGKey(0), cfg))
    b = WeightManager(eng, params=init_params(jax.random.PRNGKey(1), cfg))
    b.sleep()
    rep_sleep, rep_wake = a.switch_to(b)
    assert a.state == "asleep" and b.state == "awake"
    assert rep_sleep.nbytes == a.nbytes and rep_wake.nbytes == b.nbytes
