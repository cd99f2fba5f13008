"""Functional (real-array) backend tests: data-plane bit-exactness,
relay coverage, and real-thread Dummy-Task synchronization (C2)."""
import threading
import time

import numpy as np
import pytest

import jax

from repro.core import (
    Direction,
    MMAConfig,
    ThreadStream,
    make_functional_engine,
    multipath_device_get,
    multipath_device_put,
)
from repro.core.jax_backend import ChunkAssembler, HostPayload


@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.int32, np.int8])
@pytest.mark.parametrize("shape", [(64,), (33, 7), (4, 5, 6), (1,)])
def test_h2d_bit_exact(dtype, shape):
    rng = np.random.default_rng(0)
    x = (rng.standard_normal(shape) * 10).astype(dtype)
    eng = make_functional_engine(config=MMAConfig(chunk_bytes=64, fallback_bytes=0))
    y = multipath_device_put(x, target=0, engine=eng)
    assert np.array_equal(np.asarray(y), x)
    assert np.asarray(y).dtype == dtype


@pytest.mark.parametrize("target", [0, 1])
def test_d2h_bit_exact(target):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((129, 65)).astype(np.float32)
    eng = make_functional_engine(config=MMAConfig(chunk_bytes=1024, fallback_bytes=0))
    devs = eng.backend.devices
    t = min(target, len(devs) - 1)
    jx = jax.device_put(x, devs[t])
    back = multipath_device_get(jx, target=t, engine=eng)
    assert np.array_equal(back, x)


def test_relay_paths_actually_used_and_exact():
    """Force relaying (no direct priority) and verify exactness through the
    two-hop host->relay->target path."""
    cfg = MMAConfig(chunk_bytes=256, fallback_bytes=0, direct_priority=False)
    eng = make_functional_engine(config=cfg)
    if len(eng.backend.devices) < 2:
        pytest.skip("needs >=2 devices")
    x = np.arange(10_000, dtype=np.float32)
    y = multipath_device_put(x, target=0, engine=eng)
    assert np.array_equal(np.asarray(y), x)
    relay_chunks = sum(w.chunks_relay for w in eng.workers.values())
    assert relay_chunks > 0, "expected relay traffic with direct_priority off"


def test_odd_sizes_and_chunk_alignment():
    """Chunk sizes that don't divide the payload must still reassemble."""
    for n in (1, 7, 1023, 4096, 10_001):
        x = np.random.default_rng(n).standard_normal(n).astype(np.float32)
        eng = make_functional_engine(
            config=MMAConfig(chunk_bytes=4096, fallback_bytes=0)
        )
        y = multipath_device_put(x, target=0, engine=eng)
        assert np.array_equal(np.asarray(y), x)


def test_finished_transfers_hold_no_device_memory():
    """Once a transfer has landed and its result is dropped, the engine
    holds none of its device arrays: a weight sleep must free the HBM.
    Sizes are odd so that no other live array matches them."""
    import gc

    eng = make_functional_engine(
        config=MMAConfig(chunk_bytes=4 * 1009, fallback_bytes=0)
    )

    def live(n_elems):
        gc.collect()
        return [a for a in jax.live_arrays() if a.size == n_elems]

    x = jax.numpy.arange(123 * 101, dtype=jax.numpy.float32).reshape(123, 101)
    host = multipath_device_get(x, engine=eng)        # D2H of a flat view
    del x
    assert not live(123 * 101)
    y = multipath_device_put(host, engine=eng)        # H2D in 1009-elem chunks
    assert np.array_equal(np.asarray(y), host)
    del y
    assert not live(123 * 101) and not live(1009)


def test_relay_forwarding_multi_device_subprocess():
    """Run the relay data-plane on 8 virtual devices in a subprocess (the
    device count must not leak into this process — see dryrun.py note)."""
    import subprocess
    import sys
    import os

    code = (
        "import numpy as np, jax\n"
        "from repro.core import make_functional_engine, multipath_device_put\n"
        "from repro.core.config import MMAConfig\n"
        "assert len(jax.devices()) == 8\n"
        "cfg = MMAConfig(chunk_bytes=4096, fallback_bytes=0, direct_priority=False)\n"
        "eng = make_functional_engine(config=cfg)\n"
        "x = np.arange(100_000, dtype=np.float32)\n"
        "y = multipath_device_put(x, target=3, engine=eng)\n"
        "assert np.array_equal(np.asarray(y), x)\n"
        "assert y.device == jax.devices()[3]\n"
        "relay = sum(w.chunks_relay for w in eng.workers.values())\n"
        "assert relay > 0, 'no relay traffic'\n"
        "print('RELAY_OK', relay)\n"
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    assert "RELAY_OK" in out.stdout


def test_every_relay_link_carries_chunks_default_config_subprocess():
    """Under the default config (direct priority on), every relay link of
    a 4-device host carries chunks in both directions, and the result
    lands on the target. Completions are held until the dispatch round
    has asked every link; delivered inline, the direct link would drain
    the whole payload before a relay had its turn."""
    import os
    import subprocess
    import sys

    code = (
        "import numpy as np, jax\n"
        "from repro.core import (MMAConfig, make_functional_engine,\n"
        "    multipath_device_get, multipath_device_put)\n"
        "assert len(jax.devices()) == 4\n"
        "cfg = lambda: MMAConfig(chunk_bytes=1 << 16, fallback_bytes=0)\n"
        "x = np.arange(1 << 20, dtype=np.float32)\n"
        "h2d = make_functional_engine(config=cfg())\n"
        "y = multipath_device_put(x, target=0, engine=h2d)\n"
        "assert y.devices() == {jax.devices()[0]}\n"
        "d2h = make_functional_engine(config=cfg())\n"
        "z = multipath_device_get(y, target=0, engine=d2h)\n"
        "assert np.array_equal(np.asarray(y), x) and np.array_equal(z, x)\n"
        "for eng in (h2d, d2h):\n"
        "    relay = [eng.workers[d].chunks_relay for d in (1, 2, 3)]\n"
        "    assert all(r > 0 for r in relay), relay\n"
        "    assert eng.workers[0].chunks_direct > 0\n"
        "print('ALL_LINKS_OK')\n"
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    assert "ALL_LINKS_OK" in out.stdout


# ---------------------------------------------------------------------------
# Real-thread C2 semantics
# ---------------------------------------------------------------------------
def test_thread_stream_blocks_until_engine_completion():
    """The Dummy Task must hold the stream until the engine confirms the
    distributed transfer landed — never earlier."""
    from repro.core.sync_engine import DummyTask
    from repro.core.transfer_task import TransferTask

    order = []
    task = TransferTask(nbytes=1, target=0, direction=Direction.H2D)
    dummy = DummyTask(task=task, on_activate=lambda t: order.append("activated"))

    stream = ThreadStream("s")
    stream.run(lambda: order.append("pre"))
    stream.dummy(dummy)
    stream.run(lambda: order.append("post"))

    # let the stream reach the dummy and block on it
    deadline = time.monotonic() + 5
    while "activated" not in order and time.monotonic() < deadline:
        time.sleep(0.01)
    assert order == ["pre", "activated"], "downstream ran before release!"

    dummy.complete()  # engine: all micro-tasks landed
    stream.synchronize()
    assert order == ["pre", "activated", "post"]
    stream.close()


def test_thread_stream_end_to_end_async_copy():
    """memcpy_async through a ThreadStream: downstream reads assembled data."""
    eng = make_functional_engine(
        config=MMAConfig(chunk_bytes=2048, fallback_bytes=0)
    )
    x = np.random.default_rng(2).standard_normal(5000).astype(np.float32)
    payload = HostPayload(flat=x.reshape(-1), shape=x.shape, dtype=x.dtype)
    assembler = ChunkAssembler(eng.config.n_chunks(x.nbytes), None)
    dummy = eng.memcpy_async(
        x.nbytes, device=0, direction=Direction.H2D, src=payload, dst=assembler
    )
    results = {}
    stream = ThreadStream("io")
    stream.dummy(dummy)
    stream.run(
        lambda: results.setdefault(
            "y", np.asarray(assembler.result(x.shape, x.dtype))
        )
    )
    stream.synchronize()
    assert np.array_equal(results["y"], x)
    stream.close()


# ---------------------------------------------------------------------------
# Host block cache: D2H payloads reuse the pages of an earlier sleep
# ---------------------------------------------------------------------------
def _params(scale, shapes):
    """Device weights of ``shapes`` (name -> shape), bf16 and f32, with
    values that differ by ``scale``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        dtype = jax.numpy.bfloat16 if i % 2 else jax.numpy.float32
        n = int(np.prod(shape))
        out[name] = (jax.numpy.arange(n, dtype=jax.numpy.float32) * scale
                     + i).astype(dtype).reshape(shape)
    return out


# Every leaf spans several 1 KiB chunks, so a woken leaf is a concatenation
# and never aliases its host block.
SHAPES_A = {"w": (40, 64), "u": (3, 700), "v": (9, 130)}
SHAPES_B = {"w": (50, 64), "x": (7, 300)}


def _engine():
    return make_functional_engine(
        config=MMAConfig(chunk_bytes=1024, fallback_bytes=0))


def _addrs(host):
    return sorted((a.nbytes, a.__array_interface__["data"][0])
                  for a in jax.tree.leaves(host))


def test_sleep_wake_sleep_reuses_the_host_blocks():
    from repro.serving import WeightManager

    eng = _engine()
    params = _params(1.0, SHAPES_A)
    want = {k: np.asarray(v) for k, v in params.items()}
    wm = WeightManager(eng, params=params)
    del params
    wm.sleep()
    first = _addrs(wm._host_copy)
    wm.wake()
    cache = eng.backend.host_blocks
    assert cache.lent_bytes == 0 and cache.free_bytes == wm.nbytes
    wm.sleep()
    assert _addrs(wm._host_copy) == first
    for k, v in want.items():
        assert wm._host_copy[k].dtype == v.dtype
        assert np.array_equal(wm._host_copy[k], v)
    wm.wake()
    for k, v in want.items():
        assert np.array_equal(np.asarray(wm.params[k]), v)


def test_slept_leaf_kept_past_the_wake_keeps_its_bytes():
    """A reshaped slice of a slept leaf and a ``device_put`` of it outlive
    the wake: their block is not reissued to the two sleeps that follow."""
    from repro.serving import WeightManager

    eng = _engine()
    wm = WeightManager(eng, params=_params(1.0, SHAPES_A))
    wm.sleep()
    leaf = wm._host_copy["u"]
    want = leaf.copy()
    kept = leaf.reshape(-1, 100)[2:5]
    on_dev = jax.device_put(leaf)
    del leaf
    wm.wake()
    for scale in (-3.0, 7.0):
        other = WeightManager(eng, params=_params(scale, SHAPES_A))
        other.sleep()
        other.wake()
    assert np.array_equal(kept, want.reshape(-1, 100)[2:5])
    assert np.array_equal(np.asarray(on_dev), want)


def test_two_models_alternating_reuse_their_own_blocks(monkeypatch):
    """A sleeps and B wakes, then B sleeps and A wakes, on one engine; the
    leaves of A and B differ in size. From the second cycle on every
    payload of every sleep is served from the cache."""
    from repro.core.jax_backend import HostBlockCache
    from repro.serving import WeightManager

    served = []
    take = HostBlockCache.take

    def recording_take(self, nbytes, dtype):
        out, reused = take(self, nbytes, dtype)
        served.append(reused)
        return out, reused

    monkeypatch.setattr(HostBlockCache, "take", recording_take)
    eng = _engine()
    a = WeightManager(eng, params=_params(1.0, SHAPES_A))
    b = WeightManager(eng, params=_params(2.0, SHAPES_B))
    want = {id(m): {k: np.asarray(v) for k, v in m.params.items()}
            for m in (a, b)}
    b.sleep()
    cycles = []
    for _ in range(3):
        del served[:]
        a.switch_to(b)
        b.switch_to(a)
        cycles.append(list(served))
    assert len(cycles[0]) == len(SHAPES_A) + len(SHAPES_B)
    assert all(all(c) for c in cycles[1:]), cycles
    for m in (a, b):
        if m.state == "asleep":
            m.wake()
        for k, v in want[id(m)].items():
            assert np.array_equal(np.asarray(m.params[k]), v)


def test_cache_keeps_no_more_free_bytes_than_it_ever_lent():
    from repro.core.jax_backend import HostBlockCache

    cache = HostBlockCache()
    rng = np.random.default_rng(3)
    held = []
    for _ in range(400):
        if held and rng.random() < 0.5:
            held.pop(int(rng.integers(len(held))))
        else:
            held.append(cache.take(int(rng.choice([64, 256, 4096, 12288])),
                                   np.dtype(np.float32))[0])
        assert cache.lent_bytes == sum(a.nbytes for a in held)
        assert cache.free_bytes <= cache.peak_lent_bytes
    del held[:]
    assert cache.lent_bytes == 0
    assert 0 < cache.free_bytes <= cache.peak_lent_bytes


def test_cache_under_threads_loses_no_block():
    """Workers on more threads than cores take blocks and drop ones that
    other threads took, so blocks come back on any thread: no update of
    the cache's counts or bins is lost."""
    import os
    import sys

    from repro.core.jax_backend import HostBlockCache

    cache = HostBlockCache()
    shared = []

    def work(seed):
        rng = np.random.default_rng(seed)
        for _ in range(300):
            if shared and rng.random() < 0.5:
                try:
                    shared.pop()
                except IndexError:
                    pass
            else:
                shared.append(cache.take(
                    int(rng.choice([64, 256, 4096])),
                    np.dtype(np.float32))[0])

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(2 * (os.cpu_count() or 4))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert cache.lent_bytes == sum(a.nbytes for a in shared)
    del shared[:]
    assert cache.lent_bytes == 0
    assert cache.free_bytes == sum(b.nbytes for bin_ in cache._free.values()
                                   for b in bin_)
    assert 0 < cache.free_bytes <= cache.peak_lent_bytes


def test_engine_none_keeps_nothing(monkeypatch):
    """``multipath_device_get`` without an engine builds one for the call:
    its cache goes with it, and the payload it lent stays valid."""
    import gc
    import weakref

    from repro.core import jax_backend

    caches = []
    make = jax_backend.make_functional_engine

    def recording_make(*args, **kwargs):
        eng = make(*args, **kwargs)
        caches.append(weakref.ref(eng.backend.host_blocks))
        return eng

    monkeypatch.setattr(jax_backend, "make_functional_engine", recording_make)
    x = jax.numpy.arange(3000, dtype=jax.numpy.float32).reshape(30, 100)
    back = multipath_device_get(x)
    gc.collect()
    assert len(caches) == 1 and caches[0]() is None
    assert np.array_equal(back, np.asarray(x))


def test_payload_counters_count_only_under_a_profiler_session(tmp_path):
    from repro.obs import SPAN_METRICS

    names = ("dataplane.d2h_payload.calls",
             "dataplane.d2h_payload_reused.calls")

    def counts():
        return [SPAN_METRICS.counter(n).total() if n in SPAN_METRICS else 0
                for n in names]

    eng = _engine()
    x = jax.numpy.ones((20, 64), jax.numpy.float32)
    before = counts()
    multipath_device_get(x, engine=eng)
    assert counts() == before
    with jax.profiler.trace(str(tmp_path)):
        multipath_device_get(x, engine=eng)     # the first one's block
        kept = multipath_device_get(x, engine=eng)
        multipath_device_get(x, engine=eng)     # a miss: ``kept`` holds it
    assert [c - b for c, b in zip(counts(), before)] == [3, 2]
    del kept


# ---------------------------------------------------------------------------
# D2H: a chunk's host copy starts when it is pulled and lands in ``settle``
# ---------------------------------------------------------------------------
def _d2h_events(monkeypatch):
    """Record ``("issue",)`` for every host copy started and
    ``("land", k)`` for the landing of the k-th one."""
    from repro.core.jax_backend import JaxBackend

    events = []
    array_type = type(jax.numpy.zeros(1))
    issue = array_type.copy_to_host_async
    land = JaxBackend._land

    def spy_issue(self):
        events.append(("issue",))
        return issue(self)

    def spy_land(self, piece, dst, issued, on_done):
        events.append(("land", issued))
        return land(self, piece, dst, issued, on_done)

    monkeypatch.setattr(array_type, "copy_to_host_async", spy_issue)
    monkeypatch.setattr(JaxBackend, "_land", spy_land)
    return events


def _no_backoff(**kw):
    """1 KiB chunks with the contention backoff off: these tests pin the
    data plane's order of issue and landing, and the backoff's verdict on
    chunks of microseconds on a shared CPU is noise (its own tests are in
    ``test_core_engine.py``)."""
    return MMAConfig(chunk_bytes=1024, fallback_bytes=0,
                     backoff_enabled=False, **kw)


def test_d2h_issues_the_next_copy_before_landing_a_chunk(monkeypatch):
    """One link at queue depth 2: every chunk but the last lands while the
    copy of the chunk after it is already in flight, and chunks land in
    the order they were issued."""
    eng = make_functional_engine(
        devices=jax.devices()[:1], config=_no_backoff(queue_depth=2))
    x = jax.numpy.arange(40 * 256, dtype=jax.numpy.float32)   # 40 chunks
    events = _d2h_events(monkeypatch)
    back = multipath_device_get(x, engine=eng)
    assert np.array_equal(back, np.asarray(x))
    landed = [e[1] for e in events if e[0] == "land"]
    assert landed == list(range(1, 41))
    for i, e in enumerate(events):
        if e[0] == "land" and e[1] < 40:
            issued_before = sum(1 for f in events[:i] if f[0] == "issue")
            assert issued_before >= e[1] + 1, events[:i + 1]


@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.int32, np.int8])
@pytest.mark.parametrize("n", [1, 7, 1023, 4096, 10_001])
def test_d2h_bit_exact_dtypes_and_odd_sizes(dtype, n):
    """Chunk sizes that don't divide the payload, every dtype: the landed
    payload is the device array, bit for bit."""
    rng = np.random.default_rng(n)
    x = (rng.standard_normal(n) * 100).astype(dtype)
    eng = make_functional_engine(
        config=MMAConfig(chunk_bytes=1000, fallback_bytes=0))
    back = multipath_device_get(jax.device_put(x), engine=eng)
    assert back.dtype == dtype and np.array_equal(back, x)


def test_d2h_returns_only_after_every_chunk_is_stored(monkeypatch):
    """``multipath_device_get`` returns a payload whose every chunk has
    landed: no landing is left held, and the bytes are all there."""
    eng = make_functional_engine(
        config=MMAConfig(chunk_bytes=4096, fallback_bytes=0))
    x = jax.numpy.arange(3 * 5000, dtype=jax.numpy.int32).reshape(3, 5000)
    events = _d2h_events(monkeypatch)
    back = multipath_device_get(x, engine=eng)
    n_chunks = eng.config.n_chunks(x.nbytes)
    assert sum(e[0] == "land" for e in events) == n_chunks
    assert sum(e[0] == "issue" for e in events) == n_chunks
    assert not eng.backend._done
    assert np.array_equal(back, np.asarray(x))


def test_d2h_overlap_counter_and_reuse_under_a_profiler_session(tmp_path):
    """Under a profiler session the second sleep of one engine is served
    from the host block cache, and every chunk of a leaf but its last is
    counted as waited on while a later copy was in flight."""
    from repro.obs import SPAN_METRICS
    from repro.serving import WeightManager

    names = ("dataplane.d2h_overlapped.calls", "dataplane.d2h_chunk.calls",
             "dataplane.d2h_issue.calls", "dataplane.d2h_payload.calls",
             "dataplane.d2h_payload_reused.calls")

    def counts():
        return {n: SPAN_METRICS.counter(n).total() if n in SPAN_METRICS
                else 0 for n in names}

    eng = make_functional_engine(devices=jax.devices()[:1],
                                 config=_no_backoff())
    wm = WeightManager(eng, params=_params(1.0, SHAPES_A))
    wm.sleep()
    wm.wake()
    before = counts()
    with jax.profiler.trace(str(tmp_path)):
        wm.sleep()
    got = {n: counts()[n] - before[n] for n in names}
    chunks = sum(eng.config.n_chunks(int(np.prod(s)) * (2 if i % 2 else 4))
                 for i, s in enumerate(v for _, v in sorted(SHAPES_A.items())))
    assert got["dataplane.d2h_chunk.calls"] == chunks
    assert got["dataplane.d2h_issue.calls"] == chunks
    assert got["dataplane.d2h_overlapped.calls"] == chunks - len(SHAPES_A)
    assert got["dataplane.d2h_payload.calls"] == len(SHAPES_A)
    assert got["dataplane.d2h_payload_reused.calls"] == len(SHAPES_A)
    wm.wake()


def test_d2h_relay_routes_bit_exact_subprocess():
    """Relayed D2H chunks (direct priority off, 4 devices) take the same
    issue and landing: every dtype and an odd size come back exact, and
    up to two copies per link are in flight at once."""
    import os
    import subprocess
    import sys

    code = (
        "import numpy as np, jax\n"
        "from repro.core import MMAConfig, make_functional_engine, multipath_device_get\n"
        "from repro.core.jax_backend import JaxBackend\n"
        "assert len(jax.devices()) == 4\n"
        "inflight = []\n"
        "land = JaxBackend._land\n"
        "def spy(self, *a):\n"
        "    inflight.append(self._d2h_issued - a[2] + 1)\n"
        "    return land(self, *a)\n"
        "JaxBackend._land = spy\n"
        "for dt in (np.float32, np.float16, np.int32, np.int8):\n"
        "    x = (np.random.default_rng(5).standard_normal(10_001) * 100).astype(dt)\n"
        "    eng = make_functional_engine(config=MMAConfig(\n"
        "        chunk_bytes=1000, fallback_bytes=0, direct_priority=False))\n"
        "    back = multipath_device_get(jax.device_put(x, jax.devices()[2]),\n"
        "                                target=2, engine=eng)\n"
        "    assert back.dtype == dt and np.array_equal(back, x), dt\n"
        "    assert sum(w.chunks_relay for w in eng.workers.values()) > 0\n"
        "assert 1 < max(inflight) <= 8, max(inflight)\n"
        "print('D2H_RELAY_OK')\n"
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    assert "D2H_RELAY_OK" in out.stdout
