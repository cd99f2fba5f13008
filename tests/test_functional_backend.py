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
