"""Program spans (``repro.obs.span``): a shared no-op and no counts while
no profiler session runs; under ``jax.profiler.trace`` every span of the
transfer engine and the data plane is a host event on the profiler's
trace, nested in its caller, once per counted call."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core import (
    MMAConfig,
    make_functional_engine,
    multipath_device_get,
    multipath_device_put,
)
from repro.obs import SPAN_METRICS, span, spans
from repro.serving import FunctionalServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every program span and the span it runs inside (None: the caller's).
SPANS = {
    "engine.memcpy": None,
    "dataplane.h2d_chunk": "engine.memcpy",
    "dataplane.d2h_chunk": "engine.memcpy",
    "dataplane.d2h_wait": "dataplane.d2h_chunk",
    "dataplane.d2h_store": "dataplane.d2h_chunk",
}


def _serve_and_round_trip(engine_config):
    """One reduced request served to its end, the same prompt again (a
    prefix hit), and a multipath put/get round trip."""
    cfg = get_config("tinyllama-1.1b").reduced()
    srv = FunctionalServer(cfg, max_running=1, device_budget_tokens=2048,
                           max_len=128, page_size=16)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab, size=48)
    for _ in range(2):
        srv.submit(prompt, max_new_tokens=3)
        srv.run_until_done()
    x = np.arange(10_000, dtype=np.float32)
    eng = make_functional_engine(config=engine_config)
    y = multipath_device_put(x, target=0, engine=eng)
    assert np.array_equal(multipath_device_get(y, target=0, engine=eng), x)
    return srv, eng


def test_no_session_returns_one_shared_noop():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    a, b = span("engine.memcpy"), span("dataplane.d2h_chunk", req=3)
    assert a is b is spans._NULL
    with a:
        pass


def test_no_session_counts_nothing():
    """A whole request and a round trip, no profiler: ``SPAN_METRICS``
    gains no counter."""
    before = SPAN_METRICS.as_dict()
    srv, _ = _serve_and_round_trip(
        MMAConfig(chunk_bytes=4096, fallback_bytes=0))
    assert len(srv.scheduler.done) == 2
    assert SPAN_METRICS.as_dict() == before


def test_unknown_profiler_state_is_off(monkeypatch):
    """Without the profiler's private check, spans never record."""
    monkeypatch.setattr(spans, "_recording", spans._resolve)
    monkeypatch.setitem(sys.modules, "jax._src.lib", None)
    assert span("x") is spans._NULL
    assert spans._recording is spans._off


def test_session_counts_calls_and_seconds(tmp_path):
    name = "test_spans.session_probe"
    with jax.profiler.trace(str(tmp_path)):
        for i in range(3):
            with span(name, req=i):
                time.sleep(0.002)
    assert span(name) is spans._NULL          # the session has ended
    assert SPAN_METRICS.counter(name + ".calls").total() == 3
    assert 0.006 <= SPAN_METRICS.counter(name + ".seconds").total() < 1.0


def test_simulated_memcpy_is_not_counted(tmp_path):
    """The simulated engine's transfers (the served path's KV store) are
    not the data plane's: under a session they add no ``engine.memcpy``."""
    from repro.core import Direction, make_sim_engine

    eng, world, _ = make_sim_engine()
    before = SPAN_METRICS.as_dict()
    with jax.profiler.trace(str(tmp_path)):
        task = eng.memcpy(1 << 20, 0, Direction.H2D)
        world.run()
    assert task.complete_time is not None
    assert SPAN_METRICS.as_dict() == before


# Run under the profiler on four virtual CPU devices, with relayed chunks,
# in a child process (the device count is fixed at JAX's start).
_TRACED = r"""
import glob, json, sys
import jax, numpy as np
sys.path.insert(0, "tests")
from test_spans import SPANS, _serve_and_round_trip
from repro.core import MMAConfig
from repro.obs import SPAN_METRICS
assert len(jax.devices()) == 4
log_dir = sys.argv[1]
cfg = MMAConfig(chunk_bytes=4096, fallback_bytes=0, direct_priority=False)
_serve_and_round_trip(cfg)                      # compiles, not traced
assert not SPAN_METRICS.names()
with jax.profiler.trace(log_dir):
    with jax.profiler.TraceAnnotation("outer"):
        _, eng = _serve_and_round_trip(cfg)
events, planes = {}, set()
for path in glob.glob(log_dir + "/**/*.xplane.pb", recursive=True):
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS or e.name == "outer":
                    planes.add((e.name, plane.name))
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
calls = {n: SPAN_METRICS.counter(n + ".calls").total() for n in SPANS}
relayed = sum(w.chunks_relay for w in eng.workers.values())
print(json.dumps({"events": events, "calls": calls, "relayed": relayed,
                  "planes": sorted(planes)}))
"""


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _TRACED, str(tmp_path_factory.mktemp("xp"))],
        env=env, capture_output=True, text=True, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_traced_round_trip_relays(traced):
    assert traced["relayed"] > 0


def _inside(inner, outers):
    s, e = inner
    return any(a <= s and e <= b for a, b in outers)


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_traced_nested_and_counted(traced, name):
    events = traced["events"].get(name, [])
    assert events, f"{name} is not in the trace"
    assert {p for n, p in traced["planes"] if n == name} \
        and all(p.startswith("/host:")
                for n, p in traced["planes"] if n == name)
    assert len(events) == traced["calls"][name]
    outer = traced["events"]["outer"]
    assert all(_inside(ev, outer) for ev in events)
    parent = SPANS[name]
    if parent is not None:
        assert all(_inside(ev, traced["events"][parent]) for ev in events)
