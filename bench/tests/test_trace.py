"""The trace reductions give known answers on small traces."""
import pathlib

import numpy as np
import pytest

from harness import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


@pytest.fixture
def small():
    return tr.Trace(
        ops={DEV: [("a", 10, 30), ("b", 30, 40), ("while", 59, 71),
                   ("c", 60, 70), ("a", 98, 110)],
             "/device:TPU:1": [("d", 0, 50)]},
        modules={DEV: [("jit_prefill", 10, 40), ("jit_decode_step", 60, 70),
                       ("jit_decode_step", 98, 110)]},
        host=[("window", 0, 100), ("prefill", 5, 45), ("decode", 55, 75),
              ("wait", 80, 100)])


def test_union():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_busy_and_idle(small):
    # [10, 40], [59, 71] (a while around c) and [98, 100] in the window
    assert tr.busy_ns(small, DEV) == 44
    assert tr.idle_share(small, DEV) == pytest.approx(0.56)
    assert tr.busy_ns(small, "/device:TPU:1") == 50
    assert tr.devices(small, 1) == [DEV]


def test_module_runs(small):
    assert tr.module_runs(small, DEV, "prefill") == [30]
    assert tr.module_runs(small, DEV, "decode_step") == [10]  # one ends late


def test_top_ops(small):
    assert tr.top_ops(small, DEV) == [
        ["jit_prefill/a", 20e-9], ["jit_prefill/b", 10e-9],
        ["jit_decode_step/c", 10e-9], ["jit_decode_step/a", 2e-9]]


def test_idle_gaps_by_host_span(small):
    # gaps [0,10] [40,59] [71,98]: none 5+10+5, prefill 5+5, decode 4+4,
    # wait 18
    got = dict((k, round(v * 1e9, 6)) for k, v in tr.idle_gaps(small, DEV))
    assert got == {"none": 20, "wait": 18, "prefill": 10, "decode": 8}


def _sampled_busy(trace, dev):
    """Busy time counted on a grid of 10 ns steps, op by op."""
    lo, hi = trace.window()
    ts = np.arange(lo, hi, 10.0)
    depth = np.zeros(len(ts) + 1, int)
    for _, s, e in trace.ops[dev]:
        depth[np.searchsorted(ts, s)] += 1
        depth[np.searchsorted(ts, e)] -= 1
    return 10.0 * np.count_nonzero(np.cumsum(depth)[:-1])


def test_recorded_trace():
    """A slice of a traced docqa window on a TPU v5e: the union agrees with
    a brute-force count, and the module durations lie inside the ops."""
    t = tr.read(str(DATA / "docqa_trace_slice.json"))
    dev = tr.devices(t, 1)[0]
    lo, hi = t.window()
    assert tr.busy_ns(t, dev) == pytest.approx(_sampled_busy(t, dev),
                                               rel=0.02, abs=(hi - lo) / 500)
    assert 0.0 < tr.idle_share(t, dev) < 1.0
    gaps = tr.idle_gaps(t, dev, n=100)
    assert sum(v for _, v in gaps) * 1e9 == pytest.approx(
        (hi - lo) - tr.busy_ns(t, dev), rel=1e-9)
    assert tr.module_runs(t, dev, "decode_step")
