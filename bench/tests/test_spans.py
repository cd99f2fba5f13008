"""The readers of the program's span counters: known answers on a
hand-filled registry, nothing on an empty one, and in a traced run of a
tiny cell the window's calls and nothing of set-up or the check."""
import jax
import numpy as np
import pytest

from harness import runner, spec

SEED = 2 ** 31 + 13


def _registry(**seconds_and_calls):
    """A registry with ``<span>.seconds`` and ``<span>.calls`` filled from
    ``span=(seconds, calls)``; dots in the span name are given as ``__``."""
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    for key, (secs, n) in seconds_and_calls.items():
        name = key.replace("__", ".")
        reg.counter(name + ".seconds").inc(secs)
        reg.counter(name + ".calls").inc(n)
    return reg


FILLED = dict(
    engine__memcpy=(10.0, 300), dataplane__h2d_chunk=(1.0, 900),
    dataplane__d2h_chunk=(8.0, 1000), dataplane__d2h_wait=(6.0, 1000),
    dataplane__d2h_store=(1.5, 1000),
)

# (metric, its value on FILLED)
EXPECTED = [
    ("dataplane.d2h_chunk_ms", 1e3 * 8.0 / 1000),
    ("dataplane.d2h_wait_share", 100.0 * 6.0 / 8.0),
    ("engine.self_share", 100.0 * (10.0 - 1.0 - 8.0) / 10.0),
    ("dataplane.d2h_store_share", 100.0 * 1.5 / 8.0),
]


@pytest.mark.parametrize("metric,want", EXPECTED)
def test_reader_on_filled_registry(metric, want, monkeypatch):
    import repro.obs

    monkeypatch.setattr(repro.obs, "SPAN_METRICS", _registry(**FILLED))
    read = spec.metric_reader(spec.BENCH, metric)
    assert read(None) == pytest.approx(want)


@pytest.mark.parametrize("metric", [m for m, _ in EXPECTED])
def test_reader_on_empty_registry(metric, monkeypatch):
    import repro.obs

    monkeypatch.setattr(repro.obs, "SPAN_METRICS", _registry())
    assert spec.metric_reader(spec.BENCH, metric)(None) is None
    # a program older than its spans has no registry at all
    monkeypatch.delattr(repro.obs, "SPAN_METRICS")
    assert spec.metric_reader(spec.BENCH, metric)(None) is None


def _calls():
    from repro.obs import SPAN_METRICS

    return {k: v for k, v in SPAN_METRICS.as_dict().items()
            if k.endswith(".calls")}


def _traced(root, cell):
    """A traced run of ``cell`` and the span calls it added."""
    before = _calls()
    result, _ = runner.execute(root, cell, SEED, 1.0, True,
                               require_chip=False)
    after = _calls()
    return result, {k: v - before.get(k, 0) for k, v in after.items()}


def test_switch_counts_the_window_only(tiny_root):
    """D2H chunks: the window's switches times the chunks of one sleep; the
    untimed warm-up switch and the check's weights add none."""
    from repro.core import MMAConfig
    from harness import model

    cell = spec.load_cell("tiny.switch", tiny_root)
    result, calls = _traced(tiny_root, "tiny.switch")
    assert result["correct"]
    leaves = jax.tree.leaves(model.make_weights(cell.config, SEED))
    per_sleep = sum(MMAConfig().n_chunks(l.nbytes) for l in leaves)
    assert calls["dataplane.d2h_chunk.calls"] \
        == result["attempted"] * per_sleep
    assert calls["dataplane.d2h_wait.calls"] \
        == calls["dataplane.d2h_store.calls"] \
        == calls["dataplane.d2h_chunk.calls"]
    for m in ("dataplane.d2h_chunk_ms", "dataplane.d2h_wait_share",
              "dataplane.d2h_store_share", "engine.self_share"):
        v = result["metrics"][m]["value"]
        assert np.isfinite(v) and v > 0, m


def test_docqa_opens_no_program_span(tiny_root):
    """The served path's KV store copies through a simulated engine, which
    opens no ``engine.memcpy``: a traced docqa run counts no program span
    and reports none of the data plane's metrics."""
    result, calls = _traced(tiny_root, "tiny.docqa")
    assert result["correct"]
    assert result["attempted"] > 0
    assert not any(calls.values()), calls
    assert not any(m.startswith(("dataplane.", "engine."))
                   for m in result["metrics"])
