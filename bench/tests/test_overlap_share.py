"""The reader of the D2H overlap counter, ``dataplane.d2h_overlap_share``:
a known answer on a hand-filled registry, nothing without calls, without
the counter (a program that starts no copy ahead of its wait) or without a
registry, and in a traced run of the tiny switch the count of every chunk
but each leaf's last."""
import jax
import pytest

from harness import runner, spec

SEED = 2 ** 31 + 31
METRIC = "dataplane.d2h_overlap_share"


def _registry(chunks, overlapped):
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("dataplane.d2h_chunk.calls").inc(chunks)
    if overlapped is not None:
        reg.counter("dataplane.d2h_overlapped.calls").inc(overlapped)
    return reg


@pytest.mark.parametrize("chunks,overlapped,want", [
    (40, 30, 75.0), (1538, 1538, 100.0), (9, 0, 0.0)])
def test_reader_on_filled_registry(chunks, overlapped, want, monkeypatch):
    import repro.obs

    monkeypatch.setattr(repro.obs, "SPAN_METRICS",
                        _registry(chunks, overlapped))
    assert spec.metric_reader(spec.BENCH, METRIC)(None) == pytest.approx(want)


@pytest.mark.parametrize("chunks,overlapped", [(0, 0), (0, None), (12, None)])
def test_reader_without_calls_or_counter(chunks, overlapped, monkeypatch):
    import repro.obs

    monkeypatch.setattr(repro.obs, "SPAN_METRICS",
                        _registry(chunks, overlapped))
    assert spec.metric_reader(spec.BENCH, METRIC)(None) is None


def test_reader_without_registry(monkeypatch):
    import repro.obs

    monkeypatch.delattr(repro.obs, "SPAN_METRICS")
    assert spec.metric_reader(spec.BENCH, METRIC)(None) is None


def test_traced_switch_overlaps_every_chunk_but_each_leafs_last(
        tiny_root, monkeypatch):
    """The share is reported in a traced switch: every chunk of a leaf but
    its last lands while the next chunk's copy is in flight. (The tiny
    leaves are one 5 MB chunk each, so here it reads 0; the data plane's
    own tests count the overlap of leaves of many chunks.)"""
    import repro.obs
    from harness import model
    from repro.core import MMAConfig
    from repro.obs import MetricsRegistry

    # A registry of its own: the process-wide one sums every traced run
    # of the process, which the other readers' tests count on.
    reg = MetricsRegistry()
    monkeypatch.setattr(repro.obs.spans, "SPAN_METRICS", reg)
    monkeypatch.setattr(repro.obs, "SPAN_METRICS", reg)
    result, _ = runner.execute(tiny_root, "tiny.switch", SEED, 1.0, True,
                               require_chip=False)
    cell = spec.load_cell("tiny.switch", tiny_root)
    leaves = jax.tree.leaves(model.make_weights(cell.config, SEED))
    chunks = sum(MMAConfig().n_chunks(l.nbytes) for l in leaves)
    assert result["correct"] and result["attempted"] >= 2
    assert result["metrics"][METRIC]["value"] == pytest.approx(
        100.0 * (chunks - len(leaves)) / chunks)
