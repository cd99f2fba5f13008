"""The reader of the host block cache's counters,
``dataplane.d2h_reuse_share``: a known answer on a hand-filled registry,
nothing without calls or without a registry, and in a traced run of the
tiny switch every window sleep but the first served from the cache."""
import jax
import pytest

from harness import runner, spec

SEED = 2 ** 31 + 29
METRIC = "dataplane.d2h_reuse_share"


def _registry(payloads, reused):
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("dataplane.d2h_payload.calls").inc(payloads)
    reg.counter("dataplane.d2h_payload_reused.calls").inc(reused)
    return reg


@pytest.mark.parametrize("payloads,reused,want", [
    (50, 40, 80.0), (11, 11, 100.0), (7, 0, 0.0)])
def test_reader_on_filled_registry(payloads, reused, want, monkeypatch):
    import repro.obs

    monkeypatch.setattr(repro.obs, "SPAN_METRICS", _registry(payloads, reused))
    assert spec.metric_reader(spec.BENCH, METRIC)(None) == pytest.approx(want)


def test_reader_without_calls_or_registry(monkeypatch):
    import repro.obs

    monkeypatch.setattr(repro.obs, "SPAN_METRICS", _registry(0, 0))
    assert spec.metric_reader(spec.BENCH, METRIC)(None) is None
    # a program older than the cache has no such counters, or no registry
    monkeypatch.delattr(repro.obs, "SPAN_METRICS")
    assert spec.metric_reader(spec.BENCH, METRIC)(None) is None


def test_traced_switch_reuses_the_warm_up_blocks(tiny_root):
    """One payload per leaf and window sleep, counted in the window only.
    On the CPU a woken 1-D leaf of one chunk may alias its host block (a
    zero-copy ``device_put``) and hold it through the next sleep, so the
    first window sleep may miss once per leaf; every later one hits."""
    from repro.obs import SPAN_METRICS
    from harness import model

    def calls():
        return {k: SPAN_METRICS.counter(k).total() for k in
                ("dataplane.d2h_payload.calls",
                 "dataplane.d2h_payload_reused.calls")
                if k in SPAN_METRICS}

    before = calls()
    result, _ = runner.execute(tiny_root, "tiny.switch", SEED, 1.0, True,
                               require_chip=False)
    after = calls()
    payloads, reused = (after.get(k, 0) - before.get(k, 0) for k in
                        ("dataplane.d2h_payload.calls",
                         "dataplane.d2h_payload_reused.calls"))
    cell = spec.load_cell("tiny.switch", tiny_root)
    leaves = len(jax.tree.leaves(model.make_weights(cell.config, SEED)))
    assert result["correct"] and result["attempted"] >= 2
    assert payloads == result["attempted"] * leaves
    assert payloads - leaves <= reused <= payloads
    assert result["metrics"][METRIC]["value"] == pytest.approx(
        100.0 * reused / payloads)
