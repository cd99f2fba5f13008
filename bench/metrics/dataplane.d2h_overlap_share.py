"""Data plane: share of the device-to-host chunks whose wait began while
the host copy of a later chunk was already in flight (%):
``dataplane.d2h_overlapped`` over ``dataplane.d2h_chunk`` calls, from the
program's counters. A program that starts no copy ahead of its wait has no
such counter, and reads as nothing."""
from harness import spans


def read(run):
    import repro.obs

    reg = getattr(repro.obs, "SPAN_METRICS", None)
    n = spans.calls("dataplane.d2h_chunk")
    if not n or reg is None or "dataplane.d2h_overlapped.calls" not in reg:
        return None
    return 100.0 * spans.calls("dataplane.d2h_overlapped") / n
