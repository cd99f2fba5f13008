"""Data plane: share of the device-to-host chunks' host time spent storing
the landed piece into the host payload (``payload.flat[lo:hi] = host``)
(%): ``dataplane.d2h_store`` over ``dataplane.d2h_chunk`` seconds, from the
program's span counters. What neither this share nor
``dataplane.d2h_wait_share`` holds is the slice and relay hop's dispatch."""
from harness import spans


def read(run):
    if not spans.calls("dataplane.d2h_chunk"):
        return None
    total = spans.seconds("dataplane.d2h_chunk")
    return 100.0 * spans.seconds("dataplane.d2h_store") / total
