"""Data plane: share of the device-to-host chunks' host time spent waiting
for the copy to land in host memory (``np.asarray`` of the piece) (%):
``dataplane.d2h_wait`` over ``dataplane.d2h_chunk`` seconds, from the
program's span counters."""
from harness import spans


def read(run):
    if not spans.calls("dataplane.d2h_chunk"):
        return None
    total = spans.seconds("dataplane.d2h_chunk")
    return 100.0 * spans.seconds("dataplane.d2h_wait") / total
