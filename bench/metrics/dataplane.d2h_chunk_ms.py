"""Data plane: host time of one device-to-host chunk (slice, relay hop and
host copy) (ms): ``dataplane.d2h_chunk`` seconds over its calls, from the
program's span counters."""
from harness import spans


def read(run):
    n = spans.calls("dataplane.d2h_chunk")
    return 1e3 * spans.seconds("dataplane.d2h_chunk") / n if n else None
