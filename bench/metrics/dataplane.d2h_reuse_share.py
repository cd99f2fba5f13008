"""Data plane: share of the device-to-host payloads served from the
backend's host block cache, whose pages an earlier sleep faulted in (%):
``dataplane.d2h_payload_reused`` over ``dataplane.d2h_payload`` calls, from
the program's counters. A program without the cache counts neither, and
reads as nothing."""
from harness import spans


def read(run):
    n = spans.calls("dataplane.d2h_payload")
    return 100.0 * spans.calls("dataplane.d2h_payload_reused") / n if n else None
