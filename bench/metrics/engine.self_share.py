"""Transfer engine: share of the synchronous transfers' host time spent in
the engine itself (planning, pulls, settling) rather than in the data
plane's chunks (%): ``engine.memcpy`` less ``dataplane.h2d_chunk`` and
``dataplane.d2h_chunk`` seconds, over ``engine.memcpy`` seconds, from the
program's span counters."""
from harness import spans


def read(run):
    if not spans.calls("engine.memcpy"):
        return None
    total = spans.seconds("engine.memcpy")
    own = spans.self_seconds("engine.memcpy", ("dataplane.h2d_chunk",
                                               "dataplane.d2h_chunk"))
    return 100.0 * own / total
