"""95th percentile latency of restoring one prefix's KV from host memory
into the serving chip's HBM (ms), over every fetch of the window."""
from harness.stats import percentile


def read(run):
    v = percentile([f["seconds"] for f in run.records.get("fetches") or []], 95)
    return None if v is None else 1e3 * v
