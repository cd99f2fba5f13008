"""90th percentile time to first token (s) over every request of the
window, timed from the request's due time (open loop)."""
from harness.stats import percentile


def read(run):
    reqs = run.records.get("requests") or []
    return percentile([r["tokens"][0] - r["due"] for r in reqs if r["tokens"]],
                      90)
