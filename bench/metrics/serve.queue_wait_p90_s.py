"""Serving loop: 90th percentile wait from a request's due time to the
start of its prefill (s, benchmark clock)."""
from harness.stats import percentile


def read(run):
    reqs = run.records.get("requests") or []
    return percentile([r["prefill_start"] - r["due"] for r in reqs
                       if r["prefill_start"] is not None], 90)
