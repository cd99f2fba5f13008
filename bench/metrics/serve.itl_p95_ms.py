"""Serving loop: 95th percentile gap between consecutive output tokens
(ms), over every gap of every request of the window. Each step decodes
every running request with one call of its own, so a gap is a whole
number of decode calls (plus any prefill in the step), and the p95 moves
by a whole call when the seed's order changes how often four run at once:
too coarse to bound end to end, read here beside the TTFT it delays."""
import numpy as np

from harness.stats import percentile


def read(run):
    gaps = [g for r in run.records.get("requests") or []
            for g in np.diff(r["tokens"])]
    v = percentile(gaps, 95)
    return None if v is None else 1e3 * v
