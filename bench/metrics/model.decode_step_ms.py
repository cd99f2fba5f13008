"""Model step: mean device time of one decode-step executable (ms), from
the trace."""
from harness.stats import module_seconds


def read(run):
    secs = module_seconds(run, r"decode_step")
    return 1e3 * sum(secs) / len(secs) if secs else None
