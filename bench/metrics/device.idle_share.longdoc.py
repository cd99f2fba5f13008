"""Device: share of the traced window in which chip 0 ran no operation (%),
from the profiler's trace."""
from harness.stats import idle_percent


def read(run):
    return idle_percent(run)
