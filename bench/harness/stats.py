"""Small reductions the metric readers share."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from . import trace as tr


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100), linear between order statistics;
    None for no values."""
    return float(np.percentile(values, q)) if len(values) else None


def _chip0(run) -> Optional[str]:
    """The trace's plane of the cell's first chip, if there is one."""
    planes = tr.devices(run.trace, 1) if run.trace is not None else []
    return planes[0] if planes else None


def idle_percent(run) -> Optional[float]:
    """Idle share of the cell's first chip over the traced window, in %."""
    plane = _chip0(run)
    return None if plane is None else 100.0 * tr.idle_share(run.trace, plane)


def module_seconds(run, pattern: str):
    """Device seconds, on the cell's first chip, of each run of the
    executables matching ``pattern`` in the traced window; None without a
    trace of that chip."""
    plane = _chip0(run)
    if plane is None:
        return None
    return [ns / 1e9 for ns in tr.module_runs(run.trace, plane, pattern)]
