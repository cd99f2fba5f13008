"""The program's own span counters, read after the window.

``repro.obs.SPAN_METRICS`` holds ``<span>.calls`` and ``<span>.seconds``
(``time.perf_counter``) of every ``repro.obs.span`` closed while a profiler
session ran: in a ``--trace 1`` run, the window's and nothing of set-up or
the check. A program without them (one older than its spans) reads as no
calls, and its readers report nothing.
"""
from __future__ import annotations

from typing import Iterable


def _registry():
    import repro.obs

    return getattr(repro.obs, "SPAN_METRICS", None)


def _total(metric: str) -> float:
    reg = _registry()
    if reg is None or metric not in reg:
        return 0
    return reg.counter(metric).total()


def calls(span: str) -> int:
    """Closed calls of ``span`` (0 when it never ran under the profiler)."""
    return int(_total(span + ".calls"))


def seconds(span: str) -> float:
    """Summed host seconds of ``span``'s calls."""
    return float(_total(span + ".seconds"))


def self_seconds(span: str, children: Iterable[str]) -> float:
    """``span``'s seconds less those of the spans that run inside it."""
    return seconds(span) - sum(seconds(c) for c in children)
