"""Program spans on the profiler's clock, counted only while it records.

``span(name, **args)`` marks one layer boundary of the transfer engine or
the data plane (``engine.memcpy``, ``dataplane.d2h_chunk``, ...):

  * **no profiler session** — it returns one shared no-op context:
    nothing is counted, timed or allocated, so a site costs one check;
  * **a session running** (``jax.profiler.trace``/``start_trace``) — it
    opens ``jax.profiler.TraceAnnotation(name, **args)``, a host event
    on the clock of the device's operations, and on exit adds 1 to
    ``<name>.calls`` and the elapsed ``time.perf_counter()`` seconds to
    ``<name>.seconds`` in :data:`SPAN_METRICS`.

``count(name, n=1)`` adds ``n`` to ``<name>.calls`` under the same switch,
for an event or an amount that is counted but not timed (a D2H payload
served from the host block cache, the tokens of a KV offload).
``recording()`` says whether a session runs, for a count whose amount
costs something to read.

The profiler session is the only switch: a process that profiles one
window finds in ``SPAN_METRICS`` that window's calls and nothing else.
Pass identifiers as ``args`` (a request's ``req=``) so that the spans of
one request share them in the trace.

JAX is imported on the first call, not with this package, which the
simulator imports without it.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, ContextManager

from .metrics import MetricsRegistry

#: Process-wide ``<span>.calls`` / ``<span>.seconds`` counters.
SPAN_METRICS = MetricsRegistry()


class _NoSpan:
    """The shared no-op context returned while nothing records (cheaper to
    enter than ``contextlib.nullcontext``)."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


_NULL = _NoSpan()


def _off() -> bool:
    return False


def _resolve() -> bool:
    """Bind ``_recording`` to the profiler's own "is a session running"
    check (a private JAX symbol, kept to this one place); without it,
    nothing is ever recorded."""
    global _recording, _annotation
    try:
        from jax._src.lib import _profiler
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
        _recording = _profiler.TraceMe.is_enabled
    except (ImportError, AttributeError):  # "unknown" is off
        _recording = _off
    return _recording()


_recording: Callable[[], bool] = _resolve
_annotation: Any = None


@contextlib.contextmanager
def _recorded(name: str, args: dict):
    with _annotation(name, **args):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            SPAN_METRICS.counter(name + ".calls").inc()
            SPAN_METRICS.counter(name + ".seconds").inc(dt)


def recording() -> bool:
    """Whether a profiler session runs, so that spans and counts record."""
    return _recording()


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to ``<name>.calls`` in :data:`SPAN_METRICS`, only while a
    profiler session runs (a counted event or amount with no span of its
    own)."""
    if _recording():
        SPAN_METRICS.counter(name + ".calls").inc(n)


def span(name: str, **args: Any) -> ContextManager[None]:
    """A host span ``name`` around the enclosed code, recorded and counted
    only while a profiler session runs (module docstring)."""
    if not _recording():
        return _NULL
    return _recorded(name, args)
