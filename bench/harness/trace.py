"""The profiler's trace, reduced to the numbers the per-layer metrics read.

``capture`` records a window with JAX's profiler. ``load`` reads the
``.xplane.pb`` it wrote into a plain form, ``Trace``: per device, the
intervals of its operations and of its executables (``XLA Ops`` and
``XLA Modules`` lines); on the host, the spans that the benchmark's drivers
open with ``jax.profiler.TraceAnnotation`` around each call into a layer.
All times are nanoseconds on the trace's one clock.

The reductions, each checked on a small recorded trace in ``tests/``:

- busy time: the union of a device's operation intervals inside the window;
- idle share: 1 - busy / window;
- per-executable device time: the summed durations of one module's runs;
- idle gaps by host span: each stretch of the window in which the device
  ran nothing, charged to the innermost host span open over it.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

Interval = Tuple[str, float, float]        # (name, start_ns, end_ns)

WINDOW = "window"                          # the host span around the window
# Host spans the drivers open; any other host event is not ours.
SPANS = ("window", "step", "submit", "prefill", "decode", "sleep", "wake",
         "multipath_device_put", "checksum", "wait")


@contextlib.contextmanager
def capture(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0           # no per-function Python events
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]         # device plane -> operations
    modules: Dict[str, List[Interval]]     # device plane -> executables
    host: List[Interval]                   # our host spans

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        tup = lambda evs: [tuple(e) for e in evs]
        return cls(ops={k: tup(v) for k, v in d["ops"].items()},
                   modules={k: tup(v) for k, v in d["modules"].items()},
                   host=tup(d["host"]))

    def window(self) -> Tuple[float, float]:
        spans = [(s, e) for n, s, e in self.host if n == WINDOW]
        if not spans:
            raise ValueError("the trace holds no window span")
        return spans[0]


def _device_index(plane: str) -> int:
    m = re.search(r"(\d+)$", plane)
    return int(m.group(1)) if m else -1


def _short(name: str) -> str:
    """An operation's or executable's name without its HLO text or id:
    ``%fusion.50 = (bf16[...]) fusion(...)`` -> ``fusion.50``,
    ``jit_prefill(123)`` -> ``jit_prefill``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def load(log_dir: str) -> Trace:
    import jax

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for path in paths:
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:") and "TPU" in plane.name \
                    and "NONCORE" not in plane.name.upper():
                for line in plane.lines:
                    target = {"XLA Ops": ops, "XLA Modules": modules}.get(
                        line.name)
                    if target is None:
                        continue
                    target.setdefault(plane.name, []).extend(
                        (_short(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events if e.name in SPANS)
    return Trace(ops=ops, modules=modules, host=sorted(host, key=lambda e: e[1]))


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------
def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(trace: Trace, device: str) -> float:
    lo, hi = trace.window()
    return sum(e - s for s, e in union(
        _clip(((s, e) for _, s, e in trace.ops.get(device, [])), lo, hi)))


def devices(trace: Trace, count: int) -> List[str]:
    """The first ``count`` device planes, by device index."""
    return sorted(trace.ops, key=_device_index)[:count]


def idle_share(trace: Trace, device: str) -> float:
    lo, hi = trace.window()
    return 1.0 - busy_ns(trace, device) / (hi - lo)


def module_runs(trace: Trace, device: str, pattern: str) -> List[float]:
    """Durations (ns) of each run, inside the window, of the executables
    whose name matches ``pattern``."""
    lo, hi = trace.window()
    rx = re.compile(pattern)
    return [e - s for n, s, e in trace.modules.get(device, [])
            if rx.search(n) and s >= lo and e <= hi]


def top_ops(trace: Trace, device: str, n: int = 10) -> List[List]:
    """The device operations that took the most time in the window, summed
    by executable and name (``jit_prefill/fusion.50``). An operation that
    encloses others (a ``while`` of the layer scan) is left out: its time
    is theirs."""
    lo, hi = trace.window()
    mods = sorted(trace.modules.get(device, []), key=lambda m: m[1])
    starts = [m[1] for m in mods]
    ops = sorted(trace.ops.get(device, []), key=lambda o: (o[1], -o[2]))
    total: Dict[str, float] = defaultdict(float)
    for i, (name, s, e) in enumerate(ops):
        if i + 1 < len(ops) and ops[i + 1][1] < e:
            continue
        if e > lo and s < hi:
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and mods[k][2] >= e:
                name = f"{mods[k][0]}/{name}"
            total[name] += min(e, hi) - max(s, lo)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(trace: Trace, device: str, n: int = 10) -> List[List]:
    """Idle time of ``device`` in the window, by the innermost host span
    open at the time ("none" where no span is open), the longest first."""
    lo, hi = trace.window()
    busy = union(_clip(((s, e) for _, s, e in trace.ops.get(device, [])),
                       lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    spans = sorted((s, e, name) for name, s, e in trace.host if name != WINDOW)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    total: Dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        near = spans[bisect.bisect_left(starts, g0 - longest):
                     bisect.bisect_left(starts, g1)]
        near = [sp for sp in near if sp[1] > g0]
        # Cut the gap at every span boundary inside it; charge each piece
        # to the innermost (latest-started) span covering it.
        cuts = sorted({g0, g1, *(x for s, e, _ in near for x in (s, e)
                                 if g0 < x < g1)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_ = [(s, name) for s, e, name in near if s <= mid < e]
            total[max(open_)[1] if open_ else "none"] += b - a
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def read(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
