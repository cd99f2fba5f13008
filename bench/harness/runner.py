"""One run of one cell: set-up, the measured window, the check, the metrics.

The driver named by the cell's traffic mix (``drivers/<driver>.py``)
provides four calls:

    setup(run) -> state        make weights and data, warm every shape
    window(state, run)         drive the system for ``run.seconds``; fills
                               ``run.records`` (and ``attempted``/``failed``)
    release(state)             drop the program's state on the device
    check(state, run)          compare with the plain reference; returns
                               [(name, value, limit), ...], value <= limit
                               passing

Every metric of the cell is then read by ``metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

from . import spec, trace as tr

PEAKS = spec.BENCH / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no accelerator this benchmark can measure."""


@dataclasses.dataclass
class Run:
    cell: spec.Cell
    seed: int
    seconds: float
    traced: bool
    rate_per_s: float = 0.0            # an override, for the rate sweep
    control: bool = False              # also read the control's numbers
    devices: List[Any] = dataclasses.field(default_factory=list)
    peaks: Optional[Dict[str, Any]] = None
    records: Dict[str, Any] = dataclasses.field(default_factory=dict)
    setup_s: float = 0.0
    trace: Optional[tr.Trace] = None
    window_compiles: int = 0


def device_guard(devices, chips: int, peaks: Dict[str, Any]) -> Dict[str, Any]:
    """The peaks of the chip in use; raises ``NoChip`` before any timing
    when the platform is not a TPU, when there are fewer chips than the
    cell needs, or when the chip's kind has no entry in ``peaks.json``."""
    d = devices[0]
    if d.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {d.platform}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    if d.device_kind not in peaks:
        raise NoChip(f"no peaks for device kind {d.device_kind!r} in "
                     f"{PEAKS.name}")
    return peaks[d.device_kind]


def judge(checks: List) -> bool:
    """``correct``: every number compared is within its limit."""
    return bool(checks) and all(v <= lim for _, v, lim in checks)


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def execute(root: pathlib.Path, workload: str, seed: int, seconds: float,
            traced: bool, *, require_chip: bool = True,
            rate_per_s: float = 0.0,
            control: bool = False) -> Tuple[Dict[str, Any], List]:
    """Run ``workload`` once. Returns the result object and the checks."""
    import jax

    from repro.launch.compile_cache import CompileCounter, enable_compile_cache

    cell = spec.load_cell(workload, root)
    devices = jax.devices()
    with open(PEAKS) as f:
        peaks = json.load(f)
    if traced:
        seconds = min(seconds, cell.settings.get("trace_seconds", seconds))
    run = Run(cell=cell, seed=seed, seconds=seconds, traced=traced,
              rate_per_s=rate_per_s, control=control,
              devices=devices[:cell.chips])
    if require_chip:
        run.peaks = device_guard(devices, cell.chips, peaks)
    elif len(devices) < cell.chips:
        raise NoChip(f"needs {cell.chips} devices; JAX found {len(devices)}")

    enable_compile_cache()
    # Every executable goes to the cache, so a second run compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    driver = spec.load_driver(cell)

    t0 = time.perf_counter()
    state = driver.setup(run)
    run.setup_s = time.perf_counter() - t0
    built = compiles.built
    if traced:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            with tr.capture(log_dir):
                with jax.profiler.TraceAnnotation(tr.WINDOW):
                    driver.window(state, run)
            run.trace = tr.load(log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    else:
        driver.window(state, run)
    run.window_compiles = compiles.built - built
    compiles.close()
    memory_peak = _memory_peak(run.devices)
    driver.release(state)
    checks = driver.check(state, run)

    metrics = {}
    for m in cell.metrics(traced):
        value = spec.metric_reader(cell.bench_dir, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {
        "correct": judge(checks),
        "attempted": int(run.records.get("attempted", 0)),
        "failed": int(run.records.get("failed", 0)),
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        planes = tr.devices(run.trace, len(run.devices))
        lo, hi = run.trace.window()
        if planes:
            device["busy_s"] = sum(tr.busy_ns(run.trace, p)
                                   for p in planes) / len(planes) / 1e9
            result["breakdown"] = {
                "device_ops": tr.top_ops(run.trace, planes[0]),
                "idle_gaps": tr.idle_gaps(run.trace, planes[0]),
            }
        device["window_s"] = (hi - lo) / 1e9
    if "info" in run.records:
        result["info"] = run.records["info"]
    if "control" in run.records:
        # The control's readings in the program's place, judged the same way.
        control = run.records["control"]
        result["control"] = control
        result["control_correct"] = judge(
            [(n, control.get(n, v), lim) for n, v, lim in checks])
    result["setup_compiles"] = built
    result["window_compiles"] = run.window_compiles
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result, checks


def report(result: Dict[str, Any], checks: List) -> None:
    """The checks as the last lines of stderr, the result as the last line
    of stdout."""
    for name, value, limit in checks:
        verdict = "ok" if value <= limit else "FAIL"
        print(f"check {name}: {value!r} limit {limit!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
