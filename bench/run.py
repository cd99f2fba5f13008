"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout on a machine with the chips the cell needs.
The cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` and found under ``bench/``. With ``--trace 0`` the run
reports the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics from a profiled window. The last lines of stderr are the numbers
compared against the plain reference, each with its limit; the last line
of stdout is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``breakdown`` when traced, and ``checks`` last).

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs, or when the program under test is not in the
checkout. ``--rate`` (the rate sweep) and ``--control`` (the control's
readings beside the program's) serve to set a cell's rate and limits.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="requests per second instead of the mix's own")
    ap.add_argument("--control", action="store_true",
                    help="also report the control's readings")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program under test is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    from harness import runner

    try:
        result, checks = runner.execute(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            rate_per_s=args.rate, control=args.control)
    except runner.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    runner.report(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
