"""No chip, no result: the run stops before any timing."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from conftest import BENCH, ROOT
from harness import runner

PEAKS = json.loads((BENCH / "peaks.json").read_text())


def _dev(platform="tpu", kind="TPU v5 lite"):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_guard():
    assert runner.device_guard([_dev()], 1, PEAKS)["bf16_flops_per_s"] > 0
    with pytest.raises(runner.NoChip):
        runner.device_guard([_dev("cpu", "cpu")], 1, PEAKS)
    with pytest.raises(runner.NoChip):
        runner.device_guard([_dev(kind="TPU v99")], 1, PEAKS)
    with pytest.raises(runner.NoChip):
        runner.device_guard([_dev()] * 3, 4, PEAKS)


def test_no_tpu_prints_no_result(capsys):
    sys.path.insert(0, str(BENCH))
    import run

    assert run.main(["--workload", "qwen3-4b.switch", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    env = dict(os.environ, PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-4b.switch", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
