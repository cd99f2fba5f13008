"""Every cell finds its configuration, traffic, settings, driver and
metric readers by name; a cell made of new files alone runs."""
import json

import pytest

from conftest import ROOT
from harness import runner, spec


def _cells(root):
    return [w["name"] for w in json.loads(
        (root / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", _cells(ROOT))
def test_cell_resolves_by_name(name):
    cell = spec.load_cell(name, ROOT)
    assert cell.config["name"] in name
    assert cell.driver in ("switch", "serve", "fetch")
    spec.load_driver(cell)
    names = [m["name"] for m in cell.end_to_end + cell.per_layer]
    assert "setup_s" in names and len(cell.per_layer) >= 1
    for n in names:
        assert callable(spec.metric_reader(cell.bench_dir, n))


def test_every_config_and_metric_is_used():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for m in b["per_layer"]:
        e2e = {e["name"]: e for e in b["end_to_end"]}[m["moves"]]
        for w in m["workloads"]:
            assert w in e2e.get("workloads", [w])


@pytest.mark.parametrize("name", ["tiny.switch", "tiny.docqa",
                                  "tiny.fetch.relay4"])
def test_cell_from_new_files_runs(tiny_root, name):
    result, checks = runner.execute(tiny_root, name, 2 ** 31 + 7, 1.0, False,
                                    require_chip=False)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"]
    assert result["window_compiles"] == 0
    assert list(result)[-1] == "checks"
