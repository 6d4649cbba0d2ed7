"""Every configuration, traffic mix and per-layer metric is a file of its
own, found by name: each one in its directory loads, and BENCHMARK.json
names only files that are there."""
import importlib
import json

import pytest

from bench.harness import BENCH, ROOT, arch_config, load_cell

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = sorted((BENCH / "configs").glob("*.json"))
TRAFFIC = sorted((BENCH / "traffic").glob("*.json"))
READERS = sorted(p for p in (BENCH / "metrics").glob("*.py")
                 if p.name != "__init__.py")


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_config_loads(path):
    c = json.loads(path.read_text())
    assert c["name"] == path.stem
    cfg = arch_config(c)
    assert cfg.d_model == c["model"]["d_model"]
    importlib.import_module(f"bench.models.{c['reference']}")
    assert c["precision"] in ("float", "int8")


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_traffic_loads(path):
    t = json.loads(path.read_text())
    gen = importlib.import_module(f"bench.traffic.{t['generator']}")
    items = gen.schedule(t, 1, 2.0, 1000)
    assert any(it.phase == "window" for it in items)


@pytest.mark.parametrize("path", READERS, ids=lambda p: p.stem)
def test_reader_loads(path):
    mod = importlib.import_module(f"bench.metrics.{path.stem}")
    assert callable(mod.read)
    assert path.stem in {m["name"] for m in BENCHMARK["per_layer"]}


@pytest.mark.parametrize("cell", [c["name"] for c in BENCHMARK["workloads"]])
def test_cells_resolve(cell):
    _, c, config, traffic = load_cell(cell)
    assert config["name"] == c["config"]
    assert traffic["generator"]


def test_every_metric_has_a_reader():
    names = {p.stem for p in READERS}
    assert {m["name"] for m in BENCHMARK["per_layer"]} <= names
