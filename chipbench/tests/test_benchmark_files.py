"""``BENCHMARK.json`` and the files the harness finds by name agree."""

import importlib
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = ROOT / "chipbench"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_each_per_layer_metric_has_its_file_and_reader(m):
    spec = json.loads((HERE / "metrics" / f"{m['name']}.json").read_text())
    # the BENCHMARK.json entry alone says what the metric is and where
    assert not spec.keys() & m.keys()
    reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
    assert callable(reader.read)
    e2e = {e["name"]: e for e in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert m["workloads"] and set(m["workloads"]) <= cells
    for w in m["workloads"]:
        assert w in e2e[m["moves"]].get("workloads", [w])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_finds_its_configuration_and_traffic(w):
    configs = {c["name"]: c for c in BENCH["configs"]}
    cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    for key in configs[w["config"]]["reduced"]:
        assert key in cfg
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    driver = importlib.import_module(
        f"chipbench.drivers.{traffic['generator']}")
    assert hasattr(driver, "Driver")
    assert len(w["why"]) <= 200


def test_names_and_bounds_keep_to_the_contract():
    names = ([m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]])
    assert len(names) == len(set(names))
    assert all(NAME.fullmatch(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
