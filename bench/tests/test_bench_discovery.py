"""Every configuration, traffic mix and per-layer metric that
``BENCHMARK.json`` names is found by its name, and the file keeps to the
contract's shape."""

import json
import re

import pytest

from harness import spec, traffic

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_found_by_name(entry):
    cfg = spec.load_config(BENCH, entry["name"])
    assert cfg["name"] == entry["name"]
    mod = spec.config_module(entry["name"])
    assert callable(mod.build) and callable(mod.check)
    assert set(mod.LIMITS) >= {"score_err", "fuse_gap"}
    assert all(k in cfg for k in entry["reduced"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_parts_found_by_name(cell):
    assert spec.workload(BENCH, cell["name"]) is cell
    traffic.validate(spec.load_traffic(cell["traffic"]))
    spec.config_entry(BENCH, cell["config"])
    e2e = {m["name"] for m in spec.end_to_end_for(BENCH, cell["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer_for(BENCH, cell["name"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_found_by_name(metric):
    assert callable(spec.metric_reader(metric["name"]))
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("metric", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_reader_found_by_name(metric):
    assert callable(spec.metric_reader(metric["name"]))


def test_names_units_and_bounds():
    entries = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        spec.workload(BENCH, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")
