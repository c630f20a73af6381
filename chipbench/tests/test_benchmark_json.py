"""``BENCHMARK.json`` keeps to its own rules: names, units, keys, and a
file for every configuration, mix, driver and metric it names."""
import json
import os
import re

import pytest

from chipbench import harness as H

SPEC = H.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _names():
    out = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    out += [w["name"] for w in SPEC["workloads"]]
    out += [w["config"] for w in SPEC["workloads"]]
    out += [w["traffic"] for w in SPEC["workloads"]]
    out += [c["name"] for c in SPEC["configs"]]
    out += [k for c in SPEC["configs"] for k in c["reduced"]]
    return out


@pytest.mark.parametrize("name", _names())
def test_name_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_unit_and_keys(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    if metric in SPEC["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0 < metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
        assert os.path.exists(os.path.join(H.HERE, "metrics",
                                           metric["name"] + ".py"))


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "chipbench/run.py"]
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve(cell):
    res = H.resolve(cell["name"])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert os.path.exists(os.path.join(H.HERE, "drivers",
                                       res["traffic"]["driver"] + ".py"))
    e2e = {m["name"] for m in res["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert res["per_layer"]


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(config):
    assert config["file"].startswith("chipbench/configs/")
    data = H.load_json(H.ROOT, config["file"])
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert len(config["source"]) <= 200
