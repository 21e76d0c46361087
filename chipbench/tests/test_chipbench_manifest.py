"""BENCHMARK.json against the format of its keys, every cell resolved to its
files, and a new configuration and mix loaded from new files alone."""
import json
import re

import pytest

from chipbench import harness, traffic, weights
from chipbench.harness import ROOT
from chipbench.system import resnet_config

MANIFEST = harness.load_manifest(ROOT.parent / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["chipbench"]
    assert MANIFEST["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for entry in MANIFEST["configs"] + MANIFEST["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    w, cfg, mix = harness.resolve(MANIFEST, cell)
    assert w["chips"] == 1
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    resnet_config(cfg)                      # the program builds its widths
    assert weights.param_count(cfg) == cfg["params"]
    assert set(cfg["limits"]) <= {"logit_err_rms", "logit_err_max",
                                  "share_over_err_level"}
    e2e = harness.metrics_for(MANIFEST, w, trace_on=False)
    layer = harness.metrics_for(MANIFEST, w, trace_on=True)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert layer
    for m in layer:
        assert m["moves"] in names          # the cell reports what it moves
        assert callable(harness.reader(m["name"]))


def test_metric_entries():
    cells = set(CELLS)
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (ROOT / "metrics" / f"{m['name']}.py").is_file()
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    confs = {c["name"] for c in MANIFEST["configs"]}
    assert confs == {w["config"] for w in MANIFEST["workloads"]}


def test_new_configuration_and_mix_from_new_files_alone():
    """A cell of a configuration and a mix that exist only as files under
    testdata/ resolves with no edit to any file the benchmark has."""
    man = {"configs": [{"name": "tiny-f43",
                        "file": "chipbench/testdata/configs/tiny-f43.json"}],
           "workloads": [{"name": "tiny", "config": "tiny-f43",
                          "traffic": "tiny-closed", "chips": 1}]}
    cell, cfg, mix = harness.resolve(man, "tiny",
                                     traffic_dir=ROOT / "testdata" / "traffic")
    assert cfg["widths"] == [8, 16, 32, 64]
    assert mix["buckets"] == [8]
    assert list(resnet_config(cfg).widths) == cfg["widths"]
    assert len(traffic.image_order(mix, 1, 40)) == 40
