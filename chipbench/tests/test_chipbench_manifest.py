"""BENCHMARK.json against the format of its keys, every cell resolved to its
files, and a new configuration, mix and architecture loaded from new files
alone."""
import json
import re

import pytest

import numpy as np

from chipbench import costs, harness, reference, traffic, weights
from chipbench.harness import ROOT

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
    w, arch, cfg, mix = harness.resolve(MANIFEST, cell)
    assert w["chips"] == 1
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    arch.program(cfg)                       # the program builds the network
    assert weights.param_count(arch, cfg) == cfg["params"]
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


TESTDATA = ROOT / "testdata"


@pytest.mark.parametrize("name,arch_dir,counts", [
    # params, convolutions, operations per image (worked out by hand)
    ("tiny-f43", ROOT / "archs", (176_402, 20, 1_110_272)),
    ("tiny-bottleneck", TESTDATA / "archs", (385_392, 17, 1_974_272))],
    ids=["resnet-cifar", "bottleneck-stand-in"])
def test_new_configuration_and_mix_from_new_files_alone(name, arch_dir,
                                                         counts):
    """A cell of a configuration and a mix that exist only as files under
    testdata/ resolves with no edit to any file the benchmark has; so does
    a network whose architecture module exists only there (a bottleneck
    ResNet in ImageNet form: 7x7/2 stem, 3x3/2 max-pool, 1,000 classes).
    Each makes its weights, runs its reference and its 4-bit control, and
    counts its parameters and operations; the stand-in has no program."""
    man = {"configs": [{"name": name,
                        "file": f"chipbench/testdata/configs/{name}.json"}],
           "workloads": [{"name": "tiny", "config": name,
                          "traffic": "tiny-closed", "chips": 1}]}
    cell, arch, cfg, mix = harness.resolve(
        man, "tiny", traffic_dir=TESTDATA / "traffic", arch_dir=arch_dir)
    assert cfg["widths"] == [8, 16, 32, 64]
    assert mix["buckets"] == [8]
    assert len(traffic.image_order(mix, 1, 40)) == 40
    params, state = weights.make_weights(arch, cfg, 2**31 + 7)
    x = weights.images(cfg, 2**31 + 7, 8)
    ref = reference.logits(arch, cfg, params, state, x)
    ctl = reference.logits(arch, cfg, params, state, x, bits=4)
    assert ref.shape == ctl.shape == (8, cfg["num_classes"])
    assert np.all(np.isfinite(ctl)) and not np.array_equal(ref, ctl)
    n_params, n_convs, ops = counts
    assert weights.param_count(arch, cfg) == n_params
    assert len(list(arch.conv_layers(cfg))) == n_convs
    assert costs.model_ops_per_image(arch, cfg) == ops
    if arch_dir == ROOT / "archs":
        model, rc = arch.program(cfg)
        assert list(rc.widths) == cfg["widths"]
    else:
        with pytest.raises(NotImplementedError):
            arch.program(cfg)
