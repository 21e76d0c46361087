"""BENCHMARK.json against the format of its keys, every cell resolved to its
files, and a new configuration, mix and architecture loaded from new files
alone."""
import copy
import re

import pytest

import numpy as np

from chipbench import costs, harness, reference, traffic, weights
from chipbench.harness import ROOT
from chipbench.tests.manifest_checks import (
    ARCHS, MANIFEST, check_cell, check_counts_pinned, check_images_pinned,
    check_metric_entries, check_program, check_top_level,
    check_weights_pinned)

CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys():
    check_top_level(MANIFEST)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    check_cell(MANIFEST, cell)
    check_program(MANIFEST, cell)


def test_metric_entries():
    check_metric_entries(MANIFEST)


TESTDATA = ROOT / "testdata"

#: Configurations whose configuration and pin files exist only under
#: testdata/: a copy of ``resnet18-cifar-f43`` under a name of its own,
#: and the reference-only bottleneck stand-in, whose architecture module
#: lies there too (its stem, max-pool, blocks and head are not ResNet-18's,
#: nor are its counts).
NEW = {"resnet18-cifar-f43-copy": ARCHS,
       "tiny-bottleneck": TESTDATA / "archs"}


def _with_new_configuration(name):
    man = copy.deepcopy(MANIFEST)
    man["configs"].append({
        "name": name, "source": "https://arxiv.org/abs/2004.11077",
        "file": f"chipbench/testdata/configs/{name}.json", "reduced": [],
        "why": "a configuration that only the tests know"})
    man["workloads"].append({
        "name": "new-offline-b256", "config": name, "traffic": "offline-b256",
        "chips": 1, "why": "the offline mix on the new configuration"})
    return man


@pytest.mark.parametrize("name", NEW)
def test_new_configuration_by_new_files_alone(name):
    """One config entry and one workload entry more, and files that are
    new: every check of the manifest, of the new cell and of the new
    configuration's pins and counts passes, with no other entry or file
    changed. The stand-in has no program."""
    man, arch_dir = _with_new_configuration(name), NEW[name]
    check_top_level(man)
    check_metric_entries(man)
    check_cell(man, "new-offline-b256", arch_dir)
    e2e = harness.metrics_for(man, man["workloads"][-1], trace_on=False)
    layer = harness.metrics_for(man, man["workloads"][-1], trace_on=True)
    assert {m["name"] for m in e2e} == {m["name"]
                                        for m in MANIFEST["end_to_end"]}
    assert {m["name"] for m in layer} == {m["name"]
                                          for m in MANIFEST["per_layer"]}
    check_weights_pinned(man, name, arch_dir=arch_dir)
    check_images_pinned(man, name, arch_dir=arch_dir)
    check_counts_pinned(man, name, arch_dir=arch_dir)
    if arch_dir == ARCHS:
        check_program(man, "new-offline-b256")
    else:
        with pytest.raises(NotImplementedError):
            check_program(man, "new-offline-b256", arch_dir)


def test_configuration_without_pins_names_the_file(tmp_path):
    name = "resnet18-cifar-f43-copy"
    man = _with_new_configuration(name)
    for check in (check_weights_pinned, check_images_pinned,
                  check_counts_pinned):
        with pytest.raises(FileNotFoundError,
                           match=re.escape(f"add {tmp_path / name}.json")):
            check(man, name, tmp_path)


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
