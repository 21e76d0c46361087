"""Architecture modules: what the existing configurations read, pinned to
values recorded before the network moved into ``archs/`` (weights,
images, reference and control logits, in ``testdata/pins/``, one file per
configuration), and the refusals of a configuration whose architecture
is unknown or differs from what the program builds."""
import json

import numpy as np
import pytest

from chipbench import harness, reference, weights
from chipbench.harness import ROOT
from chipbench.tests.manifest_checks import (
    MANIFEST, check_images_pinned, check_weights_pinned, pins)

CONFIGS = [c["name"] for c in MANIFEST["configs"]]


@pytest.mark.parametrize("config", CONFIGS)
def test_weights_pinned(config):
    check_weights_pinned(MANIFEST, config)


@pytest.mark.parametrize("config", CONFIGS)
def test_images_pinned(config):
    check_images_pinned(MANIFEST, config)


@pytest.mark.parametrize("bits,key", [(None, "reference"), (4, "control")])
def test_reference_and_control_logits_pinned(bits, key):
    pin = pins("tiny-f43")
    man = {"configs": [{"name": "c",
                        "file": "chipbench/testdata/configs/tiny-f43.json"}],
           "workloads": [{"name": "w", "config": "c",
                          "traffic": "tiny-closed", "chips": 1}]}
    _, arch, cfg, _ = harness.resolve(
        man, "w", traffic_dir=ROOT / "testdata" / "traffic")
    params, state = weights.make_weights(arch, cfg, pin["seed"])
    x = weights.images(cfg, pin["seed"], pin["images"])
    got = reference.logits(arch, cfg, params, state, x, bits=bits)
    np.testing.assert_array_equal(got, np.array(pin[key]))


def _config_file(tmp_path, **change):
    cfg = json.loads((ROOT / "testdata" / "configs" / "tiny-f43.json")
                     .read_text())
    cfg.update(change)
    for k in [k for k, v in change.items() if v is None]:
        del cfg[k]
    (tmp_path / "c.json").write_text(json.dumps(cfg))
    return {"configs": [{"name": "c", "file": "c.json"}],
            "workloads": [{"name": "w", "config": "c",
                           "traffic": "tiny-closed", "chips": 1}]}


@pytest.mark.parametrize("change,match", [
    ({"arch": "no-such-arch"}, "no architecture module for arch "
                               "'no-such-arch'"),
    ({"arch": None}, "names no 'arch'"),
    ({"max_pool": True}, r"keys \['max_pool'\] are not read by arch "
                         "'resnet-cifar'")],
    ids=["unknown-arch", "no-arch", "unread-key"])
def test_resolve_refuses_an_unknown_network(tmp_path, change, match):
    """Before any weight is made or chip touched, never served as another
    network."""
    man = _config_file(tmp_path, **change)
    with pytest.raises(ValueError, match=match):
        harness.resolve(man, "w", root=tmp_path,
                        traffic_dir=ROOT / "testdata" / "traffic")


@pytest.mark.parametrize("key,value", [
    ("reduced", ["blocks_per_stage"]), ("assumed", {"image_shape": "CIFAR"}),
    ("deployment", "one chip of an offline classification fleet")])
def test_resolve_takes_how_a_configuration_was_cut(tmp_path, key, value):
    """What a configuration states of its cut from the source is common to
    every architecture, not a key its module has to read."""
    man = _config_file(tmp_path, **{key: value})
    _, _, cfg, _ = harness.resolve(man, "w", root=tmp_path,
                                   traffic_dir=ROOT / "testdata" / "traffic")
    assert cfg[key] == value


@pytest.mark.parametrize("change", [
    {"blocks_per_stage": [3, 4, 6, 3]}, {"widths": [8, 16, 32, 48]},
    {"image_shape": [8, 8, 1]}], ids=["blocks", "widths", "channels"])
def test_program_refuses_a_network_it_does_not_build(tmp_path, change):
    """The program fixes ResNet-18's blocks per stage, its widths' ratios
    and three input channels; a file that states others resolves (the
    reference could run it) but is refused by ``program``."""
    _, arch, cfg, _ = harness.resolve(
        _config_file(tmp_path, **change), "w", root=tmp_path,
        traffic_dir=ROOT / "testdata" / "traffic")
    with pytest.raises(ValueError, match="the program builds"):
        arch.program(cfg)
