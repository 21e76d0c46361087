"""Architecture modules: what the existing configurations read, pinned to
values recorded before the network moved into ``archs/`` (weights,
images, reference and control logits), and the refusals of a
configuration whose architecture is unknown or differs from what the
program builds."""
import hashlib
import json

import jax
import numpy as np
import pytest

from chipbench import harness, reference, weights
from chipbench.harness import ROOT

MANIFEST = harness.load_manifest(ROOT.parent / "BENCHMARK.json")
PINS = json.loads((ROOT / "testdata" / "pins.json").read_text())
SEED = PINS["seed"]


def _digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a, np.float32))
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


def _cell(name):
    cell, arch, cfg, mix = harness.resolve(MANIFEST, name)
    return arch, cfg, mix, PINS["configs"][cell["config"]]


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_weights_pinned(cell):
    """Every leaf of ``(params, state)``, and so the order in which the key
    is split over the flattened shape tree."""
    arch, cfg, _, pins = _cell(cell)
    params, state = weights.make_weights(arch, cfg, SEED)
    flat = jax.tree_util.tree_flatten_with_path(
        {"params": params, "state": state})[0]
    got = {jax.tree_util.keystr(k, simple=True, separator="/"): _digest(v)
           for k, v in flat}
    assert got == pins["weights"]


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_images_pinned(cell):
    """The cell's request pool and calibration batches."""
    _, cfg, mix, pins = _cell(cell)
    assert _digest(weights.images(cfg, SEED, mix["pool"])) == pins["pool"]
    assert [_digest(x) for x in weights.calibration_images(cfg, SEED)] \
        == pins["calibration"]


@pytest.mark.parametrize("bits,key", [(None, "reference"), (4, "control")])
def test_reference_and_control_logits_pinned(bits, key):
    man = {"configs": [{"name": "c",
                        "file": "chipbench/testdata/configs/tiny-f43.json"}],
           "workloads": [{"name": "w", "config": "c",
                          "traffic": "tiny-closed", "chips": 1}]}
    _, arch, cfg, _ = harness.resolve(
        man, "w", traffic_dir=ROOT / "testdata" / "traffic")
    params, state = weights.make_weights(arch, cfg, SEED)
    x = weights.images(cfg, SEED, PINS["tiny-f43"]["images"])
    got = reference.logits(arch, cfg, params, state, x, bits=bits)
    np.testing.assert_array_equal(got, np.array(PINS["tiny-f43"][key]))


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
    ({"arch": "resnet-imagenet"}, "no architecture module for arch "
                                  "'resnet-imagenet'"),
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
