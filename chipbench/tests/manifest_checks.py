"""The checks that a manifest, each of its cells and each of its
configurations pass: the format of its keys, each cell resolved to its
files and metrics, the program's build of it, and each configuration's
weights, images and counts against its pin file,
``testdata/pins/<configuration>.json``. The tests run them on
BENCHMARK.json, and on copies of it with one more configuration whose
files exist only under ``testdata/``."""
import hashlib
import json
import re
from pathlib import Path

import jax
import numpy as np

from chipbench import costs, harness, weights
from chipbench.harness import ROOT

MANIFEST = harness.load_manifest(ROOT.parent / "BENCHMARK.json")
PINS = ROOT / "testdata" / "pins"
ARCHS = ROOT / "archs"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_top_level(man: dict):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["chipbench"]
    assert man["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= man["run_seconds"] <= 51
    for entry in man["configs"] + man["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")


def check_metric_entries(man: dict):
    cells = {w["name"] for w in man["workloads"]}
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (ROOT / "metrics" / f"{m['name']}.py").is_file()
    for m in man["end_to_end"] + man["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    confs = {c["name"] for c in man["configs"]}
    assert confs == {w["config"] for w in man["workloads"]}


def check_cell(man: dict, cell: str, arch_dir: Path = ARCHS):
    w, arch, cfg, mix = harness.resolve(man, cell, arch_dir=arch_dir)
    assert w["chips"] == 1
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert weights.param_count(arch, cfg) == cfg["params"]
    assert set(cfg["limits"]) <= {"logit_err_rms", "logit_err_max",
                                  "share_over_err_level"}
    e2e = harness.metrics_for(man, w, trace_on=False)
    layer = harness.metrics_for(man, w, trace_on=True)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert layer
    for m in layer:
        assert m["moves"] in names          # the cell reports what it moves
        assert callable(harness.reader(m["name"]))


def check_program(man: dict, cell: str, arch_dir: Path = ARCHS):
    """The program builds the cell's network (``program`` refuses one whose
    parameter tree differs from the architecture's shapes)."""
    _, arch, cfg, _ = harness.resolve(man, cell, arch_dir=arch_dir)
    arch.program(cfg)


def pins(config: str, pins_dir: Path = PINS) -> dict:
    """The pin file of ``config``; a configuration without one is an
    error that names the file to add."""
    path = Path(pins_dir) / f"{config}.json"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {config!r} has no pin file: "
                                f"add {path}")
    return json.loads(path.read_text())


def digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a, np.float32))
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


def check_weights_pinned(man: dict, config: str, pins_dir: Path = PINS,
                         arch_dir: Path = ARCHS):
    """Every leaf of ``(params, state)``, and so the order in which the key
    is split over the flattened shape tree."""
    pin = pins(config, pins_dir)
    arch, cfg = harness.configuration(man, config, arch_dir=arch_dir)
    params, state = weights.make_weights(arch, cfg, pin["seed"])
    flat = jax.tree_util.tree_flatten_with_path(
        {"params": params, "state": state})[0]
    got = {jax.tree_util.keystr(k, simple=True, separator="/"): digest(v)
           for k, v in flat}
    assert got == pin["weights"]


def check_images_pinned(man: dict, config: str, pins_dir: Path = PINS,
                        arch_dir: Path = ARCHS):
    """A request pool of the pin file's ``pool_images`` images, and the
    calibration batches."""
    pin = pins(config, pins_dir)
    _, cfg = harness.configuration(man, config, arch_dir=arch_dir)
    assert digest(weights.images(cfg, pin["seed"], pin["pool_images"])) \
        == pin["pool"]
    assert [digest(x) for x in weights.calibration_images(cfg, pin["seed"])
            ] == pin["calibration"]


def check_counts_pinned(man: dict, config: str, pins_dir: Path = PINS,
                        arch_dir: Path = ARCHS):
    """The pin file's ``conv_layers`` (convolutions the architecture
    lists for ``costs``) and ``ops_per_image`` (the model's operations per
    image, the numerator of ``mfu``), and the configuration's ``params``
    against the parameters its shapes hold."""
    pin = pins(config, pins_dir)
    arch, cfg = harness.configuration(man, config, arch_dir=arch_dir)
    assert len(list(arch.conv_layers(cfg))) == pin["conv_layers"]
    assert costs.model_ops_per_image(arch, cfg) == pin["ops_per_image"]
    assert weights.param_count(arch, cfg) == cfg["params"]
