"""Rehearsals of a run on the CPU: the harness around a stand-in for the
served program (the plain reference, jitted), with and without a fault
planted in the timed path, the control in the program's place, and the
refusal to report anything without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from chipbench import harness
from chipbench.control import reference_system
from chipbench.harness import ROOT

TESTDATA = ROOT / "testdata"
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


def _manifest(config_file, traffic_name):
    return {
        "configs": [{"name": "c", "file": config_file}],
        "workloads": [{"name": "w", "config": "c", "traffic": traffic_name,
                       "chips": 1}],
        "end_to_end": [
            {"name": "images_per_s", "unit": "images/s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": []}


def _run(config_file, traffic_name, factory, seconds=1.0,
         traffic_dir=TESTDATA / "traffic", arch_dir=ROOT / "archs"):
    man = _manifest(config_file, traffic_name)
    cell, arch, cfg, mix = harness.resolve(man, "w", traffic_dir=traffic_dir,
                                           arch_dir=arch_dir)
    out = harness.run(man, cell, arch, cfg, mix, 2**31 + 29, seconds, False,
                      time.perf_counter(), factory, jax.devices())
    json.dumps(out, allow_nan=False)       # one valid JSON line
    assert list(out) == RESULT_KEYS
    return out


TINY = "chipbench/testdata/configs/tiny-f43.json"
TINY_SHARE = "chipbench/testdata/configs/tiny-f43-share.json"
TINY_BOTTLENECK = "chipbench/testdata/configs/tiny-bottleneck.json"


@pytest.mark.parametrize("config,arch_dir", [
    (TINY, ROOT / "archs"), (TINY_SHARE, ROOT / "archs"),
    (TINY_BOTTLENECK, TESTDATA / "archs")],
    ids=["rms-and-max", "share-over-level", "bottleneck-stand-in"])
def test_rehearsal_last_line(config, arch_dir):
    out = _run(config, "tiny-closed", reference_system(), arch_dir=arch_dir)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == 1
    assert out["checks"]["compiles_in_window"]["value"] == 0


def _first_reversed(y):
    return y.at[0].set(y[0, ::-1])


def _rows_rolled(y):
    return jax.numpy.roll(y, 1, axis=0)


@pytest.mark.parametrize("config,fault,number", [
    (TINY, _first_reversed, "logit_err_max"),
    (TINY_SHARE, _rows_rolled, "share_over_err_level")],
    ids=["first-answer-reversed", "answers-to-wrong-requests"])
def test_altered_answer_is_not_correct(config, fault, number):
    """A fault where answers are produced, against the number that judges
    it: the first row of every batch comes back with its logits reversed
    (the largest error of one answer), or every batch's answers come back
    one row out of place, each to another request (the share of answers
    over the error level)."""
    out = _run(config, "tiny-closed", reference_system(fault=fault))
    assert out["correct"] is False
    c = out["checks"][number]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("config", ["resnet18-cifar-f43",
                                    "resnet18-cifar-f23"])
def test_control_is_not_correct(config):
    """The control, the reference at 4 bits in the program's place, at the
    cell's widths and image size (a smaller batch and pool): the cell's
    limits refuse it."""
    out = _run(f"chipbench/configs/{config}.json",
               "full-width-closed", reference_system(bits=4), seconds=0.5)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values()), \
        out["checks"]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "f23-offline-b256", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in
                   proc.stdout.splitlines())


def test_no_tpu_no_result():
    proc = _cli(ROOT.parent)
    _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(ROOT.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_cli(tmp_path))


def test_answer_errors_by_hand():
    ref = np.array([[3.0, 4.0, 0.0], [0.0, 1.0, 0.0]])
    served = np.array([[3.0, 4.0, 0.0], [0.6, 0.5, 0.0]])
    e = harness.answer_errors(served, ref, err_level=0.5)
    # Row 0 exact (norm 5); row 1 off by |(0.6, -0.5, 0)| at norm 1.
    assert e["logit_err_max"] == pytest.approx(np.hypot(0.6, 0.5))
    assert e["logit_err_rms"] == pytest.approx(
        np.hypot(0.6, 0.5) / np.sqrt(25 + 1))
    # One answer of two is off by 0.78 > 0.5.
    assert e["share_over_err_level"] == 0.5
    assert "share_over_err_level" not in harness.answer_errors(served, ref)
