"""``setup_compile_s``: the program's compile counter summed over its
set-up phases, and nothing where the program keeps no counter."""
import sys
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from chipbench.harness import reader


def test_sums_the_set_up_phases():
    from repro import telemetry
    lines = []
    ctx = NS(log=lines.append)
    before = reader("setup_compile_s")(ctx) or 0.0
    with telemetry.setup_phase("pack"):
        jax.jit(lambda v: v - 2.0)(np.ones((3,), np.float32))
    with telemetry.setup_phase("restore"):
        pass
    phases = telemetry.snapshot()["phases"]
    expected = sum(phases[p][k] for p in telemetry.SETUP_PHASES
                   if p in phases for k in ("trace_s", "lower_s",
                                            "backend_s"))
    got = reader("setup_compile_s")(ctx)
    assert got == pytest.approx(expected) and got > before
    assert any(line.startswith("setup_compile_s: pack: trace ")
               for line in lines)


def test_none_without_the_program_counter(monkeypatch):
    """As against a program that has no ``repro.telemetry`` module."""
    import repro
    monkeypatch.delattr(repro, "telemetry", raising=False)
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert reader("setup_compile_s")(NS(log=print)) is None
