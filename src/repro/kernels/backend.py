"""The one backend decision of the Pallas kernels.

Every ``pallas_call`` in ``repro.kernels`` asks ``interpret_mode()``
while it is traced. The answer comes from ``jax.default_backend()``
alone: on ``cpu`` the kernel bodies run in Pallas interpret mode (the
test and demo path), on ``tpu`` they are compiled by Mosaic. There is
no switch to ask for interpret mode on a TPU, so a run on the chip
cannot fall back to emulation; a backend with no Pallas lowering here
is an error.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["interpret_mode", "smem_spec"]


def interpret_mode() -> bool:
    """True on the CPU backend (interpret mode), False on a TPU (Mosaic)."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"the Pallas kernels lower for 'tpu' (Mosaic) or run "
                       f"in interpret mode on 'cpu'; backend {platform!r} "
                       "has neither")


def smem_spec() -> pl.BlockSpec:
    """Whole-array kernel operand in SMEM: the scalars a kernel reads by
    index (flattened transform matrices, per-position scales)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)
