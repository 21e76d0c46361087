"""Tile extraction (``kernels.ops._extract``) against the gather oracle.

``_extract`` builds the position-major ``(n², T, C)`` tile tensor from
unit-stride slices of the padded activation. The oracle is the gather
construction it replaced: ``_extract_tiles_1d_axis`` (``jnp.take``) on H,
then on W, then the same transpose. Extraction is exact data movement, so
the two must agree bit for bit.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.winograd import _extract_tiles_1d_axis, _pad_amounts
from repro.kernels.ops import _extract

R = 3


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _gather_oracle(x, m, n, padding):
    N, H, W, C = x.shape
    lo_h, hi_h, nt_h, _ = _pad_amounts(H, m, R, padding)
    lo_w, hi_w, nt_w, _ = _pad_amounts(W, m, R, padding)
    xp = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))
    t = _extract_tiles_1d_axis(xp, xp.shape[1], m, n, nt_h, axis=1)
    t = _extract_tiles_1d_axis(t, t.shape[3], m, n, nt_w, axis=3)
    t = jnp.transpose(t, (2, 4, 0, 1, 3, 5))        # (n,n,N,th,tw,C)
    return t.reshape(n * n, N * nt_h * nt_w, C)


@pytest.mark.parametrize("padding", ["same", "valid"])
@pytest.mark.parametrize("hwc", [(32, 32, 3), (16, 16, 8), (4, 4, 16),
                                 (7, 7, 5)], ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("m", [2, 4, 6], ids=lambda m: f"F{m}3")
def test_extract_matches_gather_oracle(m, hwc, padding):
    n = m + R - 1
    x = jnp.asarray(np.random.default_rng(sum(hwc) + m)
                    .standard_normal((2, *hwc), dtype=np.float32))
    want = _gather_oracle(x, m, n, padding)
    got = _extract(x, m, R, n, padding)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_extract_lowers_to_unit_stride_slices():
    """At the F(4,3) stage-0 geometry of full-width ResNet-18 the lowered
    program has no gather (a loop of dynamic slices on a TPU v5e, most of
    the served device time) and no slice at a stride above 1 (which hung
    the served program on a TPU v5e)."""
    x = jax.ShapeDtypeStruct((8, 32, 32, 64), jnp.float32)
    text = _extract.lower(x, 4, R, 6, "same").as_text()
    ops = set(re.findall(r"stablehlo\.(\w+)", text))
    assert "slice" in ops
    assert not {"gather", "dynamic_slice"} & ops
    limits = re.findall(r"stablehlo\.slice %\w+ \[([^\]]*)\]", text)
    assert limits and all(d.count(":") == 1 for l in limits
                          for d in l.split(","))
