"""Pallas kernels vs pure-jnp oracles (interpret mode on the CPU
backend): shape/dtype sweeps with exact integer equality where the path
is integer-exact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypo import hypothesis, st
from repro.core.quantization import QuantConfig
from repro.core.winograd import WinogradSpec, direct_conv2d, make_matrices
from repro.kernels import ref as kref
from repro.kernels.ops import q8_linear, winograd_conv2d_int8
from repro.kernels.q8_matmul import q8_matmul
from repro.kernels.wino_gemm import wino_gemm
from repro.kernels.wino_transform import input_transform, output_transform

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("P,M,K,N,blocks", [
    (36, 64, 16, 24, (32, 32, 32)),
    (16, 130, 40, 72, (32, 32, 32)),    # non-divisible → padding path
    (36, 8, 3, 5, (8, 8, 8)),
])
def test_wino_gemm_exact(P, M, K, N, blocks):
    x = jax.random.randint(KEY, (P, M, K), -127, 128, jnp.int8)
    w = jax.random.randint(jax.random.PRNGKey(1), (P, K, N), -127, 128,
                           jnp.int8)
    out = wino_gemm(x, w, blocks=blocks)
    ref = kref.wino_gemm_ref(x, w)
    assert out.dtype == jnp.int32
    assert (np.asarray(out) == np.asarray(ref)).all()


@hypothesis.given(st.integers(1, 3), st.integers(1, 60), st.integers(1, 40),
                  st.integers(1, 30))
@hypothesis.settings(deadline=None, max_examples=5)
def test_wino_gemm_property(p, m, k, n):
    key = jax.random.PRNGKey(p * 1000 + m * 100 + k * 10 + n)
    x = jax.random.randint(key, (p, m, k), -127, 128, jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (p, k, n),
                           -127, 128, jnp.int8)
    out = wino_gemm(x, w, blocks=(16, 16, 16))
    assert (np.asarray(out) == np.asarray(kref.wino_gemm_ref(x, w))).all()


@pytest.mark.parametrize("M,K,N", [(64, 48, 32), (130, 100, 70), (8, 8, 8)])
def test_q8_matmul(M, K, N):
    xq = jax.random.randint(KEY, (M, K), -127, 128, jnp.int8)
    wq = jax.random.randint(jax.random.PRNGKey(2), (K, N), -127, 128,
                            jnp.int8)
    sx = jnp.float32(0.013)
    sw = jax.random.uniform(jax.random.PRNGKey(3), (N,)) * 0.02 + 1e-4
    out = q8_matmul(xq, wq, sx, sw, blocks=(32, 32, 32))
    ref = kref.q8_matmul_ref(xq, wq, sx, sw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("base", ["canonical", "legendre"])
@pytest.mark.parametrize("T,C", [(20, 9)])
def test_input_transform_kernel(base, T, C):
    spec = WinogradSpec(m=4, r=3, base=base, quant=QuantConfig.off())
    mats = make_matrices(spec)
    n = spec.n
    tiles = jax.random.normal(KEY, (n * n, T, C), jnp.float32)
    win = tiles.reshape(n, n, T, C)
    v = kref._sandwich(mats.BPT, kref._sandwich(mats.CinvT, win)) \
        if spec.changes_base else kref._sandwich(mats.BT, win)
    v = v.reshape(n * n, T, C)
    sc = (jnp.max(jnp.abs(v), axis=(1, 2)) / 127.0 + 1e-9).reshape(-1, 1)
    bpt = mats.BPT if spec.changes_base else mats.BT
    out = input_transform(tiles, mats.CinvT, bpt, sc,
                          changes_base=spec.changes_base, block=(8, 64))
    ref = kref.input_transform_ref(tiles, mats.CinvT, bpt, sc,
                                   spec.changes_base)
    assert out.dtype == jnp.int8
    # int8 results match the oracle exactly except at round-to-even
    # boundaries hit by fp reassociation — allow ±1 on <0.1% of entries
    diff = np.abs(np.asarray(out, np.int32) - np.asarray(ref, np.int32))
    assert diff.max() <= 1
    assert (diff != 0).mean() < 1e-3


@pytest.mark.parametrize("base", ["canonical", "legendre"])
def test_output_transform_kernel(base):
    spec = WinogradSpec(m=4, r=3, base=base, quant=QuantConfig.off())
    mats = make_matrices(spec)
    n = spec.n
    P, T, C = n * n, 12, 20
    h = jax.random.randint(KEY, (P, T, C), -30000, 30000, jnp.int32)
    deq = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (P, 1))) * 1e-4 \
        + 1e-6
    apt = mats.APT if spec.changes_base else mats.AT
    out = output_transform(h, deq, mats.CinvT, apt, m=4,
                           changes_base=spec.changes_base, block=(8, 16))
    ref = kref.output_transform_ref(h, deq, mats.CinvT, apt, 4,
                                    spec.changes_base)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("base", ["canonical", "legendre"])
def test_int8_conv_end_to_end(base):
    """Composed Pallas int8 conv tracks fp direct conv within dynamic-int8
    error (<10% rms on gaussian data)."""
    x = jax.random.normal(KEY, (2, 12, 12, 8))
    w = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 8, 16)) * 0.2
    spec = WinogradSpec(m=4, r=3, base=base, quant=QuantConfig.off())
    y = winograd_conv2d_int8(x, w, spec)
    ref = direct_conv2d(x, w, "same")
    assert y.shape == ref.shape
    rel = float(jnp.sqrt(jnp.mean((y - ref) ** 2)) /
                jnp.sqrt(jnp.mean(ref ** 2)))
    assert rel < 0.10


def test_q8_linear():
    x = jax.random.normal(KEY, (4, 10, 64))
    w = jax.random.normal(jax.random.PRNGKey(4), (64, 48))
    y = q8_linear(x, w)
    ref = x @ w
    rel = float(jnp.sqrt(jnp.mean((y - ref) ** 2)) /
                jnp.sqrt(jnp.mean(ref ** 2)))
    assert rel < 0.05
