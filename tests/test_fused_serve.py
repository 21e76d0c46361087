"""Fused single-pass serving kernel vs the staged pipeline: exact integer
equality in the Hadamard domain (the ``wino_gemm`` requant epilogue) and
bit-identical fp32 convolution outputs across specs, bases, Hadamard
bit-widths and non-block-aligned shapes — plus the export→restore→serve
regression for a re-pack that drops the Hadamard statistics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import restore, save
from repro.conv import ConvEngine, ConvPolicy
from repro.core.quantization import QuantConfig, qmax
from repro.core.winograd import WinogradSpec, make_matrices
from repro.kernels.fused_serve import fused_gemm_output
from repro.kernels.ops import (_extract, _geometry, _tiles_abs_max,
                               execute_int8, prepare_weights_int8,
                               scales_from_abs_max)
from repro.kernels.wino_gemm import wino_gemm
from repro.kernels.wino_transform import input_transform, output_transform

KEY = jax.random.PRNGKey(0)


def _spec(m, base, bits):
    return WinogradSpec(m=m, r=3, base=base,
                        quant=QuantConfig(hadamard_bits=bits))


def _staged_and_fused(x, w, spec, bits):
    """Run execute_int8 staged and fused on identical prepared inputs,
    with calibrated Hadamard stats when the requant stage is on."""
    u_q, w_scales = prepare_weights_int8(w, spec)
    tiles = _extract(x, spec.m, spec.r, spec.n, "same")
    geom = _geometry(x.shape, spec.m, spec.r, "same")
    in_scales = scales_from_abs_max(_tiles_abs_max(tiles, spec))
    h_amax = None
    if bits is not None:
        _, amax = execute_int8(tiles, u_q, w_scales, in_scales, spec=spec,
                               geom=geom, hadamard_bits=bits, with_stats=True)
        h_amax = amax.reshape(-1, 1)
    kw = dict(spec=spec, geom=geom, hadamard_bits=bits)
    y_staged = execute_int8(tiles, u_q, w_scales, in_scales, h_amax,
                            fused=False, **kw)
    y_fused = execute_int8(tiles, u_q, w_scales, in_scales, h_amax,
                           fused=True, **kw)
    return y_staged, y_fused


@pytest.mark.parametrize("bits", [None, 8, 9])
@pytest.mark.parametrize("base", ["canonical", "legendre"])
@pytest.mark.parametrize("m", [2, 4])
def test_fused_matches_staged(m, base, bits):
    """The fused path reproduces the staged path: the integer pipeline is
    exact (see the epilogue tests below for the Hadamard-domain proof)
    and the fp32 outputs agree to float rounding — XLA contracts the
    unrolled transform sandwich into FMAs differently in the two graphs,
    which perturbs the last bit for the base-change double sandwich."""
    x = jax.random.normal(KEY, (2, 12, 12, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16)) * 0.2
    y_staged, y_fused = _staged_and_fused(x, w, _spec(m, base, bits), bits)
    np.testing.assert_allclose(np.asarray(y_staged), np.asarray(y_fused),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bits", [9])
@pytest.mark.parametrize("shape", [
    (1, 9, 7, 5, 11),     # ragged spatial + channels
    (3, 13, 13, 3, 2),    # tiny channels, many tiles
])
def test_fused_matches_staged_ragged(bits, shape):
    """Non-block-aligned T / Cin / Cout exercise the zero-padding path."""
    B, H, W, Ci, Co = shape
    x = jax.random.normal(KEY, (B, H, W, Ci))
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 3, Ci, Co)) * 0.3
    y_staged, y_fused = _staged_and_fused(x, w,
                                          _spec(4, "legendre", bits), bits)
    np.testing.assert_allclose(np.asarray(y_staged), np.asarray(y_fused),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bits", [8, 9])
def test_gemm_requant_epilogue_exact_int(bits):
    """wino_gemm's requant epilogue lands the int32 output on exactly the
    grid the staged XLA formula produces (multi-block K accumulation and
    padding included)."""
    P, M, K, N = 16, 18, 21, 13          # ragged vs blocks=(8, 8, 8)
    x = jax.random.randint(KEY, (P, M, K), -127, 128, jnp.int8)
    w = jax.random.randint(jax.random.PRNGKey(1), (P, K, N), -127, 128,
                           jnp.int8)
    deq = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (P, 1))) * 1e-3 \
        + 1e-5
    H = wino_gemm(x, w, blocks=(8, 8, 8))
    hf = H.astype(jnp.float32) * deq[:, :, None]
    amax = jnp.max(jnp.abs(hf), axis=(1, 2), keepdims=True)
    s_h = jnp.maximum(amax, 1e-12) / qmax(bits)
    ref = jnp.clip(jnp.round(hf / s_h), -qmax(bits),
                   qmax(bits)).astype(jnp.int32)
    out = wino_gemm(x, w, blocks=(8, 8, 8),
                    requant_bits=bits, deq=deq, rq=s_h[:, :, 0])
    assert out.dtype == jnp.int32
    assert np.abs(np.asarray(out)).max() <= qmax(bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_gemm_epilogue_requires_scales():
    x = jnp.zeros((4, 8, 8), jnp.int8)
    w = jnp.zeros((4, 8, 8), jnp.int8)
    with pytest.raises(ValueError):
        wino_gemm(x, w, requant_bits=8)


@pytest.mark.parametrize("base", ["canonical", "legendre"])
@pytest.mark.parametrize("bits", [None, 9])
def test_fused_kernel_vs_staged_kernels_small_blocks(base, bits):
    """Kernel-level parity with blocks forcing a real multi-step grid:
    fused_gemm_output == wino_gemm → XLA requant → output_transform."""
    spec = _spec(4, base, bits)
    mats = make_matrices(spec)
    n, m = spec.n, spec.m
    P, T, Ci, Co = n * n, 19, 10, 13
    xq = jax.random.randint(KEY, (P, T, Ci), -127, 128, jnp.int8)
    u_q = jax.random.randint(jax.random.PRNGKey(1), (P, Ci, Co), -127, 128,
                             jnp.int8)
    deq = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (P, 1))) * 1e-3 \
        + 1e-5
    H = wino_gemm(xq, u_q)
    if bits is None:
        rq = jnp.ones_like(deq)
        ref = output_transform(H, deq, mats.CinvT, mats.APT, m=m,
                               changes_base=spec.changes_base)
    else:
        hf = H.astype(jnp.float32) * deq[:, :, None]
        amax = jnp.max(jnp.abs(hf), axis=(1, 2), keepdims=True)
        s_h = jnp.maximum(amax, 1e-12) / qmax(bits)
        Hq = jnp.clip(jnp.round(hf / s_h), -qmax(bits),
                      qmax(bits)).astype(jnp.int32)
        rq = s_h[:, :, 0]
        ref = output_transform(Hq, rq, mats.CinvT, mats.APT, m=m,
                               changes_base=spec.changes_base)
    out = fused_gemm_output(xq, u_q, deq, rq, mats.CinvT, mats.APT, m=m,
                            requant_bits=bits,
                            changes_base=spec.changes_base,
                            blocks=(8, 8, 8))
    assert out.shape == (m * m, T, Co)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_engine_fused_default_and_matches_staged():
    """ConvEngine defaults to the fused hot path for prepared+calibrated
    layers and matches the staged engine to float rounding."""
    x = jax.random.normal(KEY, (2, 16, 16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2
    spec = _spec(4, "legendre", 9)

    def serve(fused):
        eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                         fused=fused)
        eng.prepare([("c", w)])
        with eng.calibration():
            eng.conv2d(x, w, layer="c")
        return eng.conv2d(x, None, layer="c")

    assert ConvEngine(spec).fused                    # default on
    np.testing.assert_allclose(np.asarray(serve(True)),
                               np.asarray(serve(False)),
                               rtol=1e-4, atol=1e-4)


def test_engine_blocks_override_reaches_kernels():
    """``blocks=`` flows from ConvEngine through execute_int8 into the
    fused kernel (and the staged GEMM): a non-default block split forces
    a real multi-step grid and must reproduce the default-blocks serving
    output — block splits only re-tile exact integer arithmetic."""
    x = jax.random.normal(KEY, (2, 16, 16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2
    spec = _spec(4, "legendre", 9)

    def serve(blocks, fused):
        eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                         fused=fused, blocks=blocks)
        eng.prepare([("c", w)])
        with eng.calibration():
            eng.conv2d(x, w, layer="c")
        return np.asarray(eng.conv2d(x, None, layer="c"))

    for fused in (True, False):
        np.testing.assert_allclose(serve((8, 8, 8), fused),
                                   serve(None, fused),
                                   rtol=1e-4, atol=1e-4)


def test_fused_calibration_matches_dynamic():
    """PR 1's core invariant survives fusion: calibrating on the
    inference batch reproduces the dynamic-scale (staged) execution —
    bit-for-bit when serving staged (see test_conv_engine), and to
    float rounding when serving through the fused kernel."""
    x = jax.random.normal(KEY, (2, 16, 16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2
    engine = ConvEngine(_spec(4, "legendre", 9),
                        ConvPolicy(backend="winograd_int8"))
    y_dyn = engine.conv2d(x, w, layer="c")           # dynamic → staged
    engine.prepare([("c", w)])
    with engine.calibration():
        engine.conv2d(x, w, layer="c")
    y_fused = engine.conv2d(x, None, layer="c")      # calibrated → fused
    np.testing.assert_allclose(np.asarray(y_dyn), np.asarray(y_fused),
                               rtol=1e-4, atol=1e-4)


def test_export_restore_serve_after_repack(tmp_path):
    """Regression: a re-pack drops hadamard_amax (weights changed) but the
    packed+calibrated state must still export, checkpoint, restore and
    serve — with dynamic requant — instead of refusing to checkpoint."""
    x = jax.random.normal(KEY, (2, 12, 12, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2
    w2 = w * 1.7
    spec = _spec(4, "legendre", 9)
    engine = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
    engine.prepare([("c", w)])
    with engine.calibration():
        engine.conv2d(x, None, layer="c")
    engine.prepare([("c", w2)])                 # re-pack: drops h_amax
    pk = engine.packed["c"]
    assert pk.calibrated and pk.hadamard_amax is None
    y_before = engine.conv2d(x, None, layer="c")    # dynamic requant

    save(str(tmp_path), 1, engine.export_state())   # must not raise

    served = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
    served.prepare([("c", w2)])
    tree, step = restore(str(tmp_path), served.state_template())
    served.import_state(tree)
    rpk = served.packed["c"]
    assert rpk.calibrated and rpk.hadamard_amax is None   # sentinel decoded
    np.testing.assert_array_equal(np.asarray(rpk.in_scales),
                                  np.asarray(pk.in_scales))
    y_after = served.conv2d(x, None, layer="c")
    np.testing.assert_array_equal(np.asarray(y_before), np.asarray(y_after))


def test_export_mixed_hadamard_states(tmp_path):
    """An engine where one layer kept its Hadamard stats and another lost
    them to a re-pack exports one uniform tree structure (the sentinel),
    and both layers restore to their exact states."""
    x = jax.random.normal(KEY, (2, 12, 12, 8))
    w_a = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2
    w_b = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 8, 12)) * 0.2
    spec = _spec(4, "legendre", 9)
    engine = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
    engine.prepare([("a", w_a), ("b", w_b)])
    with engine.calibration():
        engine.conv2d(x, None, layer="a")
        engine.conv2d(x, None, layer="b")
    engine.prepare_layer("b", w_b * 2.0)        # drops b's h_amax only
    assert engine.packed["a"].hadamard_amax is not None
    assert engine.packed["b"].hadamard_amax is None

    save(str(tmp_path), 1, engine.export_state())
    served = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
    served.prepare([("a", w_a), ("b", w_b * 2.0)])
    tree, _ = restore(str(tmp_path), served.state_template())
    served.import_state(tree)
    np.testing.assert_array_equal(
        np.asarray(served.packed["a"].hadamard_amax),
        np.asarray(engine.packed["a"].hadamard_amax))
    assert served.packed["b"].hadamard_amax is None


def test_uncalibrated_export_still_rejected():
    """The hard error stays for the real failure mode: missing in_scales."""
    _, w = jax.random.normal(KEY, (1,)), \
        jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2
    engine = ConvEngine(_spec(4, "legendre", 9),
                        ConvPolicy(backend="winograd_int8"))
    engine.prepare([("c", w)])
    with pytest.raises(ValueError):
        engine.export_state()
