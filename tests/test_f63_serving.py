"""F(6,3) through the int8 serving stack: the same tiered parity
contract as F(2,3)/F(4,3) (docs/parity.md), at the spec where the
base-change conditioning advantage is largest — canonical vs Legendre
base × hadamard_bits {None, 8, 9} × fused vs staged vs dynamic, the
one-Xq bitwise tier, the engine lifecycle with checkpoint round-trip,
and the large-tile policy gate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import restore, save
from repro.conv import ConvEngine, ConvPolicy
from repro.core.quantization import QuantConfig, qmax
from repro.core.winograd import (WinogradSpec, direct_conv2d,
                                 make_matrices)
from repro.kernels.fused_serve import fused_gemm_output
from repro.kernels.ops import (_extract, _geometry, _reassemble,
                               _tiles_abs_max, execute_int8,
                               prepare_weights_int8, quantize_input,
                               scales_from_abs_max, winograd_conv2d_int8)
from repro.kernels.wino_gemm import wino_gemm

KEY = jax.random.PRNGKey(0)


def _spec(base, bits):
    return WinogradSpec(m=6, r=3, base=base,
                        quant=QuantConfig(hadamard_bits=bits))


def _prepared(x, w, spec, bits):
    """Prepared operands + calibrated Hadamard stats for one case."""
    u_q, w_scales = prepare_weights_int8(w, spec)
    tiles = _extract(x, spec.m, spec.r, spec.n, "same")
    geom = _geometry(x.shape, spec.m, spec.r, "same")
    in_scales = scales_from_abs_max(_tiles_abs_max(tiles, spec))
    h_amax = None
    if bits is not None:
        _, amax = execute_int8(tiles, u_q, w_scales, in_scales, spec=spec,
                               geom=geom, hadamard_bits=bits, with_stats=True)
        h_amax = amax.reshape(-1, 1)
    return tiles, geom, u_q, w_scales, in_scales, h_amax


@pytest.mark.parametrize("bits", [None, 8, 9])
@pytest.mark.parametrize("base", ["canonical", "legendre"])
def test_f63_fused_matches_staged(base, bits):
    """The F(6,3) parity sweep: fused and staged agree to float rounding
    on identical prepared inputs (the integer pipeline is shared), for
    both bases and every Hadamard bit-width."""
    spec = _spec(base, bits)
    x = jax.random.normal(KEY, (1, 12, 12, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 6)) * 0.2
    tiles, geom, u_q, w_s, in_s, h_amax = _prepared(x, w, spec, bits)
    kw = dict(spec=spec, geom=geom, hadamard_bits=bits)
    y_staged = execute_int8(tiles, u_q, w_s, in_s, h_amax, fused=False,
                            **kw)
    y_fused = execute_int8(tiles, u_q, w_s, in_s, h_amax, fused=True, **kw)
    assert y_staged.shape == y_fused.shape == (1, 12, 12, 6)
    np.testing.assert_allclose(np.asarray(y_staged), np.asarray(y_fused),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("base", ["canonical", "legendre"])
def test_f63_dynamic_matches_calibrated_staged(base):
    """Dynamic-scale execution equals calibrated execution when the
    calibration saw exactly this batch — the PR-1 invariant, at
    F(6,3)."""
    spec = _spec(base, 9)
    x = jax.random.normal(KEY, (1, 12, 12, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 6)) * 0.2
    y_dyn = winograd_conv2d_int8(x, w, spec, hadamard_bits=9, fused=False)
    tiles, geom, u_q, w_s, in_s, h_amax = _prepared(x, w, spec, 9)
    y_cal = execute_int8(tiles, u_q, w_s, in_s, h_amax, spec=spec,
                         geom=geom, hadamard_bits=9,
                         fused=False)
    np.testing.assert_array_equal(np.asarray(y_dyn), np.asarray(y_cal))


def test_f63_one_xq_bitwise_across_modes():
    """The one-Xq tier at F(6,3): ``execute_int8(fused=True)`` is
    BITWISE equal to the standalone kernel composition — both obtain Xq
    from the same ``quantize_input`` compile unit and dispatch the same
    module-level fused-kernel jit."""
    spec = _spec("legendre", 9)
    x = jax.random.normal(KEY, (1, 12, 12, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 6)) * 0.2
    tiles, geom, u_q, w_s, in_s, h_amax = _prepared(x, w, spec, 9)
    mats = make_matrices(spec)
    y = execute_int8(tiles, u_q, w_s, in_s, h_amax, spec=spec, geom=geom,
                     hadamard_bits=9, fused=True)
    Xq = quantize_input(tiles, in_s, spec=spec)
    deq = in_s * w_s
    rq = jnp.maximum(h_amax, 1e-12) / qmax(9)
    ref = _reassemble(
        fused_gemm_output(Xq, u_q, deq, rq, mats.CinvT, mats.APT,
                          m=spec.m, requant_bits=9,
                          changes_base=spec.changes_base),
        geom, spec.m)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


def test_f63_hadamard_integer_domain_exact():
    """The staged GEMM requant epilogue at P = 64 lands exactly on the
    XLA requant grid — the integer tier of the parity contract."""
    spec = _spec("legendre", 9)
    x = jax.random.normal(KEY, (1, 12, 12, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 6)) * 0.2
    tiles, geom, u_q, w_s, in_s, h_amax = _prepared(x, w, spec, 9)
    Xq = quantize_input(tiles, in_s, spec=spec)
    deq = in_s * w_s
    H = wino_gemm(Xq, u_q)
    hf = H.astype(jnp.float32) * deq[:, :, None]
    s_h = jnp.maximum(h_amax.reshape(-1, 1, 1), 1e-12) / qmax(9)
    ref = jnp.clip(jnp.round(hf / s_h), -qmax(9),
                   qmax(9)).astype(jnp.int32)
    out = wino_gemm(Xq, u_q, requant_bits=9, deq=deq,
                    rq=s_h[:, :, 0])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_f63_engine_serves_and_checkpoints(tmp_path):
    """ConvEngine lifecycle at F(6,3): prepare → calibrate → export →
    restore → fused serve, bit-identical across the round-trip, and
    sane vs the fp reference (the large-tile int8 pipeline is noisier
    than F(4,3) but must stay in the same ballpark as direct conv)."""
    spec = _spec("legendre", 9)
    x = jax.random.normal(KEY, (2, 12, 12, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 8)) * 0.2
    eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
    eng.prepare([("c", w)])
    with eng.calibration():
        eng.conv2d(x, None, layer="c")
    y = np.asarray(eng.conv2d(x, None, layer="c"))

    save(str(tmp_path), 0, eng.export_state())
    served = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
    served.prepare([("c", w)])
    tree, _ = restore(str(tmp_path), served.state_template())
    served.import_state(tree)
    np.testing.assert_array_equal(
        np.asarray(served.conv2d(x, None, layer="c")), y)

    y_fp = np.asarray(direct_conv2d(x, w, "same"))
    rel = float(np.sqrt(((y - y_fp) ** 2).mean())
                / np.sqrt((y_fp ** 2).mean()))
    assert rel < 0.5, rel


def test_f63_policy_large_tile_channel_gate():
    """The large-tile profitability gate: thin-channel layers fall back
    at F(6,3) but stay Winograd at F(4,3); explicit overrides win."""
    p = ConvPolicy(backend="winograd_int8", large_tile_min_channels=32,
                   overrides=(("forced", "winograd_int8"),))
    kw = dict(kernel_size=3, stride=1, spec_r=3)
    assert p.backend_for("l", in_channels=8, spec_m=6, **kw) == "direct"
    assert p.backend_for("l", in_channels=64, spec_m=6,
                         **kw) == "winograd_int8"
    assert p.backend_for("l", in_channels=8, spec_m=4,
                         **kw) == "winograd_int8"
    assert p.backend_for("forced", in_channels=8, spec_m=6,
                         **kw) == "winograd_int8"

    spec = _spec("legendre", 9)
    eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8",
                                      large_tile_min_channels=32))
    assert eng.backend_for("l", kernel_size=3, stride=1,
                           in_channels=8) == "direct"
    assert eng.backend_for("l", kernel_size=3, stride=1,
                           in_channels=64) == "winograd_int8"
