"""Operations and bytes of the Mosaic kernel calls, against the abstract
operands of the calls the program makes at full width (traced on the CPU,
nothing runs), and the model's operations per image."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs
from chipbench.harness import ROOT, architecture
from chipbench.tests.manifest_checks import MANIFEST, check_counts_pinned

_HLO = {"int8": "s8", "int32": "s32", "float32": "f32"}


def _config(name):
    cfg = json.loads((ROOT / "configs" / f"{name}.json").read_text())
    return architecture(cfg["arch"]), cfg


CONFIGS = [c["name"] for c in MANIFEST["configs"]]


def _pallas_calls(jaxpr):
    """Every pallas_call equation, descending into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


def _sig(avals):
    return [(_HLO[str(a.dtype)], tuple(a.shape)) for a in avals]


def _layer_calls(m, layer, batch=256):
    """The two kernel calls of one served int8 layer, as (operands,
    results) in the trace's dtype names."""
    from repro.core.quantization import QuantConfig
    from repro.core.winograd import WinogradSpec
    from repro.kernels.ops import winograd_conv2d_int8
    arch, cfg = _config("resnet18-cifar-f23")  # the layer shapes
    _, ho, wo, k, cin, cout, _ = next(
        c for c in arch.conv_layers(cfg) if c[0] == layer)
    spec = WinogradSpec(m=m, r=3, base="legendre",
                        quant=QuantConfig(hadamard_bits=9))
    P = spec.n ** 2
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct((batch, ho, wo, cin), f32),
            jax.ShapeDtypeStruct((P, 1), f32),
            jax.ShapeDtypeStruct((P, cin, cout), jnp.int8),
            jax.ShapeDtypeStruct((P, 1), f32),
            jax.ShapeDtypeStruct((P, 1), f32)]
    fn = lambda x, s_in, u_q, s_w, h: winograd_conv2d_int8(
        x, None, spec, "same", in_scales=s_in, u_q=u_q, w_scales=s_w,
        hadamard_bits=9, h_amax=h, fused=True)
    calls = list(_pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr))
    assert len(calls) == 2
    tiles = batch * math.ceil(ho / m) * math.ceil(wo / m)
    return [(_sig(e.invars[i].aval for i in range(len(e.invars))),
             _sig(v.aval for v in e.outvars)) for e in calls], \
        (P, tiles, cin, cout, m)


@pytest.mark.parametrize("m,layer", [(4, "s0b0.conv1"), (6, "s3b1.conv2")])
def test_kernel_cost_matches_the_call_shapes(m, layer):
    (inp, fused), (P, T, cin, cout, m) = _layer_calls(m, layer)
    n = math.isqrt(P)
    smem = 4 * (n * n + n * n + P)      # C^-T, B^T, per-position scales
    kind, ops, moved = costs.kernel_cost(*inp, changes_base=True)
    assert kind == "input_transform"
    assert moved == P * T * cin * (4 + 1) + smem
    assert ops == T * cin * (2 * 4 * n ** 3 + 3 * P)
    kind, ops, moved = costs.kernel_cost(*fused, changes_base=True)
    assert kind == "fused_gemm_output"
    smem = 4 * (P + P + n * n + m * n)  # deq, rq, C^-T, A^T
    assert moved == P * T * cin + P * cin * cout + 4 * m * m * T * cout \
        + smem
    assert ops >= 2 * P * T * cin * cout


def test_signature_parsing_reads_result_then_operands():
    text = ("%custom-call.3 = f32[16,256,512]{2,1,0} custom-call(s8[64,256,"
            "512]{2,1,0} %a, s8[64,512,512]{2,1,0} %b, f32[64]{0} %c), "
            "custom_call_target=\"tpu_custom_call\"")
    sig = costs.parse_shapes(text)
    kind, ops, moved = costs.kernel_cost(sig[1:], sig[:1], True)
    assert kind == "fused_gemm_output"
    assert moved == 64 * 256 * 512 + 64 * 512 * 512 \
        + 4 * 16 * 256 * 512 + 4 * 64


def test_least_time_names_its_bound():
    peak = costs.peaks("TPU v5 lite")
    t, by = costs.least_time(10 ** 12, 10 ** 6, peak)
    assert by == "ops" and t == pytest.approx(10 ** 12 / 393e12)
    t, by = costs.least_time(10, 819 * 10 ** 6, peak)
    assert by == "bytes" and t == pytest.approx(1e-3)
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name", CONFIGS)
def test_model_ops_and_parameters(name):
    """Against each configuration's own pin file: ResNet-18's pin 20
    convolutions (14 stride-1 3x3, 3 stride-2 3x3, 3 1x1) and 1,110,845,440
    operations per image with the head; its file states 11,173,962
    parameters."""
    check_counts_pinned(MANIFEST, name)
