"""Mosaic compiles of the serving kernels for a described TPU v5e.

Each test lowers one Pallas kernel at a full-width ResNet-18 layer
shape (F(4,3) and F(6,3)) and compiles it for one chip of a described
``v5e:2x2`` topology, and one sharded layer for its 2x2 (data x model)
mesh — what the chip's compiler refuses fails here, with no chip
attached. Nothing runs, so these say nothing about results or
speed. The topology is described inside a fixture (never at import), and
every test of this file shares it.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core.quantization import QuantConfig
from repro.core.winograd import WinogradSpec, _pad_amounts
from repro.kernels import backend
from repro.kernels.fused_serve import fused_gemm_output
from repro.kernels.ops import _geometry, execute_int8_sharded
from repro.kernels.wino_gemm import wino_gemm
from repro.kernels.wino_transform import input_transform, output_transform
from repro.models import resnet as RN

#: Full-width ResNet-18 (CIFAR geometry) Winograd layers, batch 8:
#: the thin-channel stem, a stage-0 conv (many tiles) and a stage-3
#: conv (widest channels, fewest tiles).
LAYERS = ("stem", "s0b0.conv1", "s3b1.conv2")
BATCH = 8
KERNELS = ("input_transform", "output_transform", "wino_gemm",
           "wino_gemm_requant", "fused_gemm_output")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # The kernels ask the backend while they are traced; the process's
    # backend is the CPU, so steer them to Mosaic for this file only and
    # drop every trace cached under either answer.
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backend, "interpret_mode", lambda: False)
        jax.clear_caches()
        yield desc
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _geom(layer: str):
    cfg = RN.ResNetConfig(width_mult=1.0)
    return next(g for g in RN.layer_geoms(cfg, BATCH) if g.layer == layer)


def _lowered(kernel: str, m: int, layer: str, dev):
    spec = WinogradSpec(m=m, r=3, base="legendre",
                        quant=QuantConfig(hadamard_bits=9))
    n, P = spec.n, spec.n ** 2
    g = _geom(layer)
    _, H, W, cin = g.x_shape
    nt_h, nt_w = (_pad_amounts(d, m, 3, "same")[2] for d in (H, W))
    T, cout = BATCH * nt_h * nt_w, g.cout

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    f32 = jnp.float32
    scales = s((P, 1), f32)
    if kernel == "input_transform":
        return input_transform.lower(s((P, T, cin), f32), s((n, n), f32),
                                     s((n, n), f32), scales,
                                     changes_base=True)
    if kernel == "output_transform":
        return output_transform.lower(s((P, T, cout), jnp.int32), scales,
                                      s((n, n), f32), s((m, n), f32), m=m,
                                      changes_base=True)
    xq, uq = s((P, T, cin), jnp.int8), s((P, cin, cout), jnp.int8)
    if kernel == "wino_gemm":
        return wino_gemm.lower(xq, uq)
    if kernel == "wino_gemm_requant":
        return wino_gemm.lower(xq, uq, requant_bits=9, deq=scales,
                               rq=scales)
    return fused_gemm_output.lower(xq, uq, scales, scales, s((n, n), f32),
                                   s((m, n), f32), m=m, requant_bits=9,
                                   changes_base=True)


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("m", [4, 6])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, m, layer):
    compiled = _lowered(kernel, m, layer, one_chip).compile()
    # One Mosaic kernel, not an emulated interpret-mode loop.
    assert compiled.as_text().count("tpu_custom_call") >= 1
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.output_size_in_bytes > 0


@pytest.mark.parametrize("kernel", ["wino_gemm", "fused_gemm_output"])
def test_int8_dots_ignore_global_matmul_precision(one_chip, kernel):
    """An fp32 reference run under "highest" matmul precision shares the
    process with the kernels; their int8 dots must still compile."""
    with jax.default_matmul_precision("highest"):
        compiled = _lowered(kernel, 4, "s0b0.conv1", one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("calibrated", [True, False])
def test_sharded_layer_compiles_for_v5e_2x2(topo, calibrated):
    """One int8 layer over the 2x2 (data x model) serving mesh: every
    Mosaic kernel must sit inside a shard_map (none is partitioned
    automatically), and the layer holds its one model-axis all_gather."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    spec = WinogradSpec(m=4, r=3, base="legendre",
                        quant=QuantConfig(hadamard_bits=9))
    g = _geom("s0b0.conv1")
    T, cin, P = BATCH * 8 * 8, g.cin, spec.n ** 2
    geom = _geometry(g.x_shape, spec.m, spec.r, "same")

    def s(shape, dtype, *axes):
        sharding = NamedSharding(mesh, PartitionSpec(*axes))
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def layer(tiles, u_q, w_scales, in_scales, h_amax):
        return execute_int8_sharded(
            tiles, u_q, w_scales, in_scales,
            h_amax if calibrated else None, spec=spec, geom=geom,
            mesh=mesh, hadamard_bits=9, model_axis="model")

    f32 = jnp.float32
    compiled = jax.jit(layer).lower(
        s((P, T, cin), f32), s((P, cin, g.cout), jnp.int8, None, None,
                               "model"),
        s((P, 1), f32), s((P, 1), f32), s((P, 1), f32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "all-gather" in text
