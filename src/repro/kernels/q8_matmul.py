"""Pallas TPU kernel: w8a8 quantized matmul with dequant epilogue.

The paper's symmetric-int8 scheme applied to transformer projections
(the quantization substrate used by the 9 assigned LM architectures that
have no convolutions).  ``y = (x_q @ w_q) · s_x · s_w[col]`` with int32
accumulation on the MXU and a fused per-output-channel dequant epilogue.

Grid: (M/bm, N/bn, K/bk), K innermost with output revisiting; the int32
accumulator lives in a VMEM scratch block and the epilogue fires on the
last K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend
from repro.kernels.wino_gemm import INT8_DOT_PRECISION

__all__ = ["q8_matmul"]

DEFAULT_BLOCKS = (128, 128, 512)


def _q8_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=INT8_DOT_PRECISION,
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        sx = sx_ref[0, 0]
        sw = sw_ref[0, :]                     # (bn,) per-output-channel
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * sx * sw[None, :]
                      ).astype(o_ref.dtype)


def _pad_axis(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


@functools.partial(jax.jit, static_argnames=("blocks", "out_dtype"))
def q8_matmul(x_q: jnp.ndarray, w_q: jnp.ndarray, s_x: jnp.ndarray,
              s_w: jnp.ndarray, blocks: tuple[int, int, int] | None = None,
              out_dtype=jnp.float32) -> jnp.ndarray:
    """x_q (M,K) int8 · w_q (K,N) int8, s_x scalar, s_w (N,) → (M,N) fp.

    Zero padding is exact in integer arithmetic; output is cropped.
    """
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2
    bm, bn, bk = blocks or DEFAULT_BLOCKS
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)

    xp = _pad_axis(_pad_axis(x_q, 0, bm), 1, bk)
    wp = _pad_axis(_pad_axis(w_q, 0, bk), 1, bn)
    Mp, Kp, Np = xp.shape[0], xp.shape[1], wp.shape[1]
    swp = _pad_axis(s_w.reshape(1, -1), 1, bn)
    sx = s_x.reshape(1, 1)
    k_steps = Kp // bk

    out = pl.pallas_call(
        functools.partial(_q8_kernel, k_steps=k_steps),
        grid=(Mp // bm, Np // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=backend.interpret_mode(),
        name="q8_matmul",
    )(xp, wp, sx, swp)
    return out[:M, :N]
