"""Pallas TPU kernel: single-pass fused int8 serving epilogue.

The staged serving path materializes the full ``(P, T, Cout)`` int32 GEMM
output to HBM, reads it back to requantize the Hadamard products in fp32
XLA glue, writes it again, and reads it a third time for the output
transform — three extra HBM passes over the largest tensor in the
pipeline.  This kernel collapses GEMM → Hadamard requant → output
transform into ONE ``pallas_call``:

    grid = (T/bm, Cout/bn, Cin/bk)          (K innermost, sequential)

    per (i, j) block:
      k loop   : acc[p] += x[p, i-block] @ w[p, j-block]   (MXU int8·int8)
      last k   : for each position p — dequant by deq[p], requant onto the
                 8/9-bit grid with the calibrated scale rq[p], dequant back
                 (all in-register), then the output-transform sandwich
                 C⁻ᵀ(·)C⁻¹ → A_Cᵀ(·)A_C over the n×n tile window
                 → write the (m², bm, bn) fp32 output block.

HBM traffic per call: read Xq + u_q once, write the (m², T, Cout)
output once.  Zero fp32 intermediates in HBM.

The per-position accumulator lives in a VMEM scratch buffer that persists
across the sequential K grid steps (the canonical Pallas revisiting
schedule, same as ``wino_gemm`` — just with the P axis folded into the
block so the epilogue sees every position of an (i, j) tile). Scales and
transform matrices are SMEM scalars; every epilogue term is a scalar
times one (bm, bn) plane.

Exactness: the requant math is ``requant_plane`` (shared with the
``wino_gemm`` epilogue) and the transform sandwich is
``wino_transform.output_planes`` (shared with the staged output kernel),
applied in the same order with the same fp32 operands as the staged
path.  The integer pipeline — GEMM accumulation and the Hadamard-domain
requantized values — is therefore *exactly* equal to staged
``execute_int8`` (asserted in tests); the fp32 spatial outputs agree to
float rounding (~1e-5 rel): XLA contracts the unrolled multiply-adds
into FMAs differently in the two graphs, which perturbs the last bit of
the base-change sandwich.  Requant needs the *calibrated* per-position
Hadamard abs-max: the dynamic requant reduction spans the whole
(T, Cout) plane, which a tiled kernel cannot see, so
calibration/``with_stats`` stay on the staged path (``kernels.ops``
handles the fallback).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import telemetry
from repro.core.quantization import qmax
from repro.kernels import backend
from repro.kernels.backend import smem_spec
from repro.kernels.wino_gemm import (INT8_DOT_PRECISION, _pad_to,
                                     default_blocks, requant_plane,
                                     validate_blocks)
from repro.kernels.wino_transform import output_planes, scalars

__all__ = ["fused_gemm_output"]

# Range contract: the (P, bm, bn) VMEM scratch accumulates int8×int8
# products over the full K = Cin grid in int32, and the epilogue casts
# it to fp32 inside ``requant_plane``. The static certifier
# (``repro.analysis.ranges``) proves per-config that the worst-case
# accumulator stays within ``wino_gemm.INT32_ACC_LIMIT`` (no overflow)
# and ``wino_gemm.FP32_EXACT_INT_LIMIT`` (the cast is exact, so the
# fused requant is faithful to the staged integer formula); the
# ConvEngine ``certify=`` gate refuses unprovable configs before any
# launch reaches this kernel.


def _fused_kernel(x_ref, w_ref, deq_ref, rq_ref, cinvt_ref, apt_ref,
                  out_ref, acc_ref, *, n: int, m: int, qm: int | None,
                  changes_base: bool):
    """One (bm, bn) tile×channel block: K-accumulated batched GEMM, then
    requant + output transform on the final K step."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=INT8_DOT_PRECISION,
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        planes = []
        deq, rq = scalars(deq_ref, n * n), scalars(rq_ref, n * n)
        for p in range(n * n):
            if qm is None:
                # No Hadamard stage: plain dequant (= staged
                # output_transform with deq scales).
                planes.append(acc_ref[p].astype(jnp.float32) * deq[p])
            else:
                q = requant_plane(acc_ref[p], deq[p], rq[p], qm)
                planes.append(q * rq[p])
        for p, y in enumerate(output_planes(planes, cinvt_ref, apt_ref, n, m,
                                            changes_base)):
            out_ref[p] = y


@functools.partial(jax.jit, static_argnames=("m", "requant_bits",
                                             "changes_base", "blocks"))
def fused_gemm_output(xq: jnp.ndarray, u_q: jnp.ndarray, deq: jnp.ndarray,
                      rq: jnp.ndarray, cinvt: jnp.ndarray,
                      apt: jnp.ndarray, *, m: int,
                      requant_bits: int | None = None,
                      changes_base: bool = True,
                      blocks: tuple[int, int, int] | None = None
                      ) -> jnp.ndarray:
    """Fused GEMM → Hadamard requant → output transform.

    xq: (P, T, Cin) int8 (from ``input_transform``), u_q: (P, Cin, Cout)
    int8 prepared weights, deq/rq: (P, 1) fp32 per-position dequant /
    requant scales (``rq`` ignored when ``requant_bits`` is None — pass
    ones), cinvt (n, n) / apt (m, n) transform operands
    → (m², T, Cout) fp32 spatial output tiles, position-major.

    ``blocks`` (bm, bn, bk) overrides ``wino_gemm.default_blocks(P)`` —
    the per-shape tuning knob, reachable from ``ops.execute_int8``,
    ``ConvEngine(blocks=...)`` and the ``repro.conv.autotune``
    per-(spec, shape) tuner; numerics are block-independent. At F(6,3)
    the P=64-position scratch accumulator changes the optimum: the
    MXU-aligned (128, 128) block would pin a 4 MiB int32 accumulator in
    VMEM before counting operands, so ``default_blocks`` halves bm/bk
    there and the autotuner searches the rest.

    Shapes need not be block-aligned: T/Cin/Cout are zero-padded (exact
    in integer arithmetic; padded rows are cropped from the output).
    Requires calibrated requant scales when ``requant_bits`` is set —
    the dynamic reduction cannot run inside a tiled kernel.
    """
    P, T, K = xq.shape
    P2, K2, N = u_q.shape
    assert P == P2 and K == K2, (xq.shape, u_q.shape)
    n = int(round(P ** 0.5))
    assert n * n == P, P
    bm, bn, bk = validate_blocks(blocks) or default_blocks(P)
    bm, bn, bk = min(bm, T), min(bn, N), min(bk, K)

    xp = _pad_to(_pad_to(xq, 1, bm), 2, bk)
    wp = _pad_to(_pad_to(u_q, 1, bk), 2, bn)
    Tp, Kp, Np = xp.shape[1], xp.shape[2], wp.shape[2]

    qm = None if requant_bits is None else qmax(requant_bits)
    grid = (Tp // bm, Np // bn, Kp // bk)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, n=n, m=m, qm=qm,
                          changes_base=changes_base),
        grid=grid,
        in_specs=[
            pl.BlockSpec((P, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((P, bk, bn), lambda i, j, k: (0, k, j)),
            smem_spec(), smem_spec(), smem_spec(), smem_spec(),
        ],
        out_specs=pl.BlockSpec((m * m, bm, bn), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((m * m, Tp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, bm, bn), jnp.int32)],
        interpret=backend.interpret_mode(),
        name=telemetry.GEMM_OUTPUT,
    )(xp, wp, deq.reshape(-1), rq.reshape(-1), cinvt.reshape(-1),
      apt.reshape(-1))
    return out[:, :T, :N]
