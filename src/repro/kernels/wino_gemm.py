"""Pallas TPU kernel: Winograd-domain batched int8 GEMM (+ optional
Hadamard-requant epilogue).

This is >90% of the FLOPs of a Winograd convolution: for each of the
``P = n²`` Winograd positions, an independent GEMM over channels

    out[p] = x[p] @ w[p]        x: (P, M, K) int8, w: (P, K, N) int8
                                out: (P, M, N) int32

where ``M = batch·tiles``, ``K = C_in``, ``N = C_out``.  int8×int8→int32
is MXU-native on TPU v5e; the kernel tiles M/N/K to 128-aligned VMEM
blocks and accumulates in the int32 output block across the K grid axis
(output revisiting on the innermost axis), the canonical Pallas matmul
schedule.

The optional *requant epilogue* runs the paper's 8/9-bit Hadamard stage
in-register on the final K grid step: the int32 accumulator is
dequantized by the calibrated per-position ``deq = in_scale·w_scale``,
requantized onto the 2^b-level grid with the calibrated per-position
requant scale, and written out as int32 on that grid — replacing the
fp32 XLA glue that used to cost two extra HBM passes over the largest
tensor in the pipeline.  The arithmetic (fp32 multiply, round-half-even,
clip) is exactly the staged formula, so the epilogue output is
bit-identical to the staged requant.

On a TPU the kernel is compiled by Mosaic; on the CPU backend it runs
in Pallas interpret mode (``kernels.backend``), where correctness is
validated against ``ref.wino_gemm_ref`` (exact integer equality).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro import telemetry
from repro.core.quantization import qmax
from repro.kernels import backend
from repro.kernels.backend import smem_spec

__all__ = ["wino_gemm", "requant_plane", "DEFAULT_BLOCKS",
           "INT8_DOT_PRECISION",
           "default_blocks", "validate_blocks", "MAX_BLOCK",
           "INT32_ACC_LIMIT", "FP32_EXACT_INT_LIMIT",
           "max_abs_accumulator"]

#: Largest magnitude the kernels' int32 accumulator can hold. Both this
#: kernel's output-revisiting accumulation and ``fused_serve``'s
#: (P, bm, bn) VMEM scratch accumulate int8×int8 products over the full
#: K = Cin grid in int32 — the static range certifier
#: (``repro.analysis.ranges``) proves configs against exactly this bound.
INT32_ACC_LIMIT = 2 ** 31 - 1

#: Precision of every int8×int8→int32 dot in the kernels, stated so a
#: process-wide ``jax_default_matmul_precision`` (e.g. "highest" for an
#: fp32 reference) never reaches them: Mosaic refuses an fp32-precision
#: contraction of int8 operands, and integer products are exact anyway.
INT8_DOT_PRECISION = jax.lax.Precision.DEFAULT

#: Largest integer magnitude fp32 represents exactly (24-bit mantissa).
#: ``requant_plane`` casts the int32 accumulator to fp32 before the
#: Hadamard requant multiply; accumulators beyond this limit round in
#: the cast itself, so the requant stops being faithful to the staged
#: integer formula. The certifier's hadamard_bits-safe verdict proves
#: the worst-case accumulator stays under it.
FP32_EXACT_INT_LIMIT = 2 ** 24


def max_abs_accumulator(K: int, bits: int = 8) -> int:
    """Worst-case |int32 accumulator| after a K-deep int8×int8 GEMM
    reduction: every operand pinned to ±qmax(bits) with aligned signs.
    Exact and attained (see the adversarial tests) — K·127² for int8."""
    return K * qmax(bits) ** 2

# MXU-aligned defaults: the systolic array is 128×128; K blocks of 256
# halve the number of grid steps at an acceptable VMEM footprint
# (128·256 + 256·128 int8 + 128·128 int32 ≈ 128 KiB per step).
DEFAULT_BLOCKS = (128, 128, 256)

#: Upper bound any single block dimension may take. Block dims beyond
#: this are never profitable on TPU (VMEM is ~16 MiB) and usually
#: indicate a units mistake (e.g. passing a channel count × dtype size);
#: they now fail fast instead of reaching ``pallas_call``.
MAX_BLOCK = 4096


def default_blocks(P: int | None = None) -> tuple[int, int, int]:
    """Default (bm, bn, bk) for the GEMM/fused kernels at ``P = n²``.

    ``DEFAULT_BLOCKS`` is tuned for F(2,3)/F(4,3) (P ≤ 36). The fused
    serving kernel keeps a (P, bm, bn) int32 accumulator in VMEM scratch
    across the K grid, so its footprint scales with P: at F(6,3)'s
    P = 64 the MXU-aligned (128, 128) block alone pins 4 MiB of scratch
    before counting the int8 operand blocks — halving bm and bk keeps a
    grid step near the F(4,3) footprint while bn stays lane-aligned.
    Per-(spec, shape) winners beyond this heuristic come from
    ``repro.conv.autotune``.
    """
    if P is not None and P >= 64:
        return (64, 128, 128)
    return DEFAULT_BLOCKS


def validate_blocks(blocks) -> tuple[int, int, int] | None:
    """Validate a user-supplied (bm, bn, bk) override; None passes through.

    Raises ``ValueError`` on malformed shapes, non-integers,
    non-positive entries, or absurd (> ``MAX_BLOCK``) entries — the
    kernels min-clamp blocks *down* to the operand shape (legitimate:
    one candidate covers every smaller shape) but must never silently
    accept a meaningless split.
    """
    if blocks is None:
        return None
    try:
        bl = tuple(blocks)
    except TypeError:
        raise ValueError(f"blocks must be a (bm, bn, bk) triple, got "
                         f"{blocks!r}")
    if len(bl) != 3:
        raise ValueError(f"blocks must be a (bm, bn, bk) triple, got "
                         f"{blocks!r}")
    for b in bl:
        if isinstance(b, bool) or not isinstance(b, (int, np.integer)):
            raise ValueError(f"blocks entries must be ints, got {blocks!r}")
        if b < 1:
            raise ValueError(f"blocks entries must be >= 1, got {blocks!r}")
        if b > MAX_BLOCK:
            raise ValueError(f"blocks entries must be <= {MAX_BLOCK}, got "
                             f"{blocks!r}")
    return tuple(int(b) for b in bl)


def requant_plane(acc: jnp.ndarray, deq: jnp.ndarray, rq: jnp.ndarray,
                  qm: int) -> jnp.ndarray:
    """One position's Hadamard requant: int32 accumulator → fp32 values on
    the signed ``qm``-grid.  ``deq``/``rq`` are that position's dequant and
    requant scales (scalars).  Shared by the GEMM epilogue and the fused
    serving kernel so both reproduce the staged XLA formula bit-for-bit
    (fp32 multiply → round-half-even → clip)."""
    hf = acc.astype(jnp.float32) * deq
    return jnp.clip(jnp.round(hf / rq), -qm, qm)


def _gemm_kernel(x_ref, w_ref, o_ref):
    """One (bm, bn) output block of one position; accumulates over k."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0, ...] += jax.lax.dot_general(
        x_ref[0], w_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=INT8_DOT_PRECISION,
        preferred_element_type=jnp.int32,
    )


def _gemm_requant_kernel(x_ref, w_ref, deq_ref, rq_ref, o_ref, *, qm: int):
    """GEMM block with the Hadamard-requant epilogue on the last K step."""
    p = pl.program_id(0)                # this block's Winograd position

    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0, ...] += jax.lax.dot_general(
        x_ref[0], w_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=INT8_DOT_PRECISION,
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _epilogue():
        q = requant_plane(o_ref[0, ...], deq_ref[p], rq_ref[p], qm)
        o_ref[0, ...] = q.astype(jnp.int32)


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


@functools.partial(jax.jit, static_argnames=("blocks", "requant_bits"))
def wino_gemm(x: jnp.ndarray, w: jnp.ndarray,
              blocks: tuple[int, int, int] | None = None,
              requant_bits: int | None = None,
              deq: jnp.ndarray | None = None,
              rq: jnp.ndarray | None = None) -> jnp.ndarray:
    """Batched per-position GEMM. x: (P,M,K) int8, w: (P,K,N) int8 → int32.

    Shapes need not be block-aligned; inputs are zero-padded (zeros are
    exact in integer arithmetic) and the output is cropped.

    With ``requant_bits`` set, the Hadamard-requant epilogue runs on the
    final K grid step: ``deq`` (P, 1) fp32 dequant scales
    (in_scale·w_scale) and ``rq`` (P, 1) fp32 requant scales (the
    calibrated ``max(h_amax, eps)/qmax(bits)``) must be passed, and the
    int32 output lands on the signed ``2^bits``-level grid — no fp32
    intermediate ever reaches HBM.
    """
    P, M, K = x.shape
    P2, K2, N = w.shape
    assert P == P2 and K == K2, (x.shape, w.shape)
    if requant_bits is not None and (deq is None or rq is None):
        raise ValueError("requant epilogue needs deq and rq scales")
    bm, bn, bk = validate_blocks(blocks) or default_blocks(P)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)

    xp = _pad_to(_pad_to(x, 1, bm), 2, bk)
    wp = _pad_to(_pad_to(w, 1, bk), 2, bn)
    Mp, Kp, Np = xp.shape[1], xp.shape[2], wp.shape[2]

    grid = (P, Mp // bm, Np // bn, Kp // bk)
    gemm_specs = [
        pl.BlockSpec((1, bm, bk), lambda p, i, j, k: (p, i, k)),
        pl.BlockSpec((1, bk, bn), lambda p, i, j, k: (p, k, j)),
    ]
    if requant_bits is None:
        kernel, in_specs, operands = _gemm_kernel, gemm_specs, (xp, wp)
    else:
        kernel = functools.partial(_gemm_requant_kernel,
                                   qm=qmax(requant_bits))
        # The per-position scales are read as scalars from SMEM.
        in_specs = gemm_specs + [smem_spec(), smem_spec()]
        operands = (xp, wp, deq.reshape(-1), rq.reshape(-1))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda p, i, j, k: (p, i, j)),
        out_shape=jax.ShapeDtypeStruct((P, Mp, Np), jnp.int32),
        interpret=backend.interpret_mode(),
        name=telemetry.GEMM,
    )(*operands)
    return out[:, :M, :N]
