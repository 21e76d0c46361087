"""Operations and bytes, worked out from shapes: the model's operations per
image (from its architecture module's convolutions and head), and the
least time of each Mosaic kernel call as the trace names its operands. The
peaks come from ``peaks.json``.
"""
from __future__ import annotations

import json
import math
import re
from pathlib import Path

_PEAKS = Path(__file__).resolve().parent / "peaks.json"

_ITEMSIZE = {"s8": 1, "u8": 1, "s16": 2, "bf16": 2, "f16": 2, "s32": 4,
             "u32": 4, "f32": 4, "pred": 1}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device not in the table
    is an error, never a default."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def model_ops_per_image(arch, cfg: dict) -> int:
    """2 x the multiply-adds of every convolution (``arch.conv_layers``) as
    a direct convolution, plus the linear head (its weight, ``head`` in
    ``arch.shapes``). Winograd's saved multiplications count as the
    model's, as is usual."""
    convs = sum(2 * ho * wo * k * k * cin * cout
                for _, ho, wo, k, cin, cout, _ in arch.conv_layers(cfg))
    return convs + 2 * math.prod(arch.shapes(cfg)[0]["head"])


def parse_shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """Every ``dtype[d0,d1,...]`` in an HLO signature, in order."""
    out = []
    for dt, dims in re.findall(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]", text):
        if dt in _ITEMSIZE:
            out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def nbytes(shapes) -> int:
    return sum(_ITEMSIZE[dt] * math.prod(dims) for dt, dims in shapes)


def sandwich_ops(n_in: int, n_out: int) -> int:
    """Operations of one separable sandwich L.X.R^T for one (T, C)
    position: 2 * n_out * n_in^2 multiply-adds of 2 ops each."""
    return 2 * 2 * n_out * n_in * n_in


def kernel_cost(operands, results, changes_base: bool
                ) -> tuple[str, int, int]:
    """``(kind, ops, bytes)`` of one Mosaic call from its operand and
    result shapes (``parse_shapes``), bytes counting every operand read
    once and every result written once.

    * input transform: one f32 ``(n^2, T, C)`` operand → s8 ``(n^2, T, C)``:
      an n x n sandwich for the base change (where the base is not the
      canonical one) and one for B^T, and the quantisation (divide,
      round, clip: 3 ops) per element;
    * fused GEMM + output transform: s8 ``(P, T, K)`` and s8 ``(P, K, N)``
      → f32 ``(m^2, T, N)``: 2 P T K N for the batched GEMM, and the
      requantisation (4 ops per element) and the output sandwiches
      (the n x n base change, then m x n) per ``(T, N)`` position.
    """
    big = [s for s in operands if len(s[1]) == 3]
    moved = nbytes(operands) + nbytes(results)
    if len(big) == 1 and big[0][0] == "f32":
        p, t, c = big[0][1]
        n = math.isqrt(p)
        ops = t * c * ((1 + changes_base) * sandwich_ops(n, n) + 3 * p)
        return "input_transform", ops, moved
    if len(big) == 2 and all(dt == "s8" for dt, _ in big):
        (_, (p, t, k)), (_, (_, _, nn)) = big
        n = math.isqrt(p)
        m = math.isqrt(results[0][1][0])
        gemm = 2 * p * t * k * nn
        epilogue = t * nn * (4 * p + changes_base * sandwich_ops(n, n)
                             + sandwich_ops(n, m))
        return "fused_gemm_output", gemm + epilogue, moved
    raise ValueError(f"not a known kernel call: {operands} -> {results}")


def least_time(ops: int, moved: int, peak: dict) -> tuple[float, str]:
    """The larger of ops over the int8 peak and bytes over HBM bandwidth,
    and which of the two bounds it."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_mem = moved / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_mem else (t_mem, "bytes")
