"""Pallas TPU kernels: fused Winograd input/output transforms (+(de)quant).

These are the bandwidth-bound stages of the Winograd pipeline. Every
block keeps the tile and channel axes (T, C) as its two minor axes —
channels on the 128 lanes, tiles on the sublanes — and the n×n tile
window on the leading axis, position-major (``p = a·n + b``). Each
sandwich term is then a scalar times one ``(bt, bc)`` plane: pure VPU
multiply-adds, with no minor-axis reshape for Mosaic to lay out. The
move between NHWC and this layout is XLA data movement in
``kernels.ops``: ``_extract`` cuts the padded activation into windows
with slices and transposes them into the n² planes, ``_reassemble``
transposes the m² output planes back.

Input transform (fused, one HBM round-trip):
    tiles (n², T, C) fp32  →  C⁻ᵀ·X·C⁻¹ → B_Cᵀ·(·)·B_C → scale→round→clip
    → (n², T, C) int8, laid out for ``wino_gemm``.

Output transform:
    H (n², T, C) int32  →  ·deq scale → C⁻ᵀ·(·)·C⁻¹ → A_Cᵀ·(·)·A_C
    → (m², T, C) fp32.

The transform matrices and the per-position scales are kernel operands
read as scalars from SMEM: for the *flex* variants the matrices are
learnable tensors, so they must not be baked into the kernel as
compile-time constants. Scales are computed OUTSIDE the kernel (a cheap
XLA reduction) and passed in; this keeps the kernel single-pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import telemetry
from repro.kernels import backend
from repro.kernels.backend import smem_spec

__all__ = ["input_transform", "output_transform", "sandwich",
           "scalars", "output_planes"]


#: Largest tile window the interpret-mode kernels unroll. Beyond it
#: (F(6,3): n = 8) XLA's CPU compile of the 2·n_out·n_in² unrolled
#: plane terms takes several times longer than the whole call, so in
#: interpret mode the sandwich runs as two small dot_generals instead.
#: Mosaic always unrolls: it has no such cost, and no leading-axis dot.
_INTERPRET_UNROLL_MAX_N = 6


def _sandwich_dot(mat_l, mat_r, x, n_in: int, n_out: int):
    """The sandwich as two dot_generals over the window stacked on the
    minor axes, (..., n, n): the layout whose CPU contraction gives the
    staged and fused interpret-mode kernels the same rounding at any
    plane shape."""
    plane = x[0].shape
    L = jnp.stack(mat_l).reshape(n_out, n_in)
    R = jnp.stack(mat_r).reshape(n_out, n_in)
    xs = jnp.stack(x, -1).reshape(*plane, n_in, n_in)
    t = jnp.einsum("aj,...jk->...ak", L, xs)
    out = jnp.einsum("bk,...ak->...ab", R, t).reshape(*plane, -1)
    return [out[..., p] for p in range(n_out * n_out)]


def sandwich(mat_l, mat_r, x, n_in: int, n_out: int):
    """L·X·Rᵀ over a position-major list of n_in² planes → n_out² planes.

    THE sandwich of every transform kernel (input, output, fused
    serving), so the staged and fused pipelines run identical
    arithmetic. ``mat_l``/``mat_r`` are row-major flattened (n_out, n_in)
    matrices as lists of scalars (``scalars`` of an SMEM ref). Separable,
    in the order of the two matrix products it stands for — rows
    t[a,k] = Σ_j L[a,j]·x[j,k], then columns out[a,b] = Σ_k R[b,k]·t[a,k]
    — so it costs 2·n_out·n_in² scalar×plane multiply-adds (432 at
    F(4,3)) instead of the n_out²·n_in² of the four-index form.
    """
    if n_in > _INTERPRET_UNROLL_MAX_N and backend.interpret_mode():
        return _sandwich_dot(mat_l, mat_r, x, n_in, n_out)
    t = []
    for a in range(n_out):
        for k in range(n_in):
            acc = None
            for j in range(n_in):
                contrib = x[j * n_in + k] * mat_l[a * n_in + j]
                acc = contrib if acc is None else acc + contrib
            t.append(acc)
    out = []
    for a in range(n_out):
        for b in range(n_out):
            acc = None
            for k in range(n_in):
                contrib = t[a * n_in + k] * mat_r[b * n_in + k]
                acc = contrib if acc is None else acc + contrib
            out.append(acc)
    return out


def scalars(ref, size: int) -> list:
    """Every entry of a 1-D SMEM ref, each read once as a scalar."""
    return [ref[i] for i in range(size)]


def output_planes(h, cinvt_ref, apt_ref, n: int, m: int,
                  changes_base: bool):
    """Dequantized Hadamard planes (n², list) → output planes (m², list):
    the output-transform sandwich shared by the staged and fused kernels."""
    if changes_base:
        cinvt = scalars(cinvt_ref, n * n)
        h = sandwich(cinvt, cinvt, h, n, n)
    apt = scalars(apt_ref, m * n)
    return sandwich(apt, apt, h, n, m)


def _input_kernel(tiles_ref, cinvt_ref, bpt_ref, scale_ref, out_ref, *,
                  n: int, changes_base: bool):
    x = [tiles_ref[p].astype(jnp.float32) for p in range(n * n)]
    if changes_base:
        cinvt = scalars(cinvt_ref, n * n)
        x = sandwich(cinvt, cinvt, x, n, n)
    bpt = scalars(bpt_ref, n * n)
    v = sandwich(bpt, bpt, x, n, n)
    for p, s in enumerate(scalars(scale_ref, n * n)):
        q = jnp.clip(jnp.round(v[p] / s), -127, 127)
        out_ref[p] = q.astype(jnp.int32).astype(jnp.int8)


def _output_kernel(h_ref, scale_ref, cinvt_ref, apt_ref, out_ref, *,
                   n: int, m: int, changes_base: bool):
    h = [h_ref[p].astype(jnp.float32) * s
         for p, s in enumerate(scalars(scale_ref, n * n))]
    for p, y in enumerate(output_planes(h, cinvt_ref, apt_ref, n, m,
                                        changes_base)):
        out_ref[p] = y


def _pad_axis(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _window(P: int) -> int:
    n = int(round(P ** 0.5))
    assert n * n == P, P
    return n


@functools.partial(jax.jit, static_argnames=("changes_base", "block"))
def input_transform(tiles: jnp.ndarray, cinvt: jnp.ndarray, bpt: jnp.ndarray,
                    pos_scale: jnp.ndarray, *, changes_base: bool = True,
                    block: tuple[int, int] = (32, 128)) -> jnp.ndarray:
    """tiles (n², T, C) fp32 → (n², T, C) int8 (position-major for GEMM).

    ``pos_scale``: (n², 1) fp32 quantization scales (per position;
    replicate a per-tensor scale to all n² rows for the paper-faithful
    mode). The default tile block is 32 rows, the int8 sublane tiling of
    the output block.
    """
    P, T, C = tiles.shape
    n = _window(P)
    bt, bc = min(block[0], T), min(block[1], C)
    tp = _pad_axis(_pad_axis(tiles, 1, bt), 2, bc)
    Tp, Cp = tp.shape[1], tp.shape[2]
    out = pl.pallas_call(
        functools.partial(_input_kernel, n=n, changes_base=changes_base),
        grid=(Tp // bt, Cp // bc),
        in_specs=[pl.BlockSpec((P, bt, bc), lambda i, j: (0, i, j)),
                  smem_spec(), smem_spec(), smem_spec()],
        out_specs=pl.BlockSpec((P, bt, bc), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((P, Tp, Cp), jnp.int8),
        interpret=backend.interpret_mode(),
        name=telemetry.INPUT_TRANSFORM,
    )(tp, cinvt.reshape(-1), bpt.reshape(-1), pos_scale.reshape(-1))
    return out[:, :T, :C]


@functools.partial(jax.jit, static_argnames=("m", "changes_base", "block"))
def output_transform(h: jnp.ndarray, pos_scale: jnp.ndarray,
                     cinvt: jnp.ndarray, apt: jnp.ndarray, *, m: int,
                     changes_base: bool = True,
                     block: tuple[int, int] = (8, 128)) -> jnp.ndarray:
    """H (n², T, C) int32 (+ per-position dequant scales) → (m², T, C)."""
    P, T, C = h.shape
    n = _window(P)
    # Shape-stability contract: the 2-D sharded dynamic-requant path runs
    # this transform per device on a (T/D_data, C/D_model) slab and
    # asserts bitwise equality with the full-tensor call, so the compiled
    # arithmetic must not depend on how many tiles a call sees. Two rules
    # achieve that: (a) bt is NOT clamped to T — the tile-block shape is
    # the same for a 5-row slab and the full tensor (zero padding covers
    # T < bt; zero rows transform to zero rows and are cropped below);
    # (b) in interpret mode the grid always has ≥ 2 steps — a single-step
    # pallas_call gets inlined into the surrounding jit and XLA re-fuses/
    # contracts its multiply-adds, while the multi-step grid loop is a
    # fusion barrier whose per-block program is identical at every grid
    # size AND block shape. When a call would compile to one step, split
    # the channel block in half (same total work, one extra step) rather
    # than padding a whole all-zero tile block; padding is the fallback
    # for odd/1-channel. Mosaic compiles one kernel body per block shape
    # that XLA never inlines, so on a TPU rule (b) is not needed — and
    # its half-lane channel blocks would break the (8, 128) block rule.
    bt, bc = block[0], min(block[1], C)
    split = backend.interpret_mode()
    if split and -(-T // bt) == 1 and -(-C // bc) == 1:
        if bc % 2 == 0:
            bc //= 2
        else:
            bt = max(1, (T + 1) // 2)
    hp = _pad_axis(_pad_axis(h, 1, bt), 2, bc)
    if split and hp.shape[1] // bt == 1 and hp.shape[2] // bc == 1:
        hp = _pad_axis(hp, 1, 2 * bt)    # T == C == 1: nothing to split
    Tp, Cp = hp.shape[1], hp.shape[2]
    out = pl.pallas_call(
        functools.partial(_output_kernel, n=n, m=m,
                          changes_base=changes_base),
        grid=(Tp // bt, Cp // bc),
        in_specs=[pl.BlockSpec((P, bt, bc), lambda i, j: (0, i, j)),
                  smem_spec(), smem_spec(), smem_spec()],
        out_specs=pl.BlockSpec((m * m, bt, bc), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((m * m, Tp, Cp), jnp.float32),
        interpret=backend.interpret_mode(),
        name=telemetry.OUTPUT_TRANSFORM,
    )(hp, pos_scale.reshape(-1), cinvt.reshape(-1), apt.reshape(-1))
    return out[:, :T, :C]
