"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each function mirrors its kernel's contract exactly (same dtypes, layouts
and quantization semantics) using only jnp ops, so kernel tests can assert
exact integer equality / fp allclose across shape & dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "wino_gemm_ref",
    "input_transform_fp",
    "input_transform_ref",
    "output_transform_ref",
    "q8_matmul_ref",
]


def wino_gemm_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """(P,M,K) int8 · (P,K,N) int8 → (P,M,N) int32, exact."""
    return jnp.einsum("pmk,pkn->pmn", x.astype(jnp.int32),
                      w.astype(jnp.int32))


def _sandwich(M, X, N=None):
    """M @ X @ Nᵀ over the LEADING two (window) axes of X, at full fp32
    precision on every backend."""
    if N is None:
        N = M
    return jnp.einsum("ij,jk...,lk->il...", M, X, N,
                      precision=jax.lax.Precision.HIGHEST)


def input_transform_fp(tiles: jnp.ndarray, cinvt: jnp.ndarray,
                       bpt: jnp.ndarray,
                       changes_base: bool = True) -> jnp.ndarray:
    """tiles (n²,T,C) fp32 → Winograd-domain (n²,T,C) fp32, no quantization.

    The pre-quantization values of ``input_transform``; dynamic-scale
    derivation and offline calibration both reduce over this tensor, so
    sharing it keeps the two paths bit-identical.
    """
    P, T, C = tiles.shape
    n = int(round(P ** 0.5))
    x = tiles.astype(jnp.float32).reshape(n, n, T, C)
    if changes_base:
        x = _sandwich(cinvt, x)
    return _sandwich(bpt, x).reshape(P, T, C)


def input_transform_ref(tiles: jnp.ndarray, cinvt: jnp.ndarray,
                        bpt: jnp.ndarray, pos_scale: jnp.ndarray,
                        changes_base: bool = True) -> jnp.ndarray:
    """tiles (n²,T,C) fp32 → (n²,T,C) int8 (= kernels.input_transform)."""
    v = input_transform_fp(tiles, cinvt, bpt, changes_base)
    q = jnp.clip(jnp.round(v / pos_scale[:, :, None]), -127, 127)
    return q.astype(jnp.int8)


def output_transform_ref(h: jnp.ndarray, pos_scale: jnp.ndarray,
                         cinvt: jnp.ndarray, apt: jnp.ndarray, m: int,
                         changes_base: bool = True) -> jnp.ndarray:
    """H (n²,T,C) int32 → (m²,T,C) fp32 (matches kernels.output_transform)."""
    P, T, C = h.shape
    n = int(round(P ** 0.5))
    hf = (h.astype(jnp.float32) * pos_scale[:, :, None]).reshape(n, n, T, C)
    if changes_base:
        hf = _sandwich(cinvt, hf)
    return _sandwich(apt, hf).reshape(m * m, T, C)


def q8_matmul_ref(x_q: jnp.ndarray, w_q: jnp.ndarray, s_x: jnp.ndarray,
                  s_w: jnp.ndarray, out_dtype=jnp.float32) -> jnp.ndarray:
    """(M,K) int8 · (K,N) int8 with symmetric dequant epilogue."""
    acc = jnp.matmul(x_q.astype(jnp.int32), w_q.astype(jnp.int32))
    return (acc.astype(jnp.float32) * s_x * s_w[None, :]).astype(out_dtype)
