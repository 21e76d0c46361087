"""Quantized Winograd/Toom-Cook convolution with polynomial base change.

Implements the paper's algorithm end-to-end:

  eq. (3)  canonical base:   Y = Aᵀ[(G W Gᵀ) ⊙ (Bᵀ X B)]A
  eq. (4)  changed base:     Y = A_Pᵀ[P⁻ᵀ[(P⁻¹(G_P W G_Pᵀ)P⁻ᵀ) ⊙
                                          (B_Pᵀ(P⁻ᵀ X P⁻¹)B_P)]P⁻¹]A_P

NOTE on the paper's eq. (4) and the orientation of P: as printed, the
input-tile factor ``B_Pᵀ (P⁻ᵀ X P) B_P`` does not reduce to eq. (3) under
*any* consistent reading (a stray P·P survives) — a known typo; the last
``P`` must be ``P⁻¹``. Furthermore the paper's prose says "P⁻¹ … changes
the result back into the canonical base", which fixes the orientation:
the paper's ``P`` is the canonical→Legendre *coefficient conversion*.
With ``C`` denoting that conversion (``C = P_coef⁻¹`` where ``P_coef``'s
columns hold the monic-Legendre canonical coefficients), we implement

    G_C = C G,  B_C = C B,  A_C = C A
    Y = A_Cᵀ [ C⁻ᵀ[(C⁻¹(G_C W G_Cᵀ)C⁻ᵀ) ⊙ (B_Cᵀ(C⁻ᵀ X C⁻¹)B_C)] C⁻¹ ] A_C

which reduces exactly to eq. (3) in rational arithmetic (verified in
tests) while changing the rounding/quantization of every intermediate —
the paper's entire point. Empirically this orientation lowers
cond₂(B_Cᵀ) from 13.8 to 8.3 for F(4,3); the literal ``P_coef·G`` reading
*raises* it to 25.8, confirming the choice.

Quantization follows [5]'s Winograd-aware pipeline (the paper's Fig. 2):
symmetric casts before/after every transform stage AND of the transform
matrices themselves, with a separately configurable bit-width for the
Hadamard-product stage (8 vs the accuracy-recovering 9 bits).

Static vs flex (Fernandez-Marques et al. 2020): *static* uses the analytic
matrices as constants; *flex* treats G_C, B_Cᵀ, A_Cᵀ as trainable
parameters (C, C⁻¹ stay fixed — parameter count is unchanged vs canonical
flex).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import legendre as _legendre
from repro.core import toom_cook as _tc
from repro.core.quantization import QuantConfig, fake_quant

__all__ = [
    "WinogradSpec",
    "WinogradMatrices",
    "make_matrices",
    "flex_init",
    "transform_weights_2d",
    "transform_weights_1d",
    "winograd_conv2d",
    "winograd_conv1d",
    "direct_conv2d",
    "direct_conv1d",
    "condition_number",
]


@dataclasses.dataclass(frozen=True)
class WinogradSpec:
    """Static configuration of a Winograd/Toom-Cook convolution."""

    m: int = 4                   # output tile size (per dim)
    r: int = 3                   # kernel size (per dim)
    base: str = "legendre"       # canonical | legendre | chebyshev
    quant: QuantConfig = QuantConfig()
    flex: bool = False           # learnable transform matrices
    dtype: jnp.dtype = jnp.float32

    @property
    def n(self) -> int:
        return self.m + self.r - 1

    @property
    def changes_base(self) -> bool:
        return self.base != "canonical"


@dataclasses.dataclass(frozen=True)
class WinogradMatrices:
    """Float transform matrices for a spec (constants unless flex).

    ``C`` is the canonical→basis coefficient conversion (the paper's "P");
    ``Cinv`` converts back. For base="canonical" both are the identity.
    """

    AT: jnp.ndarray      # (m, n)  — canonical-base output transform
    G: jnp.ndarray       # (n, r)
    BT: jnp.ndarray      # (n, n)
    C: jnp.ndarray       # (n, n)  — base change (identity for canonical)
    Cinv: jnp.ndarray    # (n, n)
    GP: jnp.ndarray      # (n, r)  = C @ G
    BPT: jnp.ndarray     # (n, n)  = (C @ B)ᵀ = Bᵀ Cᵀ
    APT: jnp.ndarray     # (m, n)  = (C @ A)ᵀ = Aᵀ Cᵀ
    CinvT: jnp.ndarray   # (n, n)  = C⁻ᵀ


def make_matrices(spec: WinogradSpec, points=None) -> WinogradMatrices:
    """Exact-rational construction of the spec's transform matrices.

    Cached per spec for the default point set: the Fraction arithmetic
    costs ~ms per call and the serving path composes eagerly-dispatched
    compile units (one-Xq contract, ``kernels.ops``), so it would
    otherwise run on every conv call. The returned arrays are
    treated as read-only constants everywhere.
    """
    if points is None:
        return _make_matrices_default(spec)
    return _build_matrices(spec, points)


@functools.lru_cache(maxsize=None)
def _make_matrices_default(spec: WinogradSpec) -> WinogradMatrices:
    return _build_matrices(spec, None)


def _build_matrices(spec: WinogradSpec, points) -> WinogradMatrices:
    AT_f, G_f, BT_f = _tc.toom_cook_matrices(spec.m, spec.r, points=points)
    # base_change returns (P_coef, P_coef⁻¹); the conversion canonical→basis
    # is C = P_coef⁻¹ (see module docstring on the paper's orientation).
    P_f, Pinv_f = _legendre.base_change(spec.n, spec.base)
    AT = _tc.to_float(AT_f)
    G = _tc.to_float(G_f)
    BT = _tc.to_float(BT_f)
    C = _tc.to_float(Pinv_f)
    Cinv = _tc.to_float(P_f)
    # Host numpy constants, deliberately NOT jnp: the result is cached
    # and make_matrices may first be hit inside a jit trace, where a
    # jnp dtype cast would capture (and leak) a tracer. Numpy constants
    # embed into any consuming trace/kernel call as-is.
    d = spec.dtype
    return WinogradMatrices(
        AT=np.asarray(AT, d), G=np.asarray(G, d), BT=np.asarray(BT, d),
        C=np.asarray(C, d), Cinv=np.asarray(Cinv, d),
        GP=np.asarray(C @ G, d), BPT=np.asarray(BT @ C.T, d),
        APT=np.asarray(AT @ C.T, d), CinvT=np.asarray(Cinv.T, d),
    )


def flex_init(spec: WinogradSpec, points=None) -> dict[str, jnp.ndarray]:
    """Initial values of the trainable transform matrices (flex mode)."""
    mats = make_matrices(spec, points=points)
    if spec.changes_base:
        return {"GP": mats.GP, "BPT": mats.BPT, "APT": mats.APT}
    return {"G": mats.G, "BT": mats.BT, "AT": mats.AT}


def _sandwich(M: jnp.ndarray, X: jnp.ndarray, N: Optional[jnp.ndarray] = None
              ) -> jnp.ndarray:
    """M @ X @ Nᵀ over the trailing two dims of X (N defaults to M), at
    full fp32 precision on every backend (a TPU's default would round
    the operands to bf16)."""
    if N is None:
        N = M
    return jnp.einsum("ij,...jk,lk->...il", M, X, N,
                      precision=jax.lax.Precision.HIGHEST)


def _q(x: jnp.ndarray, bits: Optional[int], axis=None) -> jnp.ndarray:
    return fake_quant(x, bits, axis=axis)


def _q_dom(x: jnp.ndarray, bits: Optional[int], quant: QuantConfig,
           ndims: int = 2) -> jnp.ndarray:
    """Quantize a transform-domain tensor (trailing `ndims` = tile grid).

    Per-tensor scale by default (faithful to [5]); per-Winograd-position
    scales when ``quant.position_scales`` (beyond-paper option).
    """
    axis = tuple(range(x.ndim - ndims)) if quant.position_scales else None
    return _q(x, bits, axis=axis)


def _q_mid(x: jnp.ndarray, quant: QuantConfig, ndims: int = 2) -> jnp.ndarray:
    """Cast between the base-change matmul and the main transform matmul.

    Applied only under the per-matmul cast policy (see QuantConfig).
    """
    if not quant.cast_between_stages:
        return x
    return _q_dom(x, quant.trans_bits, quant, ndims=ndims)


def _resolve(mats: WinogradMatrices, flex: Optional[dict],
             spec: WinogradSpec):
    """Pick and (fake-)quantize the per-stage transform matrices.

    Returns (kernel_mat, input_mat, output_mat, back, backT) where `back`
    = quantized C⁻¹ (None for canonical base).
    """
    mb = spec.quant.matrix_bits
    if spec.changes_base:
        GP = flex["GP"] if flex else mats.GP
        BPT = flex["BPT"] if flex else mats.BPT
        APT = flex["APT"] if flex else mats.APT
        return (_q(GP, mb), _q(BPT, mb), _q(APT, mb),
                _q(mats.Cinv, mb), _q(mats.CinvT, mb))
    G = flex["G"] if flex else mats.G
    BT = flex["BT"] if flex else mats.BT
    AT = flex["AT"] if flex else mats.AT
    return _q(G, mb), _q(BT, mb), _q(AT, mb), None, None


# ---------------------------------------------------------------------------
# 2-D pipeline
# ---------------------------------------------------------------------------

def transform_weights_2d(w: jnp.ndarray, spec: WinogradSpec,
                         mats: WinogradMatrices,
                         flex: Optional[dict] = None) -> jnp.ndarray:
    """(r, r, Cin, Cout) HWIO weights → Winograd-domain (Cin, Cout, n, n).

    Canonical: U = G W Gᵀ.  Changed base: U₁ = G_C W G_Cᵀ (quantize),
    U = C⁻¹ U₁ C⁻ᵀ (quantize) — casts between stages per Fig. 2.
    Weight quantization is per-output-channel when configured.
    """
    q = spec.quant
    wt = jnp.transpose(w, (2, 3, 0, 1))  # (Cin, Cout, r, r)
    w_axis = (0, 2, 3) if q.per_channel_weights else None
    wt = _q(wt, q.weight_bits, axis=w_axis)
    Gm, _, _, back, _ = _resolve(mats, flex, spec)
    U = _sandwich(Gm, wt)                           # G_C W G_Cᵀ (or G W Gᵀ)
    if spec.changes_base:
        U = _q_mid(U, q)
        U = _sandwich(back, U)                      # C⁻¹ (·) C⁻ᵀ
    return _q_dom(U, q.trans_bits, q)


def _transform_input_tiles(tiles: jnp.ndarray, spec: WinogradSpec,
                           mats: WinogradMatrices,
                           flex: Optional[dict]) -> jnp.ndarray:
    """(..., n, n) input tiles → Winograd domain, quantized per Fig. 2."""
    q = spec.quant
    tiles = _q(tiles, q.act_bits)
    _, BTm, _, _, backT = _resolve(mats, flex, spec)
    if spec.changes_base:
        V = _sandwich(backT, tiles)                 # C⁻ᵀ X C⁻¹
        V = _q_mid(V, q)
        V = _sandwich(BTm, V)                       # B_Cᵀ (·) B_C
    else:
        V = _sandwich(BTm, tiles)                   # Bᵀ X B
    return _q_dom(V, q.trans_bits, q)


def _transform_output_tiles(H: jnp.ndarray, spec: WinogradSpec,
                            mats: WinogradMatrices,
                            flex: Optional[dict]) -> jnp.ndarray:
    """(..., n, n) Hadamard results → (..., m, m) spatial outputs."""
    q = spec.quant
    _, _, ATm, _, backT = _resolve(mats, flex, spec)
    if spec.changes_base:
        Y = _sandwich(backT, H)                     # C⁻ᵀ (·) C⁻¹
        Y = _q_mid(Y, q)
        Y = _sandwich(ATm, Y)                       # A_Cᵀ (·) A_C
    else:
        Y = _sandwich(ATm, H)                       # Aᵀ (·) A
    return Y


def _pad_amounts(size: int, m: int, r: int, padding: str,
                 causal: bool = False) -> tuple[int, int, int, int]:
    """→ (pad_lo, pad_hi, n_tiles, out_size) along one spatial dim."""
    if padding == "same":
        out = size
        lo = r - 1 if causal else (r - 1) // 2
    elif padding == "valid":
        out = size - r + 1
        lo = 0
    else:
        raise ValueError(padding)
    nt = -(-out // m)  # ceil
    needed = nt * m + r - 1
    hi = needed - size - lo
    return lo, hi, nt, out


def _extract_tiles_1d_axis(x: jnp.ndarray, axis_len: int, m: int, n: int,
                           nt: int, axis: int) -> jnp.ndarray:
    """Slice overlapping length-n windows at stride m along `axis`.

    Returns with two new dims replacing `axis`: (..., nt, n, ...).
    """
    starts = np.arange(nt) * m
    idx = starts[:, None] + np.arange(n)[None, :]  # (nt, n)
    return jnp.take(x, jnp.asarray(idx), axis=axis)


def winograd_conv2d(x: jnp.ndarray, w: jnp.ndarray, spec: WinogradSpec,
                    mats: Optional[WinogradMatrices] = None,
                    flex: Optional[dict] = None,
                    padding: str = "same",
                    U: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Quantized Winograd convolution. x: (N,H,W,C) NHWC, w: (r,r,Cin,Cout).

    ``U`` may pass pre-transformed weights (inference; amortized).
    Stride 1, dilation 1 — the Winograd regime. Output: (N, Ho, Wo, Cout).
    """
    if mats is None:
        mats = make_matrices(spec)
    q = spec.quant
    N, H, W, Cin = x.shape
    r, m, n = spec.r, spec.m, spec.n
    assert w.shape[:2] == (r, r), (w.shape, spec)

    lo_h, hi_h, nt_h, Ho = _pad_amounts(H, m, r, padding)
    lo_w, hi_w, nt_w, Wo = _pad_amounts(W, m, r, padding)
    xp = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))

    tiles = _extract_tiles_1d_axis(xp, xp.shape[1], m, n, nt_h, axis=1)
    tiles = _extract_tiles_1d_axis(tiles, tiles.shape[3], m, n, nt_w, axis=3)
    # (N, nt_h, n, nt_w, n, C) → (N, nt_h, nt_w, C, n, n)
    tiles = jnp.transpose(tiles, (0, 1, 3, 5, 2, 4))

    V = _transform_input_tiles(tiles, spec, mats, flex)     # (N,th,tw,Cin,n,n)
    if U is None:
        U = transform_weights_2d(w, spec, mats, flex)       # (Cin,Cout,n,n)
    # Hadamard product + channel reduction: n² independent GEMMs.
    H_ = jnp.einsum("bhwcij,cdij->bhwdij", V, U)
    H_ = _q_dom(H_, q.hadamard_bits, q)
    Y = _transform_output_tiles(H_, spec, mats, flex)       # (N,th,tw,Cout,m,m)
    Y = _q(Y, q.act_bits)
    # Reassemble: (N,th,tw,Cout,m,m) → (N, th*m, tw*m, Cout) → crop.
    Y = jnp.transpose(Y, (0, 1, 4, 2, 5, 3))
    Y = Y.reshape(N, nt_h * m, nt_w * m, -1)
    return Y[:, :Ho, :Wo, :]


# ---------------------------------------------------------------------------
# 1-D pipeline (temporal convolutions, e.g. RG-LRU's width-4 conv)
# ---------------------------------------------------------------------------

def transform_weights_1d(w: jnp.ndarray, spec: WinogradSpec,
                         mats: WinogradMatrices,
                         flex: Optional[dict] = None) -> jnp.ndarray:
    """(r, Cin, Cout) weights → (Cin, Cout, n)."""
    q = spec.quant
    wt = jnp.transpose(w, (1, 2, 0))  # (Cin, Cout, r)
    w_axis = (0, 2) if q.per_channel_weights else None
    wt = _q(wt, q.weight_bits, axis=w_axis)
    Gm, _, _, back, _ = _resolve(mats, flex, spec)
    U = jnp.einsum("ij,...j->...i", Gm, wt)
    if spec.changes_base:
        U = _q_mid(U, q, ndims=1)
        U = jnp.einsum("ij,...j->...i", back, U)
    return _q_dom(U, q.trans_bits, q, ndims=1)


def winograd_conv1d(x: jnp.ndarray, w: jnp.ndarray, spec: WinogradSpec,
                    mats: Optional[WinogradMatrices] = None,
                    flex: Optional[dict] = None,
                    causal: bool = True,
                    U: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Quantized 1-D Toom-Cook convolution. x: (N,T,C), w: (r,Cin,Cout).

    ``causal=True`` left-pads r-1 (the RG-LRU temporal conv convention).
    """
    if mats is None:
        mats = make_matrices(spec)
    q = spec.quant
    N, T, Cin = x.shape
    r, m, n = spec.r, spec.m, spec.n
    assert w.shape[0] == r

    lo, hi, nt, To = _pad_amounts(T, m, r, "same", causal=causal)
    xp = jnp.pad(x, ((0, 0), (lo, hi), (0, 0)))
    tiles = _extract_tiles_1d_axis(xp, xp.shape[1], m, n, nt, axis=1)
    tiles = jnp.transpose(tiles, (0, 1, 3, 2))  # (N, nt, C, n)

    tiles = _q(tiles, q.act_bits)
    _, BTm, _, _, backT = _resolve(mats, flex, spec)
    if spec.changes_base:
        V = jnp.einsum("ij,...j->...i", backT, tiles)
        V = _q_mid(V, q, ndims=1)
        V = jnp.einsum("ij,...j->...i", BTm, V)
    else:
        V = jnp.einsum("ij,...j->...i", BTm, tiles)
    V = _q_dom(V, q.trans_bits, q, ndims=1)

    if U is None:
        U = transform_weights_1d(w, spec, mats, flex)   # (Cin, Cout, n)
    H_ = jnp.einsum("btci,cdi->btdi", V, U)
    H_ = _q_dom(H_, q.hadamard_bits, q, ndims=1)

    _, _, ATm, _, backT = _resolve(mats, flex, spec)
    if spec.changes_base:
        Y = jnp.einsum("ij,...j->...i", backT, H_)
        Y = _q_mid(Y, q, ndims=1)
        Y = jnp.einsum("ij,...j->...i", ATm, Y)
    else:
        Y = jnp.einsum("ij,...j->...i", ATm, H_)
    Y = _q(Y, q.act_bits)
    Y = jnp.transpose(Y, (0, 1, 3, 2)).reshape(N, nt * m, -1)
    return Y[:, :To, :]


# ---------------------------------------------------------------------------
# Direct-convolution references
# ---------------------------------------------------------------------------

def direct_conv2d(x: jnp.ndarray, w: jnp.ndarray,
                  padding: str = "same") -> jnp.ndarray:
    """lax direct convolution, NHWC/HWIO, stride 1 (the paper's baseline)."""
    pad = padding.upper()
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def direct_conv1d(x: jnp.ndarray, w: jnp.ndarray,
                  causal: bool = True) -> jnp.ndarray:
    r = w.shape[0]
    pad = [(r - 1, 0)] if causal else [((r - 1) // 2, (r - 1) - (r - 1) // 2)]
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1,), padding=pad,
        dimension_numbers=("NTC", "TIO", "NTC"))


def condition_number(M) -> float:
    """2-norm condition number (for the conditioning benchmark)."""
    s = np.linalg.svd(np.asarray(M, np.float64), compute_uv=False)
    return float(s.max() / s.min())
