"""Jitted wrappers composing the Pallas kernels into a full int8 Winograd
convolution (the inference path; QAT uses the fake-quant path in core/).

Staged pipeline (NHWC):
    extract tiles (XLA slices + transpose)        → (n², T, Cin) fp
    kernels.input_transform   (fused, 1 HBM pass) → (n², T, Cin) int8
    kernels.wino_gemm         (MXU int8 GEMMs)    → (n², T, Cout) int32
    [optional Hadamard requant to 8/9 bits — the paper's knob; with
     calibrated statistics it runs as wino_gemm's in-register epilogue,
     dynamic derivation stays XLA glue]
    kernels.output_transform  (fused, 1 HBM pass) → (m², T, Cout) fp
    reassemble                                    → (N, Ho, Wo, Cout)

Every tensor between extraction and reassembly is position-major, with
(T, C) as its two minor axes: the layout the Mosaic-compiled kernels
block over (see ``kernels.wino_transform``).

Fused serving pipeline (``fused=True``, requires calibrated Hadamard
statistics when the 8/9-bit stage is on):
    extract tiles → kernels.input_transform → kernels.fused_serve
    (GEMM → in-register Hadamard requant → output transform, ONE Pallas
    call) → reassemble — zero fp32 intermediates in HBM; integer-exact
    vs the staged path in the Hadamard domain, fp32 outputs equal to
    float rounding (FMA contraction differs between the graphs).
    Calibration (``with_stats``) and dynamic requant fall back to the
    staged pipeline, whose full-plane reductions cannot run inside a
    tiled kernel.

Scales: per-Winograd-position symmetric scales. Production serving uses
*calibrated* scales passed by the caller; when omitted they are derived
dynamically (an extra XLA reduction — fine for tests/benchmarks).

One Xq everywhere: the int8 input transform + quantization is pinned
into a single compile unit (``quantize_input``, dispatching the one
module-level ``input_transform`` jit) that every serving mode calls —
``execute_int8`` composes the jitted kernel units instead of wrapping
them in a monolithic jit, and the sharded path quantizes the full tile
tensor before sharding the int8 result. A rounding-boundary input value
therefore quantizes identically in all modes (the cross-XLA-program
drift fixed per docs/parity.md).

Sharded serving (``execute_int8_sharded``): the fused pipeline is
independent per (tile row, output channel), so it scales past one chip
over a 2-D (data × model) mesh — the tile axis T of the quantized
``Xq`` shard_maps across the data axis, the per-position GEMM's N axis
(Cout) shards across the model axis with each device holding only its
(P, Cin, Cout/D_model) weight shard, and one per-layer ``all_gather``
of the small (m², T_local, Cout_local) spatial outputs reassembles
the channels. Bit-identical to single-device fused execution on any
mesh shape; dynamic-requant layers run sharded too (shard-local
``|·|max`` + one ``lax.pmax`` over the plane — exact).

Prepare/execute split (the LANCE-style offline/online cut): call
``prepare_weights_int8`` once per model to get the per-position int8
weight tensor + scales, calibrate the input scales — and, when the
8/9-bit Hadamard stage is on, the requant scales — offline (see
``repro.conv.packing``), then pass them into ``winograd_conv2d_int8`` —
the jitted hot path then performs **zero** weight transforms and **zero**
scale reductions per call. ``repro.conv.ConvEngine`` wraps this lifecycle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro import telemetry
from repro.core.quantization import QuantConfig, qmax
from repro.core.winograd import (WinogradMatrices, WinogradSpec,
                                 _pad_amounts, make_matrices,
                                 transform_weights_2d)
from repro.kernels import backend
from repro.kernels import ref as kref
from repro.kernels.fused_serve import fused_gemm_output
from repro.kernels.q8_matmul import q8_matmul
from repro.kernels.wino_gemm import validate_blocks, wino_gemm
from repro.kernels.wino_transform import input_transform, output_transform

__all__ = ["prepare_weights_int8", "input_abs_max", "scales_from_abs_max",
           "quantize_input", "winograd_conv2d_int8", "execute_int8",
           "execute_int8_sharded", "q8_linear"]


def _geometry(x_shape, m: int, r: int, padding: str):
    N, H, W, _ = x_shape
    _, _, nt_h, Ho = _pad_amounts(H, m, r, padding)
    _, _, nt_w, Wo = _pad_amounts(W, m, r, padding)
    return (N, nt_h, nt_w, Ho, Wo)


def _windows(x: jnp.ndarray, axis: int, m: int, n: int, nt: int
             ) -> jnp.ndarray:
    """Length-n windows at stride m along ``axis`` → (..., nt, n, ...).

    ``x`` holds ``(nt + q - 1)·m`` rows on ``axis``, q = ⌈n/m⌉. Window i
    joins the m-row blocks i … i + q - 1, cut to n rows: unit-stride
    slices and one concatenate. (On a TPU v5e a gather compiles to a loop
    of dynamic slices, and slices at stride m hung the served program.)
    """
    q = -(-n // m)
    xb = x.reshape(x.shape[:axis] + (nt + q - 1, m) + x.shape[axis + 1:])
    parts = [lax.slice_in_dim(lax.slice_in_dim(xb, k, k + nt, axis=axis),
                              0, min(m, n - k * m), axis=axis + 1)
             for k in range(q)]
    return jnp.concatenate(parts, axis=axis + 1)


@functools.partial(jax.jit, static_argnames=("m", "r", "n", "padding"))
def _extract(x: jnp.ndarray, m: int, r: int, n: int, padding: str):
    """(N,H,W,C) → (n², T, C) overlapping tiles, one fused call.

    Windows along H, then W (``_windows``), then one transpose puts the
    window axes first: plane ``p = a·n + b``, tiles in (N, tile row,
    tile column) order.
    """
    N, H, W, C = x.shape
    q = -(-n // m)
    lo_h, _, nt_h, _ = _pad_amounts(H, m, r, padding)
    lo_w, _, nt_w, _ = _pad_amounts(W, m, r, padding)
    xp = jnp.pad(x, ((0, 0), (lo_h, (nt_h + q - 1) * m - H - lo_h),
                     (lo_w, (nt_w + q - 1) * m - W - lo_w), (0, 0)))
    t = _windows(_windows(xp, 1, m, n, nt_h), 3, m, n, nt_w)
    t = jnp.transpose(t, (2, 4, 0, 1, 3, 5))        # (n,n,N,th,tw,C)
    return t.reshape(n * n, N * nt_h * nt_w, C)


def _reassemble(y: jnp.ndarray, geom, m: int) -> jnp.ndarray:
    """(m², T, C) output tiles → (N, Ho, Wo, C)."""
    N, nt_h, nt_w, Ho, Wo = geom
    y = y.reshape(m, m, N, nt_h, nt_w, -1)
    y = jnp.transpose(y, (2, 3, 0, 4, 1, 5))        # (N,th,m,tw,m,C)
    y = y.reshape(N, nt_h * m, nt_w * m, -1)
    return y[:, :Ho, :Wo, :]


def _hadamard_rq(h_amax: jnp.ndarray, hadamard_bits: int) -> jnp.ndarray:
    """Calibrated Hadamard requant scales: (n²,)|(n²,1) abs-max → (n²,1).

    THE scale formula of the 8/9-bit requant stage — shared by the
    staged epilogue, the fused kernel's operands and the sharded path so
    their documented bit-identity cannot drift apart.
    """
    return jnp.maximum(h_amax.reshape(-1, 1), 1e-12) / qmax(hadamard_bits)


@functools.partial(jax.jit, static_argnames=("spec",))
def prepare_weights_int8(w: jnp.ndarray, spec: WinogradSpec
                         ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Offline weight packing: (r,r,Cin,Cout) fp → per-position int8.

    Exact fp Winograd transform (tiny — once per model), then symmetric
    per-position int8 quantization. Returns ``(u_q, w_scales)`` with
    ``u_q`` (P, Cin, Cout) int8 laid out for ``wino_gemm`` and
    ``w_scales`` (P, 1) fp32.

    Jitted on its own so the dynamic fallback of ``winograd_conv2d_int8``
    and offline packing compile identically — a prepared execution is
    bit-for-bit the dynamic one.
    """
    mats = make_matrices(spec)
    m, r, n = spec.m, spec.r, spec.n
    P = n * n
    fp_spec = WinogradSpec(m=m, r=r, base=spec.base, quant=QuantConfig.off())
    U = transform_weights_2d(w, fp_spec, mats)       # (Cin, Cout, n, n) fp
    u_src = jnp.moveaxis(U.reshape(*U.shape[:2], P), -1, 0)   # (P,Cin,Cout)
    s_w = jnp.max(jnp.abs(u_src), axis=(1, 2), keepdims=True) / 127.0
    s_w = jnp.maximum(s_w, 1e-12)
    u_q = jnp.clip(jnp.round(u_src / s_w), -127, 127).astype(jnp.int8)
    return u_q, s_w.reshape(P, 1)


@functools.partial(jax.jit, static_argnames=("spec",))
def _tiles_abs_max(tiles: jnp.ndarray, spec: WinogradSpec) -> jnp.ndarray:
    """Per-position abs-max of extracted (n²,T,Cin) tiles in the
    Winograd input domain → (n²,) fp32.

    The dynamic-scale fallback and offline calibration both call exactly
    this compiled function (tile extraction is exact data movement), so
    calibrating on a batch reproduces that batch's dynamic scales
    bit-for-bit.
    """
    mats = make_matrices(spec)
    v_fp = kref.input_transform_fp(tiles, mats.CinvT, mats.BPT,
                                   spec.changes_base)
    return jnp.max(jnp.abs(v_fp), axis=(1, 2))


def input_abs_max(x: jnp.ndarray, spec: WinogradSpec,
                  padding: str = "same") -> jnp.ndarray:
    """Per-position abs-max of (N,H,W,Cin) in the Winograd input domain.

    One fp pass through the input transform + a reduction → (n²,) fp32.
    The calibration entry point; the dynamic fallback of
    ``winograd_conv2d_int8`` shares ``_tiles_abs_max`` underneath.
    """
    tiles = _extract(x, spec.m, spec.r, spec.n, padding)
    return _tiles_abs_max(tiles, spec)


def scales_from_abs_max(amax: jnp.ndarray) -> jnp.ndarray:
    """(n²,) abs-max → (n², 1) symmetric int8 scales."""
    return jnp.maximum(amax, 1e-12).reshape(-1, 1) / 127.0


def winograd_conv2d_int8(x: jnp.ndarray, w: Optional[jnp.ndarray],
                         spec: WinogradSpec,
                         padding: str = "same",
                         in_scales: Optional[jnp.ndarray] = None,
                         u_q: Optional[jnp.ndarray] = None,
                         w_scales: Optional[jnp.ndarray] = None,
                         hadamard_bits: Optional[int] = None,
                         h_amax: Optional[jnp.ndarray] = None,
                         fused: bool = False,
                         blocks: Optional[tuple] = None) -> jnp.ndarray:
    """True-int8 Winograd conv via the Pallas kernels.

    Two modes, chosen per argument:

    * **dynamic** (tests/benchmarks): pass raw HWIO weights ``w``; the
      weight transform + quantization (``prepare_weights_int8``) and the
      input-scale reduction (``input_abs_max``) run per call.
    * **prepared** (serving): pass ``u_q``/``w_scales`` from
      ``prepare_weights_int8`` and calibrated ``in_scales``; only the
      jitted hot path runs — extract → input_transform → wino_gemm →
      output_transform, with zero weight transforms and zero scale
      reductions.

    Both modes funnel into the same compiled execute function, so a
    prepared call whose calibration saw this batch matches the dynamic
    call bit-for-bit.

    ``fused=True`` requests the single-pass serving kernel
    (``kernels.fused_serve``): GEMM, Hadamard requant and output
    transform in one Pallas call, zero fp32 intermediates in HBM.  It
    engages when the requant stage is off or its statistics are
    calibrated (``h_amax``); otherwise the staged path runs (the dynamic
    requant reduction needs the whole Hadamard plane).  Fused and staged
    are integer-exact in the Hadamard domain and agree at fp32 output to
    float rounding, so the flag is a performance knob.

    ``blocks`` overrides the Pallas (bm, bn, bk) tile blocks for the GEMM
    and fused kernels (``None`` → ``wino_gemm.default_blocks`` for the
    spec's P) — the per-shape tuning knob; numerics are
    block-independent. See ``repro.conv.autotune`` for the offline
    per-(spec, shape) search.

    The kernels compile through Mosaic on a TPU and run in interpret mode
    on the CPU backend (``kernels.backend.interpret_mode``).
    """
    if u_q is None:
        if w is None:
            raise ValueError("pass either raw weights w or prepared "
                             "(u_q, w_scales)")
        u_q, w_scales = prepare_weights_int8(w, spec)
    elif w_scales is None:
        raise ValueError("prepared u_q requires w_scales")
    with jax.named_scope(telemetry.EXTRACT):
        tiles = _extract(x, spec.m, spec.r, spec.n, padding)    # once
    geom = _geometry(x.shape, spec.m, spec.r, padding)
    if in_scales is None:
        in_scales = scales_from_abs_max(_tiles_abs_max(tiles, spec))
    return execute_int8(tiles, u_q, w_scales, in_scales, h_amax,
                        spec=spec, geom=geom, hadamard_bits=hadamard_bits,
                        fused=fused, blocks=blocks)


def quantize_input(tiles: jnp.ndarray, in_scales: jnp.ndarray, *,
                   spec: WinogradSpec) -> jnp.ndarray:
    """THE int8 input transform + quantization compile unit.

    Every serving mode — staged/fused ``execute_int8``, the standalone
    kernel composition, and ``execute_int8_sharded`` — obtains its
    quantized Winograd-domain input ``Xq`` by calling exactly this
    function, which dispatches the one module-level
    ``kernels.wino_transform.input_transform`` jit. That makes the Xq
    bytes identical across modes by construction: a rounding-boundary
    input value can no longer quantize differently because a mode folded
    the transform into a differently-FMA-contracted XLA program (the
    pre-fix failure documented in docs/parity.md).
    """
    mats = make_matrices(spec)
    return input_transform(tiles, mats.CinvT, mats.BPT, in_scales,
                           changes_base=spec.changes_base)


def execute_int8(tiles: jnp.ndarray, u_q: jnp.ndarray,
                 w_scales: jnp.ndarray, in_scales: jnp.ndarray,
                 h_amax: Optional[jnp.ndarray] = None, *,
                 spec: WinogradSpec, geom: tuple,
                 hadamard_bits: Optional[int],
                 with_stats: bool = False,
                 fused: bool = False,
                 blocks: Optional[tuple] = None):
    """The serving hot path: consumes extracted tiles, prepared weights
    and static scales.

    Deliberately NOT one monolithic jit: it composes the module-level
    jitted units (``quantize_input`` → ``wino_gemm`` /
    ``fused_gemm_output`` → ``output_transform``), so every serving mode
    shares the same compiled programs — in particular the input
    quantization (one Xq everywhere; docs/parity.md). The historical
    monolithic-jit form compiled the input transform into its own larger
    program, whose FMA contraction could flip an int8 input-quantization
    decision on a rounding boundary against the standalone/sharded
    compositions. Production serving wraps the whole forward in an outer
    ``jax.jit`` anyway, which inlines these units into one program.

    With calibrated ``h_amax`` — the (n²,) per-position abs-max of the
    Hadamard products, recorded offline — the requant stage does no
    reduction either: the fully-prepared path is reduction-free. The
    statistic rides as a raw abs-max (not a final scale) so the
    scale formula stays inside this graph in both modes, keeping
    calibrated and dynamic executions bit-identical on the calibration
    batch. ``with_stats=True`` (calibration) additionally returns that
    abs-max.

    ``fused=True`` routes GEMM → Hadamard requant → output transform
    through the single-pass ``kernels.fused_serve`` kernel whenever no
    dynamic reduction is needed (requant off, or ``h_amax`` calibrated,
    and not ``with_stats``); the staged path remains the fallback and
    the numerical reference (integer-exact agreement in the Hadamard
    domain, fp32 agreement to rounding).

    ``blocks`` overrides the Pallas (bm, bn, bk) tile blocks of the GEMM
    / fused kernel; ``None`` keeps ``wino_gemm.default_blocks`` for the
    spec. Malformed overrides raise ``ValueError`` here, before any
    kernel launch.
    """
    assert not (with_stats and hadamard_bits is None)
    blocks = validate_blocks(blocks)    # also normalizes lists → tuple
    mats = make_matrices(spec)
    m = spec.m

    with jax.named_scope(telemetry.INPUT_TRANSFORM):
        Xq = quantize_input(tiles, in_scales, spec=spec)
    deq = in_scales * w_scales                       # (P, 1)

    use_fused = (fused and not with_stats
                 and (hadamard_bits is None or h_amax is not None))
    if use_fused:
        with jax.named_scope(telemetry.GEMM_OUTPUT):
            if hadamard_bits is None:
                rq = jnp.ones_like(deq)
            else:
                # Same scale formula as the staged requant below — keeping
                # the fused and staged executions bit-identical.
                rq = _hadamard_rq(h_amax, hadamard_bits)
            y = fused_gemm_output(Xq, u_q, deq, rq, mats.CinvT, mats.APT,
                                  m=m, requant_bits=hadamard_bits,
                                  changes_base=spec.changes_base,
                                  blocks=blocks)
        with jax.named_scope(telemetry.REASSEMBLE):
            return _reassemble(y, geom, m)

    amax_h = None
    if (hadamard_bits is not None and h_amax is not None
            and not with_stats):
        # Staged serving with calibrated requant scales runs the
        # Hadamard stage as the wino_gemm in-register epilogue: exactly
        # the grid the XLA formula below produces (asserted in tests),
        # minus two HBM passes over the (P, T, Cout) plane.
        rq = _hadamard_rq(h_amax, hadamard_bits)
        H = wino_gemm(Xq, u_q, blocks=blocks,
                      requant_bits=hadamard_bits, deq=deq, rq=rq)
        deq = rq
    else:
        H = wino_gemm(Xq, u_q, blocks=blocks)       # (P, T, Cout) int32
        if hadamard_bits is not None:
            # The paper's 8/9-bit Hadamard stage: requantize the int32
            # products onto a 2^b-level grid (per position) before the
            # output transform — deriving the scale dynamically (no
            # calibration, or recording statistics for one).
            hf = H.astype(jnp.float32) * deq[:, :, None]
            if h_amax is None or with_stats:
                amax_h = jnp.max(jnp.abs(hf), axis=(1, 2), keepdims=True)
            amax = amax_h if h_amax is None else h_amax.reshape(-1, 1, 1)
            s_h = jnp.maximum(amax, 1e-12) / qmax(hadamard_bits)
            H = jnp.clip(jnp.round(hf / s_h), -qmax(hadamard_bits),
                         qmax(hadamard_bits)).astype(jnp.int32)
            deq = s_h[:, :, 0]

    with jax.named_scope(telemetry.OUTPUT_TRANSFORM):
        y = output_transform(H, deq, mats.CinvT, mats.APT, m=m,
                             changes_base=spec.changes_base)
    with jax.named_scope(telemetry.REASSEMBLE):
        out = _reassemble(y, geom, m)
    if with_stats:
        return out, amax_h[:, 0, 0]
    return out


def execute_int8_sharded(tiles: jnp.ndarray, u_q: jnp.ndarray,
                         w_scales: jnp.ndarray, in_scales: jnp.ndarray,
                         h_amax: Optional[jnp.ndarray] = None, *,
                         spec: WinogradSpec, geom: tuple, mesh,
                         hadamard_bits: Optional[int],
                         blocks: Optional[tuple] = None,
                         data_axis="data",
                         model_axis=None) -> jnp.ndarray:
    """Multi-device serving over a 2-D (data × model) mesh: shard the
    Winograd tile axis T over ``data_axis`` and the per-position GEMM's
    N axis (Cout) over ``model_axis``.

    The fused hot path is embarrassingly parallel over tiles AND over
    output channels — every stage past extraction (input transform,
    per-position GEMM, Hadamard requant, output transform) is
    independent per (tile row, output channel), and the requant scales
    are per-position statistics shared by every (t, c) element. So the
    tensor splits both ways: each device runs the *same* single-pass
    ``kernels.fused_serve`` kernel on its ``(T/D_data, Cout/D_model)``
    slab against only its ``(P, Cin, Cout/D_model)`` weight shard —
    packed bytes per device scale as 1/D_model, which is what lets one
    hot layer outgrow a single device. Exactly ONE model-axis
    collective runs per layer: an ``all_gather`` of the small
    ``(m², T_local, Cout_local)`` spatial outputs; the (P, T, Cout)
    Hadamard plane never crosses the interconnect. ``model_axis=None``
    (default) is the degenerate D_model = 1 mesh — the PR-3 data-only
    path, bit for bit.

    Numerics: the input quantization runs on the full tile tensor
    through ``quantize_input`` — the same compile unit every other mode
    dispatches, replicated on every device of the mesh because a Mosaic
    kernel is never partitioned automatically — and only the resulting
    int8 ``Xq`` is sharded (slicing
    integer data is exact), so "one Xq everywhere" holds by
    construction. Per-element arithmetic downstream is untouched (same
    fused kernel, same operand order, the K grid is not split — "cin"
    never shards), so the sharded execution is **integer-exact in the
    Hadamard domain and bit-identical at fp32 output** to single-device
    fused execution on any mesh shape; asserted in
    ``tests/test_distributed.py``.

    Dynamic requant (``hadamard_bits`` set, no calibrated ``h_amax``)
    now runs sharded too, instead of falling back to one device: each
    shard reduces its local ``|·|max`` over its (T_local, Cout_local)
    Hadamard slab and ONE ``lax.pmax`` over both mesh axes merges them.
    max-of-maxima IS the global abs-max — exactly, not approximately —
    so the requant grid every shard then applies is identical to the
    single-device derivation and the output is exactly equal to
    single-device dynamic requant (the staged ``execute_int8`` path).
    This costs a second (scalar-sized: (P, 1, 1)) collective per layer,
    which is why calibrated layers remain the hot-path default.

    ``T`` is zero-padded up to the data-axis extent (exact: zero int8
    rows produce zero GEMM rows — and zero Hadamard products, which
    never raise an abs-max — cropped before reassembly). ``Cout`` must
    divide the model-axis extent: the weight shards are placed that way
    (``conv.packing.place_packed_state``), and a ragged N split would
    desynchronize the gather from the placement.
    """
    from repro.distributed.sharding import axis_extent
    blocks = validate_blocks(blocks)    # also normalizes lists → tuple
    dm = axis_extent(mesh, model_axis)
    cout = u_q.shape[-1]
    if cout % dm != 0:
        raise ValueError(
            f"sharded serving: Cout={cout} is not divisible by the "
            f"{model_axis!r} mesh axis extent {dm} — conv tensor "
            "parallelism slices the per-position GEMM's N axis into "
            "equal per-device slabs (see conv.packing)")
    deq = in_scales * w_scales
    dynamic = hadamard_bits is not None and h_amax is None
    if hadamard_bits is None:
        rq = jnp.ones_like(deq)
    elif not dynamic:
        # Same scale formula as execute_int8 (shared helper) — sharded,
        # single-device fused and staged requantize onto one grid.
        rq = _hadamard_rq(h_amax, hadamard_bits)

    # One Xq: quantize the FULL tile tensor in the shared compile unit,
    # then shard the int8 result across the mesh. Mosaic kernels are
    # never partitioned automatically, so on a TPU the unit runs inside
    # a replicated shard_map; interpret mode has no such limit.
    if backend.interpret_mode():
        Xq = quantize_input(tiles, in_scales, spec=spec)
    else:
        Xq = _replicated_quantizer(spec, mesh)(tiles, in_scales)

    ndev = axis_extent(mesh, data_axis)
    T = Xq.shape[1]
    pad = (-T) % ndev
    if pad:
        Xq = jnp.pad(Xq, ((0, 0), (0, pad), (0, 0)))

    da = tuple(data_axis) if isinstance(data_axis, list) else data_axis
    fn = _sharded_executor(spec, mesh, hadamard_bits, blocks, da,
                           model_axis, dynamic)
    y = fn(Xq, u_q, deq) if dynamic else fn(Xq, u_q, deq, rq)
    return _reassemble(y[:, :T], geom, spec.m)


@functools.lru_cache(maxsize=None)
def _replicated_quantizer(spec: WinogradSpec, mesh: jax.sharding.Mesh):
    """``quantize_input`` run whole on every device of ``mesh``: each
    device quantizes the full tile tensor with the single-device
    program, so the replicated Xq is the single-device Xq."""
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(functools.partial(quantize_input, spec=spec),
                         mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                         check_vma=False)


@functools.lru_cache(maxsize=None)
def _sharded_executor(spec: WinogradSpec, mesh: jax.sharding.Mesh,
                      hadamard_bits: Optional[int],
                      blocks: Optional[tuple], data_axis: str | tuple,
                      model_axis: Optional[str], dynamic: bool):
    """shard_map slab executor, cached per static configuration.

    The heavy lowering is cached regardless — ``input_transform``,
    ``wino_gemm``, ``output_transform`` and ``fused_gemm_output`` are
    module-level jits, so their compile caches hit on every call; this
    cache additionally stops an eagerly-served mesh engine from
    rebuilding the slab closure + shard_map wrapper per call.
    Deliberately NOT wrapped in an outer ``jax.jit``: folding the slab
    into one compile unit perturbs FMA contraction by a last bit and
    would break the documented bitwise parity with the standalone fused
    composition (docs/parity.md); production serving jits the whole
    forward anyway. One entry per (spec, mesh, …) — a handful of live
    meshes, so unbounded is fine.

    The 2-D layout: ``Xq`` (P, T, Cin) shards T over ``data_axis``;
    ``u_q`` (P, Cin, Cout) shards Cout over ``model_axis`` (matching
    its ``place_packed_state`` placement, so the weights are already
    local); the per-position scale vectors are replicated. Each slab
    produces (m², T_local, Cout_local) and the one per-layer
    model-axis ``all_gather`` (tiled, in mesh-index order — the same
    order the weight shards were sliced in) reassembles the full Cout
    before the data-axis outputs concatenate via ``out_specs``.
    """
    from jax.sharding import PartitionSpec as P
    mats = make_matrices(spec)
    qm = qmax(hadamard_bits) if hadamard_bits is not None else None
    # The dynamic pmax spans the whole (T, Cout) plane — T is sharded
    # over the data axis and Cout over the model axis, so the reduction
    # names both (a single collective over the full mesh).
    red_axes = data_axis if isinstance(data_axis, tuple) else (data_axis,)
    if model_axis is not None:
        red_axes = red_axes + (model_axis,)

    def _gather(y_l):
        if model_axis is None:
            return y_l
        # THE one model-axis collective of the calibrated hot path:
        # (m², T_local, Cout_local) → (m², T_local, Cout), tiled concat
        # along the channel axis.
        return jax.lax.all_gather(y_l, model_axis, axis=2, tiled=True)

    def _slab(xq_l, uq_l, deq, rq):
        # Consumes a pre-quantized (P, T_local, Cin) int8 slab — the
        # input transform runs once on the full tensor (one Xq
        # everywhere), NOT per slab — and this device's
        # (P, Cin, Cout_local) weight shard.
        return _gather(fused_gemm_output(
            xq_l, uq_l, deq, rq, mats.CinvT, mats.APT,
            m=spec.m, requant_bits=hadamard_bits,
            changes_base=spec.changes_base, blocks=blocks))

    def _slab_dynamic(xq_l, uq_l, deq):
        # Sharded dynamic requant: the staged pipeline per slab, with
        # the plane-wide abs-max assembled from shard-local maxima by
        # one pmax. Same formulas, same operand order as the staged
        # ``execute_int8`` dynamic branch — max-of-maxima is exact, so
        # every downstream elementwise value matches the single-device
        # derivation bit for bit.
        H = wino_gemm(xq_l, uq_l, blocks=blocks)
        hf = H.astype(jnp.float32) * deq[:, :, None]
        amax = jnp.max(jnp.abs(hf), axis=(1, 2), keepdims=True)
        amax = jax.lax.pmax(amax, red_axes)
        s_h = jnp.maximum(amax, 1e-12) / qm
        Hq = jnp.clip(jnp.round(hf / s_h), -qm, qm).astype(jnp.int32)
        return _gather(output_transform(
            Hq, s_h[:, :, 0], mats.CinvT, mats.APT, m=spec.m,
            changes_base=spec.changes_base))

    xq_spec = P(None, data_axis)        # Xq is (P, T, Cin): shard T
    wq_spec = P(None, None, model_axis)  # u_q (P, Cin, Cout): shard Cout
    out = P(None, data_axis)             # (m², T, Cout): T is sharded
    if dynamic:
        return jax.shard_map(_slab_dynamic, mesh=mesh,
                             in_specs=(xq_spec, wq_spec, P()),
                             out_specs=out, check_vma=False)
    return jax.shard_map(_slab, mesh=mesh,
                         in_specs=(xq_spec, wq_spec, P(), P()),
                         out_specs=out, check_vma=False)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def q8_linear(x: jnp.ndarray, w: jnp.ndarray,
              out_dtype=jnp.float32) -> jnp.ndarray:
    """Dynamic w8a8 linear: quantize x per-tensor / w per-col, MXU int8 GEMM.

    x: (..., K) fp, w: (K, N) fp → (..., N) fp.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    s_x = jnp.maximum(jnp.max(jnp.abs(x2)), 1e-12) / 127.0
    s_w = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-12) / 127.0
    xq = jnp.clip(jnp.round(x2 / s_x), -127, 127).astype(jnp.int8)
    wq = jnp.clip(jnp.round(w / s_w[None, :]), -127, 127).astype(jnp.int8)
    y = q8_matmul(xq, wq, s_x, s_w, out_dtype=out_dtype)
    return y.reshape(*lead, -1)
