"""Kernel micro-benchmarks (CPU wall-time is indicative only; TPU numbers
come from the §Roofline model). Compares the Winograd path against direct
convolution and im2col-GEMM at paper-realistic layer shapes, plus an
engine-level sweep over the ConvEngine backends including the
dynamic-vs-calibrated int8 scaling split and the fused-vs-staged serving
pipelines.

Emits the brief's CSV rows to stdout and a machine-readable
``BENCH_kernel.json`` at the repo root (``--json`` to relocate); pass
``--smoke`` for the CI-sized subset (``make bench-smoke``).
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, ensure_host_devices, time_fn, write_json
from repro.conv import BACKENDS, ConvEngine, ConvPolicy
from repro.core.quantization import QuantConfig
from repro.core.winograd import (WinogradSpec, _pad_amounts, direct_conv2d,
                                 winograd_conv2d)
from repro.kernels import ref as kref
from repro.kernels.wino_gemm import wino_gemm
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import require_host_devices

SHAPES = [  # (B, H, W, Cin, Cout) — ResNet18-CIFAR ×0.5 stage shapes
    (8, 32, 32, 32, 32),
    (8, 16, 16, 64, 64),
    (8, 8, 8, 128, 128),
]

ENGINE_SHAPES = [(4, 16, 16, 32, 32), (2, 8, 8, 128, 128)]
SMOKE_ENGINE_SHAPES = [(2, 8, 8, 16, 16)]


def im2col_conv(x, w):
    B, H, W, C = x.shape
    r = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = jnp.stack([xp[:, i:i + H, j:j + W, :]
                      for i in range(r) for j in range(r)], -2)
    return jnp.einsum("bhwkc,kcd->bhwd", cols,
                      w.reshape(r * r, C, -1))


def hbm_bytes_model(B, H, W, Ci, Co, spec: WinogradSpec,
                    requant_glue: bool) -> tuple[int, int]:
    """Analytic HBM bytes moved by the int8 pipeline past tile extraction.

    Staged: input_transform writes Xq int8; wino_gemm reads Xq + u_q and
    writes the (P, T, Cout) int32 H (the calibrated Hadamard requant
    runs as its in-register epilogue; only the *dynamic* derivation —
    ``requant_glue`` — pays an extra XLA read+write of H);
    output_transform reads H and writes the fp32 output tiles.  Fused:
    the H round-trips vanish — one kernel reads Xq + u_q and writes the
    output tiles.  Returns ``(staged, fused)`` bytes per call (tile
    reads and Xq traffic are common to both and included).
    """
    _, _, nt_h, _ = _pad_amounts(H, spec.m, spec.r, "same")
    _, _, nt_w, _ = _pad_amounts(W, spec.m, spec.r, "same")
    T = B * nt_h * nt_w
    P = spec.n * spec.n
    tiles_r = T * Ci * spec.n * spec.n * 4          # fp32 tile read
    xq = P * T * Ci                                  # int8
    uq = P * Ci * Co                                 # int8
    h32 = P * T * Co * 4                             # int32 Hadamard plane
    out_w = T * Co * spec.m * spec.m * 4             # fp32 output tiles
    common = tiles_r + xq + xq + uq                  # transform + gemm reads
    staged = common + h32                            # gemm writes H
    if requant_glue:
        staged += 2 * h32                            # XLA requant r+w
    staged += h32 + out_w                            # output transform
    fused = common + out_w
    return staged, fused


def hbm_model_crosscheck(smoke: bool = False) -> dict:
    """Gate ``hbm_bytes_model`` against the compiler's own accounting.

    The analytic model above is what the benchmark rows and the roofline
    narrative lean on — if it drifts from what XLA actually materializes
    (a kernel grows an HBM intermediate, a dtype widens), every derived
    number silently lies. This cross-checks it per compiled unit: the
    fused serving path is exactly two ``pallas_call`` jits
    (``input_transform`` → ``fused_gemm_output``), and the model's fused
    total decomposes as the sum of their ENTRY-boundary bytes
    (``repro.analysis.hlo_cost.entry_boundary_bytes``: parameters in,
    ROOT out — the "touch operands once, write result once" semantics
    the model prices). Boundary bytes, not ``analyze_hlo``'s
    instruction total: interpret-mode Pallas emulation materializes
    VMEM-resident compute as instructions and inflates that total ~17×.

    The run FAILS (RuntimeError) on >2× divergence; the slack covers
    the scale/matrix operands and padding the model rounds away.
    """
    from repro.analysis.hlo_cost import entry_boundary_bytes
    from repro.core.winograd import make_matrices
    from repro.kernels.fused_serve import fused_gemm_output
    from repro.kernels.wino_transform import input_transform

    spec = WinogradSpec(m=4, r=3, base="legendre",
                        quant=QuantConfig(hadamard_bits=9))
    B, H, W, Ci, Co = SMOKE_ENGINE_SHAPES[0]
    _, _, nt_h, _ = _pad_amounts(H, spec.m, spec.r, "same")
    _, _, nt_w, _ = _pad_amounts(W, spec.m, spec.r, "same")
    T, n = B * nt_h * nt_w, spec.n
    P = n * n
    mats = make_matrices(spec)
    tiles = jnp.zeros((P, T, Ci), jnp.float32)
    scales = jnp.ones((P, 1), jnp.float32)
    xq = jnp.zeros((P, T, Ci), jnp.int8)
    uq = jnp.zeros((P, Ci, Co), jnp.int8)
    cinvt = jnp.asarray(mats.CinvT, jnp.float32)
    bpt = jnp.asarray(mats.BPT, jnp.float32)
    apt = jnp.asarray(mats.APT, jnp.float32)

    boundary = 0
    for name, lowered in (
        ("input_transform",
         input_transform.lower(tiles, cinvt, bpt, scales,
                               changes_base=True)),
        ("fused_gemm_output",
         fused_gemm_output.lower(xq, uq, scales, scales, cinvt, apt,
                                 m=spec.m, requant_bits=9,
                                 changes_base=True)),
    ):
        bb = entry_boundary_bytes(lowered.compile().as_text())
        boundary += bb["total"]
        print(f"# hbm_crosscheck {name}: params {bb['parameter_bytes']} "
              f"+ root {bb['root_bytes']} bytes")

    _, model_fused = hbm_bytes_model(B, H, W, Ci, Co, spec,
                                     requant_glue=False)
    ratio = max(boundary, model_fused) / max(min(boundary, model_fused), 1)
    emit("hbm_model_crosscheck_fused", ratio,
         "compiled ENTRY-boundary bytes vs analytic model (ratio)",
         shape=f"{B}x{H}x{W}x{Ci}->{Co}",
         boundary_bytes=boundary, model_bytes=model_fused)
    if ratio > 2.0:
        raise RuntimeError(
            f"hbm_bytes_model diverged from the compiled kernels: "
            f"model {model_fused} vs ENTRY-boundary {boundary} bytes "
            f"({ratio:.2f}x > 2x) — the model or a kernel changed; "
            f"reconcile them before trusting the HBM columns")
    print(f"# hbm_crosscheck: model {model_fused} vs boundary {boundary} "
          f"bytes ({ratio:.2f}x <= 2x)")
    return {"boundary": boundary, "model": model_fused, "ratio": ratio}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized subset: engine fused-vs-staged rows "
                         "(incl. the F(6,3) pipeline + autotune rows) "
                         "only")
    ap.add_argument("--json", default="BENCH_kernel.json",
                    help="machine-readable output path")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="CPU only: split the host CPU into N XLA devices "
                         "so the sharded rows cover real multi-device "
                         "meshes")
    args = ap.parse_args(argv)
    ensure_host_devices(args.host_devices, "benchmarks.kernel_bench",
                        argv if argv is not None else sys.argv[1:])
    require_host_devices(args.host_devices)
    enable_compile_cache()

    hbm_model_crosscheck(smoke=args.smoke)
    if not args.smoke:
        xla_sweep()
        gemm_micro()
    engine_bench(smoke=args.smoke)
    f63_bench(smoke=args.smoke)
    autotune_bench(smoke=args.smoke)
    sharded_bench(smoke=args.smoke)
    tp_bench(smoke=args.smoke)
    plan_bench(smoke=args.smoke)
    write_json(args.json, smoke=args.smoke,
               backend=jax.default_backend(),
               note=f"Pallas on {jax.default_backend()} (interpret mode "
                    "on cpu); CPU walls are regression guards, not speed")


def xla_sweep():
    key = jax.random.PRNGKey(0)
    for (B, H, W, Ci, Co) in SHAPES:
        x = jax.random.normal(key, (B, H, W, Ci))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, Ci, Co)) * 0.1
        tag = f"{B}x{H}x{W}x{Ci}->{Co}"

        us = time_fn(jax.jit(lambda x, w: direct_conv2d(x, w, "same")), x, w)
        emit(f"direct_conv_{tag}", us, "lax.conv", shape=tag)
        us = time_fn(jax.jit(im2col_conv), x, w)
        emit(f"im2col_conv_{tag}", us, "im2col+gemm", shape=tag)

        spec_fp = WinogradSpec(m=4, r=3, base="legendre",
                               quant=QuantConfig.off())
        us = time_fn(jax.jit(lambda x, w: winograd_conv2d(x, w, spec_fp)),
                     x, w)
        emit(f"wino_fp32_legendre_{tag}", us, "XLA einsum pipeline",
             shape=tag)

        spec_q = WinogradSpec(m=4, r=3, base="legendre",
                              quant=QuantConfig(hadamard_bits=9))
        us = time_fn(jax.jit(lambda x, w: winograd_conv2d(x, w, spec_q)),
                     x, w)
        emit(f"wino_q8_legendre_{tag}", us, "fake-quant QAT pipeline",
             shape=tag)


def gemm_micro():
    # Winograd-domain GEMM: interpret-mode Pallas vs jnp oracle (CPU;
    # correctness/latency smoke only — the MXU path is the TPU target)
    key = jax.random.PRNGKey(0)
    P, M, K, N = 36, 256, 64, 64
    xq = jax.random.randint(key, (P, M, K), -127, 128, jnp.int8)
    wq = jax.random.randint(jax.random.PRNGKey(2), (P, K, N), -127, 128,
                            jnp.int8)
    us = time_fn(lambda a, b: wino_gemm(a, b, blocks=(128, 64, 64)),
                 xq, wq, iters=3)
    emit(f"pallas_wino_gemm_interp_{P}x{M}x{K}x{N}", us,
         "interpret-mode (CPU emulation)")
    us = time_fn(jax.jit(kref.wino_gemm_ref), xq, wq)
    emit(f"jnp_wino_gemm_ref_{P}x{M}x{K}x{N}", us, "XLA int32 einsum")


def prepared_pipeline_rows(spec, shape, tag, iters, warmup,
                           derived=None) -> dict:
    """Time the prepared staged/fused engine rows for one (spec, shape).

    THE single encoding of the prepared-pipeline row protocol (engine
    build → prepare → calibrate → eager serve timing, the
    ``engine_winograd_int8_prepared_<label>_<tag>`` naming that
    ``trend_check.PIPELINE_ROW`` gates, and the HBM-bytes model
    column) — shared by the F(4,3) and F(6,3) sections so the gate
    contract cannot drift between them. Returns {label: us}.
    """
    B, H, W, Ci, Co = shape
    x = jax.random.normal(jax.random.PRNGKey(0), (B, H, W, Ci))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, Ci, Co)) * 0.1
    bytes_staged, bytes_fused = hbm_bytes_model(
        B, H, W, Ci, Co, spec, requant_glue=False)     # calibrated rows
    rows = {}
    for fused in (False, True):
        eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                         fused=fused)
        eng.prepare([("bench", w, 1)])
        with eng.calibration():
            eng.conv2d(x, w, layer="bench")
        label = "fused" if fused else "staged"
        us = time_fn(lambda a, e=eng: e.conv2d(a, None, layer="bench"),
                     x, warmup=warmup, iters=iters)
        rows[label] = us
        emit(f"engine_winograd_int8_prepared_{label}_{tag}", us,
             (derived or {}).get(label,
                                 f"packed+calibrated {label} hot path"),
             shape=tag,
             hbm_bytes_model=bytes_fused if fused else bytes_staged)
    return rows


def engine_bench(smoke: bool = False):
    """ConvEngine backend sweep + the prepare/execute split + fusion.

    The int8 rows isolate what offline packing+calibration buys: the
    dynamic path re-transforms weights and re-derives per-position scales
    inside every call; the prepared path runs the
    extract→transform→GEMM→output hot path only — staged as three Pallas
    calls with fp32 XLA requant glue, or fused into a single
    GEMM→requant→output-transform kernel (bit-identical; the HBM-bytes
    columns model what fusion saves).  The deep-stage shape
    (weight-heavy, small tile grid) is where the offline split pays most;
    interpret-mode Pallas inflates the shared hot-path cost, so TPU
    speedups are larger than these CPU numbers.
    """
    spec = WinogradSpec(m=4, r=3, base="legendre",
                        quant=QuantConfig(hadamard_bits=9))
    # Interpret-mode medians at few iters are noisy enough to flip the
    # close fused-vs-staged comparison — and since trend_check gates
    # smoke rows against the committed full-run baseline, smoke must
    # measure with the same 9 iters (per-call cost at the smoke shape is
    # milliseconds; compile time dominates either way).
    iters = 9
    warmup = 2
    backends = ("winograd_int8",) if smoke else BACKENDS
    # Full runs also cover the smoke shape so the committed
    # BENCH_kernel.json always has baselines for the rows that CI's
    # --smoke run emits (benchmarks.trend_check compares on row names).
    for (B, H, W, Ci, Co) in (SMOKE_ENGINE_SHAPES if smoke
                              else ENGINE_SHAPES + SMOKE_ENGINE_SHAPES):
        tag = f"{B}x{H}x{W}x{Ci}->{Co}"
        x = jax.random.normal(jax.random.PRNGKey(0), (B, H, W, Ci))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, Ci, Co)) * 0.1
        bytes_staged, bytes_fused = hbm_bytes_model(
            B, H, W, Ci, Co, spec, requant_glue=False)  # calibrated rows

        dyn_us = {}
        for backend in backends:
            engine = ConvEngine(spec, ConvPolicy(backend=backend))
            us = time_fn(lambda a, b, e=engine: e.conv2d(a, b,
                                                         layer="bench"),
                         x, w, warmup=warmup, iters=iters)
            emit(f"engine_{backend}_{tag}", us,
                 "dynamic scales" if backend == "winograd_int8"
                 else "stateless", shape=tag)
            dyn_us[backend] = us
        us_dyn = dyn_us["winograd_int8"]    # bound explicitly, not by
        #                                     BACKENDS iteration order

        rows = prepared_pipeline_rows(
            spec, (B, H, W, Ci, Co), tag, iters, warmup,
            derived={"fused": "packed+calibrated hot path: single-pass "
                              "GEMM+requant+output kernel",
                     "staged": "packed+calibrated hot path: 3 Pallas "
                               "calls (requant epilogue in GEMM)"})
        print(f"# {tag}: prepared staged int8 speedup over dynamic: "
              f"{us_dyn / max(rows['staged'], 1e-9):.2f}x")
        print(f"# {tag}: fused over staged: "
              f"{rows['staged'] / max(rows['fused'], 1e-9):.2f}x wall, "
              f"{bytes_staged / bytes_fused:.2f}x modelled HBM bytes "
              f"({bytes_staged} -> {bytes_fused})")


def f63_bench(smoke: bool = False):
    """F(6,3) int8 serving rows: the large-tile spec through the same
    prepared fused/staged pipelines (P = 64 positions, 2.25× fewer
    multiplications per output than F(4,3) at higher transform cost).
    Rows follow the prepared-pipeline naming, so the trend gate covers
    them once a baseline is committed; the dynamic row doubles as the
    per-shape normalizer."""
    spec = WinogradSpec(m=6, r=3, base="legendre",
                        quant=QuantConfig(hadamard_bits=9))
    iters, warmup = 9, 2
    shapes = [(2, 12, 12, 16, 16)] if smoke else \
        [(2, 12, 12, 16, 16), (2, 12, 12, 64, 64)]
    for (B, H, W, Ci, Co) in shapes:
        tag = f"f63_{B}x{H}x{W}x{Ci}->{Co}"
        x = jax.random.normal(jax.random.PRNGKey(0), (B, H, W, Ci))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, Ci, Co)) * 0.1

        engine = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
        us_dyn = time_fn(lambda a, b, e=engine: e.conv2d(a, b,
                                                         layer="bench"),
                         x, w, warmup=warmup, iters=iters)
        emit(f"engine_winograd_int8_{tag}", us_dyn, "dynamic scales",
             shape=tag)
        prepared_pipeline_rows(
            spec, (B, H, W, Ci, Co), tag, iters, warmup,
            derived={"fused": "packed+calibrated F(6,3) hot path",
                     "staged": "packed+calibrated F(6,3) hot path"})


def autotune_bench(smoke: bool = False):
    """Autotuned-vs-default block rows for the fused serving kernel.

    One pair of rows per (spec, shape): the spec-default (bm, bn, bk)
    heuristic and the ``repro.conv.autotune`` winner on synthetic
    operands of exactly the serving shape. These are wall-only rows
    (numerics are block-independent) and deliberately do NOT match the
    trend gate's pipeline-row pattern — the tuner's own argmin already
    guarantees tuned ≤ default up to timer noise; re-gating them in CI
    would gate the noise.
    """
    from repro.conv.autotune import autotune_blocks

    cases = [("f43", WinogradSpec(m=4, r=3, base="legendre",
                                  quant=QuantConfig(hadamard_bits=9)),
              (288, 32, 32)),
             ("f63", WinogradSpec(m=6, r=3, base="legendre",
                                  quant=QuantConfig(hadamard_bits=9)),
              (128, 64, 64))]
    if smoke:
        cases = cases[-1:]
    for name, spec, (T, Ci, Co) in cases:
        tag = f"{name}_T{T}x{Ci}->{Co}"
        res = autotune_blocks(spec, T, Ci, Co, hadamard_bits=9,
                              iters=3 if smoke else 5,
                              warmup=1, max_candidates=6 if smoke else 10)
        emit(f"autotune_fused_default_{tag}", res.default_us,
             "spec-default blocks", shape=tag,
             blocks=list(res.default_blocks))
        emit(f"autotune_fused_tuned_{tag}", res.us,
             "autotuned blocks", shape=tag, blocks=list(res.blocks),
             speedup_over_default=round(res.speedup, 3))
        print(f"# autotune {tag}: {res.default_blocks} "
              f"{res.default_us:.0f}us -> {res.blocks} {res.us:.0f}us "
              f"({res.speedup:.2f}x)")


def sharded_bench(smoke: bool = False):
    """Sharded fused serving: one throughput row per device count.

    The prepared+calibrated engine serves through
    ``ConvEngine(mesh=...)`` — tile-axis shard_map, every device running
    the fused kernel on its slab — under an outer jit (the production
    shape: one XLA program per mesh). On a stock CPU run there is one
    device and the 1-device mesh row simply measures the shard_map
    overhead over the unsharded fused row; pass ``--host-devices 4`` (or
    run on a real multi-chip backend) for the scaling rows. These rows
    are device-topology-dependent and therefore *excluded* from the
    trend gate (``benchmarks.trend_check`` matches only the
    fused/staged pipeline rows).
    """
    from jax.sharding import Mesh

    spec = WinogradSpec(m=4, r=3, base="legendre",
                        quant=QuantConfig(hadamard_bits=9))
    iters = 2 if smoke else 5
    warmup = 1 if smoke else 2
    ndev = len(jax.devices())
    counts = sorted({d for d in (1, 2, 4, 8) if d <= ndev} | {ndev})
    for (B, H, W, Ci, Co) in (SMOKE_ENGINE_SHAPES if smoke
                              else ENGINE_SHAPES[-1:]):
        tag = f"{B}x{H}x{W}x{Ci}->{Co}"
        x = jax.random.normal(jax.random.PRNGKey(0), (B, H, W, Ci))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, Ci, Co)) * 0.1
        for d in counts:
            mesh = Mesh(np.array(jax.devices()[:d]), ("data",))
            eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                             mesh=mesh)
            eng.prepare([("bench", w, 1)])
            with eng.calibration():
                eng.conv2d(x, w, layer="bench")
            fn = jax.jit(lambda a, e=eng: e.conv2d(a, None, layer="bench"))
            us = time_fn(fn, x, warmup=warmup, iters=iters)
            emit(f"engine_winograd_int8_sharded_fused_{d}dev_{tag}", us,
                 "tile-axis shard_map, fused kernel per slab",
                 shape=tag, devices=d)


def tp_bench(smoke: bool = False):
    """Conv tensor parallelism: wall + per-device packed bytes per mesh
    split — data-only, model-only and 2-D (data × model) over the same
    device budget.

    What the splits trade: the data axis shards the tile slab (compute
    scales, weights replicate — per-device packed bytes stay at 1×);
    the model axis shards Cout (per-device ``u_q`` bytes drop to
    1/D_model at the cost of one per-layer all_gather); 2-D buys both.
    The ``packed_bytes_per_device`` field is *measured* from the placed
    arrays' addressable shards, not modelled — it is the acceptance
    number for the weight-memory claim. Like the sharded rows these are
    topology-dependent and excluded from the trend gate
    (``benchmarks.trend_check``).
    """
    from jax.sharding import Mesh

    from repro.conv.packing import place_packed_state

    spec = WinogradSpec(m=4, r=3, base="legendre",
                        quant=QuantConfig(hadamard_bits=9))
    iters = 2 if smoke else 5
    warmup = 1 if smoke else 2
    ndev = len(jax.devices())
    budget = max(d for d in (1, 2, 4) if d <= ndev)
    splits = sorted({(budget, 1), (1, budget)}
                    | ({(budget // 2, 2)} if budget >= 4 else set()))
    for (B, H, W, Ci, Co) in (SMOKE_ENGINE_SHAPES if smoke
                              else ENGINE_SHAPES[-1:]):
        tag = f"{B}x{H}x{W}x{Ci}->{Co}"
        x = jax.random.normal(jax.random.PRNGKey(0), (B, H, W, Ci))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, Ci, Co)) * 0.1
        for dd, dm in splits:
            mesh = Mesh(np.array(jax.devices()[:dd * dm]).reshape(dd, dm),
                        ("data", "model"))
            ma = "model" if dm > 1 else None
            eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                             mesh=mesh, model_axis=ma)
            eng.prepare([("bench", w, 1)])
            with eng.calibration():
                eng.conv2d(x, w, layer="bench")
            placed = place_packed_state(mesh, eng.export_state(),
                                        model_axis=ma)
            dev0 = mesh.devices.flat[0]
            per_dev = sum(
                next(s.data.nbytes for s in leaf.addressable_shards
                     if s.device == dev0)
                for leaf in jax.tree.leaves(placed["packed"]))
            fn = jax.jit(lambda a, e=eng: e.conv2d(a, None, layer="bench"))
            us = time_fn(fn, x, warmup=warmup, iters=iters)
            emit(f"engine_winograd_int8_tp_{dd}x{dm}dev_{tag}", us,
                 "2-D (data x model) shard_map: tiles x Cout slabs, "
                 "one model-axis all_gather per layer",
                 shape=tag, devices=dd * dm, split=f"{dd}x{dm}",
                 packed_bytes_per_device=int(per_dev))


def plan_bench(smoke: bool = False):
    """Planner outcome rows: the measured per-layer plan vs the direct
    fallback on the same layer menu (``repro.conv.planner``).

    One row pair per layer geometry: ``plan_planned_<tag>`` is the wall
    of the config the solver picked for that layer — measured on the
    exact prepared serving path the plan will dispatch — and
    ``plan_direct_<tag>`` is the always-feasible exact fallback of the
    same geometry, which doubles as the per-tag normalizer the trend
    gate divides by (``benchmarks.trend_check.PLAN_ROW``). The solver
    re-runs on every bench invocation over a restricted candidate grid
    (CI-sized; the full grid is the launcher's default), so these rows
    gate the planner's *outcome* — the planned wall must never regress
    against its committed self — not a frozen choice. By construction
    planned ≤ direct (direct is always a feasible candidate and the
    solver is an argmin), asserted here so a solver regression fails
    the bench run itself, before the trend gate.
    """
    from repro.conv import LayerGeom, build_plan, plan_cost_us

    geoms = [LayerGeom("p_small", (2, 8, 8, 8), 8)]
    if not smoke:
        geoms.append(LayerGeom("p_mid", (2, 16, 16, 16), 16))
    plan, costs = build_plan(geoms, tile_sizes=(2, 4),
                             bases=("legendre",), hadamard_bits=(9,),
                             iters=3, warmup=1)
    for g in geoms:
        B, H, W, Ci = g.x_shape
        tag = f"{B}x{H}x{W}x{Ci}->{g.cout}"
        table = costs[g.layer]
        won = next(c for c in table if c.entry == plan.get(g.layer))
        direct = next(c for c in table if not c.entry.is_winograd)
        assert won.us <= direct.us, (won, direct)
        emit(f"plan_planned_{tag}", won.us,
             f"solver pick: {won.entry.describe()}", shape=tag,
             rel_err=round(won.rel_err, 5))
        emit(f"plan_direct_{tag}", direct.us,
             "exact fallback; per-tag normalizer", shape=tag)
    print(f"# plan_bench: total planned wall "
          f"{plan_cost_us(plan, costs):.0f}us over {len(geoms)} layers "
          f"— {plan.describe()}")


if __name__ == "__main__":
    main()
