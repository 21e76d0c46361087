"""Int8 ResNet serving launcher: calibrate → pack → serve.

    PYTHONPATH=src python -m repro.launch.infer_resnet \
        --width 0.25 --batch 8 --calib-steps 4 --ckpt-dir /tmp/resnet_int8

The production lifecycle for the paper's model on the Pallas int8
kernels, end to end:

1. **pack**    — transform every eligible conv's weights once into
                 per-position int8 (``ConvEngine.prepare``).
2. **calibrate** — run calibration batches through the model; the engine
                 records per-layer, per-position input maxima and turns
                 them into static quantization scales. With
                 ``--autotune`` it also times the fused kernel's
                 candidate (bm, bn, bk) block splits per layer shape on
                 exit and caches the winners in the packed state (the
                 checkpoint then serves them; step 4 prints the
                 autotuned-vs-default wall row).
3. **checkpoint** — serialize the packed+calibrated state through
                 ``repro.checkpoint`` (atomic manifest write).
4. **serve**   — restore into a fresh engine and run inference on the
                 zero-weight-transform, zero-scale-reduction hot path
                 (single-pass fused GEMM→requant→output-transform kernel
                 by default); report agreement vs the staged pipeline,
                 the dynamic-scale path and the fp reference, plus
                 wall-times.
5. **sharded serve** — restore the same checkpoint into mesh-backed
                 engines and serve the batch across 1/2/4/… devices
                 (tile-axis shard_map, ``ConvEngine(mesh=...)``); one
                 throughput row per device count. ``--host-devices N``
                 splits the host CPU into N XLA devices for a local
                 multi-device demo (must be set before jax initializes,
                 which this launcher does for you).

``--plan`` inserts stage 0: measure every layer geometry across the
certifier-proved {direct, F(2,3)/F(4,3)/F(6,3)} × {canonical, legendre}
× Hadamard-width candidate grid and solve for the per-layer plan
(``repro.conv.planner``) under the no-added-error-vs-fp budget. The
plan rides in the checkpoint (recovered template-free via
``Plan.from_checkpoint`` before serving) and the planned serving wall
is asserted no worse than the best single-algorithm configuration.
"""
from __future__ import annotations

import argparse
import sys
import time


def _maybe_fork_host_devices(argv):
    """Re-exec with XLA_FLAGS when --host-devices is asked for — before
    the jax backend initializes, so the operator need not remember the
    incantation. Shared logic: ``repro.launch.mesh``."""
    from repro.launch.mesh import ensure_host_device_count
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--host-devices", type=int, default=0)
    ns, _ = ap.parse_known_args(argv)
    ensure_host_device_count(ns.host_devices,
                             "repro.launch.infer_resnet", argv)


if __name__ == "__main__":          # before jax backend init
    _maybe_fork_host_devices(sys.argv[1:])

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint.checkpoint import restore, save
from repro.conv import Plan, PlanEntry, build_plan, plan_cost_us
from repro.core.quantization import QuantConfig
from repro.core.winograd import WinogradSpec
from repro.data.pipeline import cifar_batch_at
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import require_host_devices
from repro.models import resnet as RN
from repro.models.param import init_params


def _logits(params, state, images, cfg, engine):
    out, _ = RN.forward(params, state, images, cfg, training=False,
                        engine=engine)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--base", default="legendre",
                    choices=["canonical", "legendre", "chebyshev"])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--calib-steps", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/resnet_int8_ckpt")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="CPU only: split the host CPU into N XLA devices "
                         "for the sharded-serving demo (re-execs with "
                         "XLA_FLAGS)")
    ap.add_argument("--autotune", action="store_true",
                    help="tune the fused kernel's Pallas (bm, bn, bk) "
                         "block split per layer shape at calibration "
                         "time; the winners ride in the checkpoint and "
                         "an autotuned-vs-default serving row is printed")
    ap.add_argument("--plan", action="store_true",
                    help="measure a per-layer algorithm plan "
                         "(repro.conv.planner) before packing; the plan "
                         "rides in the checkpoint and a planned-vs-best-"
                         "single-algorithm serving row is printed")
    ap.add_argument("--plan-iters", type=int, default=3,
                    help="timing iterations per plan candidate")
    ap.add_argument("--plan-tiles", default="2,4,6",
                    help="comma-separated Winograd output tiles the "
                         "planner considers (interpret-mode measurement "
                         "is slow; restrict for quick runs)")
    ap.add_argument("--plan-bases", default="canonical,legendre",
                    help="comma-separated polynomial bases the planner "
                         "considers")
    ap.add_argument("--plan-bits", default="none,8,9",
                    help="comma-separated Hadamard widths the planner "
                         "considers ('none' = fp Hadamard scales)")
    args = ap.parse_args(argv)
    if args.calib_steps < 1:
        ap.error("--calib-steps must be >= 1 (int8 serving needs "
                 "calibrated scales)")
    # The XLA_FLAGS re-exec only runs when launched as a script; a
    # programmatic main([...]) call, or a TPU host, lands here with the
    # backend already fixed — refuse rather than serve a smaller mesh.
    require_host_devices(args.host_devices)
    enable_compile_cache()

    cfg = RN.ResNetConfig(
        width_mult=args.width,
        wino=WinogradSpec(m=4, r=3, base=args.base,
                          quant=QuantConfig(hadamard_bits=9)))
    params = init_params(RN.param_specs(cfg), jax.random.PRNGKey(0))
    state = init_params(RN.state_specs(cfg), jax.random.PRNGKey(1))

    # 0. plan (optional) — measure the certifier-proved candidate grid
    # per layer geometry and solve under the no-added-error budget. The
    # baseline is the exact single-spec config the unplanned engine
    # would serve, so the plan may trade algorithms but not add error.
    plan = None
    if args.plan:
        baseline = PlanEntry("winograd_int8", m=4, r=3, base=args.base,
                             hadamard_bits=9)
        t0 = time.time()
        plan, plan_costs = build_plan(
            RN.layer_geoms(cfg, args.batch),
            baseline=baseline,
            tile_sizes=tuple(int(t) for t in args.plan_tiles.split(",")),
            bases=tuple(args.plan_bases.split(",")),
            hadamard_bits=tuple(None if b.lower() == "none" else int(b)
                                for b in args.plan_bits.split(",")),
            iters=args.plan_iters)
        print(f"[plan] {plan.describe()}; modelled "
              f"{plan_cost_us(plan, plan_costs) / 1e3:.1f}ms conv/batch "
              f"({time.time() - t0:.1f}s to plan)")
        for l, e in sorted(plan.entries.items()):
            if e.is_winograd:
                print(f"[plan]   {l}: {e.describe()}")

    # 1. pack — offline weight transform + int8 quantization
    # (plan-direct layers stay unpacked: direct conv serves fp weights).
    engine = RN.make_engine(cfg, backend="winograd_int8",
                            autotune=args.autotune,
                            autotune_opts=dict(iters=2, warmup=1,
                                               max_candidates=6),
                            plan=plan)
    t0 = time.time()
    packed = engine.prepare(RN.conv_layers(params, cfg))
    print(f"[pack] {len(packed)} conv layers → int8 Winograd domain "
          f"({time.time() - t0:.2f}s)")

    # 2. calibrate — per-layer per-position input scales (and, with
    # --autotune, the per-shape Pallas block search on exit: calibration
    # is what fixes each layer's tile geometry).
    t0 = time.time()
    with engine.calibration():
        for step in range(args.calib_steps):
            batch = cifar_batch_at(step, args.batch)
            _logits(params, state, batch["images"], cfg, engine)
    print(f"[calibrate] {args.calib_steps} batches × {args.batch} "
          f"({time.time() - t0:.2f}s)")
    if args.autotune:
        tuned = {l: p.block_tuple() for l, p in engine.packed.items()
                 if p.blocks is not None}
        shapes = sorted({b for b in tuned.values()})
        print(f"[autotune] {len(tuned)} layers tuned → "
              f"{len(shapes)} distinct block split(s): {shapes}")

    # 3. checkpoint the serving state (the plan rides along as the
    # top-level ``plan`` group — the checkpoint fully determines routing).
    path = save(args.ckpt_dir, 0, engine.export_state())
    print(f"[checkpoint] packed+calibrated state → {path}")

    # 4. serve from the checkpoint with a fresh engine. The plan is
    # recovered template-free from the checkpoint itself (None for a
    # pre-plan checkpoint → pure policy routing), because the plan is
    # what defines which layers the restore template expects packed.
    plan = Plan.from_checkpoint(args.ckpt_dir)
    if plan is not None:
        print(f"[plan] recovered from checkpoint: {plan.describe()}")
    served = RN.make_engine(cfg, backend="winograd_int8", plan=plan)
    served.prepare(RN.conv_layers(params, cfg))
    tree, step = restore(args.ckpt_dir, served.state_template())
    served.import_state(tree)

    eval_batch = cifar_batch_at(10_000, args.batch)
    images = eval_batch["images"]

    # Same restored state through the staged (three-kernel) pipeline —
    # the bit-identical reference for the fused serving kernel.
    staged = RN.make_engine(cfg, backend="winograd_int8", fused=False,
                            plan=plan)
    staged.prepare(RN.conv_layers(params, cfg))
    staged.import_state(tree)

    dyn_engine = RN.make_engine(cfg, backend="winograd_int8",  # no prepare
                                plan=plan)
    fp_engine = RN.make_engine(cfg, backend="winograd_fp")

    # Serving runs under jit: the whole forward — tile extraction, the
    # Pallas stages, BN, the head — fuses into one XLA program.
    prep_fn = jax.jit(
        lambda im: _logits(params, state, im, cfg, served))
    staged_fn = jax.jit(
        lambda im: _logits(params, state, im, cfg, staged))
    dyn_fn = jax.jit(
        lambda im: _logits(params, state, im, cfg, dyn_engine))

    # Warm-up must be block_until_ready'd: jax dispatch is async, and an
    # in-flight warm-up call would otherwise inflate the timed run.
    jax.block_until_ready(prep_fn(images))               # warm the jit
    t0 = time.time()
    y_prep = jax.block_until_ready(prep_fn(images))
    t_prep = time.time() - t0

    jax.block_until_ready(staged_fn(images))
    t0 = time.time()
    y_staged = jax.block_until_ready(staged_fn(images))
    t_staged = time.time() - t0

    jax.block_until_ready(dyn_fn(images))
    t0 = time.time()
    y_dyn = jax.block_until_ready(dyn_fn(images))
    t_dyn = time.time() - t0

    y_fp = _logits(params, state, images, cfg, fp_engine)

    def rel(a, b):
        return float(jnp.sqrt(jnp.mean((a - b) ** 2)) /
                     jnp.sqrt(jnp.mean(b ** 2)))

    agree = float(jnp.mean((jnp.argmax(y_prep, -1)
                            == jnp.argmax(y_dyn, -1)).astype(jnp.float32)))
    # Per layer, fused and staged agree to float rounding (~1e-5; the
    # integer Hadamard pipeline is exact — see tests/test_fused_serve).
    # Composed through 14 re-quantizing layers those last-bit deltas flip
    # occasional int8 rounding decisions and cascade, so network outputs
    # separate to quantization-noise level — the meaningful check is that
    # fused adds no error vs the fp reference beyond what staged has.
    rel_fs = rel(y_prep, y_staged)
    agree_fs = float(jnp.mean((jnp.argmax(y_prep, -1)
                               == jnp.argmax(y_staged, -1))
                              .astype(jnp.float32)))
    print(f"[serve] fused vs staged pipeline: rel {rel_fs:.4f}, argmax "
          f"agreement {agree_fs:.2f} (per-layer integer-exact; fp32 "
          "rounding deltas cascade through the quantized stack)")
    print(f"[serve] calibrated-int8 vs dynamic-int8: rel "
          f"{rel(y_prep, y_dyn):.4f}, argmax agreement {agree:.2f}")
    print(f"[serve] calibrated-int8 vs fp winograd:  rel "
          f"{rel(y_prep, y_fp):.4f}")
    print(f"[serve] wall: fused {t_prep * 1e3:.0f}ms vs staged "
          f"{t_staged * 1e3:.0f}ms vs dynamic {t_dyn * 1e3:.0f}ms per batch "
          f"({t_dyn / max(t_prep, 1e-9):.2f}× over dynamic, "
          f"{jax.default_backend()})")

    if args.autotune:
        # Autotuned-vs-default serving row: the restored engine carries
        # the tuned per-layer blocks; strip them from a sibling engine
        # to time the spec-default splits on the identical state.
        # Numerics are block-independent, so this is a pure wall row.
        default_eng = RN.make_engine(cfg, backend="winograd_int8",
                                     plan=plan)
        default_eng.prepare(RN.conv_layers(params, cfg))
        default_eng.import_state(tree)
        default_eng.clear_tuned_blocks()
        default_fn = jax.jit(
            lambda im: _logits(params, state, im, cfg, default_eng))
        jax.block_until_ready(default_fn(images))
        t0 = time.time()
        y_def = jax.block_until_ready(default_fn(images))
        t_def = time.time() - t0
        print(f"[serve] autotuned blocks {t_prep * 1e3:.0f}ms vs default "
              f"blocks {t_def * 1e3:.0f}ms per batch "
              f"({t_def / max(t_prep, 1e-9):.2f}× from tuning, "
              f"{jax.default_backend()}; per-layer wins don't always survive "
              "the outer jit here — the kernel-level rows in "
              "BENCH_kernel.json are the tuner's contract)")
        # Per layer a block split only re-tiles exact integer work (fp32
        # to rounding), but through 14 re-quantizing layers last-bit
        # deltas cascade — so the gate is the same as for every other
        # mode pair: no added error vs the fp reference (docs/parity.md).
        err_tuned, err_def = rel(y_prep, y_fp), rel(y_def, y_fp)
        assert abs(err_tuned - err_def) < 0.05, \
            (f"autotuned serving adds error vs the fp reference: "
             f"{err_tuned:.4f} vs default-blocks {err_def:.4f}")
    err_fused, err_staged = rel(y_prep, y_fp), rel(y_staged, y_fp)
    assert abs(err_fused - err_staged) < 0.05, \
        (f"fused serving adds error over staged vs the fp reference: "
         f"{err_fused:.4f} vs {err_staged:.4f}")
    np.testing.assert_array_less(rel(y_prep, y_fp), 1.0)

    if args.plan:
        # Planned-vs-best-single-algorithm gate: the planned engine must
        # serve no slower than the best configuration a single
        # engine-wide algorithm choice could reach — direct everywhere,
        # or the F(4,3) config the unplanned engine serves. min-of-3
        # walls damp shared-machine noise (cf. benchmarks/common).
        def _wall(fn, n=3):
            jax.block_until_ready(fn(images))
            best = float("inf")
            for _ in range(n):
                t0 = time.time()
                jax.block_until_ready(fn(images))
                best = min(best, time.time() - t0)
            return best

        t_planned = _wall(prep_fn)
        direct_eng = RN.make_engine(cfg, backend="direct")
        y_direct = _logits(params, state, images, cfg, direct_eng)
        t_direct = _wall(jax.jit(
            lambda im: _logits(params, state, im, cfg, direct_eng)))
        single = RN.make_engine(cfg, backend="winograd_int8")
        single.prepare(RN.conv_layers(params, cfg))
        with single.calibration():
            for step in range(args.calib_steps):
                batch = cifar_batch_at(step, args.batch)
                _logits(params, state, batch["images"], cfg, single)
        single_fn = jax.jit(
            lambda im: _logits(params, state, im, cfg, single))
        y_single = single_fn(images)
        t_single = _wall(single_fn)
        t_best = min(t_direct, t_single)
        best_nm = "direct" if t_direct <= t_single else "winograd F(4,3)"
        print(f"[plan] planned {t_planned * 1e3:.0f}ms vs best single "
              f"algorithm ({best_nm}) {t_best * 1e3:.0f}ms per batch "
              f"(direct {t_direct * 1e3:.0f}ms, F(4,3) "
              f"{t_single * 1e3:.0f}ms)")
        assert t_planned <= t_best * 1.25, \
            (f"planned serving wall {t_planned * 1e3:.0f}ms exceeds the "
             f"best single-algorithm configuration {t_best * 1e3:.0f}ms "
             "beyond timing noise — the plan should never lose to a "
             "config in its own candidate set")
        # No-added-error gate, planned vs each single-algorithm config:
        # the plan trades algorithms under the budget, never accuracy.
        err_planned = rel(y_prep, y_fp)
        err_single = rel(y_single, y_fp)
        err_direct = rel(y_direct, y_fp)
        print(f"[plan] rel-vs-fp: planned {err_planned:.4f}, single-"
              f"winograd {err_single:.4f}, direct {err_direct:.4f}")
        assert err_planned <= max(err_single, err_direct) + 0.05, \
            (f"planned serving adds error vs the fp reference: "
             f"{err_planned:.4f} vs single-algorithm "
             f"{max(err_single, err_direct):.4f}")

    # 5. sharded serving: the same checkpoint restored into mesh-backed
    # engines — the tile axis of every int8 conv shards across the
    # mesh's "data" axis and each device runs the fused kernel on its
    # slab. One throughput row per device count (on one CPU device the
    # 1-device mesh row still exercises the full shard_map path; pass
    # --host-devices 4 for a local multi-device run).
    ndev = len(jax.devices())
    counts = sorted({d for d in (1, 2, 4, 8) if d <= ndev} | {ndev})
    for d in counts:
        mesh = Mesh(np.array(jax.devices()[:d]), ("data",))
        sharded = RN.make_engine(cfg, backend="winograd_int8", mesh=mesh)
        # the restored tree fully defines the packed state (no
        # prepare() needed); import replicates it across the mesh
        sharded.import_state(tree)
        sh_fn = jax.jit(
            lambda im, e=sharded: _logits(params, state, im, cfg, e))
        jax.block_until_ready(sh_fn(images))
        t0 = time.time()
        y_sh = jax.block_until_ready(sh_fn(images))
        t_sh = time.time() - t0
        y_sh = np.asarray(y_sh)
        qps = args.batch / max(t_sh, 1e-9)
        agree_sh = float(np.mean(np.argmax(y_sh, -1)
                                 == np.asarray(jnp.argmax(y_prep, -1))))
        print(f"[serve] sharded fused ({d} device{'s' if d > 1 else ''}): "
              f"{t_sh * 1e3:.0f}ms/batch, {qps:.1f} img/s, rel vs "
              f"single-device fused {rel(y_sh, y_prep):.4f}, argmax "
              f"agreement {agree_sh:.2f}")
        # Per layer the sharded execution is bit-identical to the fused
        # kernel on the full tile tensor (tests/test_distributed.py);
        # network logits land at quantization-noise level — each mesh
        # compiles its own BN/glue program and one-ULP fp32 deltas flip
        # int8 rounding downstream (docs/parity.md) — so the gate is the
        # same as fused-vs-staged: no added error vs the fp reference.
        err_sh = rel(y_sh, y_fp)
        assert abs(err_sh - err_fused) < 0.05, \
            (f"sharded serving adds error vs the fp reference: "
             f"{err_sh:.4f} vs fused {err_fused:.4f}")


if __name__ == "__main__":
    main()
