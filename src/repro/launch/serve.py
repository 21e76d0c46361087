"""Online int8 serving launcher: calibrate → pack → checkpoint → serve
under continuous batching.

    PYTHONPATH=src python -m repro.launch.serve \
        --width 0.25 --buckets 1,2,4,8 --rate 8 --requests 64

The request-level production lifecycle for the paper's model on the
Pallas int8 kernels (the offline stages are identical to
``repro.launch.infer_resnet``; this launcher is what sits *in front* of
them when traffic is ragged single-image requests instead of fixed
offline batches):

1. **pack / calibrate / checkpoint** — exactly the offline flow of
   PRs 1–5: transform weights once, calibrate per-position scales (and
   optionally autotune the Pallas block splits), serialize the packed
   state through ``repro.checkpoint``.
2. **restore + warmup** — a fresh engine (optionally sharded over a
   ``--mesh-devices`` data axis × ``--model-devices`` model axis, with
   packed weights cout-sharded on restore) imports the checkpoint, then
   pre-compiles every
   registered serving geometry (``ConvEngine.warmup`` over the bucket
   set) so no request ever waits on XLA.
3. **serve** — ``repro.serving.ServingLoop`` coalesces Poisson arrivals
   into dynamic batches, pads them into the pre-compiled buckets, and
   double-buffers dispatch; the closed-loop Poisson generator
   (``repro.serving.loadgen``) drives it and reports p50/p99 latency,
   throughput, batch/padding statistics, and the compile count after
   warmup (asserted zero).

A serve-each-request-alone baseline runs first so the continuous-
batching win is printed next to it.
"""
from __future__ import annotations

import argparse
import sys


def _maybe_fork_host_devices(argv):
    """Re-exec with XLA_FLAGS when --host-devices is asked for — before
    the jax backend initializes (see ``repro.launch.mesh``)."""
    from repro.launch.mesh import ensure_host_device_count
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--host-devices", type=int, default=0)
    ns, _ = ap.parse_known_args(argv)
    ensure_host_device_count(ns.host_devices, "repro.launch.serve", argv)


if __name__ == "__main__":          # before jax backend init
    _maybe_fork_host_devices(sys.argv[1:])

import numpy as np

import jax

from repro import telemetry
from repro.checkpoint.checkpoint import restore, save
from repro.conv import Plan, PlanEntry, build_plan
from repro.core.quantization import QuantConfig
from repro.core.winograd import WinogradSpec
from repro.data.pipeline import cifar_batch_at
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import require_host_devices, serving_devices
from repro.models import resnet as RN
from repro.models.param import init_params
from repro.serving import (ServeConfig, ServingLoop, run_poisson_load,
                           solo_latencies)

IMAGE_SHAPE = (32, 32, 3)


def build_serving_state(args, cfg):
    """Offline stages: init → pack → calibrate → checkpoint. Returns the
    (params, state, checkpoint tree) the online loop serves from."""
    params = init_params(RN.param_specs(cfg), jax.random.PRNGKey(0))
    state = init_params(RN.state_specs(cfg), jax.random.PRNGKey(1))
    plan = None
    if args.plan:
        # Measure the per-layer algorithm plan on the LARGEST serving
        # bucket geometry (the throughput-critical shape); the plan
        # rides the checkpoint into the online engine below.
        buckets = tuple(int(b) for b in args.buckets.split(","))
        baseline = PlanEntry("winograd_int8", m=4, r=3, base=args.base,
                             hadamard_bits=9)
        plan, _ = build_plan(
            RN.layer_geoms(cfg, buckets[-1]), baseline=baseline,
            tile_sizes=tuple(int(t) for t in args.plan_tiles.split(",")),
            bases=tuple(args.plan_bases.split(",")),
            hadamard_bits=tuple(None if b.lower() == "none" else int(b)
                                for b in args.plan_bits.split(",")))
        print(f"[plan] {plan.describe()}")
    engine = RN.make_engine(cfg, backend="winograd_int8",
                            autotune=args.autotune,
                            autotune_opts=dict(iters=2, warmup=1,
                                               max_candidates=6),
                            plan=plan)
    packed = engine.prepare(RN.conv_layers(params, cfg))
    print(f"[pack] {len(packed)} conv layers → int8 Winograd domain")
    with engine.calibration():
        for step in range(args.calib_steps):
            batch = cifar_batch_at(step, args.calib_batch)
            RN.forward(params, state, batch["images"], cfg,
                       training=False, engine=engine)
    print(f"[calibrate] {args.calib_steps} batches × {args.calib_batch}")
    if args.autotune:
        tuned = sorted({p.block_tuple() for p in engine.packed.values()
                        if p.blocks is not None})
        print(f"[autotune] tuned block split(s): {tuned}")
    path = save(args.ckpt_dir, 0, engine.export_state())
    print(f"[checkpoint] packed+calibrated state → {path}")
    return params, state, engine.state_template()


def make_served_engine(args, cfg, template):
    """Online stage 2: restore the checkpoint into a fresh (optionally
    mesh-backed) engine — packed weights, calibrated scales and tuned
    blocks all come from the checkpoint, unchanged.

    ``--mesh-devices D --model-devices M`` serves over a 2-D
    (data × model) mesh of D×M devices: request tiles shard over the
    data axis, every layer's Cout (and its 1/M of the packed weight
    bytes) over the model axis. The checkpoint itself is
    topology-free — ``restore(shardings=...)`` reshards the full saved
    arrays onto whatever mesh this process serves with."""
    mesh, model_axis, shardings = None, None, None
    if args.mesh_devices > 0 or args.model_devices > 1:
        from jax.sharding import Mesh
        dd = max(args.mesh_devices, 1)
        dm = max(args.model_devices, 1)
        devs = serving_devices(dd * dm)       # raises rather than shrink
        if dm > 1:
            mesh = Mesh(np.array(devs).reshape(dd, dm), ("data", "model"))
            model_axis = "model"
            print(f"[mesh] serving across {dd}×{dm} (data × model) "
                  "devices: tiles × Cout shard_map, weights "
                  f"cout-sharded 1/{dm} per device")
        else:
            mesh = Mesh(np.array(devs), ("data",))
            print(f"[mesh] serving across {dd} device(s), tile-axis "
                  "shard_map")
        from repro.conv.packing import packed_tree_shardings
        shardings = packed_tree_shardings(mesh, template,
                                          model_axis=model_axis)
    # The plan (if the checkpoint carries one) is recovered template-
    # free first: it defines which layers the restore template expects
    # packed, so the engine must know it before import (None for a
    # pre-plan checkpoint → pure policy routing, unchanged).
    with telemetry.setup_phase("restore"):
        plan = Plan.from_checkpoint(args.ckpt_dir)
        if plan is not None:
            print(f"[plan] serving the checkpoint's plan: "
                  f"{plan.describe()}")
        engine = RN.make_engine(cfg, backend="winograd_int8", mesh=mesh,
                                model_axis=model_axis, plan=plan)
        tree, _ = restore(args.ckpt_dir, template, shardings=shardings)
        engine.import_state(tree)
    return engine


def build_parser() -> argparse.ArgumentParser:
    """The launcher's options (``chip_smoke.py`` parses its settings
    through this same parser)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--base", default="legendre",
                    choices=["canonical", "legendre", "chebyshev"])
    ap.add_argument("--buckets", default="1,2,4,8",
                    help="comma-separated serving batch geometries; "
                         "every dynamic batch is padded up to one of "
                         "these pre-compiled shapes")
    ap.add_argument("--max-wait-ms", type=float, default=20.0,
                    help="partial-batch flush deadline: a lone request "
                         "never waits longer than this for companions")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--solo-requests", type=int, default=8,
                    help="requests for the serve-each-alone baseline")
    ap.add_argument("--calib-steps", type=int, default=2)
    ap.add_argument("--calib-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/resnet_serve_ckpt")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune", action="store_true",
                    help="tune Pallas block splits at calibration; the "
                         "winners ride the checkpoint into serving")
    ap.add_argument("--plan", action="store_true",
                    help="measure a per-layer algorithm plan "
                         "(repro.conv.planner) before packing; the plan "
                         "rides the checkpoint into online serving")
    ap.add_argument("--plan-tiles", default="2,4,6",
                    help="comma-separated Winograd output tiles the "
                         "planner considers (restrict for quick runs — "
                         "interpret-mode measurement is slow)")
    ap.add_argument("--plan-bases", default="canonical,legendre",
                    help="comma-separated polynomial bases the planner "
                         "considers")
    ap.add_argument("--plan-bits", default="none,8,9",
                    help="comma-separated Hadamard widths the planner "
                         "considers ('none' = fp Hadamard scales)")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="serve through a data-axis mesh of N devices "
                         "(0 = single device)")
    ap.add_argument("--model-devices", type=int, default=0,
                    help="add a model axis of M devices: a 2-D "
                         "(data × model) mesh of N×M devices shards "
                         "each layer's Cout (and 1/M of the packed "
                         "weight bytes) per device")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="CPU only: split the host CPU into N XLA devices "
                         "(re-execs with XLA_FLAGS; for --mesh-devices)")
    return ap


def make_config(args) -> RN.ResNetConfig:
    """The served model: ResNet-18 at ``--width`` with F(4,3) in
    ``--base`` and the 9-bit Hadamard stage."""
    return RN.ResNetConfig(
        width_mult=args.width,
        wino=WinogradSpec(m=4, r=3, base=args.base,
                          quant=QuantConfig(hadamard_bits=9)))


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.calib_steps < 1:
        ap.error("--calib-steps must be >= 1")
    require_host_devices(args.host_devices)
    enable_compile_cache()
    buckets = tuple(int(b) for b in args.buckets.split(","))
    cfg = make_config(args)

    # Offline: pack → calibrate → checkpoint (stage 1).
    params, state, template = build_serving_state(args, cfg)

    # Online: restore → warmup → continuous batching (stages 2–3).
    engine = make_served_engine(args, cfg, template)
    engine.serve_fn = RN.serving_forward(params, state, cfg, engine)
    loop = ServingLoop(engine.serve_fn, IMAGE_SHAPE,
                       ServeConfig(buckets=buckets,
                                   max_wait_ms=args.max_wait_ms),
                       engine=engine)
    loop.start()                       # pre-compiles every bucket geometry
    for g, secs in loop.warmup_times.items():
        print(f"[warmup] geometry {g}: {secs:.1f}s compile+execute")

    # Serve-each-request-alone baselines (same compiled programs): the
    # provisioned largest-bucket geometry — what a single-geometry
    # deployment pays per lone request, the throughput comparison
    # target — and the smallest-bucket latency floor.
    imgs = [np.asarray(cifar_batch_at(100 + i, 1,
                                      seed=args.seed)["images"][0])
            for i in range(max(args.solo_requests, 1))]
    solo = solo_latencies(engine.serve_fn, imgs, bucket=buckets[-1])
    solo_ms = 1e3 * sum(solo) / len(solo)
    floor = solo_latencies(engine.serve_fn, imgs, bucket=buckets[0])
    floor_ms = 1e3 * sum(floor) / len(floor)
    print(f"[solo] serve-each-alone through bucket {buckets[-1]}: mean "
          f"{solo_ms:.0f}ms/request ({1e3 / solo_ms:.2f} req/s); "
          f"latency floor (bucket {buckets[0]}): {floor_ms:.0f}ms")

    # Poisson load through the continuous-batching loop.
    def make_request(i):
        return np.asarray(cifar_batch_at(1000 + i, 1,
                                         seed=args.seed)["images"][0])

    report = run_poisson_load(loop, rate_rps=args.rate,
                              n_requests=args.requests,
                              make_request=make_request, seed=args.seed)
    print("[serve] " + report.describe())
    edges, counts = _histogram_ms(report.latencies_s)
    print("[serve] latency histogram (ms): "
          + " ".join(f"{e:.0f}:{c}" for e, c in zip(edges, counts)))
    speedup = report.throughput_rps * solo_ms / 1e3
    print(f"[serve] continuous batching vs serve-alone "
          f"(bucket-{buckets[-1]} geometry): {speedup:.2f}× throughput "
          f"at p50 {report.p50_ms():.0f}ms / p99 {report.p99_ms():.0f}ms")
    assert report.compiles in (0, None), \
        (f"{report.compiles} XLA programs compiled on the hot path — "
         "every serving geometry must be pre-compiled at warmup")
    loop.shutdown(drain=True)
    print("[serve] drained and shut down")


def _histogram_ms(latencies_s, bins: int = 8):
    from repro.serving import latency_histogram
    edges, counts = latency_histogram([s * 1e3 for s in latencies_s],
                                      bins=bins)
    return edges[:-1], counts


if __name__ == "__main__":
    main()
