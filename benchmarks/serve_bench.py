"""Online-serving benchmark: continuous batching vs serve-each-alone,
p50/p99 latency and throughput per offered load, with the SLO rows the
CI trend gate tracks (``BENCH_serve.json``).

    PYTHONPATH=src:. python -m benchmarks.serve_bench [--smoke]

What one run does:

1. builds the int8 ResNet serving stack (pack → calibrate → jitted
   forward) and a ``ServingLoop`` over the bucket geometries, warmed at
   startup (compile count asserted zero afterwards);
2. measures the **serve-each-request-alone** baseline: one dispatch per
   request through the provisioned serving geometry — the largest
   bucket, i.e. the single-geometry deployment the device is sized for.
   Serving a lone request there pays the whole bucket's compute as
   padding; that waste is exactly what continuous batching exists to
   reclaim. Its mean latency is the 2×-comparison baseline and the
   per-machine normalizer the trend gate divides by
   (``serve_solo_<tag>``). The per-request latency *floor* (a dispatch
   through the smallest bucket) is reported as ``serve_floor_<tag>``,
   ungated — on batch-amortizing hardware (TPU MXU) floor and baseline
   converge; on CPU interpret mode, where kernel cost is proportional
   to real rows, they differ and the floor is the honest lower bound no
   serving discipline on this substrate can beat;
3. derives offered rates from the measured batched capacity (rate =
   ρ · max_bucket / service(max_bucket), so "60% utilization" means the
   same thing on a fast and a slow machine), then drives the loop with
   the deterministic Poisson generator at each ρ and emits
   ``serve_p50_util<ρ>_<tag>`` / ``serve_p99_util<ρ>_<tag>`` rows (µs);
4. replays the *same* arrival trace against a serve-alone loop (one
   request per dispatch through the provisioned geometry, no
   coalescing) and asserts the ISSUE's SLO: the continuous-batching
   loop sustains ≥ 2× the serve-alone throughput at equal or better
   p99, device > 50% busy, with zero XLA recompiles after warmup.

Latency rows are queue measurements (arrival jitter + service noise),
so the gate runs them at a wider tolerance than kernel wall rows —
``make bench-serve-smoke`` passes ``--tol 0.5`` — and the
both-raw-and-normalized rule in ``benchmarks.trend_check`` absorbs
machine-speed differences via the solo row.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax

from benchmarks.common import emit, time_fn, write_json
from repro.data.pipeline import cifar_batch_at
from repro.launch.compile_cache import enable_compile_cache
from repro.models import resnet as RN
from repro.models.param import init_params
from repro.serving import (ServeConfig, ServingLoop, run_poisson_load,
                           solo_latencies)

IMAGE_SHAPE = (32, 32, 3)


def build_stack(width: float, calib_steps: int, calib_batch: int):
    """Pack+calibrate an int8 engine and return (engine, jitted fwd)."""
    from repro.core.quantization import QuantConfig
    from repro.core.winograd import WinogradSpec
    cfg = RN.ResNetConfig(
        width_mult=width,
        wino=WinogradSpec(m=4, r=3, base="legendre",
                          quant=QuantConfig(hadamard_bits=9)))
    params = init_params(RN.param_specs(cfg), jax.random.PRNGKey(0))
    state = init_params(RN.state_specs(cfg), jax.random.PRNGKey(1))
    engine = RN.make_engine(cfg, backend="winograd_int8")
    engine.prepare(RN.conv_layers(params, cfg))
    with engine.calibration():
        for step in range(calib_steps):
            RN.forward(params, state,
                       cifar_batch_at(step, calib_batch)["images"], cfg,
                       training=False, engine=engine)
    engine.serve_fn = RN.serving_forward(params, state, cfg, engine)
    return engine, engine.serve_fn


def request_maker(seed: int):
    def make_request(i):
        return np.asarray(cifar_batch_at(1000 + i, 1,
                                         seed=seed)["images"][0])
    return make_request


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: fewer buckets/requests, one "
                         "utilization point")
    ap.add_argument("--json", default="BENCH_serve.json",
                    help="machine-readable output path")
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    buckets = (1, 8) if args.smoke else (1, 2, 4, 8)
    utils = (0.6,) if args.smoke else (0.4, 0.7)
    n_requests = 32 if args.smoke else 64
    solo_n = 6 if args.smoke else 10
    tag = f"w{args.width}"
    max_bucket = buckets[-1]

    t0 = time.time()
    engine, fwd = build_stack(args.width,
                              calib_steps=1 if args.smoke else 2,
                              calib_batch=max_bucket)
    print(f"# stack built (pack+calibrate) in {time.time() - t0:.0f}s")

    loop = ServingLoop(fwd, IMAGE_SHAPE,
                       ServeConfig(buckets=buckets, max_wait_ms=20.0),
                       engine=engine)
    loop.start()       # pre-compiles every bucket geometry
    print("# warmup: " + ", ".join(f"{g}: {s:.0f}s"
                                   for g, s in loop.warmup_times.items()))

    # Measured capacity of the batched hot path → offered rates.
    # device_put, matching the loop's dispatch flavor — a raw numpy
    # argument would compile (and count) a separate jit-cache entry.
    make_request = request_maker(args.seed)
    xb = jax.device_put(np.stack([make_request(i)
                                  for i in range(max_bucket)]))
    us_batch = time_fn(fwd, xb, warmup=1, iters=3 if args.smoke else 5)
    capacity_rps = max_bucket / (us_batch / 1e6)

    # Baselines: serve-each-alone through the provisioned (largest)
    # geometry — the 2×-comparison target and the gate's normalizer —
    # and the smallest-geometry latency floor, informational.
    reqs = [make_request(i) for i in range(solo_n)]
    solo = solo_latencies(fwd, reqs, bucket=max_bucket)
    solo_us = 1e6 * sum(solo) / len(solo)
    solo_rps = 1e6 / solo_us
    floor = solo_latencies(fwd, reqs, bucket=buckets[0])
    floor_us = 1e6 * sum(floor) / len(floor)
    emit(f"serve_solo_{tag}", solo_us,
         "serve-each-request-alone through the provisioned (largest) "
         "bucket geometry — single-geometry deployment baseline + "
         "trend normalizer", shape=tag, bucket=max_bucket, n=solo_n)
    emit(f"serve_floor_{tag}", floor_us,
         "per-request latency floor (smallest bucket geometry; ungated "
         "— converges to the solo row on batch-amortizing hardware)",
         shape=tag, bucket=buckets[0], n=solo_n)
    print(f"# batched capacity {capacity_rps:.2f} req/s "
          f"(bucket {max_bucket} in {us_batch / 1e3:.0f}ms); "
          f"serve-alone {solo_rps:.2f} req/s; "
          f"floor {floor_us / 1e3:.1f}ms/req")

    reports = {}
    for rho in utils:
        # ≥2× the solo capacity even when ρ·capacity is below it, so the
        # SLO comparison is made at a rate the solo server cannot hold.
        rate = max(rho * capacity_rps, 2.2 * solo_rps)
        label = f"util{int(rho * 100)}"
        rep = run_poisson_load(loop, rate_rps=rate, n_requests=n_requests,
                               make_request=make_request, seed=args.seed)
        reports[rho] = rep
        print("# " + rep.describe(f"{label}: "))
        extra = dict(shape=tag, rate_rps=round(rate, 2),
                     throughput_rps=round(rep.throughput_rps, 2),
                     mean_batch=round(rep.mean_batch, 2),
                     padding_frac=round(rep.padding_frac, 3),
                     busy_frac=round(rep.busy_frac, 3),
                     compiles=rep.compiles, n=n_requests)
        emit(f"serve_p50_{label}_{tag}", rep.p50_ms() * 1e3,
             "continuous batching, Poisson load", **extra)
        emit(f"serve_p99_{label}_{tag}", rep.p99_ms() * 1e3,
             "continuous batching, Poisson load", **extra)
        assert rep.compiles in (0, None), \
            (f"{rep.compiles} XLA programs compiled on the hot path at "
             f"{label} — warmup must cover every serving geometry")

    # The SLO acceptance run: same arrival trace, serve-each-alone loop
    # (one request per dispatch through the provisioned geometry).
    rho_slo = utils[-1]
    rate_slo = max(rho_slo * capacity_rps, 2.2 * solo_rps)
    solo_loop = ServingLoop(fwd, IMAGE_SHAPE,
                            ServeConfig(buckets=(max_bucket,),
                                        max_wait_ms=0.0))
    solo_loop.start(warmup=False)      # geometry already compiled
    rep_solo = run_poisson_load(solo_loop, rate_rps=rate_slo,
                                n_requests=n_requests,
                                make_request=make_request, seed=args.seed)
    solo_loop.shutdown(drain=True)
    print("# " + rep_solo.describe("serve-alone, same trace: "))
    emit(f"serve_alone_p99_{tag}", rep_solo.p99_ms() * 1e3,
         "serve-each-request-alone under the same Poisson trace "
         "(SLO comparator; not gated — it measures the baseline's "
         "overload, not our code)", shape=tag,
         throughput_rps=round(rep_solo.throughput_rps, 2))

    rep = reports[rho_slo]
    speedup = rep.throughput_rps / max(rep_solo.throughput_rps, 1e-9)
    print(f"# SLO: continuous batching {rep.throughput_rps:.2f} req/s vs "
          f"serve-alone {rep_solo.throughput_rps:.2f} req/s = "
          f"{speedup:.2f}×; p99 {rep.p99_ms():.0f}ms vs "
          f"{rep_solo.p99_ms():.0f}ms; busy {rep.busy_frac:.0%}; "
          f"compiles after warmup: {rep.compiles}")
    assert speedup >= 2.0, \
        (f"continuous batching sustained only {speedup:.2f}× the "
         "serve-each-alone throughput (ISSUE SLO: >= 2×)")
    assert rep.p99_ms() <= rep_solo.p99_ms(), \
        (f"continuous batching p99 {rep.p99_ms():.0f}ms worse than "
         f"serve-alone {rep_solo.p99_ms():.0f}ms under the same trace")
    assert rep.busy_frac > 0.5, \
        (f"device only {rep.busy_frac:.0%} busy at the SLO rate — the "
         "comparison must be made under load (ISSUE: >50% busy)")

    loop.shutdown(drain=True)
    write_json(args.json, smoke=args.smoke,
               backend=jax.default_backend(),
               note="online serving SLO rows; latency percentiles in us; "
                    "interpret-mode Pallas on CPU (kernel cost scales "
                    "with real rows, so the serve-alone baseline is the "
                    "provisioned max-bucket geometry — see module doc)")


if __name__ == "__main__":
    main()
