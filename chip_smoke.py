"""Chip smoke test: serve int8 ResNet-18 at full width on a TPU through
the launcher's own functions, check the answers, print one JSON line.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 2x2 (data x model) mesh phase

One chip: pack -> calibrate (2 batches of 32) -> checkpoint -> restore
-> warmup (buckets 1, 8, 32) -> ``ServingLoop`` under Poisson load,
exactly as ``repro.launch.serve`` runs it (``build_serving_state``,
``make_served_engine``), at ``width_mult=1.0`` with F(4,3) Legendre and
the 9-bit Hadamard stage. Checks:

* JAX runs on a TPU (anything else exits non-zero before any work);
* the compiled serving program holds Mosaic ``tpu_custom_call`` kernels;
* zero compiles after warmup;
* every served response equals the same image served alone through the
  batch forward of its bucket, bitwise (padding is row-independent);
* against a plain fp32 direct-conv forward of the same params and
  images (at full fp32 matmul precision), the fused serving path's
  relative error is at most 0.05 above the staged pipeline's — the
  repo's no-added-error margin (docs/parity.md).

``--chips 4`` runs only the mesh phase: the same checkpoint restored
onto a 2x2 (data x model) mesh with Cout-sharded weights serves the same
requests; per layer its output must equal the single-device fused
engine's bitwise, and through the network it must add no error over the
single-device engine against the fp32 reference.

Informational numbers go to earlier lines; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: The repo's no-added-error margin between two serving modes' relative
#: errors against the fp32 reference (infer_resnet.py, docs/parity.md).
NO_ADDED_ERROR = 0.05


def require_tpu(chips: int):
    """Exit non-zero, naming the platform, unless JAX sees ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devs)}")
    return devs


def _log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def _rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b ** 2)))


def _top1(a, b) -> float:
    import numpy as np
    return float(np.mean(np.argmax(np.asarray(a), -1)
                         == np.argmax(np.asarray(b), -1)))


def _serve_args(ckpt_dir: str, width: float, requests: int, rate: float,
                seed: int, mesh: tuple[int, int] = (0, 0)):
    """The launcher's own parser, filled with the smoke settings."""
    from repro.launch import serve
    return serve.build_parser().parse_args([
        "--width", str(width), "--buckets", "1,8,32",
        "--calib-steps", "2", "--calib-batch", "32",
        "--requests", str(requests), "--rate", str(rate),
        "--max-wait-ms", "20", "--seed", str(seed),
        "--ckpt-dir", ckpt_dir,
        "--mesh-devices", str(mesh[0]), "--model-devices", str(mesh[1])])


def _fp32_reference(params, state, cfg, images):
    """Plain fp32 direct-conv forward at full matmul precision."""
    import jax
    from repro.models import resnet as RN
    direct = RN.make_engine(cfg, backend="direct")
    fn = jax.jit(lambda im: RN.forward(params, state, im, cfg,
                                       training=False, engine=direct)[0])
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(fn(images))


def _request_images(n: int, seed: int):
    import numpy as np
    from repro.data.pipeline import cifar_batch_at
    return [np.asarray(cifar_batch_at(1000 + i, 1, seed=seed)["images"][0])
            for i in range(n)]


def _serve(engine, params, state, cfg, buckets, images, rate, seed):
    """Warm the buckets, serve ``images`` as Poisson requests; return
    (loop, report). The loop is drained and shut down."""
    from repro.launch.serve import IMAGE_SHAPE
    from repro.models import resnet as RN
    from repro.serving import ServeConfig, ServingLoop, run_poisson_load
    engine.serve_fn = RN.serving_forward(params, state, cfg, engine)
    loop = ServingLoop(engine.serve_fn, IMAGE_SHAPE,
                       ServeConfig(buckets=buckets, max_wait_ms=20.0),
                       engine=engine)
    loop.start()
    for g, secs in loop.warmup_times.items():
        _log(f"warmup bucket {g[0]}: {secs:.2f}s compile+execute")
    report = run_poisson_load(loop, rate_rps=rate, n_requests=len(images),
                              make_request=lambda i: images[i], seed=seed)
    loop.shutdown(drain=True)
    _log("serve " + report.describe())
    if report.compiles != 0:
        raise AssertionError(f"{report.compiles} compiles after warmup")
    return loop, report


def run_single(ckpt_dir: str, width: float = 1.0, requests: int = 48,
               rate: float = 40.0, seed: int = 0) -> None:
    """The one-chip phase (module docstring), checkpointing into
    ``ckpt_dir``. Raises on a failed check."""
    import jax
    import numpy as np
    from repro.checkpoint.checkpoint import restore
    from repro.conv import Plan
    from repro.launch import serve
    from repro.models import resnet as RN
    from repro.serving.buckets import device_put, serve_padded

    args = _serve_args(ckpt_dir, width, requests, rate, seed)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    cfg = serve.make_config(args)
    _log(f"ResNet-18 width_mult={cfg.width_mult} widths={cfg.widths} "
         f"spec=F({cfg.wino.m},{cfg.wino.r}) {cfg.wino.base} "
         f"hadamard_bits=9 buckets={buckets}")

    t0 = time.perf_counter()
    params, state, template = serve.build_serving_state(args, cfg)
    _log(f"pack+calibrate+checkpoint: {time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    engine = serve.make_served_engine(args, cfg, template)
    _log(f"restore: {time.perf_counter() - t0:.2f}s, "
         f"{len(engine.packed)} int8 layers")

    images = _request_images(requests, seed)
    loop, report = _serve(engine, params, state, cfg, buckets, images,
                          rate, seed)

    # Padded-vs-alone: each response against its image served alone
    # through the batch forward of the bucket it was served in.
    bucket_of = {r.rid: r.bucket for r in loop.records}
    mismatched = [i for i, y in enumerate(report.outputs)
                  if not np.array_equal(
                      serve_padded(engine.serve_fn, images[i][None],
                                   bucket_of[i])[0], y)]
    _log(f"padded-vs-alone bitwise: {requests - len(mismatched)}/"
         f"{requests} equal, buckets used "
         f"{sorted(set(bucket_of.values()))}")
    if mismatched:
        raise AssertionError(f"requests {mismatched} differ from the same "
                             "image served alone")
    if loop.compiles_after_warmup != 0:
        raise AssertionError(f"{loop.compiles_after_warmup} compiles after "
                             "warmup")
    _log("compiles after warmup: 0")

    batch = np.stack(images[:buckets[-1]])
    x = device_put(batch)
    t0 = time.perf_counter()
    hlo = engine.serve_fn.lower(x).compile().as_text()
    kernels = hlo.count('custom_call_target="tpu_custom_call"')
    _log(f"served program (bucket {buckets[-1]}): {kernels} "
         f"tpu_custom_call kernels ({time.perf_counter() - t0:.2f}s to "
         "fetch the compiled program)")
    if jax.default_backend() == "tpu" and kernels == 0:
        raise AssertionError("the served program holds no Mosaic kernels")

    # Fused serving vs the staged pipeline, both against fp32.
    y_fused = np.asarray(engine.serve_fn(x))
    plan = Plan.from_checkpoint(ckpt_dir)
    staged = RN.make_engine(cfg, backend="winograd_int8", fused=False,
                            plan=plan)
    tree, _ = restore(ckpt_dir, template)
    staged.import_state(tree)
    y_staged = np.asarray(RN.serving_forward(params, state, cfg, staged)(x))
    y_ref = np.asarray(_fp32_reference(params, state, cfg, batch))
    err_fused, err_staged = _rel(y_fused, y_ref), _rel(y_staged, y_ref)
    _log(f"rel error vs fp32 direct: fused {err_fused:.6f}, staged "
         f"{err_staged:.6f} (margin {NO_ADDED_ERROR})")
    _log(f"top-1 agreement with fp32: fused {_top1(y_fused, y_ref):.4f}, "
         f"staged {_top1(y_staged, y_ref):.4f}; fused vs staged logits "
         f"bitwise {bool(np.array_equal(y_fused, y_staged))}")
    if not np.all(np.isfinite(y_fused)) or y_fused.shape != y_ref.shape:
        raise AssertionError(f"bad logits {y_fused.shape}")
    if err_fused > err_staged + NO_ADDED_ERROR:
        raise AssertionError(f"fused serving adds error over staged: "
                             f"{err_fused:.6f} vs {err_staged:.6f}")


class _Recorder:
    """Engine stand-in that records each int8 layer's input on the way
    through ``resnet.forward`` (which only calls ``conv2d``)."""

    def __init__(self, engine):
        self.engine, self.inputs = engine, {}

    def conv2d(self, x, w, *, layer, **kw):
        if layer in self.engine.packed:
            self.inputs[layer] = x
        return self.engine.conv2d(x, w, layer=layer, **kw)


def run_mesh(ckpt_dir: str, width: float = 1.0, requests: int = 48,
             rate: float = 40.0, seed: int = 0) -> None:
    """The four-chip phase (module docstring), checkpointing into
    ``ckpt_dir``. Raises on a failed check."""
    import numpy as np
    from repro.launch import serve
    from repro.models import resnet as RN

    args = _serve_args(ckpt_dir, width, requests, rate, seed)
    mesh_args = _serve_args(ckpt_dir, width, requests, rate, seed,
                            mesh=(2, 2))
    buckets = tuple(int(b) for b in args.buckets.split(","))
    cfg = serve.make_config(args)
    params, state, template = serve.build_serving_state(args, cfg)
    single = serve.make_served_engine(args, cfg, template)
    meshed = serve.make_served_engine(mesh_args, cfg, template)
    _log(f"restored onto mesh {dict(meshed.mesh.shape)}, weights "
         f"Cout-sharded over {meshed.model_axis!r}")

    # Per layer: the same input through the single-device fused engine
    # and the 2x2 mesh engine must agree bitwise (docs/parity.md).
    images = _request_images(requests, seed)
    batch = np.stack(images[:buckets[-1]])
    rec = _Recorder(single)
    RN.forward(params, state, batch, cfg, training=False, engine=rec)
    equal = []
    for layer, x in rec.inputs.items():
        a = np.asarray(single.conv2d(x, None, layer=layer))
        b = np.asarray(meshed.conv2d(x, None, layer=layer))
        equal.append(np.array_equal(a, b))
        if not equal[-1]:
            _log(f"layer {layer}: max |diff| "
                 f"{float(np.max(np.abs(a - b))):.3e}")
    _log(f"per-layer mesh vs single-device fused bitwise: "
         f"{sum(equal)}/{len(equal)} layers equal")
    if not all(equal):
        raise AssertionError("a mesh layer differs from single-device")

    _, rep_1 = _serve(single, params, state, cfg, buckets, images, rate,
                      seed)
    _, rep_4 = _serve(meshed, params, state, cfg, buckets, images, rate,
                      seed)
    y1 = np.stack([np.asarray(y) for y in rep_1.outputs])
    y4 = np.stack([np.asarray(y) for y in rep_4.outputs])
    y_ref = np.asarray(_fp32_reference(params, state, cfg, np.stack(images)))
    err_1, err_4 = _rel(y1, y_ref), _rel(y4, y_ref)
    _log(f"served logits mesh vs single-device: bitwise "
         f"{bool(np.array_equal(y1, y4))}, top-1 agreement "
         f"{_top1(y4, y1):.4f}")
    _log(f"rel error vs fp32 direct: mesh {err_4:.6f}, single-device "
         f"{err_1:.6f} (margin {NO_ADDED_ERROR})")
    if err_4 > err_1 + NO_ADDED_ERROR:
        raise AssertionError(f"mesh serving adds error: {err_4:.6f} vs "
                             f"{err_1:.6f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 2x2 (data x model) mesh phase")
    ap.add_argument("--width", type=float, default=1.0)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--rate", type=float, default=40.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # Import the program before touching the chip: a copy of this script
    # without the repository fails here, and prints no result.
    from repro.launch.compile_cache import enable_compile_cache
    devs = require_tpu(args.chips)
    _log(f"compile cache: {enable_compile_cache()}")
    _log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    run = run_mesh if args.chips == 4 else run_single
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        run(ckpt, width=args.width, requests=args.requests, rate=args.rate,
            seed=args.seed)
    _log(f"total {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
