"""The program's own spans and scopes in a traced window: device time by
(layer, stage) scope, the dispatcher's host time, device idle time inside
garbage collections, and idle gaps labelled by the program's spans.

    python3 chipbench/scopes.py --workload <cell> --seed <n>

serves one window of the cell as ``run.py --trace 1`` does, without the
check of the answers, and prints as one JSON line the readers of the
program's scopes and spans (``metrics/``), the summary of
``trace.Scopes`` that the harness logs, the seconds of the ``op_name``
fetch, and the program's set-up phases and loop counters. The reduction
is the harness's own (``harness.served_hlo``, ``trace.op_names``,
``trace.scopes``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

READERS = ("extract_us_per_image", "direct_us_per_image",
           "host_us_per_image", "idle_in_gc.offline")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from chipbench import harness, trace
    from chipbench.system import Served
    from repro import telemetry
    from repro.launch.compile_cache import enable_compile_cache

    manifest = harness.load_manifest(ROOT / "BENCHMARK.json")
    cell, arch, cfg, mix = harness.resolve(manifest, args.workload)
    harness.log(f"compile cache: {enable_compile_cache()}")
    server = harness.Server(arch, cfg, mix, args.seed, Served)
    setup_s = time.perf_counter() - T_START
    warm = server.system.compiles()
    prof = harness.Profiler(lead=min(2.0, args.seconds / 4),
                            length=min(3.0, args.seconds / 2))
    w = server.window(args.seconds, profiler=prof)
    server.loop.shutdown(drain=True)
    compiles = server.system.compiles() - warm
    t0 = time.perf_counter()
    names = trace.op_names(harness.served_hlo(server.system, mix, cfg))
    fetch_s = time.perf_counter() - t0
    red = prof.reduction(1)
    t_a, t_b = prof.span
    images = sum(b.n for b in w.batches if t_a <= b.t_done <= t_b)
    ctx = harness.context(red, names, images, log=harness.log)
    out = {"workload": args.workload, "seed": args.seed, "setup_s": setup_s,
           "compiles_after_warmup": compiles, "hlo_fetch_s": fetch_s,
           "images_traced": images,
           "images_per_s_traced": images / (t_b - t_a),
           "device_idle": 100.0 * red.idle_share,
           **{n: harness.reader(n)(ctx) for n in READERS},
           **ctx.scopes.summary(),
           "idle_gaps": red.breakdown()["idle_gaps"],
           "setup_phases": telemetry.snapshot()["phases"],
           "counters": server.loop.counters()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
