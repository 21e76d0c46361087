"""Benchmark of the served int8 Winograd ResNet-18 on a TPU.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
Everything else goes on earlier lines. Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
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


def require_chips(n: int):
    """The devices of this machine, or exit non-zero unless JAX finds at
    least ``n`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chipbench: needs a TPU, JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < n:
        sys.exit(f"chipbench: the cell asks for {n} chips, JAX found "
                 f"{len(devs)}")
    return devs[:n]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    # The program is imported before the chip is touched: a checkout
    # without it fails here and prints no result.
    from chipbench.system import Served
    from repro.launch.compile_cache import enable_compile_cache

    manifest = harness.load_manifest(ROOT / "BENCHMARK.json")
    cell, arch, cfg, mix = harness.resolve(manifest, args.workload)
    devices = require_chips(cell["chips"])
    harness.log(f"compile cache: {enable_compile_cache()}")
    harness.log(f"device: {devices[0].platform} {devices[0].device_kind} "
                f"x{len(devices)}")
    result = harness.run(manifest, cell, arch, cfg, mix, args.seed,
                         args.seconds, bool(args.trace), T_START, Served,
                         devices)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
