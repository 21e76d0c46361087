"""The control: the plain reference at 4 bits (the architecture module's
``forward`` with ``bits=4``) put in the served program's place, driven
through the whole run of a cell, so that its answers are judged by the
same checks and limits. Its runs have to come out not correct.

    python chipbench/control.py --workload f23-offline-b256 \
        --seeds 11,12,13 --seconds 3

prints one JSON line per seed with the checks. The benchmark's own runs
never run it; ``tests/test_chipbench_run.py`` runs it on the CPU at a
smaller batch.
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


def reference_system(bits=None, fault=None):
    """A system factory serving the reference at ``bits`` (None: float32),
    with ``fault`` applied to each batch's answers where they are
    produced."""
    import jax
    from jax.profiler import TraceAnnotation

    class ReferenceSystem:
        engine = None

        def __init__(self, arch, cfg, params, state, calibration, ckpt, log):
            def fwd(x):
                y = arch.forward(cfg, params, state, x, bits)
                return y if fault is None else fault(y)
            self.jitted = jax.jit(fwd)

        def forward(self, x):
            with TraceAnnotation("chipbench.dispatch"):
                return self.jitted(x)

        def compiles(self):
            return int(self.jitted._cache_size())
    return ReferenceSystem


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from chipbench import harness
    from chipbench.run import require_chips

    manifest = harness.load_manifest(ROOT / "BENCHMARK.json")
    cell, arch, cfg, mix = harness.resolve(manifest, args.workload)
    devices = require_chips(cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(manifest, cell, arch, cfg, mix, seed, args.seconds,
                          False, time.perf_counter(),
                          reference_system(bits=4), devices)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
