"""The program's own spans and scopes in a traced window: device time by
(layer, stage) scope, the dispatcher's host time, device idle time inside
garbage collections, and idle gaps labelled by the program's spans.

It reads what ``repro.telemetry`` writes: the ``repro.*`` host spans of the
trace, and the ``op_name`` scope of each HLO instruction. The trace's
device events name instructions only, so the scopes come from the served
executable's compiled HLO text (``jitted.lower(x).compile().as_text()``),
parsed by ``repro.analysis.hlo_cost.op_names``.

    python3 chipbench/scopes.py --workload <cell> --seed <n>

serves one window of the cell as ``run.py --trace 1`` does, without the
check of the answers, and prints these numbers as one JSON line. The
harness does not call it (see ``PERF.md``, section 7).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench.trace import DEVICE_PLANE, _union  # noqa: E402

PREFIX = "repro."
GC_SPAN = "repro.gc"
HOST_STAGES = tuple(f"repro.serving.{s}" for s in
                    ("coalesce", "pad", "put", "dispatch", "deliver"))


def program_spans(profile) -> list:
    """``(name, start, end)`` in seconds of every ``repro.*`` host span."""
    return [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9)
            for plane in profile.planes
            if not plane.name.startswith(DEVICE_PLANE)
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIX)]


def _clip(spans, window, names):
    a, b = window
    return [(max(s, a), min(e, b)) for n, s, e in spans
            if n in names and min(e, b) > max(s, a)]


def _overlap(xs, ys) -> float:
    """Seconds in both of two lists of sorted, disjoint intervals."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        tot += max(0.0, min(xs[i][1], ys[j][1]) - max(xs[i][0], ys[j][0]))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def label(spans, start: float, end: float) -> str:
    """The program span whose own time overlaps ``[start, end]`` most: its
    overlap less that of the spans it holds, so a collection that stalls a
    dispatcher stage names the gap, and not the stage around it."""
    clip = [(n, max(s, start), min(e, end), s, e) for n, s, e in spans
            if min(e, end) > max(s, start)]
    best, name = 0.0, "no repro span"
    for n, a, b, s, e in clip:
        inner = _union((a2, b2) for n2, a2, b2, s2, e2 in clip
                       if s <= s2 and e2 <= e and (s2, e2) != (s, e))
        own = b - a - sum(y - x for x, y in inner)
        if own > best:
            best, name = own, n
    return name


def reduce_window(red, spans, op_names: dict, scope_of, images: int,
                  top: int = 10) -> dict:
    """The numbers of one traced window: ``red`` is its
    ``chipbench.trace.Reduction``, ``spans`` its ``program_spans``,
    ``op_names`` instruction name to ``op_name``, ``scope_of`` ``op_name``
    to ``(layer, stage)``, ``images`` the images answered in it."""
    by_scope, glue, named = {}, 0.0, 0.0
    for ops in red.ops:
        for o in ops:
            key = scope_of(op_names.get(o.name, ""))
            by_scope[key] = by_scope.get(key, 0.0) + o.self_s / red.n_devices
            if not o.mosaic:
                glue += o.self_s
                named += o.self_s if key[1] is not None else 0.0
    stage = {}
    for (_, st), t in by_scope.items():
        stage[st] = stage.get(st, 0.0) + t
    per_image = (lambda t: 1e6 * t / images) if images else (lambda t: None)
    gaps = red.gaps(0)
    gc = _union(_clip(spans, red.window, {GC_SPAN}))
    host = sum(e - s for s, e in _clip(spans, red.window, HOST_STAGES))
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "extract_us_per_image": per_image(stage.get("wino_extract", 0.0)),
        "direct_us_per_image": per_image(stage.get("direct", 0.0)),
        "host_us_per_image": per_image(host),
        "idle_in_gc": 100.0 * _overlap(gaps, gc)
        / red.window_s,
        "glue_named_share": named / glue if glue else None,
        "stage_us_per_image": {str(k): per_image(v) for k, v in sorted(
            stage.items(), key=lambda kv: -kv[1])},
        "device_scopes": [[layer, st, t] for (layer, st), t in sorted(
            by_scope.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps_program": [[label(spans, s, e), e - s]
                              for s, e in longest],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import harness, trace
    from chipbench.system import Served
    from repro import telemetry
    from repro.analysis.hlo_cost import op_names
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
    t0 = time.perf_counter()
    x = jax.device_put(np.zeros((mix["buckets"][-1], *cfg["image_shape"]),
                                np.float32))
    names = op_names(server.system.jitted.lower(x).compile().as_text())
    fetch_s = time.perf_counter() - t0
    try:
        profile = trace.load(prof.dir)
    finally:
        shutil.rmtree(prof.dir, ignore_errors=True)
    red = trace.reduce(profile, 1)
    t_a, t_b = prof.span
    images = sum(b.n for b in w.batches if t_a <= b.t_done <= t_b)
    out = {"workload": args.workload, "seed": args.seed, "setup_s": setup_s,
           "compiles_after_warmup": server.system.compiles() - warm,
           "hlo_fetch_s": fetch_s, "images_traced": images,
           "images_per_s_traced": images / (t_b - t_a),
           "device_idle": 100.0 * red.idle_share,
           **reduce_window(red, program_spans(profile), names,
                           telemetry.scope_of, images),
           "idle_gaps": red.breakdown()["idle_gaps"],
           "setup_phases": telemetry.snapshot()["phases"],
           "counters": server.loop.counters()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
