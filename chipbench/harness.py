"""One run of one cell: set-up, the measured window, the check of every
answer against the reference, and the result line.

``run`` takes the system under test as a factory, so that the tests can
drive the rest of a run on the CPU with a stand-in or a planted fault.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import threading
import time
from collections import deque
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from chipbench import costs, reference, trace, traffic, weights

ROOT = Path(__file__).resolve().parent


def log(msg: str):
    print(f"[chipbench] {msg}", flush=True)


def load_manifest(path: Path) -> dict:
    return json.loads(Path(path).read_text())


#: Keys of a configuration file that every architecture shares: what it is
#: and where it comes from, how it was cut from its source (the keys it
#: changed, the sizes it assumed, the deployment it stands for), its
#: images, the Winograd tile and bits it is served with, and how its
#: answers are judged. Each architecture module names the keys of its own
#: in ``KEYS``.
COMMON_KEYS = frozenset({"model", "source", "arch", "reduced", "assumed",
                         "deployment", "params", "image_shape",
                         "num_classes", "winograd", "bits", "calibration",
                         "limits", "err_level"})


def architecture(name: str, arch_dir: Path = ROOT / "archs"):
    """The architecture module ``<arch_dir>/<name, - as _>.py``: all the
    benchmark knows about one network. A name with no module is an error,
    never a default."""
    path = Path(arch_dir) / f"{name.replace('-', '_')}.py"
    if not path.is_file():
        known = sorted(q.stem.replace("_", "-")
                       for q in Path(arch_dir).glob("*.py"))
        raise ValueError(f"no architecture module for arch {name!r} "
                         f"({path.name} in {arch_dir}); known: {known}")
    return _load(path, "arch")


def _load(path: Path, kind: str):
    """The module in the file ``path``, by its path: a data directory, not
    a package."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def configuration(manifest: dict, name: str, root: Path = ROOT.parent,
                  arch_dir: Path = ROOT / "archs"):
    """The manifest's configuration ``name``: its architecture module (the
    file's ``arch``, from ``arch_dir``) and its file (the manifest's
    ``file``, relative to ``root``). A file that names no architecture,
    one with no module, or a key that neither the module nor
    ``COMMON_KEYS`` knows is refused here, before anything is built."""
    conf = {c["name"]: c for c in manifest["configs"]}[name]
    cfg = json.loads((root / conf["file"]).read_text())
    if "arch" not in cfg:
        raise ValueError(f"{conf['file']} names no 'arch'")
    arch = architecture(cfg["arch"], arch_dir)
    unknown = set(cfg) - COMMON_KEYS - set(arch.KEYS)
    if unknown:
        raise ValueError(f"{conf['file']}: keys {sorted(unknown)} are not "
                         f"read by arch {cfg['arch']!r}")
    return arch, cfg


def resolve(manifest: dict, workload: str, root: Path = ROOT.parent,
            traffic_dir: Path = ROOT / "traffic",
            arch_dir: Path = ROOT / "archs"):
    """The cell, its configuration's architecture module and file
    (``configuration``) and its traffic mix
    (``<traffic_dir>/<name>.json``)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    arch, cfg = configuration(manifest, cell["config"], root, arch_dir)
    mix = traffic.load(traffic_dir / f"{cell['traffic']}.json")
    return cell, arch, cfg, mix


def metrics_for(manifest: dict, cell: dict, trace_on: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with tracing its per-layer ones."""
    group = manifest["per_layer" if trace_on else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


def reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    return _load(ROOT / "metrics" / f"{name}.py", "metric").read


def context(red, names: dict, images: int, **given) -> SimpleNamespace:
    """What a metric reader reads: ``trace``, the device trace reduced to
    the traced window; ``scopes``, the program's scopes and host spans in
    it (``names``: each HLO instruction's ``op_name``); ``images_traced``,
    the images answered in it; and ``given`` (the cell, its architecture,
    configuration and mix, ``chips``, ``peaks``, ``window``, ``log``)."""
    return SimpleNamespace(trace=red, scopes=trace.scopes(red, names, images),
                           images_traced=images, **given)


def served_hlo(system, mix: dict, cfg: dict) -> str:
    """The compiled HLO text of the served program at the mix's largest
    bucket: the executable whose instructions the trace names (a mix with
    several buckets runs others too, whose names this does not give)."""
    import jax
    x = jax.device_put(np.zeros((max(mix["buckets"]), *cfg["image_shape"]),
                                np.float32))
    return system.jitted.lower(x).compile().as_text()


class Profiler(threading.Thread):
    """Traces ``length`` seconds of a window, starting ``lead`` seconds
    after it opens, from a thread of its own so that starting and
    stopping the profiler stalls neither the client nor the loop."""

    def __init__(self, lead: float, length: float):
        super().__init__(name="chipbench-profiler")
        self.lead, self.length = lead, length
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        self.span, self.error, self.t_open = None, None, None

    def begin(self, t_open: float):
        self.t_open = t_open
        self.start()

    def run(self):
        import jax
        from jax.profiler import TraceAnnotation
        try:
            time.sleep(max(0.0, self.t_open + self.lead
                           - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # spans, not every Python call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                with TraceAnnotation(trace.WINDOW_SPAN):
                    t_a = time.perf_counter()
                    time.sleep(self.length)
                    t_b = time.perf_counter()
            finally:
                jax.profiler.stop_trace()
            self.span = (t_a, t_b)
        except Exception as e:          # raised in reduction()
            self.error = e

    def reduction(self, n_devices: int):
        """The trace reduced to the traced window; the trace is deleted."""
        try:
            if self.span is None:
                raise RuntimeError(f"the profiler failed: {self.error!r}")
            return trace.reduce(trace.load(self.dir), n_devices)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class GcPauses:
    """Python's garbage collections while it is entered: the generation
    and the pause of each (a full collection stops every thread)."""

    def __init__(self):
        self.pauses, self._t = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def describe(self) -> str:
        parts = []
        for g in range(3):
            p = [s for gen, s in self.pauses if gen == g]
            parts.append(f"gen{g} {len(p)} in {1e3 * sum(p):.1f}ms (longest "
                         f"{1e3 * max(p, default=0.0):.1f}ms)")
        return "garbage collection in the window: " + ", ".join(parts)


def answer_errors(served: np.ndarray, ref: np.ndarray,
                  err_level: float = None) -> dict:
    """The numbers that judge served logits against the reference's:
    ``logit_err_rms``, the relative RMS error of all answers' logits,
    ``logit_err_max``, the largest relative error of one answer, and with
    ``err_level`` ``share_over_err_level``, the share of answers whose
    relative error exceeds it."""
    norm = np.linalg.norm(ref, axis=-1)
    err = np.linalg.norm(served - ref, axis=-1)
    out = {"logit_err_rms": float(np.sqrt(np.sum(err ** 2)
                                          / np.sum(norm ** 2))),
           "logit_err_max": float(np.max(err / norm))}
    if err_level is not None:
        out["share_over_err_level"] = float(np.mean(err / norm > err_level))
    return out


def error_profile(served: np.ndarray, ref: np.ndarray) -> str:
    """Quantiles of the answers' relative errors and their shares over a
    few levels, for the log."""
    rel = (np.linalg.norm(served - ref, axis=-1)
           / np.linalg.norm(ref, axis=-1))
    qs = np.quantile(rel, [0.5, 0.9, 0.99, 0.999])
    return ("per-answer error p50/p90/p99/p99.9 "
            + "/".join(f"{q:.4f}" for q in qs) + "; share over "
            + ", ".join(f"{lv}: {np.mean(rel > lv):.6f}"
                        for lv in (0.1, 0.15, 0.2, 0.25, 0.3)))


def _check(arch, cfg, params, state, pool, answers, compiles_in_window,
           failed):
    """Every delivered answer against the reference of its image, each
    number the configuration sets a limit on beside it, with compiles
    inside the window and failed requests (limits 0). A number that
    cannot be read (no answers, or a non-finite logit) is ``None`` and
    fails."""
    values = dict.fromkeys(cfg["limits"])
    served = np.stack([y for _, y in answers]) if answers else None
    if served is not None and np.all(np.isfinite(served)):
        idx = np.array([i for i, _ in answers])
        uniq, inv = np.unique(idx, return_inverse=True)
        t0 = time.perf_counter()
        ref = reference.logits(arch, cfg, params, state, pool[uniq])[inv]
        log(f"reference over {len(uniq)} distinct images "
            f"({len(answers)} answers): {time.perf_counter() - t0:.2f}s")
        served = served.astype(np.float64)
        readings = answer_errors(served, ref, cfg.get("err_level"))
        log("readings: " + ", ".join(f"{k} {v}" for k, v in readings.items()))
        log(error_profile(served, ref))
        values.update({k: readings[k] for k in cfg["limits"]})
    checks = {k: (values[k], cfg["limits"][k]) for k in cfg["limits"]}
    checks.update(compiles_in_window=(compiles_in_window, 0),
                  failed_requests=(failed, 0))
    ok = all(v is not None and v <= lim for v, lim in checks.values())
    return ok, {k: {"value": v, "limit": lim}
                for k, (v, lim) in checks.items()}


class Server:
    """Set-up: the benchmark's weights and images from the seed, the
    system under test built from them, and a started ``ServingLoop`` with
    the mix's buckets warmed up. ``window`` then serves the measured
    window."""

    def __init__(self, arch, cfg: dict, mix: dict, seed: int,
                 system_factory):
        from repro.serving import ServeConfig, ServingLoop
        self.cfg, self.mix, self.seed = cfg, mix, seed
        t0 = time.perf_counter()
        self.params, self.state = weights.make_weights(arch, cfg, seed)
        log(f"weights {time.perf_counter() - t0:.3f}s")
        with tempfile.TemporaryDirectory(prefix="chipbench_ckpt_") as ckpt:
            self.system = system_factory(
                arch, cfg, self.params, self.state,
                weights.calibration_images(cfg, seed), ckpt, log)
        self.pool = weights.images(cfg, seed, mix["pool"])
        self.loop = ServingLoop(
            self.system.forward, tuple(cfg["image_shape"]),
            ServeConfig(buckets=tuple(mix["buckets"]),
                        max_wait_ms=float(mix["max_wait_ms"])),
            engine=self.system.engine)
        self.loop.start()
        for g, s in self.loop.warmup_times.items():
            log(f"warmup bucket {g[0]}: {s:.3f}s")

    def _closed(self, order, t_open: float, seconds: float):
        """Offline backlog: keep ``outstanding`` requests queued until the
        window closes. Returns ``(pool index, future)`` of each request."""
        from jax.profiler import TraceAnnotation
        sent, queue = [], deque()
        while time.perf_counter() < t_open + seconds:
            with TraceAnnotation("chipbench.submit"):
                while len(queue) < self.mix["outstanding"]:
                    i = int(order[len(sent) % len(order)])
                    sent.append((i, self.loop.submit(self.pool[i])))
                    queue.append(sent[-1][1])
            with TraceAnnotation("chipbench.wait"):
                queue[0].result()
            while queue and queue[0].done():
                queue.popleft()
        return sent

    def window(self, seconds: float, profiler=None):
        """Serve one window of ``seconds`` of the mix, then wait for every
        answer, each up to a minute past the close. Returns the window's
        requests, records and answers."""
        first = len(self.loop.records), len(self.loop.batches)
        order = traffic.image_order(self.mix, self.seed, self.mix["pool"])
        with GcPauses() as gcp:
            t_open = time.perf_counter()
            if profiler is not None:
                profiler.begin(t_open)
            sent = self._closed(order, t_open, seconds)
        if profiler is not None:
            profiler.join()
        deadline = max(time.perf_counter(), t_open + seconds) + 60.0
        answers, failed = [], 0
        for i, fut in sent:
            try:
                y = fut.result(timeout=max(0.0, deadline - time.perf_counter()))
                answers.append((i, np.asarray(y)))
            except Exception as e:          # a missing answer is a failure
                failed += 1
                log(f"request failed: {type(e).__name__}: {e}")
        return SimpleNamespace(
            t_open=t_open, t_close=t_open + seconds, seconds=seconds,
            sent=sent, answers=answers, failed=failed, gc=gcp,
            records=self.loop.records[first[0]:],
            batches=self.loop.batches[first[1]:])


def end_to_end(w) -> dict:
    """``images_per_s``: answers delivered inside the window over its
    length."""
    done = sum(1 for r in w.records if w.t_open <= r.t_done <= w.t_close)
    return {"images_per_s": done / w.seconds}


def describe(w) -> str:
    done = sum(1 for r in w.records if r.t_done <= w.t_close)
    return (f"window {w.seconds}s: {len(w.sent)} requests sent, {done} "
            f"answered inside it, {len(w.batches)} batches, "
            f"{w.failed} failed; " + w.gc.describe())


def run(manifest: dict, cell: dict, arch, cfg: dict, mix: dict, seed: int,
        seconds: float, trace_on: bool, t_start: float, system_factory,
        devices) -> dict:
    """One run of ``cell``; returns the result line's object."""
    server = Server(arch, cfg, mix, seed, system_factory)
    warm_compiles = server.system.compiles()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s (process start to window)")

    prof = Profiler(lead=min(2.0, seconds / 4), length=min(3.0, seconds / 2)
                    ) if trace_on else None
    w = server.window(seconds, profiler=prof)
    server.loop.shutdown(drain=True)
    compiles_in_window = server.system.compiles() - warm_compiles
    log(describe(w) + f", compiles inside {compiles_in_window}")
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": int((devices[0].memory_stats() or {}).get(
               "peak_bytes_in_use", 0))}
    if trace_on:                # after the window, its compiles and peak
        t0 = time.perf_counter()
        names = trace.op_names(served_hlo(server.system, mix, cfg))
        log(f"op_name fetch: {time.perf_counter() - t0:.3f}s, "
            f"{len(names)} instructions")
    params, state, pool = server.params, server.state, server.pool
    del server                  # the program's state, before the reference

    metrics, out_extra = {}, {}
    wanted = metrics_for(manifest, cell, trace_on)
    if trace_on:
        red = prof.reduction(len(devices))
        dev["busy_s"], dev["window_s"] = red.busy_s, red.window_s
        out_extra["breakdown"] = red.breakdown()
        t_a, t_b = prof.span
        ctx = context(red, names, sum(b.n for b in w.batches
                                      if t_a <= b.t_done <= t_b),
                      arch=arch, cfg=cfg, mix=mix, cell=cell,
                      chips=len(devices),
                      peaks=costs.peaks(devices[0].device_kind), window=w,
                      log=log)
        log("scopes: " + json.dumps(ctx.scopes.summary()))
        values = {m["name"]: reader(m["name"])(ctx) for m in wanted}
    else:
        values = dict(end_to_end(w), setup_s=setup_s)
    for m in wanted:
        if values.get(m["name"]) is not None:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    ok, checks = _check(arch, cfg, params, state, pool, w.answers,
                        compiles_in_window, w.failed)
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return {"correct": ok, "attempted": len(w.sent), "failed": w.failed,
            "metrics": metrics, "device": dev, **out_extra,
            "checks": checks}
