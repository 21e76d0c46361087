"""The program's own tracing: every span, scope and counter name, the
garbage-collection hook and the compile counter.

Host spans are ``jax.profiler.TraceAnnotation``: with a profiler running
they land in its trace, on the clock of the device ops, and cost about a
microsecond each when none is. Device scopes are ``jax.named_scope``: they
change only the ``op_name`` metadata of the HLO, so every XLA op names the
layer and the stage it was traced under (``scope_of``). Nothing here keeps
spans: the profiler is the one recorder.

Host spans (``repro.*``):

* ``repro.serving.{coalesce,pad,put,dispatch,block,deliver}`` — one per
  stage of each batch on the ``ServingLoop`` dispatcher thread, with the
  batch's sequence number ``batch``, its ``n`` and its ``bucket``.
* ``repro.gc`` — a garbage collection, with its ``generation``, on the
  thread that collects.
* ``repro.setup.{pack,calibrate,restore,warmup}`` — the set-up phases.

Process-wide counters (``snapshot()``), registered once per process by
``install()``: collections and pause seconds per generation, and per
set-up phase the count and seconds of JAX tracing, lowering and backend
compiles, with persistent-cache hits and misses.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Optional

from jax import monitoring
from jax.profiler import TraceAnnotation

__all__ = ["install", "setup_phase", "snapshot", "scope_of", "STAGES",
           "SETUP_PHASES"]

# -- host spans -------------------------------------------------------------

SERVING_COALESCE = "repro.serving.coalesce"
SERVING_PAD = "repro.serving.pad"
SERVING_PUT = "repro.serving.put"
SERVING_DISPATCH = "repro.serving.dispatch"
SERVING_BLOCK = "repro.serving.block"
SERVING_DELIVER = "repro.serving.deliver"
GC_SPAN = "repro.gc"
SETUP_PREFIX = "repro.setup."
SETUP_PHASES = ("pack", "calibrate", "restore", "warmup")

# -- device scopes: the stage vocabulary --------------------------------------
# ``ConvEngine.conv2d`` opens a scope named after the layer; inside it each
# stage below. Every Pallas kernel is named after its stage.

EXTRACT = "wino_extract"                   # pad, tile windows, transpose
INPUT_TRANSFORM = "wino_input_transform"   # int8 input transform + quantize
GEMM_OUTPUT = "wino_gemm_output"           # fused GEMM + requant + output
GEMM = "wino_gemm"                         # staged GEMM (+ requant)
OUTPUT_TRANSFORM = "wino_output_transform"  # staged output transform
REASSEMBLE = "wino_reassemble"             # output tiles back to NHWC
DIRECT = "direct"                          # convs routed to direct conv
BN = "bn"                                  # batch norm (and its ReLU)
RELU_ADD = "relu_add"                      # residual add and ReLU
HEAD = "head"                              # pooling and the classifier
STAGES = (EXTRACT, INPUT_TRANSFORM, GEMM_OUTPUT, GEMM, OUTPUT_TRANSFORM,
          REASSEMBLE, DIRECT, BN, RELU_ADD, HEAD)

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
_OTHER = "other"          # compiles outside every set-up phase


def scope_of(op_name: str) -> tuple[Optional[str], Optional[str]]:
    """``(layer, stage)`` of an HLO ``op_name``: the stage is the first
    path component in ``STAGES``, the layer the component before it
    unless that is a ``jit(...)`` frame. Either may be None."""
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if p in STAGES:
            layer = parts[i - 1] if i and not parts[i - 1].startswith(
                "jit(") else None
            return layer, p
    return None, None


class _Counters:
    """The process's gc and compile counters (one instance, ``_PROCESS``)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.installed = False
        self.phase = _OTHER
        self.gc_collections = [0, 0, 0]
        self.gc_pause_s = [0.0, 0.0, 0.0]
        self.phases: dict[str, dict] = {}
        self._gc_open = None      # (span, start) of the running collection
        self._spans = []          # compile intervals no later one contains

    def _phase(self) -> dict:
        return self.phases.setdefault(self.phase, {
            "trace_count": 0, "trace_s": 0.0, "lower_count": 0,
            "lower_s": 0.0, "backend_count": 0, "backend_s": 0.0,
            "cache_hits": 0, "cache_misses": 0})

    def on_gc(self, phase: str, info: dict):
        # Collections never overlap: one slot holds the open span.
        if phase == "start":
            span = TraceAnnotation(GC_SPAN, generation=info["generation"])
            span.__enter__()
            self._gc_open = (span, time.perf_counter())
        elif self._gc_open is not None:
            span, t0 = self._gc_open
            self._gc_open = None
            span.__exit__(None, None, None)
            g = info["generation"]
            self.gc_collections[g] += 1
            self.gc_pause_s[g] += time.perf_counter() - t0

    def on_span(self, event: str, start: float, end: float, **_):
        kind = _COMPILE_EVENTS.get(event)
        if kind is None:
            return
        with self.lock:
            # A nested jit's trace (or an eager compile inside a trace)
            # closes before the span that holds it: count each span's
            # time less what it holds, so the seconds add up to the union.
            inner = 0.0
            while self._spans and self._spans[-1][0] >= start:
                s, e = self._spans.pop()
                inner += e - s
            self._spans.append((start, end))
            del self._spans[:-256]
            p = self._phase()
            p[f"{kind}_count"] += 1
            p[f"{kind}_s"] += max(0.0, end - start - inner)

    def on_event(self, event: str, **_):
        key = _CACHE_EVENTS.get(event)
        if key is not None:
            with self.lock:
                self._phase()[key] += 1


_PROCESS = _Counters()


def install():
    """Register the gc hook and the compile listeners, once per process."""
    with _PROCESS.lock:
        if _PROCESS.installed:
            return
        _PROCESS.installed = True
    gc.callbacks.append(_PROCESS.on_gc)
    monitoring.register_event_time_span_listener(_PROCESS.on_span)
    monitoring.register_event_listener(_PROCESS.on_event)


@contextlib.contextmanager
def setup_phase(name: str, **args):
    """Attribute compiles to set-up phase ``name`` (one of
    ``SETUP_PHASES``) and open its ``repro.setup.<name>`` span."""
    install()
    prev, _PROCESS.phase = _PROCESS.phase, name
    try:
        with TraceAnnotation(SETUP_PREFIX + name, **args):
            yield
    finally:
        _PROCESS.phase = prev


def snapshot() -> dict:
    """A copy of the process-wide counters: ``gc_collections`` and
    ``gc_pause_s`` per generation; ``compiles`` (backend compiles, from
    the persistent cache or not), ``compile_s`` (seconds of tracing,
    lowering and backend compiles), ``cache_hits`` and ``cache_misses``
    in all; and the same by set-up phase under ``phases``."""
    with _PROCESS.lock:
        phases = {k: dict(v) for k, v in _PROCESS.phases.items()}
        out = {"gc_collections": list(_PROCESS.gc_collections),
               "gc_pause_s": list(_PROCESS.gc_pause_s)}
    total = lambda key: sum(p[key] for p in phases.values())
    out.update(compiles=total("backend_count"),
               compile_s=total("trace_s") + total("lower_s")
               + total("backend_s"),
               cache_hits=total("cache_hits"),
               cache_misses=total("cache_misses"), phases=phases)
    return out
