"""The program's own tracing (``repro.telemetry``): the garbage-collection
hook, the compile counter with its set-up phases, and the layer and stage
scopes that reach the served forward's HLO ``op_name``s."""
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.analysis.hlo_cost import op_names
from repro.conv import ConvEngine, ConvPolicy
from repro.core.quantization import QuantConfig
from repro.core.winograd import WinogradSpec
from repro.models import resnet as RN
from repro.models.param import ParamSpec


def _events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    prof = ProfileData.from_file(path)
    return [ev for plane in prof.planes for line in plane.lines
            for ev in line.events]


def test_gc_hook_counts_a_forced_collection_and_spans_it(tmp_path):
    telemetry.install()
    telemetry.install()                 # once per process, however called
    assert gc.callbacks.count(telemetry._PROCESS.on_gc) == 1
    before = telemetry.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    after = telemetry.snapshot()
    assert after["gc_collections"][2] >= before["gc_collections"][2] + 1
    assert after["gc_pause_s"][2] > before["gc_pause_s"][2]
    spans = [ev for ev in _events(str(tmp_path))
             if ev.name.startswith(telemetry.GC_SPAN)]
    assert spans and all(ev.duration_ns >= 0 for ev in spans)


def test_compile_counter_counts_fresh_compiles_by_phase():
    telemetry.install()
    x = np.ones((5,), np.float32)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    s0 = telemetry.snapshot()
    with telemetry.setup_phase("warmup", bucket=5):
        f(x)
    s1 = telemetry.snapshot()
    f(x)                                # cached: no compile
    s2 = telemetry.snapshot()
    assert s1["compiles"] == s0["compiles"] + 1
    assert s2["compiles"] == s1["compiles"]
    w0 = s0["phases"].get("warmup", {})
    w1 = s1["phases"]["warmup"]
    assert w1["backend_count"] == w0.get("backend_count", 0) + 1
    assert w1["trace_count"] > w0.get("trace_count", 0)
    assert w1["backend_s"] > w0.get("backend_s", 0.0)
    assert s1["compile_s"] > s0["compile_s"]


def test_compile_seconds_count_nested_spans_once():
    """A span that holds earlier ones (a nested jit's trace) adds only its
    own time, so the phase's seconds are the union of the spans."""
    c = telemetry._Counters()
    ev = "/jax/core/compile/jaxpr_trace_duration"
    c.on_span(ev, 1.0, 2.0)             # inner traces close first
    c.on_span(ev, 2.5, 3.0)
    c.on_span(ev, 0.0, 4.0)             # the outer trace holds both
    c.on_span("/jax/core/compile/backend_compile_duration", 5.0, 7.0)
    c.on_event("/jax/compilation_cache/cache_hits")
    p = c.phases["other"]
    assert p["trace_count"] == 3 and p["trace_s"] == 4.0
    assert p["backend_count"] == 1 and p["backend_s"] == 2.0
    assert p["cache_hits"] == 1 and p["cache_misses"] == 0


def test_scope_of_reads_layer_and_stage():
    assert telemetry.scope_of(
        "jit(serve_resnet)/s0b0.conv1/wino_extract/jit(_extract)/pad") == \
        ("s0b0.conv1", "wino_extract")
    assert telemetry.scope_of("jit(serve_resnet)/head/dot_general") == \
        (None, "head")
    assert telemetry.scope_of("jit(serve_resnet)/add") == (None, None)


def test_served_forward_hlo_names_layers_and_stages():
    """The served forward at width 1/8 is ``jit(serve_resnet)``, and its
    HLO ``op_name``s carry the layer and stage scopes: an int8 Winograd
    layer's extraction and reassembly, and the stride-2 and 1x1 convs that
    the policy routes to direct convolution. (One layer takes the int8
    path, which keeps the interpret-mode lowering short.)"""
    cfg = RN.ResNetConfig(width_mult=0.125, wino=WinogradSpec(
        m=4, r=3, base="legendre", quant=QuantConfig(hadamard_bits=9)))
    ones = lambda specs: jax.tree.map(
        lambda s: np.ones(s.shape, np.float32), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))
    params, state = ones(RN.param_specs(cfg)), ones(RN.state_specs(cfg))
    engine = ConvEngine(cfg.wino, ConvPolicy(
        backend="direct", overrides=(("s0b0.conv1", "winograd_int8"),)))
    fwd = RN.serving_forward(params, state, cfg, engine)
    lowered = fwd.lower(jax.device_put(jnp.zeros((1, 32, 32, 3))))
    text = lowered.compiler_ir("hlo").as_hlo_module().to_string()
    assert text.startswith("HloModule jit_serve_resnet")
    scopes = {telemetry.scope_of(n) for n in op_names(text).values()}
    assert {("s0b0.conv1", "wino_extract"),
            ("s0b0.conv1", "wino_reassemble"),
            ("s1b0.conv1", "direct"), ("s1b0.proj", "direct"),
            ("s0b0.conv1", "bn"), ("s0b0", "relu_add"),
            (None, "head")} <= scopes
    assert any(n.startswith("jit(serve_resnet)/s0b0.conv1/wino_extract/")
               for n in op_names(text).values())
