"""The program's scopes and spans in a made-up trace, as the harness gives
them to the metric readers (``trace.scopes``, ``harness.context``): device
time by the program's scopes, the dispatcher's host time, idle time inside
a collection, and idle gaps labelled by the program's spans."""
from types import SimpleNamespace as NS

import pytest

from chipbench import trace
from chipbench.harness import context, reader

MS = 1_000_000       # ns
KERNEL = ('%input_transform.3 = s8[36,512,64]{2,1,0} custom-call(f32[36,512,'
          '64]{2,1,0} %a), custom_call_target="tpu_custom_call"')
OP_NAMES = {
    "input_transform.3": "jit(serve_resnet)/stem/wino_input_transform/"
                         "jit(input_transform)/pallas_call",
    "fusion.1": "jit(serve_resnet)/s0b0.conv1/wino_extract/jit(_extract)/pad",
    "fusion.2": "jit(serve_resnet)/s1b0.conv1/direct/conv_general_dilated",
}


def _ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=[])


def _profile():
    """A 10 ms window: the kernel 0-2 ms, an extraction 2-5 ms, a direct
    conv 8-10 ms, an unnamed op 5-6 ms, so the device idles 6-8 ms. The
    dispatcher pads over 0-1 ms and blocks over 1-5.5 ms; a collection
    runs 6-8 ms inside a coalesce span of 5.5-9 ms, which overlaps the
    idle gap as much as the collection does."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("chipbench.window", 100, 10),
        _ev("repro.serving.pad", 100, 1),
        _ev("repro.serving.block", 101, 4.5),
        _ev("repro.serving.coalesce", 105.5, 3.5),
        _ev("repro.gc", 106, 2),
        _ev("repro.setup.warmup", 90, 5)])])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev(KERNEL, 100, 2), _ev("%fusion.1 = f32[8] fusion()", 102, 3),
        _ev("%copy-done.1 = f32[8] copy-done()", 105, 1),
        _ev("%fusion.2 = f32[8] fusion()", 108, 2)])])
    return NS(planes=[host, dev])


def test_program_spans_are_the_repro_host_spans():
    spans = trace.reduce(_profile(), 1).program_spans
    assert [n for n, _, _ in spans] == [
        "repro.serving.pad", "repro.serving.block",
        "repro.serving.coalesce", "repro.gc", "repro.setup.warmup"]
    assert spans[3][1:] == pytest.approx((0.106, 0.108))


def test_reduce_window():
    ctx = context(trace.reduce(_profile(), 1), OP_NAMES, 10)
    assert reader("extract_us_per_image")(ctx) == pytest.approx(300.0)
    assert reader("direct_us_per_image")(ctx) == pytest.approx(200.0)
    # pad 1 ms + coalesce 3.5 ms; block is waiting, not host work
    assert reader("host_us_per_image")(ctx) == pytest.approx(450.0)
    assert reader("idle_in_gc.offline")(ctx) == pytest.approx(20.0)  # 2/10
    # a layer's scope as well as a stage's
    assert ctx.scopes.scope_s("s0b0.conv1") == pytest.approx(0.003)
    assert ctx.scopes.scope_s("stem") == pytest.approx(0.002)
    assert ctx.scopes.scope_s("max_pool") is None
    out = ctx.scopes.summary()
    assert out["glue_named_share"] == pytest.approx(5 / 6)  # copy-done
    assert out["device_scopes"][0] == ["s0b0.conv1", "wino_extract",
                                       pytest.approx(0.003)]
    # the gap 6-8 ms: coalesce holds the collection, which names the gap
    assert out["idle_gaps_program"] == [["repro.gc", pytest.approx(0.002)]]


def test_label_by_own_time():
    """A collection inside a longer stage names the gap while it covers
    most of the gap, the stage when the stage's own time does."""
    spans = [("repro.serving.pad", 0.0, 10.0), ("repro.gc", 2.0, 6.0)]
    assert trace.program_label(spans, 1.0, 7.0) == "repro.gc"      # 4 of 6
    assert trace.program_label(spans, 0.0, 10.0) == \
        "repro.serving.pad"                                         # 6 of 10
    assert trace.program_label([], 0.0, 1.0) == "no repro span"


def test_readers_silent_without_what_they_read():
    """A trace with no program spans and no scoped instruction, or a window
    that answered no image: the readers return nothing, never 0. A window
    with the program's spans but no collection in it is idle in none: 0."""
    prof = _profile()
    prof.planes[0].lines[0].events = prof.planes[0].lines[0].events[:1]
    names = ("extract_us_per_image", "direct_us_per_image",
             "host_us_per_image", "idle_in_gc.offline")
    ctx = context(trace.reduce(prof, 1), {}, 10)
    assert [reader(n)(ctx) for n in names] == [None] * 4
    ctx = context(trace.reduce(_profile(), 1), OP_NAMES, 0)
    assert [reader(n)(ctx) for n in names[:3]] == [None] * 3
    prof = _profile()
    prof.planes[0].lines[0].events = [
        e for e in prof.planes[0].lines[0].events if e.name != "repro.gc"]
    ctx = context(trace.reduce(prof, 1), OP_NAMES, 10)
    assert reader("idle_in_gc.offline")(ctx) == 0.0


HLO = '''HloModule jit_serve_resnet, entry_computation_layout={(f32[8]{0})->f32[8]}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %pad.3 = f32[8]{0} pad(f32[8]{0} %param_0.1, f32[] %c), padding=0_0, metadata={op_name="jit(serve_resnet)/s0b0.conv1/wino_extract/jit(_extract)/pad" source_file="ops.py" source_line=12}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %input_transform.3 = s8[8]{0} custom-call(f32[8]{0} %Arg_0.1), custom_call_target="tpu_custom_call", backend_config={"custom_call_config": {"body": "x}y"}}, metadata={op_name="jit(serve_resnet)/stem/wino_input_transform/pallas_call"}
  ROOT %fusion.1 = f32[8]{0} fusion(f32[8]{0} %Arg_0.1), kind=kLoop, calls=%fused_computation.1
}
'''


def test_op_names_and_scopes_from_hlo_text():
    """Every instruction of every computation by name, with its
    ``op_name`` or "" where it has none, and the ``(layer, stage)`` of
    each."""
    names = trace.op_names(HLO)
    assert names == {
        "param_0.1": "", "Arg_0.1": "", "fusion.1": "",
        "pad.3": OP_NAMES["fusion.1"].replace("fusion.1", "pad.3"),
        "input_transform.3": "jit(serve_resnet)/stem/wino_input_transform/"
                             "pallas_call"}
    assert trace.scope_of(names["pad.3"]) == ("s0b0.conv1", "wino_extract")
    assert trace.scope_of(names["input_transform.3"]) == (
        "stem", "wino_input_transform")
    assert trace.scope_of(OP_NAMES["fusion.2"]) == ("s1b0.conv1", "direct")
    assert trace.scope_of("jit(serve_resnet)/head/dot_general") == (
        None, "head")
    assert trace.scope_of("jit(serve_resnet)/add") == (None, None)
    # any scope the program opens, with no list of them
    assert trace.scope_path("jit(serve_resnet)/stem/max_pool/jit(_pool)/"
                            "reduce_window") == ("stem", "max_pool")
