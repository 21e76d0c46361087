"""``chipbench/scopes.py`` on a made-up trace: device time by the program's
scopes, the dispatcher's host time, idle time inside a collection, and idle
gaps labelled by the program's spans."""
from types import SimpleNamespace as NS

import pytest

from chipbench import scopes, trace
from repro.telemetry import scope_of

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
    spans = scopes.program_spans(_profile())
    assert [n for n, _, _ in spans] == [
        "repro.serving.pad", "repro.serving.block",
        "repro.serving.coalesce", "repro.gc", "repro.setup.warmup"]
    assert spans[3][1:] == pytest.approx((0.106, 0.108))


def test_reduce_window():
    prof = _profile()
    red = trace.reduce(prof, 1)
    out = scopes.reduce_window(red, scopes.program_spans(prof), OP_NAMES,
                               scope_of, images=10)
    assert out["extract_us_per_image"] == pytest.approx(300.0)
    assert out["direct_us_per_image"] == pytest.approx(200.0)
    # pad 1 ms + coalesce 3.5 ms; block is waiting, not host work
    assert out["host_us_per_image"] == pytest.approx(450.0)
    assert out["idle_in_gc"] == pytest.approx(20.0)        # 2 of 10 ms
    assert out["glue_named_share"] == pytest.approx(5 / 6)  # copy-done
    assert out["device_scopes"][0] == ["s0b0.conv1", "wino_extract",
                                       pytest.approx(0.003)]
    # the gap 6-8 ms: coalesce holds the collection, which names the gap
    assert out["idle_gaps_program"] == [["repro.gc", pytest.approx(0.002)]]


def test_label_by_own_time():
    """A collection inside a longer stage names the gap while it covers
    most of the gap, the stage when the stage's own time does."""
    spans = [("repro.serving.pad", 0.0, 10.0), ("repro.gc", 2.0, 6.0)]
    assert scopes.label(spans, 1.0, 7.0) == "repro.gc"      # 4 of 6
    assert scopes.label(spans, 0.0, 10.0) == "repro.serving.pad"  # 6 of 10
    assert scopes.label([], 0.0, 1.0) == "no repro span"
