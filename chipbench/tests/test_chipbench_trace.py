"""The reduction from a profiler trace to device numbers, against numbers
worked out by hand: on a made-up trace, and on a small trace recorded on
a TPU v5e (two batches of 8 through the served F(4,3) program)."""
from types import SimpleNamespace as NS

import pytest

from chipbench import costs, trace
from chipbench.harness import ROOT, architecture, reader

MS = 1_000_000       # ns
K1 = ('%input_transform.3 = s8[36,512,64]{2,1,0} custom-call(f32[36,512,64]'
      '{2,1,0} %a, f32[36]{0} %b), custom_call_target="tpu_custom_call", '
      'operand_layout_constraints={f32[36,512,64]{2,1,0}, f32[36]{0}}')
K2 = K1.replace("input_transform.3", "input_transform.4")


def _ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=[])


def _profile():
    """A window of 10 ms on one device: kernel K1 0-2 ms, glue g1 1-3 ms
    (overlapping K1), kernel K2 5-6 ms, a while loop 8-10.5 ms whose body
    op w1 runs 9-10 ms (both cut at the window's end), glue g0 before the
    window. The host holds a submit span over 3-5 ms and a wait span over
    6-9 ms."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("chipbench.window", 100, 10),
        _ev("chipbench.submit", 103, 2),
        _ev("chipbench.wait", 106, 3),
        _ev("PjitFunction", 100, 1)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit__lambda", 99, 13)]),
        NS(name="XLA Ops", events=[
            _ev("%g0 = f32[8] fusion()", 98, 1), _ev(K1, 100, 2),
            _ev("%g1 = f32[8] fusion()", 101, 2), _ev(K2, 105, 1),
            _ev("%while.1 = (s32[]) while()", 108, 2.5),
            _ev("%w1 = f32[8] fusion()", 109, 1)]),
        NS(name="Async XLA Ops", events=[_ev("%copy-start.1 = f32[8]", 103,
                                              1)])])
    return NS(planes=[host, dev])


def test_busy_idle_and_classes():
    r = trace.reduce(_profile(), 1)
    assert r.window_s == pytest.approx(0.010)
    # busy: 0-3 (K1 with g1), 5-6 (K2), 8-10 (while, cut) = 6 ms
    assert r.busy_s == pytest.approx(0.006)
    assert r.idle_share == pytest.approx(0.4)
    assert r.time_s(mosaic=True) == pytest.approx(0.003)      # K1 + K2
    # g1 2 ms, while 2 ms of which its body w1 1 ms: self 1 + 1 = 2 ms
    assert r.time_s(mosaic=False) == pytest.approx(0.004)
    assert [o.name for o in r.kernels()] == ["input_transform.3",
                                             "input_transform.4"]
    assert costs.parse_shapes(r.kernels()[0].signature) == [
        ("s8", (36, 512, 64)), ("f32", (36, 512, 64)), ("f32", (36,))]


def test_gaps_and_breakdown():
    r = trace.reduce(_profile(), 1)
    gaps = [(round((s - 0.1) * 1e3, 6), round((e - 0.1) * 1e3, 6))
            for s, e in r.gaps()]
    assert gaps == [(3.0, 5.0), (6.0, 8.0)]
    b = r.breakdown()
    assert [n for n, _ in b["device_ops"]][:2] == ["input_transform.3", "g1"]
    assert b["device_ops"][0][1] == pytest.approx(0.002)
    assert b["idle_gaps"] == [["chipbench.submit", pytest.approx(0.002)],
                              ["chipbench.wait", pytest.approx(0.002)]]


def test_missing_window_or_device_is_an_error():
    p = _profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(RuntimeError, match="chipbench.window"):
        trace.reduce(p, 1)
    with pytest.raises(RuntimeError, match="device planes"):
        trace.reduce(_profile(), 4)


def _chip_trace():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(ROOT / "testdata" /
                                     "small_trace.xplane.pb"))


def test_recorded_chip_trace():
    """Numbers worked out apart from ``trace.py`` (a plain loop over the
    events: clip to the window span, merge, sum the custom calls)."""
    r = trace.reduce(_chip_trace(), 1)
    assert r.window_s == pytest.approx(10.586329e-3, rel=1e-9)
    assert len(r.ops[0]) == 946
    assert r.busy_s == pytest.approx(1.088081e-3, rel=1e-6)
    assert r.time_s(mosaic=True) == pytest.approx(0.696968e-3, rel=1e-6)
    assert r.time_s(mosaic=False) == pytest.approx(
        (1.088081 - 0.696968) * 1e-3, rel=1e-6)
    assert len(r.kernels()) == 56          # 2 batches x 14 layers x 2
    assert {n for n, _, _ in r.spans} >= {"chipbench.window",
                                          "chipbench.submit"}
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10


def test_kernel_readers_on_the_chip_trace():
    """The stem's two calls at bucket 8 (512 tiles of 3 channels), and the
    kernel metrics of the recorded window."""
    import json
    r = trace.reduce(_chip_trace(), 1)
    inp, fused = r.kernels()[:2]
    sig = costs.parse_shapes(inp.signature)
    assert sig[:2] == [("s8", (36, 512, 3)), ("f32", (36, 512, 3))]
    assert costs.kernel_cost(sig[1:], sig[:1], True)[0] == "input_transform"
    sig = costs.parse_shapes(fused.signature)
    assert costs.kernel_cost(sig[1:], sig[:1], True)[0] == \
        "fused_gemm_output"
    cfg = json.loads((ROOT / "configs" / "resnet18-cifar-f23.json")
                     .read_text())
    cfg["winograd"]["m"] = 4            # the trace is of the F(4,3) program
    ctx = NS(arch=architecture(cfg["arch"]), cfg=cfg, trace=r,
             peaks=costs.peaks("TPU v5 lite"), chips=1, images_traced=16,
             log=lambda msg: None)
    roof = reader("wino_roofline")(ctx)
    assert 0 < roof < 100
    assert reader("wino_us_per_image")(ctx) == pytest.approx(
        1e6 * 0.696968e-3 / 16, rel=1e-6)
    assert 0 < reader("mfu")(ctx) < 100
