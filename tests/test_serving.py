"""Online serving front-end tests: bucket helpers, the bitwise
bucketed-padding parity contract on a calibrated int8 conv engine, the
continuous-batching queue semantics (max-wait flush, max-batch cap,
per-client ordering, graceful drain), the loop's counters and batch
numbers, and the warmup / zero-recompile instrumentation."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.conv import ConvEngine, ConvPolicy
from repro.core.quantization import QuantConfig
from repro.core.winograd import WinogradSpec
from repro.serving import (DEFAULT_BUCKETS, ServeConfig, ServingLoop,
                           bucket_for, jit_cache_size, pad_batch,
                           run_poisson_load, serve_padded, slice_batch,
                           solo_latencies, validate_buckets)

KEY = jax.random.PRNGKey(0)


# -- bucket helpers ----------------------------------------------------------

def test_validate_buckets():
    assert validate_buckets([8, 1, 4, 2]) == (1, 2, 4, 8)
    assert validate_buckets((3, 3, 5)) == (3, 5)
    with pytest.raises(ValueError):
        validate_buckets(())
    with pytest.raises(ValueError):
        validate_buckets((0, 2))
    with pytest.raises(ValueError):
        validate_buckets((1, 2.5))


def test_bucket_for_boundaries():
    buckets = (1, 2, 4, 8)
    assert [bucket_for(n, buckets) for n in range(1, 9)] == \
        [1, 2, 4, 4, 8, 8, 8, 8]
    with pytest.raises(ValueError):
        bucket_for(0, buckets)
    with pytest.raises(ValueError):
        bucket_for(9, buckets)          # the queue must cap coalescing
    assert bucket_for(3, (8,)) == 8     # single-bucket degenerate set


def test_pad_and_slice_roundtrip():
    x = np.arange(3 * 4, dtype=np.float32).reshape(3, 4)
    padded = pad_batch(x, 8)
    assert padded.shape == (8, 4) and padded.dtype == x.dtype
    np.testing.assert_array_equal(padded[:3], x)
    np.testing.assert_array_equal(padded[3:], 0.0)
    np.testing.assert_array_equal(slice_batch(padded, 3), x)
    assert pad_batch(x, 3) is x         # exact fit: no copy
    with pytest.raises(ValueError):
        pad_batch(x, 2)


def test_serve_padded_slices_real_rows():
    calls = []

    def fwd(x):
        calls.append(x.shape)
        return x * 2.0

    x = np.ones((3, 4), np.float32)
    y = serve_padded(fwd, x, 8)
    assert calls == [(8, 4)]            # dispatched at the bucket geometry
    np.testing.assert_array_equal(y, x * 2.0)


# -- bucketed-padding parity (the contract that makes padding safe) ----------

@pytest.mark.parametrize("base", ["canonical", "legendre"])
def test_padded_parity_bitwise_conv_engine(base):
    """A request served inside a zero-padded bucket is BITWISE identical
    to the same request served alone, on the prepared+calibrated int8
    path, across every bucket-boundary fill level. This is the property
    the serving loop's correctness rests on: calibrated scales are
    constants and no serving-path op reduces over the batch axis."""
    spec = WinogradSpec(m=4, r=3, base=base,
                        quant=QuantConfig(hadamard_bits=9))
    engine = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 5, 7)) * 0.2
    engine.prepare([("c", w)])
    xs = jax.random.normal(KEY, (8, 10, 10, 5))
    with engine.calibration():
        engine.conv2d(xs, None, layer="c")

    def fwd(x):
        return np.asarray(engine.conv2d(jnp.asarray(x), None, layer="c"))

    solo = [fwd(np.asarray(xs[i:i + 1]))[0] for i in range(8)]
    for n in (1, 2, 3, 5, 8):           # across the (1,2,4,8) boundaries
        y = serve_padded(fwd, np.asarray(xs[:n]), 8)
        assert y.shape[0] == n
        for i in range(n):
            np.testing.assert_array_equal(
                y[i], solo[i], err_msg=f"{base} n={n} row {i}")


# -- queue semantics (fake forward; no jax on the hot path) ------------------

class FakeForward:
    """Callable recording every dispatched batch shape, with an optional
    per-call service delay so the queue actually accumulates."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.shapes = []
        self.lock = threading.Lock()

    def __call__(self, x):
        with self.lock:
            self.shapes.append(tuple(x.shape))
        if self.delay_s:
            time.sleep(self.delay_s)
        return np.asarray(x) + 1.0


def _loop(fwd, **cfg):
    defaults = dict(buckets=(1, 2, 4, 8), max_wait_ms=30.0, poll_ms=5.0)
    defaults.update(cfg)
    return ServingLoop(fwd, (4,), ServeConfig(**defaults))


def test_results_are_per_request_rows():
    fwd = FakeForward()
    loop = _loop(fwd).start()
    xs = [np.full((4,), i, np.float32) for i in range(5)]
    futs = [loop.submit(x) for x in xs]
    for x, f in zip(xs, futs):
        np.testing.assert_array_equal(f.result(timeout=10), x + 1.0)
    loop.shutdown()
    assert all(s[0] in DEFAULT_BUCKETS for s in fwd.shapes)


def test_max_wait_flushes_partial_batch():
    """A lone request must not wait for companions forever: it ships,
    padded, within ~max_wait_ms of arrival."""
    fwd = FakeForward()
    loop = _loop(fwd, max_wait_ms=25.0).start()
    t0 = time.perf_counter()
    y = loop.submit(np.zeros((4,), np.float32)).result(timeout=10)
    waited = time.perf_counter() - t0
    loop.shutdown()
    np.testing.assert_array_equal(y, 1.0)
    assert waited < 5.0                 # not stuck on a full-batch wait
    assert fwd.shapes[0] == (1, 4)      # padded to the smallest bucket


def test_max_batch_caps_coalescing():
    """A backlog larger than the biggest bucket splits into max-bucket
    dispatches — coalescing is capped, never unbounded."""
    fwd = FakeForward(delay_s=0.05)
    loop = _loop(fwd, buckets=(1, 2, 4), max_wait_ms=100.0).start()
    futs = [loop.submit(np.zeros((4,), np.float32)) for _ in range(11)]
    for f in futs:
        f.result(timeout=30)
    loop.shutdown()
    assert max(s[0] for s in fwd.shapes) <= 4
    assert sum(b.n for b in loop.batches) == 11
    assert any(b.n > 1 for b in loop.batches)  # it did coalesce


def test_counters_add_up_and_records_carry_their_batch():
    """Every dispatched row is a delivered request or padding, and every
    record names its batch: the sequence number its spans carry."""
    fwd = FakeForward(delay_s=0.01)
    loop = _loop(fwd, buckets=(4,), max_wait_ms=20.0).start()
    futs = [loop.submit(np.zeros((4,), np.float32)) for _ in range(5)]
    for f in futs:
        f.result(timeout=30)
    loop.shutdown()
    c = loop.counters()
    assert c["requests_delivered"] == 5 and c["rows_padded"] >= 3
    assert c["rows_dispatched"] == c["rows_padded"] + c["requests_delivered"]
    assert c["batches"] == len(loop.batches)
    assert c["rows_dispatched"] == sum(b.bucket for b in loop.batches)
    assert [b.batch for b in loop.batches] == list(range(c["batches"]))
    by_batch = {b.batch: b for b in loop.batches}
    assert sorted(r.rid for r in loop.records) == list(range(5))
    for r in loop.records:
        assert (r.batch_n, r.bucket) == (by_batch[r.batch].n,
                                         by_batch[r.batch].bucket)


def test_completion_in_submission_order_per_client():
    """A single FIFO dispatcher delivers in submission order globally —
    hence in order for every client interleaved into the stream."""
    fwd = FakeForward(delay_s=0.01)
    loop = _loop(fwd).start()
    done = []
    futs = []
    for i in range(16):
        client = f"c{i % 3}"
        fut = loop.submit(np.full((4,), i, np.float32), client=client)
        fut.add_done_callback(
            lambda f, i=i, c=client: done.append((c, i)))
        futs.append(fut)
    for f in futs:
        f.result(timeout=30)
    loop.drain(timeout=10)
    loop.shutdown()
    for c in ("c0", "c1", "c2"):
        seq = [i for cc, i in done if cc == c]
        assert seq == sorted(seq), (c, seq)
    rids = [r.rid for r in loop.records]
    assert rids == sorted(rids)


def test_graceful_drain_completes_everything():
    fwd = FakeForward(delay_s=0.02)
    loop = _loop(fwd, max_wait_ms=50.0).start()
    futs = [loop.submit(np.zeros((4,), np.float32)) for _ in range(9)]
    loop.shutdown(drain=True)           # flush queue + in-flight ring
    assert all(f.done() for f in futs)
    assert len(loop.records) == 9
    with pytest.raises(RuntimeError):
        loop.submit(np.zeros((4,), np.float32))


def test_submit_validates_shape_and_lifecycle():
    loop = _loop(FakeForward())
    with pytest.raises(RuntimeError):   # not started yet
        loop.submit(np.zeros((4,), np.float32))
    loop.start()
    with pytest.raises(ValueError):
        loop.submit(np.zeros((5,), np.float32))
    loop.shutdown()


# -- warmup + compile-count instrumentation ----------------------------------

def test_warmup_precompiles_every_bucket_geometry():
    """After start(), serving any mix of batch sizes compiles nothing:
    the jit cache holds exactly one program per bucket."""
    fwd = jax.jit(lambda x: x * 2.0 + 1.0)
    loop = ServingLoop(fwd, (4,), ServeConfig(buckets=(1, 2, 4),
                                              max_wait_ms=5.0,
                                              poll_ms=5.0))
    loop.start()
    assert set(loop.warmup_times) == {(1, 4), (2, 4), (4, 4)}
    assert jit_cache_size(fwd) == 3
    futs = [loop.submit(np.full((4,), i, np.float32)) for i in range(7)]
    for i, f in enumerate(futs):
        np.testing.assert_allclose(f.result(timeout=10), i * 2.0 + 1.0)
    assert loop.compiles_after_warmup == 0
    loop.shutdown()


def test_jit_cache_size_none_for_plain_callables():
    assert jit_cache_size(lambda x: x) is None
    loop = _loop(FakeForward()).start()
    assert loop.compiles_after_warmup is None
    loop.shutdown()


def test_make_engine_warmup_integration():
    """resnet.make_engine(warmup=...) builds the jitted serving forward,
    stores it as engine.serve_fn, and pre-compiles every geometry — so a
    ServingLoop over it performs zero compiles on the hot path."""
    from repro.models import resnet as RN
    from repro.models.param import init_params

    cfg = RN.ResNetConfig(width_mult=0.25,
                          wino=WinogradSpec(m=4, r=3, base="legendre",
                                            quant=QuantConfig(
                                                hadamard_bits=9)))
    params = init_params(RN.param_specs(cfg), jax.random.PRNGKey(0))
    state = init_params(RN.state_specs(cfg), jax.random.PRNGKey(1))
    geoms = [(1, 32, 32, 3), (2, 32, 32, 3)]
    # winograd_fp: stateless backend (no prepare/calibrate), so the
    # engine holds its final serving state at construction — the case
    # the warmup= kwarg is for. The int8 restore flow warms explicitly
    # after import_state (covered by launch/serve + serve_bench).
    eng = RN.make_engine(cfg, backend="winograd_fp",
                         warmup=(params, state, geoms))
    assert eng.serve_fn is not None
    assert jit_cache_size(eng.serve_fn) == 2

    loop = ServingLoop(eng.serve_fn, (32, 32, 3),
                       ServeConfig(buckets=(1, 2), max_wait_ms=10.0,
                                   poll_ms=5.0), engine=eng)
    loop.start()                        # warm geometries: cache hits only
    futs = [loop.submit(np.zeros((32, 32, 3), np.float32))
            for _ in range(3)]
    for f in futs:
        assert f.result(timeout=60).shape == (RN.NUM_CLASSES,)
    assert loop.compiles_after_warmup == 0
    loop.shutdown()


# -- load generator ----------------------------------------------------------

def test_poisson_load_report_and_solo_baseline():
    fwd = FakeForward(delay_s=0.005)
    loop = _loop(fwd, max_wait_ms=10.0).start()
    rep = run_poisson_load(loop, rate_rps=200.0, n_requests=20,
                           make_request=lambda i: np.full((4,), i,
                                                          np.float32),
                           seed=3)
    loop.shutdown()
    assert rep.n_requests == 20 and len(rep.latencies_s) == 20
    assert rep.throughput_rps > 0
    assert 0.0 <= rep.padding_frac < 1.0
    assert rep.p50_ms() <= rep.p99_ms()
    assert rep.mean_batch >= 1.0
    # Deterministic arrivals: same seed → same schedule → same batching
    # inputs (wall-clock jitter aside), so reports are reproducible in
    # expectation; at least the request accounting must be exact.
    assert sum(b.n for b in loop.batches) == 20

    solo = solo_latencies(fwd, [np.zeros((4,), np.float32)] * 3)
    assert len(solo) == 3 and all(s > 0 for s in solo)
