"""Continuous-batching serving loop: an online request queue over a
pre-compiled, shape-bucketed forward.

The structure (one dispatcher thread, depth-2 pipeline):

    clients ──submit()──▶ FIFO queue ──coalesce──▶ bucket-pad ──▶
        device_put + forward (async dispatch)  ──▶ pending ring ──▶
        block_until_ready → slice rows → complete futures

* **Coalescing** — the dispatcher takes the oldest waiting request and
  keeps pulling until either ``max(buckets)`` requests are in hand or
  ``max_wait_ms`` has passed since the batch opened. A lone request
  therefore never waits longer than ``max_wait_ms`` (the partial-batch
  flush), and a burst is capped at the largest bucket.
* **Bucketing** — the coalesced batch is zero-padded up to the smallest
  registered bucket (``repro.serving.buckets``), so every dispatch hits
  a program compiled at startup: zero XLA recompiles on the hot path
  (``compiles_after_warmup`` counts them via the jit cache).
* **Double buffering** — dispatch is asynchronous (jax returns before
  the device finishes), so the loop forms, transfers and dispatches
  batch *k+1* while batch *k* computes, and only then blocks on *k*.
  When the queue goes idle the pending batch is delivered immediately
  instead of waiting for a successor.
* **Ordering** — a single FIFO dispatcher forms and delivers batches in
  arrival order, so completion is in submission order globally, hence
  per client.
* **Drain** — ``shutdown(drain=True)`` stops intake, flushes the queue
  and the pending ring, completes every future, and joins the thread.
* **Tracing** — each stage of each batch on the dispatcher thread is a
  ``repro.serving.*`` profiler span (``repro.telemetry``) carrying the
  batch's sequence number, which its records carry too; ``counters()``
  counts batches, rows dispatched and padded, and requests delivered.

The loop is model-agnostic: ``forward`` is any callable mapping a
``(B, *input_shape)`` array to per-row outputs (rows independent — the
bucketed-padding parity contract). For the int8 conv stack, pass the
jitted model forward and the ``ConvEngine`` so ``start()`` runs
``engine.warmup`` over the bucket geometries.
"""
from __future__ import annotations

import dataclasses
import queue as _queue
import threading
import time
from concurrent.futures import Future
from typing import Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from repro import telemetry
from repro.serving.buckets import (DEFAULT_BUCKETS, bucket_for, device_put,
                                   pad_batch, validate_buckets)

__all__ = ["ServeConfig", "ServingLoop", "RequestRecord", "BatchRecord",
           "jit_cache_size"]


def jit_cache_size(fn) -> Optional[int]:
    """Number of programs a ``jax.jit`` callable has compiled, or None
    for a non-jit callable. The compile-count instrumentation behind the
    zero-recompiles-after-warmup contract."""
    try:
        return int(fn._cache_size())
    except AttributeError:
        return None


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs of the online loop (see module docstring)."""
    buckets: tuple = DEFAULT_BUCKETS
    max_wait_ms: float = 2.0     # partial-batch flush deadline
    pipeline_depth: int = 2      # in-flight batches (2 = double buffer)
    poll_ms: float = 20.0        # idle wakeup for drain/shutdown checks

    def __post_init__(self):
        object.__setattr__(self, "buckets",
                           validate_buckets(self.buckets))
        if self.pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        if self.max_wait_ms < 0:
            raise ValueError("max_wait_ms must be >= 0")

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]


@dataclasses.dataclass
class RequestRecord:
    """Per-request accounting, appended at delivery time."""
    rid: int
    client: Optional[str]
    t_submit: float
    t_dispatch: float
    t_done: float
    batch_n: int                 # real requests in the dispatched batch
    bucket: int                  # geometry it was padded into
    batch: int                   # sequence number of its batch

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


@dataclasses.dataclass
class BatchRecord:
    """Per-dispatch accounting (padding waste, service time)."""
    n: int
    bucket: int
    t_open: float                # first request dequeued
    t_dispatch: float
    t_done: float
    batch: int                   # sequence number, as in its spans


@dataclasses.dataclass
class _Request:
    rid: int
    client: Optional[str]
    x: np.ndarray
    future: Future
    t_submit: float


@dataclasses.dataclass
class _InFlight:
    batch: int
    requests: list
    y: object                    # dispatched (possibly async) result
    t_open: float
    t_dispatch: float
    bucket: int


_SENTINEL = object()


class ServingLoop:
    """Request-level continuous batching over a bucket-compiled forward.

    ``forward``: callable ``(B, *input_shape) -> (B, ...)``;
    ``input_shape``: the per-request shape (one request = one row);
    ``engine``: optional ``ConvEngine`` — ``start()`` then warms the
    bucket geometries through ``engine.warmup`` (otherwise the loop
    warms ``forward`` directly).
    """

    def __init__(self, forward, input_shape: Sequence[int],
                 config: ServeConfig = ServeConfig(), engine=None):
        self.forward = forward
        self.input_shape = tuple(int(d) for d in input_shape)
        self.config = config
        self.engine = engine
        self.records: list[RequestRecord] = []
        self.batches: list[BatchRecord] = []
        self.warmup_times: dict = {}
        self._queue: _queue.Queue = _queue.Queue()
        self._pending: list[_InFlight] = []
        self._thread: Optional[threading.Thread] = None
        self._accepting = False
        self._stopping = False
        self._lock = threading.Lock()
        self._next_rid = 0
        self._outstanding = 0        # accepted but not yet delivered
        self._warm_cache: Optional[int] = None
        self._counters = dict(batches=0, rows_dispatched=0, rows_padded=0,
                              requests_delivered=0)
        telemetry.install()

    # -- lifecycle ----------------------------------------------------------

    def start(self, warmup: bool = True) -> "ServingLoop":
        """Warm every bucket geometry, then start the dispatcher."""
        if self._thread is not None:
            raise RuntimeError("loop already started")
        if warmup:
            geoms = [(b, *self.input_shape) for b in self.config.buckets]
            if self.engine is not None:
                self.warmup_times = self.engine.warmup(geoms, self.forward)
            else:
                for g in geoms:
                    t0 = time.perf_counter()
                    # Through device_put, same as _dispatch: a raw numpy
                    # argument keys a different jit-cache entry, and
                    # warmup must compile the hot path's entry.
                    with telemetry.setup_phase("warmup", bucket=g[0]):
                        _block(self.forward(device_put(
                            np.zeros(g, np.float32))))
                    self.warmup_times[g] = time.perf_counter() - t0
        self._warm_cache = jit_cache_size(self.forward)
        self._accepting = True
        self._thread = threading.Thread(target=self._run,
                                        name="serving-loop", daemon=True)
        self._thread.start()
        return self

    @property
    def compiles_after_warmup(self) -> Optional[int]:
        """XLA programs compiled since ``start()`` — 0 is the contract
        (every serving geometry was pre-compiled); None when ``forward``
        is not a jit callable."""
        cur = jit_cache_size(self.forward)
        if cur is None or self._warm_cache is None:
            return None
        return cur - self._warm_cache

    def counters(self) -> dict:
        """A copy of the loop's counters: ``batches``, ``rows_dispatched``
        (bucket rows sent to the device), ``rows_padded`` (of those, the
        padding) and ``requests_delivered``. The process-wide gc and
        compile counters are ``repro.telemetry.snapshot()``."""
        return dict(self._counters)

    def submit(self, x: np.ndarray, client: Optional[str] = None) -> Future:
        """Enqueue one request (shape ``input_shape``); the Future
        resolves to that request's output row(s), sliced out of whatever
        bucket it was served in."""
        x = np.asarray(x)
        if x.shape != self.input_shape:
            raise ValueError(f"request shape {x.shape} != registered "
                             f"input shape {self.input_shape}")
        if not self._accepting:
            raise RuntimeError("serving loop is not accepting requests "
                               "(not started, or shut down)")
        fut: Future = Future()
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self._outstanding += 1
        self._queue.put(_Request(rid, client, x, fut, time.perf_counter()))
        return fut

    def drain(self, timeout: Optional[float] = None):
        """Block until every accepted request has been delivered."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while self._outstanding > 0:
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError("drain timed out")
            time.sleep(self.config.poll_ms / 1e3)

    def shutdown(self, drain: bool = True):
        """Stop intake; flush (``drain=True``) or abandon the queue."""
        self._accepting = False
        if self._thread is None:
            return
        if not drain:
            self._stopping = True
        self._queue.put(_SENTINEL)
        self._thread.join()
        self._thread = None

    # -- dispatcher ---------------------------------------------------------

    def _run(self):
        cfg = self.config
        poll_s = cfg.poll_ms / 1e3
        while True:
            # Deliver when the pipeline is full — or when there is
            # nothing new to coalesce, so an idle tail never waits for a
            # successor batch before completing.
            if self._pending and (len(self._pending) >= cfg.pipeline_depth
                                  or self._queue.empty()):
                self._deliver(self._pending.pop(0))
                continue
            try:
                item = self._queue.get(timeout=poll_s)
            except _queue.Empty:
                if self._stopping and not self._pending:
                    return
                continue
            if item is _SENTINEL:
                self._stopping = True     # flush queue + pending, then exit
                continue
            self._dispatch(*self._coalesce(item))

    def _coalesce(self, first: _Request):
        """Pull requests until the largest bucket is full or the batch
        deadline (``max_wait_ms`` after the batch opened) passes."""
        cfg = self.config
        with TraceAnnotation(telemetry.SERVING_COALESCE,
                             batch=self._counters["batches"]) as span:
            t_open = time.perf_counter()
            deadline = t_open + cfg.max_wait_ms / 1e3
            batch = [first]
            while len(batch) < cfg.max_batch:
                remain = deadline - time.perf_counter()
                if remain <= 0:
                    break
                try:
                    item = self._queue.get(timeout=remain)
                except _queue.Empty:
                    break
                if item is _SENTINEL:
                    self._stopping = True
                    break
                batch.append(item)
            span.set_metadata(n=len(batch),
                              bucket=bucket_for(len(batch), cfg.buckets))
        return batch, t_open

    def _dispatch(self, batch: list, t_open: float):
        c = self._counters
        n, k = len(batch), c["batches"]
        bucket = bucket_for(n, self.config.buckets)
        args = dict(batch=k, n=n, bucket=bucket)
        with TraceAnnotation(telemetry.SERVING_PAD, **args):
            x = pad_batch(np.stack([r.x for r in batch]), bucket)
        with TraceAnnotation(telemetry.SERVING_PUT, **args):
            x = device_put(x)                    # host→device, async
        with TraceAnnotation(telemetry.SERVING_DISPATCH, **args):
            y = self.forward(x)                  # async dispatch
        c["batches"] += 1
        c["rows_dispatched"] += bucket
        c["rows_padded"] += bucket - n
        self._pending.append(_InFlight(k, batch, y, t_open,
                                       time.perf_counter(), bucket))

    def _deliver(self, inflight: _InFlight):
        n = len(inflight.requests)
        args = dict(batch=inflight.batch, n=n, bucket=inflight.bucket)
        with TraceAnnotation(telemetry.SERVING_BLOCK, **args):
            _block(inflight.y)
        with TraceAnnotation(telemetry.SERVING_DELIVER, **args):
            y = np.asarray(inflight.y)
            t_done = time.perf_counter()
            self.batches.append(BatchRecord(
                n, inflight.bucket, inflight.t_open, inflight.t_dispatch,
                t_done, inflight.batch))
            for i, req in enumerate(inflight.requests):
                self.records.append(RequestRecord(
                    req.rid, req.client, req.t_submit, inflight.t_dispatch,
                    t_done, n, inflight.bucket, inflight.batch))
                req.future.set_result(y[i])
            self._counters["requests_delivered"] += n
        with self._lock:
            self._outstanding -= n

    # -- reporting ----------------------------------------------------------

    def busy_fraction(self, wall_s: float) -> float:
        """Approximate device-busy fraction over ``wall_s`` — batch
        service intervals, serialized (delivery of batch k overlaps the
        dispatch of k+1, so consecutive intervals are clipped)."""
        busy, prev_done = 0.0, -float("inf")
        for b in self.batches:
            start = max(b.t_dispatch, prev_done)
            busy += max(0.0, b.t_done - start)
            prev_done = max(prev_done, b.t_done)
        return 0.0 if wall_s <= 0 else min(1.0, busy / wall_s)


def _block(y):
    if hasattr(y, "block_until_ready"):
        return y.block_until_ready()
    return y
