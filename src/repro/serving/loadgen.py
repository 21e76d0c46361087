"""Closed-loop Poisson load generator and latency reporting for the
online serving loop.

The generator materializes a deterministic Poisson arrival process
(exponential inter-arrival times from a seeded RNG), submits one
request per arrival against a running ``ServingLoop``, and blocks until
every response lands before reporting — a *closed* experiment over an
*open-loop* arrival process: offered load does not slow down when the
server falls behind (that is what pushes queueing delay into the p99),
but the run has a definite end and every latency sample is collected.

Percentile math lives in ``repro.serving.metrics`` (re-exported by
``benchmarks.common``) so the benchmark suite and this module cannot
disagree on the definition.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence

import numpy as np

from repro.serving.metrics import percentile

__all__ = ["LoadReport", "run_poisson_load", "solo_latencies"]


@dataclasses.dataclass
class LoadReport:
    """One load run: offered rate, measured latency/throughput."""
    rate_rps: float              # offered (nominal Poisson) rate
    n_requests: int
    wall_s: float
    latencies_s: list            # per request, submit → delivery
    mean_batch: float            # real requests per dispatched batch
    padding_frac: float          # padded rows / dispatched rows
    busy_frac: float             # approximate device utilization
    compiles: Optional[int]      # XLA programs built during the run
    outputs: list = dataclasses.field(default_factory=list, repr=False)
    # per request, in submission order: the served output row(s)

    @property
    def throughput_rps(self) -> float:
        return self.n_requests / max(self.wall_s, 1e-9)

    def p50_ms(self) -> float:
        return percentile(self.latencies_s, 50.0) * 1e3

    def p99_ms(self) -> float:
        return percentile(self.latencies_s, 99.0) * 1e3

    def describe(self, label: str = "") -> str:
        return (f"{label}rate {self.rate_rps:.2f}/s → "
                f"{self.throughput_rps:.2f}/s served, "
                f"p50 {self.p50_ms():.0f}ms p99 {self.p99_ms():.0f}ms, "
                f"mean batch {self.mean_batch:.2f}, "
                f"padding {self.padding_frac:.0%}, "
                f"busy {self.busy_frac:.0%}, "
                f"compiles {self.compiles}")


def run_poisson_load(loop, rate_rps: float, n_requests: int,
                     make_request: Callable[[int], np.ndarray],
                     seed: int = 0) -> LoadReport:
    """Drive ``loop`` with ``n_requests`` Poisson arrivals at
    ``rate_rps``; block for every response; report latencies.

    ``make_request(i)`` materializes the i-th request payload (shape
    ``loop.input_shape``). Arrivals are scheduled against the wall
    clock, so a late submit (the generator itself got descheduled) does
    not silently compress subsequent inter-arrival gaps.
    """
    if rate_rps <= 0:
        raise ValueError("rate_rps must be positive")
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, size=n_requests))
    first_batch = len(loop.batches)
    first_rec = len(loop.records)

    t0 = time.perf_counter()
    futures = []
    for i in range(n_requests):
        lag = arrivals[i] - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        futures.append(loop.submit(make_request(i), client="loadgen"))
    outputs = [f.result() for f in futures]
    wall = time.perf_counter() - t0

    recs = loop.records[first_rec:]
    batches = loop.batches[first_batch:]
    real = sum(b.n for b in batches)
    rows = sum(b.bucket for b in batches)
    return LoadReport(
        rate_rps=rate_rps, n_requests=n_requests, wall_s=wall,
        latencies_s=[r.latency_s for r in recs],
        mean_batch=real / max(len(batches), 1),
        padding_frac=0.0 if rows == 0 else 1.0 - real / rows,
        busy_frac=loop.busy_fraction(wall),
        compiles=loop.compiles_after_warmup, outputs=outputs)


def solo_latencies(forward, requests: Sequence[np.ndarray],
                   bucket: int = 1) -> list[float]:
    """Serve each request alone (one dispatch per request, padded to the
    smallest geometry), synchronously; per-request wall seconds.

    The serve-each-request-alone baseline that continuous batching is
    measured against, and the per-machine normalizer the SLO trend gate
    divides by (``benchmarks.trend_check``).
    """
    from repro.serving.buckets import serve_padded
    out = []
    for x in requests:
        t0 = time.perf_counter()
        # serve_padded materializes its result via np.asarray — the
        # device work is finished before the window closes.
        serve_padded(forward, np.asarray(x)[None], bucket)
        out.append(time.perf_counter() - t0)  # lint: waive=unsynced-timing
    return out
