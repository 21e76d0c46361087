"""Per-(spec, shape) Pallas tile autotuning for int8 Winograd serving.

The fused serving kernel's block split ``(bm, bn, bk)`` trades grid
steps against per-step VMEM footprint, and the optimum moves with the
problem: the (P, bm, bn) int32 scratch accumulator scales with the
position count P (F(2,3): 16, F(4,3): 36, F(6,3): 64), and small or
ragged layer shapes waste padded work under the MXU-aligned defaults.
``wino_gemm.default_blocks`` encodes the static heuristic; this module
finds the actual winner *offline*:

1. ``candidate_blocks`` enumerates the deduplicated, VMEM-feasible
   block splits for one ``(P, T, Cin, Cout)`` problem (always including
   the spec default).
2. ``autotune_blocks`` times the fused serving kernel on synthetic int8
   operands of exactly the serving shape for each candidate and returns
   the fastest, with the full timing table for benchmarks.

The search runs at **pack time** (``ConvEngine(autotune=True)`` tunes
each layer when calibration fixes its tile geometry — see
``repro.conv.engine``) and the winner is cached as a leaf of the packed
state (``PackedWinogradWeights.blocks``), so it rides through
checkpoints and **serving never re-tunes**. Results are additionally
memoised per (spec, shape) in-process so a model with many
identically-shaped layers times each shape once.

Numerics are block-independent (asserted in tests): the tuner changes
wall-time only, never output bytes.

Under a per-layer algorithm plan (``repro.conv.planner``) the tuner
composes orthogonally: the plan decides each layer's *spec* — tile
size, base, Hadamard grid — and the tuner then searches the block
split for exactly that spec's P and the layer's tile geometry (the
engine resolves ``_layer_spec(layer)`` before calling in, so two
layers planned onto different tile sizes tune independent grids and
the per-(spec, shape) memo keeps them apart). Plan and blocks ride
the same checkpoint: re-planning invalidates nothing the tuner cached
for specs that survived, because the memo key already contains the
spec.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.winograd import WinogradSpec, make_matrices
from repro.kernels.fused_serve import fused_gemm_output
from repro.kernels.wino_gemm import default_blocks, validate_blocks

__all__ = ["TuneResult", "candidate_blocks", "autotune_blocks",
           "clear_cache", "VMEM_BUDGET_BYTES"]

#: Per-grid-step VMEM budget the candidate generator enforces: the
#: (P, bm, bn) int32 scratch accumulator + the two int8 operand blocks
#: + the (bm, bn, m, m) fp32 output block must fit comfortably inside a
#: TPU core's ~16 MiB VMEM (leaving headroom for double-buffering).
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

#: Block-dimension grid the tuner searches (clamped to the shape; the
#: kernels min-clamp anyway, so one super-shape candidate covers every
#: smaller extent and clamping dedups the grid).
_BM_GRID = (8, 16, 32, 64, 128, 256)
_BN_GRID = (64, 128, 256)
_BK_GRID = (64, 128, 256)


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Outcome of one (spec, shape) search.

    ``blocks``/``us``: the winner. ``default_blocks``/``default_us``:
    the spec-default heuristic on the same shape (the baseline the
    benchmarks report against). ``timings``: every candidate as
    ``(blocks, us)``, fastest first.
    """

    blocks: tuple
    us: float
    default_blocks: tuple
    default_us: float
    timings: tuple

    @property
    def speedup(self) -> float:
        """Default wall-time over tuned wall-time (>1 = tuner won)."""
        return self.default_us / max(self.us, 1e-9)


def _fused_step_bytes(P: int, m: int, bm: int, bn: int, bk: int) -> int:
    """Modelled VMEM bytes of one fused-kernel grid step."""
    scratch = P * bm * bn * 4           # int32 accumulator (K-persistent)
    x_blk = P * bm * bk                 # int8
    w_blk = P * bk * bn                 # int8
    out_blk = bm * bn * m * m * 4       # fp32
    return scratch + x_blk + w_blk + out_blk


def candidate_blocks(P: int, m: int, T: int, cin: int, cout: int,
                     budget_bytes: int = VMEM_BUDGET_BYTES) -> list[tuple]:
    """Deduplicated, VMEM-feasible (bm, bn, bk) candidates for one shape.

    Each grid value is clamped to its axis extent before dedup (the
    kernel clamps identically, so distinct tuples here are distinct
    compiled programs), then filtered by the per-step VMEM model. The
    spec default is always included even when the model would reject it
    — it is the baseline being challenged, and on small shapes clamping
    shrinks it into budget anyway.
    """
    cands = set()
    for bm in _BM_GRID:
        for bn in _BN_GRID:
            for bk in _BK_GRID:
                c = (min(bm, T), min(bn, cout), min(bk, cin))
                if _fused_step_bytes(P, m, *c) <= budget_bytes:
                    cands.add(c)
    d = default_blocks(P)
    cands.add((min(d[0], T), min(d[1], cout), min(d[2], cin)))
    # Deterministic order: big blocks (fewest grid steps) first.
    return sorted(cands, key=lambda c: (-c[0] * c[1] * c[2], c))


def _time_fused(xq, u_q, deq, rq, mats, spec, hadamard_bits, blocks,
                iters: int, warmup: int) -> float:
    fn = lambda: fused_gemm_output(
        xq, u_q, deq, rq, mats.CinvT, mats.APT, m=spec.m,
        requant_bits=hadamard_bits, changes_base=spec.changes_base,
        blocks=blocks)
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


#: In-process memo: (spec, T, cin, cout, hadamard_bits, options) →
#: TuneResult. Layers sharing a tile geometry tune once.
_CACHE: dict = {}


def clear_cache():
    _CACHE.clear()


def autotune_blocks(spec: WinogradSpec, T: int, cin: int, cout: int, *,
                    hadamard_bits: Optional[int] = None,
                    iters: int = 3, warmup: int = 1,
                    max_candidates: int = 12,
                    budget_bytes: int = VMEM_BUDGET_BYTES) -> TuneResult:
    """Time the fused serving kernel per candidate block split; return
    the winner for ``(spec, T, cin, cout)``.

    Operands are synthetic int8/fp32 tensors of exactly the serving
    shapes, from a fixed PRNG seed — timing depends on shapes only, so
    the search is deterministic and needs no model data. ``iters``
    median wall-times per candidate (interpret-mode on CPU, Mosaic on a
    real TPU — tune where you serve). ``max_candidates`` caps the
    search, keeping the biggest-block (fewest-grid-steps) candidates,
    which always include the clamped spec default.

    Cached per (spec, shape, bits, search options)
    in-process; the durable cache is the packed state
    (``PackedWinogradWeights.blocks``). The search options are part of
    the key so a capped quick search never masquerades as a wider one.
    """
    key = (spec, T, cin, cout, hadamard_bits,
           iters, warmup, max_candidates, budget_bytes)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit

    P = spec.n * spec.n
    cands = candidate_blocks(P, spec.m, T, cin, cout, budget_bytes)
    d = default_blocks(P)
    d_clamped = (min(d[0], T), min(d[1], cout), min(d[2], cin))
    cands = cands[:max_candidates]
    if d_clamped not in cands:
        cands.append(d_clamped)

    mats = make_matrices(spec)
    kx = jax.random.PRNGKey(0)
    xq = jax.random.randint(kx, (P, T, cin), -127, 128, jnp.int8)
    u_q = jax.random.randint(jax.random.PRNGKey(1), (P, cin, cout),
                             -127, 128, jnp.int8)
    deq = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (P, 1))) \
        * 1e-3 + 1e-5
    rq = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (P, 1))) \
        * 1e-2 + 1e-4

    timings = []
    for c in cands:
        validate_blocks(c)
        us = _time_fused(xq, u_q, deq, rq, mats, spec, hadamard_bits, c,
                         iters, warmup)
        timings.append((c, us))
    timings.sort(key=lambda t: t[1])
    default_us = next(us for c, us in timings if c == d_clamped)
    best, best_us = timings[0]
    result = TuneResult(blocks=best, us=best_us,
                        default_blocks=d_clamped, default_us=default_us,
                        timings=tuple(timings))
    _CACHE[key] = result
    return result
