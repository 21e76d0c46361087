"""Per-layer algorithm planner: measure → error-budget → solve → serve.

The paper's central result is that the best (algorithm, base,
hadamard_bits) choice is accuracy/cost-dependent *per layer* — and the
BENCH data shows the latency crossover (direct wins small planes,
Winograd wins channel-heavy layers). Until now that crossover was
encoded as the hand-set ``ConvPolicy.large_tile_min_channels``
threshold. This module replaces the hand rule with a measured plan, the
cuDNN-style planner the ROADMAP names:

1. **candidates** — for each layer geometry, enumerate
   {direct} ∪ {winograd F(2,3)/F(4,3)/F(6,3)} × {canonical, legendre} ×
   hadamard_bits {None, 8, 9}, *pre-filtered by the static range
   certifier* (``repro.analysis.ranges.certify_config``): a config the
   certifier cannot prove int32-safe and Hadamard-faithful is never even
   timed, so a plan can only ever carry proved configs.
2. **measure** — time each surviving candidate on synthetic operands of
   exactly the layer's serving geometry (prepare → calibrate → the
   jitted hot path, median of ``iters``, ``block_until_ready``-synced)
   and record its output error relative to the fp32 direct convolution.
   Measurements are memoised per (geometry, candidate), so layers
   sharing a shape are timed once — the same idiom as
   ``repro.conv.autotune``.
3. **solve** — per layer, pick the fastest candidate whose error stays
   within the layer's budget. Latency is additive across layers and the
   error constraint is per-layer, so the exact network optimum is the
   per-layer argmin — no search needed. The budget encodes the repo's
   no-added-error-vs-fp gate (docs/parity.md): with a ``baseline``
   entry (e.g. the engine-wide config the hand policy would serve), a
   layer's budget is the *baseline's own measured error* at that layer
   plus ``err_slack`` — the plan may trade algorithms but may not add
   error over what the unplanned engine already had. Layers where the
   baseline is infeasible (outside the Winograd regime) get the bare
   slack, which the exact ``direct`` candidate always satisfies.
4. **serialize** — the plan rides in the packed-state checkpoint as a
   ``plan/<layer>`` int32 leaf per layer (sentinel-encoded like PR 5's
   autotuned ``blocks``), so a checkpoint fully determines the serving
   configuration: ``ConvEngine.export_state``/``import_state`` carry
   it, ``Plan.from_checkpoint`` recovers it without a template (for
   serve-from-checkpoint flows), and ``ConvPolicy``'s hand thresholds
   remain the fallback when no plan is present.

``ConvEngine(plan=...)`` consumes the result: plan entries win over the
policy, each layer packs/serves with its *own* ``WinogradSpec`` and
Hadamard bit-width (heterogeneous specs in one engine), and a plan
entry that contradicts the certifier raises at pack time — the planner
pre-filters candidates, so a contradicting plan is corrupted state, not
a tunable.

On the CPU backend, where the kernels run in interpret mode, the measured plan
typically routes *everything* direct (emulated Pallas kernels lose to
XLA's native conv at every shape — see BENCH_kernel.json); that is the
correct answer for this backend, and the crossover the plan exists to
find moves with the hardware. The frozen-cost-table tests pin the
solver's behavior on a realistic accelerator cost surface.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Iterable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantization import QuantConfig
from repro.core.winograd import WinogradSpec

__all__ = [
    "PlanEntry", "Plan", "LayerGeom", "CandidateCost",
    "candidate_entries", "measure_layer", "solve_plan", "build_plan",
    "plan_cost_us", "TP_COLLECTIVE_US", "clear_measure_cache",
    "PLAN_VEC_LEN",
    "DEFAULT_TILE_SIZES", "DEFAULT_BASES", "DEFAULT_HADAMARD_BITS",
]

#: The planner's candidate grid (the ISSUE/paper menu). ``chebyshev``
#: is a valid base for hand-written plans but is not enumerated by
#: default — the paper's accuracy story is canonical vs Legendre.
DEFAULT_TILE_SIZES = (2, 4, 6)
DEFAULT_BASES = ("canonical", "legendre")
DEFAULT_HADAMARD_BITS = (None, 8, 9)

_ALGORITHMS = ("direct", "winograd_int8")
#: Index space of the serialized base field (append-only: the encoding
#: is persisted in checkpoints).
_BASE_IDS = ("canonical", "legendre", "chebyshev")
#: Sentinel for absent integer fields in the serialized plan vector
#: (mirrors ``PackedWinogradWeights.BLOCKS_MISSING``).
_MISSING = -1
#: Serialized layout: (algo_id, m, r, base_id, hadamard_bits) int32.
PLAN_VEC_LEN = 5


@dataclasses.dataclass(frozen=True)
class PlanEntry:
    """One layer's planned serving configuration.

    ``algorithm == "direct"`` carries no spec fields; ``winograd_int8``
    requires ``m``/``r``/``base`` (``hadamard_bits=None`` disables the
    8/9-bit Hadamard requant stage, as on the engine).
    """

    algorithm: str = "direct"
    m: Optional[int] = None
    r: Optional[int] = None
    base: Optional[str] = None
    hadamard_bits: Optional[int] = None

    def __post_init__(self):
        if self.algorithm not in _ALGORITHMS:
            raise ValueError(f"unknown plan algorithm {self.algorithm!r}; "
                             f"one of {_ALGORITHMS}")
        if self.algorithm == "winograd_int8":
            if not (self.m and self.r and self.base):
                raise ValueError("winograd_int8 plan entries need m, r "
                                 f"and base, got {self}")
            if self.base not in _BASE_IDS:
                raise ValueError(f"unknown base {self.base!r}; one of "
                                 f"{_BASE_IDS}")
        elif (self.m or self.r or self.base
              or self.hadamard_bits is not None):
            raise ValueError("direct plan entries carry no spec fields, "
                             f"got {self}")

    @property
    def is_winograd(self) -> bool:
        return self.algorithm == "winograd_int8"

    def spec(self) -> Optional[WinogradSpec]:
        """The entry's WinogradSpec (None for direct). Cached per entry —
        the engine resolves it on every dispatch and ``make_matrices``
        is keyed on the spec instance's hash."""
        return _entry_spec(self) if self.is_winograd else None

    def encode(self) -> np.ndarray:
        """(5,) int32 checkpoint vector; ``_MISSING`` for absent fields."""
        if not self.is_winograd:
            return np.array([0, _MISSING, _MISSING, _MISSING, _MISSING],
                            np.int32)
        bits = self.hadamard_bits if self.hadamard_bits is not None \
            else _MISSING
        return np.array([1, self.m, self.r,
                         _BASE_IDS.index(self.base), bits], np.int32)

    @classmethod
    def decode(cls, vec) -> "PlanEntry":
        v = [int(x) for x in np.asarray(vec).reshape(-1)]
        if len(v) != PLAN_VEC_LEN:
            raise ValueError(f"plan vector must have {PLAN_VEC_LEN} "
                             f"fields, got {len(v)}")
        if v[0] == 0:
            return cls()
        if v[0] != 1:
            raise ValueError(f"unknown plan algorithm id {v[0]}")
        if not 0 <= v[3] < len(_BASE_IDS):
            raise ValueError(f"unknown plan base id {v[3]}")
        return cls("winograd_int8", m=v[1], r=v[2], base=_BASE_IDS[v[3]],
                   hadamard_bits=None if v[4] == _MISSING else v[4])

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "PlanEntry":
        return cls(**d)

    def describe(self) -> str:
        if not self.is_winograd:
            return "direct"
        bits = "fp" if self.hadamard_bits is None else \
            f"{self.hadamard_bits}b"
        return f"F({self.m},{self.r})/{self.base}/{bits}"


@functools.lru_cache(maxsize=None)
def _entry_spec(entry: PlanEntry) -> WinogradSpec:
    return WinogradSpec(m=entry.m, r=entry.r, base=entry.base,
                        quant=QuantConfig(hadamard_bits=entry.hadamard_bits))


class Plan:
    """A {layer: PlanEntry} mapping with checkpoint codecs.

    The serialized form is one ``(5,)`` int32 vector per layer under a
    top-level ``plan`` group of the packed-state tree — *every* routed
    layer appears, including direct-routed ones, so a restored
    checkpoint fully determines routing with no policy consultation.
    """

    def __init__(self, entries: Mapping[str, PlanEntry]):
        for layer, e in entries.items():
            if not isinstance(e, PlanEntry):
                raise TypeError(f"layer {layer!r}: expected PlanEntry, "
                                f"got {type(e).__name__}")
        self.entries: dict[str, PlanEntry] = dict(entries)

    def get(self, layer: str) -> Optional[PlanEntry]:
        return self.entries.get(layer)

    def __len__(self):
        return len(self.entries)

    def __eq__(self, other):
        return isinstance(other, Plan) and self.entries == other.entries

    def __repr__(self):
        inner = ", ".join(f"{l}: {e.describe()}"
                          for l, e in sorted(self.entries.items()))
        return f"Plan({{{inner}}})"

    def describe(self) -> str:
        n_w = sum(e.is_winograd for e in self.entries.values())
        return (f"{len(self.entries)} layers: {n_w} winograd_int8, "
                f"{len(self.entries) - n_w} direct")

    # -- checkpoint codecs ---------------------------------------------------

    def to_tree(self) -> dict:
        return {layer: jnp.asarray(e.encode())
                for layer, e in self.entries.items()}

    @classmethod
    def from_tree(cls, tree: Mapping) -> "Plan":
        return cls({layer: PlanEntry.decode(np.asarray(vec))
                    for layer, vec in tree.items()})

    def to_dict(self) -> dict:
        return {layer: e.to_dict() for layer, e in self.entries.items()}

    @classmethod
    def from_dict(cls, d: Mapping) -> "Plan":
        return cls({layer: PlanEntry.from_dict(e) for layer, e in d.items()})

    @classmethod
    def from_checkpoint(cls, directory: str,
                        step: Optional[int] = None) -> "Optional[Plan]":
        """Recover the plan a checkpoint carries, or None for a pre-plan
        checkpoint (serve with the policy fallback).

        Template-free: reads the ``plan/`` leaves straight from the
        checkpoint arrays (``repro.checkpoint.peek_leaves``), breaking
        the chicken-and-egg of ``state_template()`` needing an engine
        that already knows the plan.
        """
        from repro.checkpoint.checkpoint import peek_leaves
        flat = peek_leaves(directory, step=step, prefix="plan/")
        if not flat:
            return None
        return cls({key[len("plan/"):]: PlanEntry.decode(arr)
                    for key, arr in flat.items()})


# ---------------------------------------------------------------------------
# candidate enumeration (certifier-prefiltered)
# ---------------------------------------------------------------------------

def candidate_entries(kernel_size: int, stride: int, cin: int, *,
                      tile_sizes: Sequence[int] = DEFAULT_TILE_SIZES,
                      bases: Sequence[str] = DEFAULT_BASES,
                      hadamard_bits: Sequence[Optional[int]]
                      = DEFAULT_HADAMARD_BITS,
                      certify: bool = True) -> list[PlanEntry]:
    """The plan candidates for one layer geometry.

    ``direct`` is always first (the exact, always-feasible fallback).
    Winograd candidates exist only inside the Winograd regime (stride 1,
    kernel == r) and — with ``certify`` (default) — only when the static
    range certifier *proves* the config int32-safe and
    Hadamard-faithful at this ``cin``: unprovable configs are never
    timed, so a measured plan cannot contradict the certifier.
    """
    cands = [PlanEntry()]
    if stride != 1:
        return cands
    for m in tile_sizes:
        if kernel_size != 3:
            continue            # the pipeline implements F(m, 3) only
        for base in bases:
            for bits in hadamard_bits:
                if certify:
                    from repro.analysis.ranges import certify_config
                    if not certify_config(m, kernel_size, base, bits,
                                          cin).proved:
                        continue
                cands.append(PlanEntry("winograd_int8", m=m, r=kernel_size,
                                       base=base, hadamard_bits=bits))
    return cands


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerGeom:
    """Static facts the planner needs about one layer: its serving input
    shape ``x_shape`` = (batch, H, W, Cin), output channels, kernel and
    stride. ``repro.models.resnet.layer_geoms`` enumerates these for the
    paper's model."""

    layer: str
    x_shape: tuple
    cout: int
    kernel_size: int = 3
    stride: int = 1

    @property
    def cin(self) -> int:
        return int(self.x_shape[3])

    def key(self) -> tuple:
        """The shape key measurements are memoised on (layer-name-free:
        same-shaped layers share one timed run)."""
        return (tuple(int(d) for d in self.x_shape), int(self.cout),
                int(self.kernel_size), int(self.stride))


@dataclasses.dataclass(frozen=True)
class CandidateCost:
    """One measured (or synthesized) candidate: median serving wall in
    µs and output error relative to the fp32 direct convolution."""

    entry: PlanEntry
    us: float
    rel_err: float


#: (geom.key(), entry, iters, warmup, padding) → CandidateCost.
#: Search options are part of the key so a quick 1-iter plan never
#: masquerades as a carefully-timed one (same contract as
#: ``repro.conv.autotune._CACHE``).
_MEASURE_CACHE: dict = {}


def clear_measure_cache():
    _MEASURE_CACHE.clear()


def _time_call(fn, *args, iters: int, warmup: int) -> float:
    """Median wall µs of ``fn(*args)``, dispatch-synced."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def _layer_operands(geom: LayerGeom):
    """Synthetic fp32 operands of exactly the serving geometry, from
    fixed seeds — measurement depends on shapes only, so plans are
    deterministic and need no model data."""
    kx, kw = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
    x = jax.random.normal(kx, geom.x_shape, jnp.float32)
    w = jax.random.normal(
        kw, (geom.kernel_size, geom.kernel_size, geom.cin, geom.cout),
        jnp.float32) * 0.1
    return x, w


def _direct_fn(stride: int, padding: str):
    return jax.jit(lambda x, w: jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding.upper(),
        dimension_numbers=("NHWC", "HWIO", "NHWC")))


def measure_layer(geom: LayerGeom,
                  candidates: Optional[Sequence[PlanEntry]] = None, *,
                  iters: int = 3, warmup: int = 1,
                  padding: str = "same") -> tuple[CandidateCost, ...]:
    """Time every candidate of one layer geometry on its serving path.

    Winograd candidates run the production int8 lifecycle — prepare
    (pack weights) → calibrate on the synthetic batch → the jitted
    prepared hot path — so the measured wall is the wall the plan will
    actually serve. Errors are relative RMS vs the fp32 direct
    convolution of the same operands (``direct`` therefore scores 0).
    Results are memoised per (geometry, candidate, options).
    """
    from repro.conv.engine import ConvEngine
    from repro.conv.policy import ConvPolicy

    if candidates is None:
        candidates = candidate_entries(geom.kernel_size, geom.stride,
                                       geom.cin)
    x, w = _layer_operands(geom)
    direct = _direct_fn(geom.stride, padding)
    y_ref = None
    out = []
    for entry in candidates:
        key = (geom.key(), entry, iters, warmup, padding)
        hit = _MEASURE_CACHE.get(key)
        if hit is not None:
            out.append(hit)
            continue
        if not entry.is_winograd:
            us = _time_call(direct, x, w, iters=iters, warmup=warmup)
            cost = CandidateCost(entry, us, 0.0)
        else:
            if y_ref is None:
                y_ref = np.asarray(direct(x, w))
            # certify="off": candidates reaching this point were already
            # filtered by the certifier (candidate_entries), and timing
            # engines must not re-warn per candidate.
            eng = ConvEngine(entry.spec(),
                             ConvPolicy(backend="winograd_int8"),
                             hadamard_bits=entry.hadamard_bits,
                             certify="off")
            eng.prepare([(geom.layer, w, geom.stride)])
            with eng.calibration():
                eng.conv2d(x, w, layer=geom.layer, stride=geom.stride)
            fn = jax.jit(lambda a, e=eng: e.conv2d(a, None,
                                                   layer=geom.layer,
                                                   stride=geom.stride))
            us = _time_call(fn, x, iters=iters, warmup=warmup)
            y = np.asarray(fn(x))
            denom = float(np.sqrt(np.mean(y_ref ** 2))) or 1.0
            err = float(np.sqrt(np.mean((y - y_ref) ** 2))) / denom
            cost = CandidateCost(entry, us, err)
        _MEASURE_CACHE[key] = cost
        out.append(cost)
    return tuple(out)


# ---------------------------------------------------------------------------
# solve
# ---------------------------------------------------------------------------

def solve_plan(costs: Mapping[str, Sequence[CandidateCost]], *,
               baseline: Optional[PlanEntry] = None,
               err_slack: float = 0.02,
               err_budget: Optional[float] = None) -> Plan:
    """Pick each layer's fastest error-feasible candidate.

    Network latency is additive over layers and the error constraint is
    per-layer, so the per-layer argmin IS the constrained network
    optimum — no combinatorial search.

    Per-layer error budget, in order of precedence:

    * ``err_budget`` — a flat relative-error cap, when given;
    * ``baseline`` — the budget is the baseline entry's own measured
      error at that layer plus ``err_slack``: the plan may not add
      error over what the unplanned (single-config) engine already
      incurred, which is exactly the repo's no-added-error-vs-fp gate
      (docs/parity.md) applied layer-wise. Layers where the baseline
      was not measured (infeasible/unproved there) budget ``err_slack``
      alone;
    * neither — ``err_slack`` alone.

    The exact ``direct`` candidate (rel_err 0) is always feasible, so
    the solve never fails. Ties break deterministically: lower error,
    then direct before Winograd, then the smaller/earlier config — a
    frozen cost table therefore yields a reproducible golden plan.
    """
    entries = {}
    for layer, cands in costs.items():
        if not cands:
            raise ValueError(f"layer {layer!r}: empty candidate set")
        budget = err_budget
        if budget is None:
            budget = err_slack
            if baseline is not None:
                base_cost = next((c for c in cands if c.entry == baseline),
                                 None)
                if base_cost is not None:
                    budget = base_cost.rel_err + err_slack
        feasible = [c for c in cands if c.rel_err <= budget]
        if not feasible:
            raise ValueError(
                f"layer {layer!r}: no candidate within error budget "
                f"{budget:.4f} — include the exact 'direct' candidate")
        entries[layer] = min(
            feasible,
            key=lambda c: (c.us, c.rel_err, c.entry.is_winograd,
                           c.entry.m or 0,
                           c.entry.base or "",
                           c.entry.hadamard_bits or 0)).entry
    return Plan(entries)


#: Modelled fixed cost (µs) of the single per-layer model-axis
#: ``all_gather`` the 2-D TP executor issues — the only collective on
#: the sharded hot path (one per layer, by construction; see
#: ``kernels.ops.execute_int8_sharded``). A flat constant, not a
#: measurement: on the interpret-mode host backend collectives are
#: memcpy-cheap, and on real interconnects the latency term dominates
#: at serving-sized (T, Cout, m, m) payloads.
TP_COLLECTIVE_US = 20.0


def plan_cost_us(plan: Plan,
                 costs: Mapping[str, Sequence[CandidateCost]], *,
                 mesh=None, data_axis="data", model_axis=None,
                 collective_us: float = TP_COLLECTIVE_US) -> float:
    """Total modelled latency of ``plan`` under a cost table (µs).

    Without ``mesh`` this is the sum of the single-device measured
    walls. With a mesh the model becomes topology-aware, mirroring how
    the serving executor actually distributes each algorithm:

    * ``winograd_int8`` layers run the 2-D sharded executor — the GEMM
      slab shrinks by BOTH axes (tiles over ``data_axis`` × Cout over
      ``model_axis``), so compute divides by the full device count, and
      each layer pays one model-axis ``all_gather`` (``collective_us``)
      iff the model axis is real (extent > 1).
    * ``direct`` layers are data-parallel only: batch shards over
      ``data_axis``; the model axis buys them nothing.

    The asymmetry is the point: on a fixed device budget the planner's
    cost ranking can flip between a data-only and a 2-D mesh — a
    Winograd candidate that loses single-device can win under TP, which
    is exactly the crossover a mesh-aware plan exists to find.
    """
    from repro.distributed.sharding import axis_extent
    dd = dm = 1
    if mesh is not None:
        dd = axis_extent(mesh, data_axis)
        dm = axis_extent(mesh, model_axis)
    total = 0.0
    for layer, entry in plan.entries.items():
        cost = next((c for c in costs[layer] if c.entry == entry), None)
        if cost is None:
            raise ValueError(f"layer {layer!r}: plan entry "
                             f"{entry.describe()} not in the cost table")
        if entry.is_winograd:
            total += cost.us / (dd * dm) + (collective_us if dm > 1
                                            else 0.0)
        else:
            total += cost.us / dd
    return total


def build_plan(geoms: Iterable[LayerGeom], *,
               baseline: Optional[PlanEntry] = None,
               tile_sizes: Sequence[int] = DEFAULT_TILE_SIZES,
               bases: Sequence[str] = DEFAULT_BASES,
               hadamard_bits: Sequence[Optional[int]]
               = DEFAULT_HADAMARD_BITS,
               certify: bool = True,
               iters: int = 3, warmup: int = 1,
               err_slack: float = 0.02,
               err_budget: Optional[float] = None,
               ) -> tuple[Plan, dict[str, tuple[CandidateCost, ...]]]:
    """Measure + solve for a layer menu. Returns (plan, cost table).

    The calibration-time entry point: enumerate certifier-proved
    candidates per layer (``candidate_entries``), measure them on
    synthetic operands of the serving geometries (``measure_layer``,
    memoised per shape), and solve under the no-added-error budget
    (``solve_plan``). The returned cost table is what benchmarks and
    the golden-plan tests inspect.
    """
    costs: dict[str, tuple[CandidateCost, ...]] = {}
    for geom in geoms:
        cands = candidate_entries(geom.kernel_size, geom.stride, geom.cin,
                                  tile_sizes=tile_sizes, bases=bases,
                                  hadamard_bits=hadamard_bits,
                                  certify=certify)
        costs[geom.layer] = measure_layer(geom, cands, iters=iters,
                                          warmup=warmup)
    return solve_plan(costs, baseline=baseline, err_slack=err_slack,
                      err_budget=err_budget), costs
