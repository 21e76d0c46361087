"""ConvEngine: unified dispatch over the paper's convolution family.

Backend matrix
==============

===================  ========================================  ==========
backend              implementation                            use
===================  ========================================  ==========
``direct``           ``lax.conv_general_dilated``              baseline; strided
                                                               convs, 1×1
                                                               shortcuts
``winograd_fp``      ``core.winograd`` pipeline, quant off     exact F(m, r)
                                                               reference
``winograd_fakequant`` ``core.winograd`` pipeline, Fig.-2      QAT training
                     symmetric casts (8-bit, 8/9-bit           (differentiable,
                     Hadamard), canonical or changed base      STE gradients)
``winograd_int8``    Pallas kernels (``kernels.ops``): int8    inference
                     input transform → MXU int8×int8→int32     serving
                     GEMM per Winograd position → fused
                     dequant output transform.  With
                     ``fused=True`` (default) a prepared+
                     calibrated layer serves through the
                     single-pass ``kernels.fused_serve``
                     kernel — GEMM, 8/9-bit Hadamard requant
                     and output transform in ONE Pallas call,
                     zero fp32 intermediates in HBM;
                     integer-exact vs the staged path, fp32
                     outputs equal to float rounding
===================  ========================================  ==========

Every convolution in a model goes through ``ConvEngine.conv2d`` with a
stable ``layer`` name; a ``ConvPolicy`` maps static layer facts (stride,
kernel size vs the spec's r, channel count, per-layer overrides) to a
backend, replacing the per-call-site branching that used to live in the
models. Winograd-aware trained checkpoints therefore deploy onto the int8
kernels by switching the policy, with no model-code changes.

Prepare/execute lifecycle (int8 serving)
========================================

1. **prepare** — ``engine.prepare(named_weights)`` transforms each
   eligible layer's weights once into ``PackedWinogradWeights``
   (per-position int8 ``u_q`` + weight scales). Offline; the hot path
   never transforms weights again.
2. **calibrate** — under ``with engine.calibration():`` run
   representative batches through the model (eager, not jitted: the
   engine records concrete per-position abs-maxima in the Winograd input
   domain and, when the 8/9-bit Hadamard stage is on, of the Hadamard
   products). On exit the running maxima become per-layer, per-position
   input and requant scales. Calibrating on a batch reproduces the
   dynamic scales of that batch bit-for-bit (same compiled reductions).
3. **serialize** — ``export_state()`` / ``import_state()`` round-trip the
   packed+calibrated state through ``repro.checkpoint`` (use
   ``state_template()`` as the restore skeleton).
4. **execute** — ``conv2d`` on a prepared+calibrated layer dispatches to
   the hot path: extract → ``input_transform`` → fused GEMM+requant+
   output-transform kernel (``kernels.fused_serve``), with zero weight
   transforms, zero scale reductions (the Hadamard requant scale is
   calibrated too) and zero fp32 intermediates in HBM. Pass
   ``fused=False`` to force the staged three-kernel pipeline — the two
   agree exactly in the integer Hadamard domain and to float rounding
   (~1e-5 rel, FMA contraction) at fp32 output, so the switch is a
   performance knob. Unprepared int8 layers fall back to dynamic scales
   (correct, one extra fp pass + reductions per call, staged requant).

Sharded serving (``mesh=``)
===========================

Built with a ``jax.sharding.Mesh``, the engine serves prepared+
calibrated int8 layers across devices
(``kernels.ops.execute_int8_sharded``): the Winograd tile axis T is
sharded over the mesh's data axis, and — when ``model_axis`` names a
second mesh axis — the packed weights' Cout axis is sharded over it
(conv tensor parallelism: 1/D_model of the packed bytes per device,
one all_gather of the (T_local, Cout_local, m, m) spatial outputs per
layer). Per-element arithmetic is untouched, so the sharded execution
is integer-exact in the Hadamard domain and bit-identical at fp32
output across mesh shapes. ``import_state`` places restored state over
the mesh (replicated statistics, cout-sharded ``u_q``), resharding
checkpoints written on any other topology. Dynamic-requant layers
serve sharded too — shard-local abs-max merged by one ``lax.pmax``,
exactly the single-device derivation; calibration and ``fused=False``
calls fall back to the single-device pipeline.

A layer re-packed after a weight update keeps its calibrated
``in_scales`` (input-only statistic) but drops ``hadamard_amax``
(weight-dependent): it serves correctly with dynamic requant and can
still be exported — the missing statistic round-trips as a sentinel
leaf so recalibrate-inputs-only flows can checkpoint. Only uncalibrated
``in_scales`` block ``export_state``.

Training backends (``winograd_fakequant``/``winograd_fp``/``direct``)
are stateless and differentiable; ``flex`` transform parameters pass
straight through to the fake-quant pipeline.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import Iterable, Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.conv.packing import (PackedWinogradWeights, merge_abs_max,
                                pack_weights, place_packed_state,
                                scales_from_abs_max)
from repro.conv.policy import BACKENDS, ConvPolicy
from repro.core.quantization import QuantConfig
from repro.core.winograd import (WinogradSpec, make_matrices,
                                 winograd_conv2d)
from repro.kernels.ops import (_extract, _geometry, _tiles_abs_max,
                               execute_int8, execute_int8_sharded,
                               prepare_weights_int8, winograd_conv2d_int8)
from repro.kernels.wino_gemm import validate_blocks

__all__ = ["ConvEngine"]


def _direct(x, w, stride, padding):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding.upper(),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _same_packed_weights(a: PackedWinogradWeights,
                         b: PackedWinogradWeights) -> bool:
    """Whether two packs encode identical weights. Both leaves matter: a
    pure rescale of w leaves u_q unchanged (the symmetric quantizer
    absorbs it into w_scales)."""
    return (a.u_q.shape == b.u_q.shape
            and bool(jnp.all(a.u_q == b.u_q))
            and bool(jnp.all(a.w_scales == b.w_scales)))


class ConvEngine:
    """Dispatches convolutions through a policy-selected backend and owns
    the prepared/calibrated serving state (see module docstring)."""

    def __init__(self, spec: Optional[WinogradSpec],
                 policy: Optional[ConvPolicy] = None,
                 padding: str = "same",
                 hadamard_bits: "Optional[int] | str" = "from_spec",
                 fused: bool = True,
                 mesh=None,
                 data_axis="data",
                 model_axis=None,
                 blocks: Optional[tuple] = None,
                 autotune: bool = False,
                 autotune_opts: Optional[dict] = None,
                 certify: str = "warn",
                 plan: "Optional[object]" = None):
        """``hadamard_bits``: the int8 backend's 8/9-bit Hadamard requant
        stage. The default mirrors the spec's QAT setting
        (``spec.quant.hadamard_bits``) so serving matches what the model
        trained with; pass an int to override or None to disable.

        ``fused``: serve int8 layers through the single-pass
        GEMM→requant→output-transform kernel whenever no dynamic
        reduction is needed (default on; engages automatically for
        prepared+calibrated layers — calibration and dynamic-requant
        calls stay staged). Integer-exact vs the staged pipeline in the
        Hadamard domain; fp32 outputs agree to float rounding.

        ``mesh``: a ``jax.sharding.Mesh`` to serve across. Prepared+
        calibrated int8 layers then run through
        ``kernels.ops.execute_int8_sharded``: the Winograd tile axis is
        sharded over ``data_axis`` (a mesh axis name or tuple of names)
        and — when ``model_axis`` names a second mesh axis — the packed
        weights' Cout axis is sharded over it (conv tensor parallelism:
        each device holds 1/D_model of every layer's packed bytes, runs
        the fused kernel on its (T/D_data, Cout/D_model) slab, and one
        per-layer all_gather reassembles the channels). Bit-identical
        output on any mesh shape. ``import_state`` places the restored
        packed state accordingly (replicated leaves + cout-sharded
        ``u_q``), resharding a checkpoint written under any other mesh.
        Dynamic-requant layers serve sharded too (shard-local abs-max +
        one ``lax.pmax`` — exactly the single-device derivation);
        layers that cannot take the sharded path (uncalibrated input
        scales, ``fused=False``, calibration passes) fall back to the
        single-device pipeline unchanged.

        ``blocks``: (bm, bn, bk) Pallas block override reaching both the
        staged ``wino_gemm`` and the fused serving kernel — the manual
        per-shape tuning knob. When set it wins over everything,
        including per-layer autotuned blocks; ``None`` defers to the
        packed state's autotuned blocks, then to the spec default
        (``wino_gemm.default_blocks``). Malformed values raise
        ``ValueError`` here, before any kernel launch.

        ``autotune``: tune the Pallas block split per (spec, shape)
        offline (``repro.conv.autotune``). Calibration fixes each int8
        layer's tile geometry, so ``end_calibration`` times the fused
        kernel over the candidate splits once per distinct shape and
        caches each layer's winner in its packed state — a checkpoint
        then carries the tuned ``(bm, bn, bk)`` and *serving never
        re-tunes*. Numerics are block-independent; the knob changes
        wall-time only. ``autotune_opts`` forwards keyword arguments to
        ``repro.conv.autotune.autotune_blocks`` (``iters``,
        ``max_candidates``, …) to bound the search cost.

        ``certify``: pack-time static range certification
        (``repro.analysis.ranges``). Every int8 layer's
        ``(spec, base, hadamard_bits, Cin)`` is proved
        int32-accumulator-safe and Hadamard-faithful before its weights
        are packed: ``"warn"`` (default) emits a ``RuntimeWarning`` on
        an unprovable config, ``"error"`` refuses it (``ValueError``),
        ``"off"`` skips the check. The proof is symbolic (exact-rational
        worst case) and cached per config, so the gate costs microseconds
        after the first layer.

        ``plan``: a ``repro.conv.planner.Plan`` mapping layer names to
        measured per-layer serving configs. A planned layer ignores the
        policy: ``algorithm="direct"`` serves direct regardless of
        eligibility, ``"winograd_int8"`` packs and serves with the
        entry's OWN ``(m, r, base, hadamard_bits)`` — heterogeneous
        specs coexist in one engine (the engine-wide ``spec``/
        ``hadamard_bits`` cover only unplanned layers, the policy
        fallback). The plan rides in ``export_state``/
        ``state_template``/``import_state`` as a ``plan/<layer>`` leaf
        group, so a planned checkpoint fully determines routing;
        restoring a tree that carries a plan adopts it. Because the
        planner only emits certifier-proved candidates, a plan entry
        the certifier cannot prove raises at pack time *unconditionally*
        (``certify`` gates only the unplanned path): a contradicting
        plan is corrupted state, not a tunable."""
        if spec is None:
            policy = policy or ConvPolicy(backend="direct",
                                          fallback="direct")
            routed = ({policy.backend, policy.fallback}
                      | {b for _, b in policy.overrides})
            if any(b != "direct" for b in routed):
                raise ValueError("Winograd backends need a WinogradSpec")
        if hadamard_bits == "from_spec":
            hadamard_bits = (spec.quant.hadamard_bits
                             if spec is not None else None)
        self.spec = spec
        self.fp_spec = (dataclasses.replace(spec, quant=QuantConfig.off())
                        if spec is not None else None)
        self.policy = policy or ConvPolicy()
        self.padding = padding
        self.hadamard_bits = hadamard_bits
        self.fused = fused
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.blocks = validate_blocks(blocks)
        if certify not in ("off", "warn", "error"):
            raise ValueError(f"certify must be 'off', 'warn' or 'error', "
                             f"got {certify!r}")
        self.certify = certify
        self.plan = plan
        self.autotune = autotune
        self.autotune_opts = dict(autotune_opts or {})
        self.mats = make_matrices(spec) if spec is not None else None
        self.packed: dict[str, PackedWinogradWeights] = {}
        self._calibrating = False
        self._amax: dict[str, jnp.ndarray] = {}     # input-domain running max
        self._amax_h: dict[str, jnp.ndarray] = {}   # Hadamard-product max
        self._scales: dict[str, jnp.ndarray] = {}   # finalized calibrations
        self._h_amax_final: dict[str, jnp.ndarray] = {}
        # (T, Cin, Cout) tile geometry observed per layer during
        # calibration — the shape key the autotuner searches over.
        self._tile_geom: dict[str, tuple] = {}
        # The packed weights each calibration observed, as (u_q,
        # w_scales): the Hadamard abs-max is weight-dependent, so it may
        # only reattach to a later prepare() that packs the *same*
        # weights — and a pure rescale of w leaves u_q unchanged (the
        # symmetric quantizer absorbs it into w_scales), so both leaves
        # are part of the fingerprint.
        self._calib_uq: dict[str, tuple] = {}
        # The serving callable warmup() defaults to — set by
        # model-level factories (e.g. resnet.make_engine(warmup=...)).
        self.serve_fn = None

    # -- warmup -------------------------------------------------------------

    def warmup(self, geometries: Iterable[tuple],
               forward=None) -> dict[tuple, float]:
        """Jit-compile and execute every registered serving geometry once.

        ``geometries``: input shapes (e.g. ``(batch, H, W, Cin)``) the
        online loop will dispatch — one XLA program compiles per shape,
        so running each through ``forward`` here (``block_until_ready``)
        moves the whole compile storm to startup: the first request of
        any registered geometry then hits a warm cache, and serving
        performs **zero recompiles** (the loop's
        ``compiles_after_warmup`` instrumentation asserts it).

        ``forward``: the serving callable (typically the outer
        ``jax.jit`` of the model forward closed over this engine);
        defaults to ``self.serve_fn``. Warm up *after* the engine holds
        its final serving state (prepare/import_state) — compiling an
        unprepared engine caches the dynamic-fallback programs instead.

        Returns {shape: seconds} compile+execute wall per geometry.
        """
        forward = forward if forward is not None else self.serve_fn
        if forward is None:
            raise ValueError("warmup needs a serving callable: pass "
                             "forward= or set engine.serve_fn")
        times = {}
        for g in geometries:
            g = tuple(int(d) for d in g)
            t0 = time.perf_counter()
            with telemetry.setup_phase("warmup", bucket=g[0]):
                # device_put, matching the serving loop's dispatch: a
                # committed array keys a different jit-cache entry than
                # an uncommitted one, and warmup must build the hot
                # path's.
                x = jax.device_put(jnp.zeros(g, jnp.float32))
                jax.block_until_ready(forward(x))
            times[g] = time.perf_counter() - t0
        return times

    # -- dispatch -----------------------------------------------------------

    def _plan_entry(self, layer: str):
        """The layer's PlanEntry, or None (unplanned → policy rules)."""
        return self.plan.get(layer) if self.plan is not None else None

    def _layer_spec(self, layer: str) -> Optional[WinogradSpec]:
        """The WinogradSpec serving this layer: its plan entry's own
        spec when planned winograd, else the engine-wide spec."""
        e = self._plan_entry(layer)
        return e.spec() if e is not None and e.is_winograd else self.spec

    def _layer_hbits(self, layer: str) -> Optional[int]:
        """The 8/9-bit Hadamard requant width serving this layer."""
        e = self._plan_entry(layer)
        return (e.hadamard_bits if e is not None and e.is_winograd
                else self.hadamard_bits)

    def backend_for(self, layer: str, *, kernel_size: int, stride: int,
                    in_channels: Optional[int] = None) -> str:
        e = self._plan_entry(layer)
        if e is not None:
            # A plan wins over the policy: it is a measured, certified
            # per-layer decision (repro.conv.planner). Entries are only
            # generated inside the Winograd regime, so an out-of-regime
            # winograd entry is corrupted plan state — refuse loudly
            # rather than silently falling back (the silent fallback
            # would serve a config nobody measured).
            if not e.is_winograd:
                return "direct"
            if stride != 1 or kernel_size != e.r:
                raise ValueError(
                    f"plan routes layer {layer!r} to {e.describe()} but "
                    f"the layer is outside that Winograd regime (kernel "
                    f"{kernel_size}, stride {stride}) — the plan does "
                    f"not match this model; re-plan")
            return "winograd_int8"
        r = self.spec.r if self.spec is not None else None
        m = self.spec.m if self.spec is not None else None
        return self.policy.backend_for(layer, kernel_size=kernel_size,
                                       stride=stride, spec_r=r,
                                       in_channels=in_channels, spec_m=m)

    def _layer_blocks(self, pk: Optional[PackedWinogradWeights]
                      ) -> Optional[tuple]:
        """Resolve the Pallas blocks for one call: the engine-wide manual
        override wins, then the layer's autotuned blocks, then None (the
        kernels fall back to the spec default)."""
        if self.blocks is not None:
            return self.blocks
        if pk is not None and pk.blocks is not None:
            return pk.block_tuple()
        return None

    def conv2d(self, x: jnp.ndarray, w: Optional[jnp.ndarray], *,
               layer: str = "conv", stride: int = 1,
               flex: Optional[dict] = None,
               padding: Optional[str] = None) -> jnp.ndarray:
        """One convolution. x: (N,H,W,Cin) NHWC; w: (k,k,Cin,Cout) HWIO.

        ``w`` may be None for a prepared+calibrated ``winograd_int8``
        layer (weights live in the packed state). For an int8 layer with
        packed state, the packed weights are authoritative and a
        caller-passed ``w`` is ignored — after updating model weights,
        re-run ``prepare``/``clear_packed`` so serving state tracks them.

        Traced under ``jax.named_scope(layer)``, and inside it the stage
        scopes of ``repro.telemetry``, so each XLA op's ``op_name`` names
        its layer and stage.
        """
        with jax.named_scope(layer):
            return self._conv2d(x, w, layer, stride, flex, padding)

    def _conv2d(self, x, w, layer, stride, flex, padding):
        pad = padding or self.padding
        pk = self.packed.get(layer)
        spec = self._layer_spec(layer)
        hbits = self._layer_hbits(layer)
        if w is None:
            if pk is None or spec is None:
                raise ValueError(f"layer {layer!r}: no weights and no "
                                 "prepared state")
            k, cin = spec.r, pk.u_q.shape[1]
        else:
            k, cin = w.shape[0], w.shape[2]
        backend = self.backend_for(layer, kernel_size=k, stride=stride,
                                   in_channels=cin)
        if w is None and backend != "winograd_int8":
            raise ValueError(
                f"layer {layer!r}: no weights passed but policy routes to "
                f"{backend!r} — packed state only serves winograd_int8")

        if backend == "direct":
            with jax.named_scope(telemetry.DIRECT):
                return _direct(x, w, stride, pad)
        if backend == "winograd_fp":
            return winograd_conv2d(x, w, self.fp_spec, mats=self.mats,
                                   flex=flex, padding=pad)
        if backend == "winograd_fakequant":
            return winograd_conv2d(x, w, self.spec, mats=self.mats,
                                   flex=flex, padding=pad)
        assert backend == "winograd_int8", backend
        if flex is not None:
            raise ValueError(
                "the winograd_int8 backend packs analytic transform "
                "matrices; flex-trained transforms are not supported — "
                "serve flex models via winograd_fakequant/winograd_fp")
        if self._calibrating:
            return self._calibrate_conv(x, w, pk, layer, pad, spec, hbits)
        if pk is not None:
            # Packed weights win over any caller-passed ``w`` (the
            # serving contract — see the docstring); dynamic scales when
            # uncalibrated, e.g. recalibrating a restored engine.
            if self.mesh is not None and self.fused and pk.calibrated:
                # Sharded serving: tile slabs across the mesh's data
                # axis × Cout-sharded weights across its model axis.
                # Calibrated-requant layers run the fused kernel per
                # slab (bit-identical to the single-device fused path);
                # dynamic-requant layers run the staged slab with the
                # plane abs-max assembled by one pmax — exactly the
                # single-device dynamic derivation.
                with jax.named_scope(telemetry.EXTRACT):
                    tiles = _extract(x, spec.m, spec.r, spec.n, pad)
                geom = _geometry(x.shape, spec.m, spec.r, pad)
                return execute_int8_sharded(
                    tiles, pk.u_q, pk.w_scales, pk.in_scales,
                    pk.hadamard_amax, spec=spec, geom=geom,
                    mesh=self.mesh, hadamard_bits=hbits,
                    blocks=self._layer_blocks(pk),
                    data_axis=self.data_axis,
                    model_axis=self.model_axis)
            return winograd_conv2d_int8(
                x, None, spec, pad,
                in_scales=pk.in_scales if pk.calibrated else None,
                u_q=pk.u_q, w_scales=pk.w_scales,
                hadamard_bits=hbits,
                h_amax=pk.hadamard_amax if pk.calibrated else None,
                fused=self.fused, blocks=self._layer_blocks(pk))
        return winograd_conv2d_int8(
            x, w, spec, pad, hadamard_bits=hbits,
            fused=self.fused, blocks=self.blocks)

    def _calibrate_conv(self, x, w, pk, layer, pad, spec, hbits):
        """One int8 conv under calibration: extract tiles once, record
        input-domain and Hadamard-product maxima, execute with this
        batch's statistics (bit-identical to the dynamic derivation).
        ``spec``/``hbits`` are the layer's own (plan-resolved) config."""
        if pk is not None:
            u_q, w_scales = pk.u_q, pk.w_scales
        else:
            u_q, w_scales = prepare_weights_int8(w, spec)
        with jax.named_scope(telemetry.EXTRACT):
            tiles = _extract(x, spec.m, spec.r, spec.n, pad)
        geom = _geometry(x.shape, spec.m, spec.r, pad)
        amax = _tiles_abs_max(tiles, spec)
        self._amax[layer] = merge_abs_max(self._amax.get(layer), amax)
        self._calib_uq[layer] = (u_q, w_scales)
        # Calibration fixes the serving tile geometry — the shape key
        # the block autotuner searches at end_calibration.
        self._tile_geom[layer] = (int(tiles.shape[1]),
                                  int(u_q.shape[1]), int(u_q.shape[2]))
        blocks = self._layer_blocks(pk)
        scales = scales_from_abs_max(amax)
        if hbits is None:
            return execute_int8(tiles, u_q, w_scales, scales, spec=spec,
                                geom=geom, hadamard_bits=None,
                                blocks=blocks)
        y, amax_h = execute_int8(tiles, u_q, w_scales, scales, spec=spec,
                                 geom=geom, hadamard_bits=hbits,
                                 blocks=blocks, with_stats=True)
        self._amax_h[layer] = merge_abs_max(self._amax_h.get(layer), amax_h)
        return y

    # -- prepare / calibrate ------------------------------------------------

    def _certify_layer(self, layer: str, *, cin: int):
        """Pack-time range gate: prove this layer's config safe before
        its weights are packed (see ``certify`` in ``__init__``).

        A *planned* layer is gated unconditionally — the planner only
        emits certifier-proved candidates (``candidate_entries``
        pre-filters), so a plan entry the certifier refuses means the
        plan is corrupted (hand-edited, stale encoding, wrong model):
        raise instead of silently serving or falling back, regardless
        of the ``certify`` knob, which governs only the unplanned
        policy path.
        """
        from repro.analysis.ranges import certify_config
        e = self._plan_entry(layer)
        if e is not None and e.is_winograd:
            rep = certify_config(e.m, e.r, e.base, e.hadamard_bits, cin)
            if rep.proved:
                return
            raise ValueError(
                f"plan contradicts the range certifier for layer "
                f"{layer!r}: {e.describe()} at Cin={cin} is "
                f"{rep.summary()} — the planner only emits proved "
                f"configs (repro.conv.planner.candidate_entries), so "
                f"this plan is corrupted or belongs to another model; "
                f"re-plan instead of overriding")
        if self.certify == "off":
            return
        rep = certify_config(self.spec.m, self.spec.r, self.spec.base,
                             self.hadamard_bits, cin)
        if rep.proved:
            return
        acc = rep.stage("gemm_accumulator")
        msg = (f"layer {layer!r}: {rep.summary()} — worst-case int32 "
               f"accumulator {int(acc.bound)} ({acc.bits:.0f} bits) "
               f"{'overflows int32' if not rep.int32_safe else 'exceeds the fp32-exact limit; the Hadamard requant cast can round'}"
               f". Reduce Cin, split the reduction, or pass "
               f"certify='off' to override.")
        if self.certify == "error":
            raise ValueError(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)

    def prepare_layer(self, layer: str, w: jnp.ndarray, *,
                      stride: int = 1) -> bool:
        """Pack one layer's weights if the policy routes it to int8.

        Returns True when the layer was packed (already-calibrated scales
        for the layer are preserved across a re-pack).
        """
        backend = self.backend_for(layer, kernel_size=w.shape[0],
                                   stride=stride, in_channels=w.shape[2])
        if backend != "winograd_int8":
            return False
        self._certify_layer(layer, cin=w.shape[2])
        old = self.packed.get(layer)
        new = pack_weights(w, self._layer_spec(layer))
        if (old is not None and old.blocks is not None
                and old.u_q.shape == new.u_q.shape):
            # Autotuned blocks depend on the (spec, shape) only — they
            # survive any same-shape re-pack, weight update or not.
            new = dataclasses.replace(new, blocks=old.blocks)
        if old is not None and old.calibrated:
            # in_scales depend only on the input distribution and survive
            # a re-pack; the Hadamard abs-max depends on the weights, so
            # it survives only an *idempotent* re-pack (same packed
            # weights — the old pack is the fingerprint) and is dropped
            # on a real update (dynamic requant until recalibrated).
            new = dataclasses.replace(
                new, in_scales=old.in_scales,
                hadamard_amax=(old.hadamard_amax
                               if _same_packed_weights(old, new) else None))
        elif layer in self._scales:      # calibrated before packing
            # The Hadamard abs-max reattaches only when these are the
            # weights the calibration actually observed — a
            # clear_packed() → prepare(new weights) flow must NOT
            # resurrect a stale weight-dependent statistic (requant
            # against the wrong abs-max would clip the 8/9-bit grid).
            seen = self._calib_uq.get(layer)
            same_w = (seen is not None
                      and _same_packed_weights(
                          PackedWinogradWeights(u_q=seen[0],
                                                w_scales=seen[1]), new))
            new = dataclasses.replace(
                new, in_scales=self._scales[layer],
                hadamard_amax=(self._h_amax_final.get(layer)
                               if same_w else None))
        self.packed[layer] = new
        return True

    def prepare(self, named_weights: Iterable[tuple]) -> list[str]:
        """Pack every int8-routed layer. Items: (layer, w[, stride])."""
        packed = []
        with telemetry.setup_phase("pack"):
            for item in named_weights:
                layer, w, stride = item if len(item) == 3 else (*item, 1)
                if self.prepare_layer(layer, w, stride=stride):
                    packed.append(layer)
        return packed

    def clear_packed(self, calibrations: bool = False):
        """Drop packed weights (stale after a weight update); keep the
        calibrated scales unless ``calibrations`` is also set."""
        self.packed = {}
        if calibrations:
            self._scales = {}
            self._h_amax_final = {}
            self._calib_uq = {}

    @contextlib.contextmanager
    def calibration(self):
        """Record per-layer input statistics; finalize scales on exit.

        Run forwards eagerly inside the block (the engine folds concrete
        abs-maxima into running state, which a jit trace cannot do).
        """
        with telemetry.setup_phase("calibrate"):
            self.begin_calibration()
            try:
                yield self
            finally:
                self.end_calibration()

    def begin_calibration(self):
        self._calibrating = True
        self._amax = {}
        self._amax_h = {}

    def end_calibration(self) -> dict[str, jnp.ndarray]:
        """Finalize: running abs-maxima → per-layer in_scales (and
        Hadamard requant scales when that stage is on).

        Scales are kept for layers not packed yet, so
        calibrate-then-prepare orderings work too.

        With ``autotune=True`` this is also where the Pallas block
        search runs: calibration observed each layer's tile geometry, so
        every packed layer's fused-kernel block split is tuned here —
        once per distinct (spec, shape) — and cached into the packed
        state, riding into ``export_state`` checkpoints.
        """
        self._calibrating = False
        scales = {}
        for layer, amax in self._amax.items():
            s = scales_from_abs_max(amax)
            scales[layer] = s
            self._scales[layer] = s
            hs = None
            if layer in self._amax_h:
                # Stored as the raw abs-max: execute_int8 applies the
                # same in-graph scale formula as the dynamic requant,
                # keeping the two paths bit-identical.
                hs = self._amax_h[layer].reshape(-1, 1)
                self._h_amax_final[layer] = hs
            if layer in self.packed:
                self.packed[layer] = dataclasses.replace(
                    self.packed[layer], in_scales=s, hadamard_amax=hs)
        self._amax = {}
        self._amax_h = {}
        if self.autotune:
            self.autotune_packed()
        return scales

    def autotune_packed(self) -> dict[str, tuple]:
        """Tune the fused-kernel block split of every packed layer whose
        tile geometry calibration recorded; cache each winner in the
        packed state (``PackedWinogradWeights.blocks``).

        Runs automatically from ``end_calibration`` when the engine was
        built with ``autotune=True``; callable directly for an explicit
        re-tune. Identically-shaped layers share one timed search
        (``repro.conv.autotune`` memoises per shape). Returns
        {layer: (bm, bn, bk)}.
        """
        from repro.conv.autotune import autotune_blocks
        tuned = {}
        for layer, geom in self._tile_geom.items():
            pk = self.packed.get(layer)
            if pk is None:
                continue
            res = autotune_blocks(self._layer_spec(layer), *geom,
                                  hadamard_bits=self._layer_hbits(layer),
                                  **self.autotune_opts)
            tuned[layer] = res.blocks
            self.packed[layer] = dataclasses.replace(
                pk, blocks=jnp.asarray(res.blocks, jnp.int32))
        return tuned

    def clear_tuned_blocks(self):
        """Drop every layer's autotuned blocks (serve with the spec
        defaults again) — the tuned-vs-default comparison knob."""
        self.packed = {l: dataclasses.replace(p, blocks=None)
                       for l, p in self.packed.items()}

    # -- serialization ------------------------------------------------------

    def export_state(self) -> dict:
        """Packed+calibrated state as a checkpointable pytree.

        Uncalibrated ``in_scales`` are a hard error (serving would fall
        back to per-call reductions — never ship that silently). A
        missing ``hadamard_amax`` is legal: ``prepare_layer``
        deliberately drops it on a weight update (dynamic requant until
        recalibrated), and it round-trips as a sentinel leaf so the tree
        structure matches ``state_template`` regardless of per-layer
        calibration history.
        """
        missing = [l for l, p in self.packed.items() if not p.calibrated]
        if missing:
            raise ValueError(f"layers not calibrated: {sorted(missing)}")
        state = {"packed": {
            l: p.to_tree(
                include_hadamard=self._layer_hbits(l) is not None)
            for l, p in self.packed.items()}}
        if self.plan is not None:
            # The plan group covers EVERY routed layer (direct entries
            # too): a planned checkpoint fully determines the serving
            # configuration with no policy consultation on restore.
            state["plan"] = self.plan.to_tree()
        return state

    def state_template(self) -> dict:
        """Zero-filled tree matching ``export_state`` — the restore
        skeleton for ``repro.checkpoint.restore`` after ``prepare()``.

        The template carries a ``plan`` group only when this engine
        holds a plan, so a *pre-plan* checkpoint restores into a
        plan-less engine without a named-leaf schema error (the policy
        fallback), while a planned engine round-trips its plan. To
        serve a planned checkpoint without re-running the planner,
        recover the plan first with ``planner.Plan.from_checkpoint``
        and build the engine with it.
        """
        def tmpl(l: str, p: PackedWinogradWeights) -> dict:
            P = p.u_q.shape[0]
            zeros = jnp.zeros((P, 1), jnp.float32)
            t = {"u_q": p.u_q, "w_scales": p.w_scales,
                 "in_scales": p.in_scales if p.calibrated else zeros}
            if self._layer_hbits(l) is not None:
                t["hadamard_amax"] = (p.hadamard_amax
                                        if p.hadamard_amax is not None
                                        else zeros)
            t["blocks"] = (p.blocks if p.blocks is not None
                           else jnp.full((3,), PackedWinogradWeights
                                         .BLOCKS_MISSING, jnp.int32))
            return t
        state = {"packed": {l: tmpl(l, p) for l, p in self.packed.items()}}
        if self.plan is not None:
            state["plan"] = self.plan.to_tree()
        return state

    def import_state(self, tree: dict):
        """Adopt a restored packed+calibrated tree. Under a mesh the
        arrays are first placed across it (``place_packed_state``):
        per-position statistics replicated, and — when the engine has a
        ``model_axis`` — every ``u_q`` sharded along Cout, so each
        device's shard_map slab finds exactly its weight shard local.
        Checkpoints carry full (gathered) arrays, so a state written
        under ANY mesh shape reshards onto this engine's mesh here. A
        tree carrying a ``plan`` group (restored through a planned
        engine's template) makes the checkpoint authoritative: the
        decoded plan replaces whatever plan the engine was built with."""
        if self.mesh is not None:
            tree = place_packed_state(self.mesh, tree,
                                      model_axis=self.model_axis)
        if "plan" in tree:
            from repro.conv.planner import Plan
            self.plan = Plan.from_tree(tree["plan"])
        self.packed = {l: PackedWinogradWeights.from_tree(sub)
                       for l, sub in tree["packed"].items()}
