"""The system under test, built the way the serving launcher builds it.

The architecture module's ``program(cfg)`` gives the program's model module
and its config; then engine → prepare (pack) → calibrate → checkpoint →
restore through ``repro.launch.serve.make_served_engine`` → the model's
``serving_forward``, the jitted forward that ``repro.serving.ServingLoop``
drives. Weights and calibration images come from the benchmark
(``weights.py``), not from the program's fixed keys.
"""
from __future__ import annotations

import time

import jax
from jax.profiler import TraceAnnotation

from repro.checkpoint.checkpoint import save
from repro.launch import serve


class Served:
    """The served model: ``forward`` is what the loop calls, ``jitted`` the
    program's own jitted forward (its compile count is read there)."""

    def __init__(self, arch, cfg: dict, params, state, calibration,
                 ckpt_dir: str, log=print):
        self.split = {}
        model, rc = arch.program(cfg)
        t0 = time.perf_counter()
        engine = model.make_engine(rc, backend="winograd_int8")
        packed = engine.prepare(model.conv_layers(params, rc))
        jax.block_until_ready([p.u_q for p in engine.packed.values()])
        self._lap("pack", t0)
        t0 = time.perf_counter()
        with engine.calibration():
            for batch in calibration:
                jax.block_until_ready(model.forward(
                    params, state, batch, rc, training=False,
                    engine=engine)[0])
        self._lap("calibrate", t0)
        t0 = time.perf_counter()
        save(ckpt_dir, 0, engine.export_state())
        args = serve.build_parser().parse_args(["--ckpt-dir", ckpt_dir])
        self.engine = serve.make_served_engine(args, rc,
                                               engine.state_template())
        self._lap("checkpoint_restore", t0)
        self.int8_layers = len(packed)
        self.jitted = model.serving_forward(params, state, rc, self.engine)
        self.engine.serve_fn = self.jitted
        log(f"set-up: {self.int8_layers} int8 Winograd layers; "
            + ", ".join(f"{k} {v:.3f}s" for k, v in self.split.items()))

    def _lap(self, name: str, t0: float):
        self.split[name] = time.perf_counter() - t0

    def forward(self, x):
        with TraceAnnotation("chipbench.dispatch"):
            return self.jitted(x)

    def compiles(self) -> int:
        return int(self.jitted._cache_size())
