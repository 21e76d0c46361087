"""Multi-device distribution tests.

These need >1 device, so each test execs a fresh interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (conftest must NOT
set this globally — smoke tests and benches see 1 device, per the brief).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_ENV = {**os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": "src"}


def _run(body: str, timeout=420):
    code = textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code], env=_ENV,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    """A dense tiny model trains identically (loss curve) on a 4×2 mesh
    and on a single device — SPMD correctness end-to-end."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS, tiny_variant
        from repro.configs.base import RunConfig
        from repro.data.pipeline import batch_at
        from repro.launch.steps import make_train_setup, init_train_state

        cfg = tiny_variant(ARCHS["llama3.2-1b"])
        run = RunConfig(model=cfg, seq_len=32, global_batch=8,
                        total_steps=10, warmup_steps=1)

        losses = {}
        for shape, axes in [((4, 2), ("data", "model")),
                            ((1, 1), ("data", "model"))]:
            devs = jax.devices()[: shape[0] * shape[1]]
            import numpy as np
            mesh = jax.sharding.Mesh(
                np.array(devs).reshape(shape), axes)
            with mesh:
                setup = make_train_setup(run, mesh, False)
                params, opt = init_train_state(run, setup, 0)
                ls = []
                for step in range(3):
                    batch = batch_at(cfg, 32, 8, step)
                    params, opt, m = setup.step_fn(params, opt, batch,
                                                   jnp.int32(step))
                    ls.append(float(m["loss"]))
                losses[shape] = ls
        a, b = losses[(4, 2)], losses[(1, 1)]
        for x, y in zip(a, b):
            assert abs(x - y) < 5e-2, (a, b)
        print("OK", a)
    """)
    assert "OK" in out


def test_microbatched_matches_full_batch_grads():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS, tiny_variant
        from repro.configs.base import RunConfig
        from repro.data.pipeline import batch_at
        from repro.launch.steps import _loss_with_microbatch
        from repro.distributed.sharding import rules
        from repro.models import registry
        from repro.models.param import init_params

        cfg = tiny_variant(ARCHS["llama3.2-1b"])
        model = registry.get_model(cfg)
        params = init_params(model.param_specs(cfg), jax.random.PRNGKey(0))
        batch = batch_at(cfg, 32, 8, 0)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rm = rules(False, False)
        with mesh:
            run_full = RunConfig(model=cfg, seq_len=32, global_batch=8)
            run_micro = RunConfig(model=cfg, seq_len=32, global_batch=8,
                                  microbatch=2)
            lf = _loss_with_microbatch(model, cfg, run_full, mesh, rm)
            lm = _loss_with_microbatch(model, cfg, run_micro, mesh, rm)
            (l1, g1) = jax.jit(lf)(params, batch)
            (l2, g2) = jax.jit(lm)(params, batch)
        assert abs(float(l1) - float(l2)) < 1e-2, (float(l1), float(l2))
        flat1 = jax.tree.leaves(g1)
        flat2 = jax.tree.leaves(g2)
        err = max(float(jnp.abs(a - b).max()) for a, b in zip(flat1, flat2))
        assert err < 0.1, err
        print("OK", float(l1), float(l2), err)
    """)
    assert "OK" in out


def test_grad_compression_ring_allreduce():
    """int8 ring all-reduce over a 2-pod axis: mean matches fp within the
    quantization bound; error feedback captures the residual; the HLO
    contains s8 collective-permutes (the compressed traffic)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import (compressed_grad_mean,
                                                   init_error_state)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))

        g_global = jax.random.normal(jax.random.PRNGKey(0), (2, 64))

        def f(g):
            grads = {"w": g[0] if False else g}
            # inside shard_map over pod: g arrives per-pod (1, 64)
            grads = {"w": g.reshape(64)}
            errs = {"w": jnp.zeros(64)}
            out, err = compressed_grad_mean(grads, errs, 2)
            return out["w"], err["w"]

        def sm(f):
            return jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                 out_specs=(P(), P("pod")),
                                 axis_names={"pod"}, check_vma=False)

        fn = jax.jit(sm(f))
        mean, err = fn(g_global)
        expect = np.asarray(g_global).mean(0)
        got = np.asarray(mean)
        assert np.abs(got - expect).max() < 0.05, np.abs(got-expect).max()
        hlo = jax.jit(sm(f)).lower(
            jax.ShapeDtypeStruct((2, 64), jnp.float32)).compile().as_text()
        assert "collective-permute" in hlo
        assert "s8[" in hlo, "compressed payload must be int8"
        print("OK", np.abs(got - expect).max())
    """)
    assert "OK" in out


def test_multipod_mesh_and_fsdp_sharding():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh_for
        mesh = make_mesh_for(8, model_parallel=2, chips_per_pod=4)
        assert mesh.axis_names == ("pod", "data", "model")
        assert dict(mesh.shape) == {"pod": 2, "data": 2, "model": 2}

        from repro.configs import ARCHS, tiny_variant
        from repro.configs.base import RunConfig
        from repro.data.pipeline import batch_at
        from repro.launch.steps import make_train_setup, init_train_state
        cfg = tiny_variant(ARCHS["qwen2-moe-a2.7b"])
        run = RunConfig(model=cfg, seq_len=32, global_batch=8, fsdp=True)
        with mesh:
            setup = make_train_setup(run, mesh, True)
            params, opt = init_train_state(run, setup, 0)
            batch = batch_at(cfg, 32, 8, 0)
            params, opt, m = setup.step_fn(params, opt, batch,
                                           jnp.int32(0))
            assert jnp.isfinite(m["loss"])
        print("OK", float(m["loss"]))
    """)
    assert "OK" in out


def test_sharded_fused_serving_parity():
    """The tentpole contract of sharded int8 serving: on 1/2/4-device CPU
    meshes, ``execute_int8_sharded`` is **bitwise identical** to the
    single-device fused kernel composition (input_transform →
    fused_gemm_output → reassemble on the full tile tensor) across
    F(2,3)/F(4,3) × canonical/legendre × hadamard_bits 8/9 — per-tile
    arithmetic is untouched by the tile-axis shard_map. The Hadamard
    integer domain is additionally checked exactly via the wino_gemm
    requant epilogue on per-device slabs vs the global plane."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.quantization import QuantConfig, qmax
        from repro.core.winograd import WinogradSpec, make_matrices
        from repro.kernels.fused_serve import fused_gemm_output
        from repro.kernels.ops import (_extract, _geometry, _reassemble,
                                       _tiles_abs_max, execute_int8,
                                       execute_int8_sharded,
                                       prepare_weights_int8,
                                       scales_from_abs_max)
        from repro.kernels.wino_gemm import wino_gemm
        from repro.kernels.wino_transform import input_transform

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 12, 4))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 6)) * 0.2
        for m in (2, 4):
            for base in ("canonical", "legendre"):
                for bits in (8, 9):
                    spec = WinogradSpec(m=m, r=3, base=base,
                                        quant=QuantConfig(
                                            hadamard_bits=bits))
                    mats = make_matrices(spec)
                    u_q, w_s = prepare_weights_int8(w, spec)
                    tiles = _extract(x, m, 3, spec.n, "same")
                    geom = _geometry(x.shape, m, 3, "same")
                    in_s = scales_from_abs_max(_tiles_abs_max(tiles, spec))
                    _, amax = execute_int8(
                        tiles, u_q, w_s, in_s, spec=spec, geom=geom,
                        hadamard_bits=bits,
                        with_stats=True)
                    h_amax = amax.reshape(-1, 1)
                    deq = in_s * w_s
                    rq = jnp.maximum(h_amax, 1e-12) / qmax(bits)
                    Xq = input_transform(tiles, mats.CinvT, mats.BPT,
                                         in_s,
                                         changes_base=spec.changes_base)
                    # single-device fused kernel on the full tile tensor
                    ref = np.asarray(_reassemble(fused_gemm_output(
                        Xq, u_q, deq, rq, mats.CinvT, mats.APT, m=m,
                        requant_bits=bits,
                        changes_base=spec.changes_base), geom, m))
                    for d in (1, 2, 4):
                        mesh = Mesh(np.array(jax.devices()[:d]),
                                    ("data",))
                        y = np.asarray(execute_int8_sharded(
                            tiles, u_q, w_s, in_s, h_amax, spec=spec,
                            geom=geom, mesh=mesh, hadamard_bits=bits))
                        assert np.array_equal(y, ref), \\
                            (m, base, bits, d, np.abs(y - ref).max())
                    # Hadamard-domain integers: per-slab GEMM+requant
                    # epilogue == the matching slice of the global plane
                    H = np.asarray(wino_gemm(Xq, u_q,
                                             requant_bits=bits, deq=deq,
                                             rq=rq))
                    T = Xq.shape[1]
                    for d in (2, 4):
                        parts = [np.asarray(wino_gemm(
                            Xq[:, i * T // d:(i + 1) * T // d], u_q,
                            requant_bits=bits, deq=deq,
                            rq=rq)) for i in range(d)]
                        assert np.array_equal(
                            np.concatenate(parts, axis=1), H), \\
                            (m, base, bits, d)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_export_restore_serve_under_mesh():
    """The full serving lifecycle under a mesh: calibrate+pack on one
    engine, checkpoint, restore into mesh-backed engines
    (``import_state`` replicates the packed state), and serve — sharded
    outputs BITWISE identical across 1/2/4-device meshes AND to the
    single-device fused engine. The second equality is the one-Xq fix:
    every mode now quantizes the input through the same compile unit
    and dispatches the same kernel jits, so the old quantization-noise
    allowance (a rounding-boundary input flipping across XLA programs,
    docs/parity.md) tightened to the bitwise tier."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.checkpoint.checkpoint import restore, save
        from repro.conv import ConvEngine, ConvPolicy
        from repro.conv.packing import packed_tree_shardings
        from repro.core.quantization import QuantConfig
        from repro.core.winograd import WinogradSpec
        import tempfile

        spec = WinogradSpec(m=4, r=3, base="legendre",
                            quant=QuantConfig(hadamard_bits=9))
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 8))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2

        src = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
        src.prepare([("c", w)])
        with src.calibration():
            src.conv2d(x, w, layer="c")
        ckpt = tempfile.mkdtemp()
        save(ckpt, 0, src.export_state())
        y_fused = np.asarray(src.conv2d(x, None, layer="c"))

        ys = {}
        for d in (1, 2, 4):
            mesh = Mesh(np.array(jax.devices()[:d]), ("data",))
            eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                             mesh=mesh)
            eng.prepare([("c", w)])
            tree, _ = restore(ckpt, eng.state_template())
            eng.import_state(tree)
            # the restored packed state is replicated across the mesh
            shd = packed_tree_shardings(mesh, eng.state_template())
            for name, arr in [("u_q", eng.packed["c"].u_q),
                              ("in_scales", eng.packed["c"].in_scales)]:
                want = shd["packed"]["c"][name]
                assert arr.sharding.is_equivalent_to(want, arr.ndim), \\
                    (d, name, arr.sharding)
            ys[d] = np.asarray(eng.conv2d(x, None, layer="c"))
        assert np.array_equal(ys[1], ys[2]) and \\
            np.array_equal(ys[1], ys[4])
        # the one-Xq tier: sharded == single-device fused, bitwise
        assert np.array_equal(ys[1], y_fused)
        print("OK")
    """)
    assert "OK" in out


def test_one_xq_across_modes_and_f63_sharded():
    """The headline Xq fix, asserted across every serving mode — plus
    the F(6,3) sharded case. ``execute_int8`` (staged AND fused), the
    standalone kernel composition and ``execute_int8_sharded`` on a
    2-device mesh all consume byte-identical Xq (one
    ``quantize_input`` compile unit), and the fused/sharded/composition
    outputs are bitwise equal — for F(4,3) and F(6,3) × canonical/
    legendre with 9-bit Hadamard requant."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.quantization import QuantConfig, qmax
        from repro.core.winograd import WinogradSpec, make_matrices
        from repro.kernels.fused_serve import fused_gemm_output
        from repro.kernels.ops import (_extract, _geometry, _reassemble,
                                       _tiles_abs_max, execute_int8,
                                       execute_int8_sharded,
                                       prepare_weights_int8,
                                       quantize_input,
                                       scales_from_abs_max)

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 12, 4))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 6)) * 0.2
        for m in (4, 6):
            for base in ("canonical", "legendre"):
                spec = WinogradSpec(m=m, r=3, base=base,
                                    quant=QuantConfig(hadamard_bits=9))
                mats = make_matrices(spec)
                u_q, w_s = prepare_weights_int8(w, spec)
                tiles = _extract(x, m, 3, spec.n, "same")
                geom = _geometry(x.shape, m, 3, "same")
                in_s = scales_from_abs_max(_tiles_abs_max(tiles, spec))
                _, amax = execute_int8(
                    tiles, u_q, w_s, in_s, spec=spec, geom=geom,
                    hadamard_bits=9, with_stats=True)
                h_amax = amax.reshape(-1, 1)
                # the one compile unit every mode dispatches
                Xq = quantize_input(tiles, in_s, spec=spec)
                deq = in_s * w_s
                rq = jnp.maximum(h_amax, 1e-12) / qmax(9)
                ref = np.asarray(_reassemble(fused_gemm_output(
                    Xq, u_q, deq, rq, mats.CinvT, mats.APT, m=m,
                    requant_bits=9, changes_base=spec.changes_base), geom, m))
                y_fused = np.asarray(execute_int8(
                    tiles, u_q, w_s, in_s, h_amax, spec=spec, geom=geom,
                    hadamard_bits=9, fused=True))
                assert np.array_equal(y_fused, ref), (m, base)
                mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
                y_sh = np.asarray(execute_int8_sharded(
                    tiles, u_q, w_s, in_s, h_amax, spec=spec, geom=geom,
                    mesh=mesh, hadamard_bits=9))
                assert np.array_equal(y_sh, ref), (m, base)
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_cell_on_test_mesh():
    """The dry-run path itself (lower→compile→analysis) on an 8-device
    mesh — exercises the exact production code with a small mesh."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS, tiny_variant
        from repro.configs.base import RunConfig
        from repro.launch.steps import make_serve_setup
        from repro.analysis.hlo_cost import analyze_hlo
        cfg = tiny_variant(ARCHS["recurrentgemma-2b"])
        run = RunConfig(model=cfg, seq_len=64, global_batch=4)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        with mesh:
            setup = make_serve_setup(run, mesh, False, "decode")
            lowered = setup.step_fn.lower(
                setup.abstract["params"], setup.abstract["cache"],
                setup.abstract["tokens"], setup.abstract["pos"])
            compiled = lowered.compile()
            cost = analyze_hlo(compiled.as_text())
            assert cost.flops > 0
            mem = compiled.memory_analysis()
            assert mem is not None
        print("OK", cost.flops)
    """)
    assert "OK" in out


def test_planned_checkpoint_restores_into_mesh_engine():
    """Checkpoint schema growth under a mesh: a heterogeneous per-layer
    plan (winograd F(2,3)+F(4,3) mixed with a planned-direct layer)
    rides the checkpoint as the ``plan`` leaf group, is recovered
    template-free (``Plan.from_checkpoint``) and restored into a
    2-device mesh engine — serving output bitwise identical to the
    single-device planned engine for every layer, including the
    planned-direct one."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.checkpoint.checkpoint import restore, save
        from repro.conv import ConvEngine, ConvPolicy, Plan, PlanEntry
        from repro.core.quantization import QuantConfig
        from repro.core.winograd import WinogradSpec
        import tempfile

        spec = WinogradSpec(m=4, r=3, base="legendre",
                            quant=QuantConfig(hadamard_bits=9))
        plan = Plan({
            "a": PlanEntry("winograd_int8", m=2, r=3, base="canonical",
                           hadamard_bits=8),
            "b": PlanEntry("winograd_int8", m=4, r=3, base="legendre",
                           hadamard_bits=9),
            "d": PlanEntry("direct"),
        })
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 12, 4))
        ws = {n: jax.random.normal(jax.random.PRNGKey(i + 1),
                                   (3, 3, 4, 6)) * 0.2
              for i, n in enumerate("abd")}

        src = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                         plan=plan)
        src.prepare(ws.items())
        assert set(src.packed) == {"a", "b"}   # planned-direct unpacked
        with src.calibration():
            for n, w in ws.items():
                src.conv2d(x, w, layer=n)
        ckpt = tempfile.mkdtemp()
        save(ckpt, 0, src.export_state())
        y1 = {n: np.asarray(src.conv2d(x, ws[n], layer=n)) for n in ws}

        got = Plan.from_checkpoint(ckpt)
        assert got == plan, (got, plan)

        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        dst = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                         mesh=mesh, plan=got)
        dst.prepare(ws.items())
        tree, _ = restore(ckpt, dst.state_template())
        dst.import_state(tree)
        for n in ws:
            y2 = np.asarray(dst.conv2d(x, ws[n], layer=n))
            assert np.array_equal(y1[n], y2), n
        # round-trip the restored engine's state: bitwise stable
        t2, _ = restore(ckpt, dst.state_template())
        for l1, l2 in zip(jax.tree.leaves(tree), jax.tree.leaves(t2)):
            assert np.array_equal(np.asarray(l1), np.asarray(l2))
        print("OK")
    """)
    assert "OK" in out


def test_tp_axis_extent_and_cout_divisibility():
    """Satellite contracts of conv tensor parallelism: ``axis_extent``
    reads any 1×1/2×1/1×2/2×2 mesh (absent axes and None count as
    extent 1, tuples multiply), and a Cout the model axis does not
    divide is a loud error naming the offending packed leaf — never a
    silent replication that would desynchronize placement from the
    executor's per-device slab slicing."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.conv.packing import packed_tree_shardings
        from repro.distributed.sharding import axis_extent

        for dd, dm in ((1, 1), (2, 1), (1, 2), (2, 2)):
            mesh = Mesh(np.array(jax.devices()[:dd * dm]).reshape(dd, dm),
                        ("data", "model"))
            assert axis_extent(mesh, "data") == dd, (dd, dm)
            assert axis_extent(mesh, "model") == dm, (dd, dm)
            assert axis_extent(mesh, None) == 1
            assert axis_extent(mesh, "absent") == 1
            assert axis_extent(mesh, ("data", "model")) == dd * dm
        # 1-D legacy mesh: the model axis simply does not exist
        mesh1 = Mesh(np.array(jax.devices()[:2]), ("data",))
        assert axis_extent(mesh1, "model") == 1

        # Cout=6 is not divisible by a model axis of extent 4
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4),
                    ("data", "model"))
        tree = {"packed": {"c": {
            "u_q": jnp.zeros((16, 4, 6), jnp.int8),
            "w_scales": jnp.ones((16, 1)),
            "in_scales": jnp.ones((16, 1)),
        }}}
        try:
            packed_tree_shardings(mesh, tree, model_axis="model")
        except ValueError as e:
            assert "packed/c/u_q" in str(e), e
            assert "Cout=6" in str(e), e
        else:
            raise AssertionError("non-divisible Cout must raise")
        # the same tree is fine on a model axis that divides 6
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                    ("data", "model"))
        shd = packed_tree_shardings(mesh, tree, model_axis="model")
        assert shd["packed"]["c"]["u_q"] is not None
        print("OK")
    """)
    assert "OK" in out


def test_tp_reshard_on_restore():
    """A checkpoint written on ONE device restores onto a 2×2
    (data × model) mesh with every ``u_q`` cout-sharded (half the
    packed bytes per device), the per-position statistics replicated —
    and the TP engine's serving output bitwise identical to the
    single-device fused engine that wrote the checkpoint."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.checkpoint.checkpoint import restore, save
        from repro.conv import ConvEngine, ConvPolicy
        from repro.conv.packing import packed_tree_shardings
        from repro.core.quantization import QuantConfig
        from repro.core.winograd import WinogradSpec
        import tempfile

        spec = WinogradSpec(m=4, r=3, base="legendre",
                            quant=QuantConfig(hadamard_bits=9))
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 8))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2

        src = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
        src.prepare([("c", w)])
        with src.calibration():
            src.conv2d(x, w, layer="c")
        ckpt = tempfile.mkdtemp()
        save(ckpt, 0, src.export_state())
        y_ref = np.asarray(src.conv2d(x, None, layer="c"))

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("data", "model"))
        eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                         mesh=mesh, model_axis="model")
        eng.prepare([("c", w)])
        shd = packed_tree_shardings(mesh, eng.state_template(),
                                    model_axis="model")
        tree, _ = restore(ckpt, eng.state_template(), shardings=shd)
        eng.import_state(tree)

        pk = eng.packed["c"]
        # u_q: (P, Cin, Cout=12) sharded to (P, Cin, 6) per device
        shards = pk.u_q.addressable_shards
        assert {s.data.shape[-1] for s in shards} == {6}, \\
            [s.data.shape for s in shards]
        # per-position stats: replicated (full shape on every device)
        assert all(s.data.shape == pk.in_scales.shape
                   for s in pk.in_scales.addressable_shards)
        y_tp = np.asarray(eng.conv2d(x, None, layer="c"))
        assert np.array_equal(y_tp, y_ref)
        print("OK")
    """)
    assert "OK" in out


def test_tp_2d_sharded_parity_sweep():
    """The tentpole acceptance sweep: 2-D (data × model) sharded serving
    is BITWISE equal to the single-device fused composition for
    calibrated layers across F(2,3)/F(4,3) × canonical/legendre ×
    hadamard_bits {None, 8, 9} on 1-, 2- and 4-device meshes — and the
    sharded DYNAMIC requant (per-shard |·|max + one ``lax.pmax``) is
    exactly equal to the single-device dynamic staged path. The
    max-of-maxima is the true global max, so dynamic TP serving is not
    an approximation."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.quantization import QuantConfig
        from repro.core.winograd import WinogradSpec
        from repro.kernels.ops import (_extract, _geometry,
                                       _tiles_abs_max, execute_int8,
                                       execute_int8_sharded,
                                       prepare_weights_int8,
                                       scales_from_abs_max)

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 12, 4))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 8)) * 0.2
        meshes = ((1, 1), (2, 1), (1, 2), (2, 2))
        for m in (2, 4):
            for base in ("canonical", "legendre"):
                spec0 = WinogradSpec(m=m, r=3, base=base)
                u_q, w_s = prepare_weights_int8(w, spec0)
                tiles = _extract(x, m, 3, spec0.n, "same")
                geom = _geometry(x.shape, m, 3, "same")
                in_s = scales_from_abs_max(_tiles_abs_max(tiles, spec0))
                for bits in (None, 8, 9):
                    spec = WinogradSpec(m=m, r=3, base=base,
                                        quant=QuantConfig(
                                            hadamard_bits=bits))
                    h_amax = None
                    if bits is not None:
                        _, amax = execute_int8(
                            tiles, u_q, w_s, in_s, spec=spec, geom=geom,
                            hadamard_bits=bits,
                            with_stats=True)
                        h_amax = amax.reshape(-1, 1)
                    ref = np.asarray(execute_int8(
                        tiles, u_q, w_s, in_s, h_amax, spec=spec,
                        geom=geom, hadamard_bits=bits, fused=True))
                    ref_dyn = None
                    if bits is not None:
                        ref_dyn = np.asarray(execute_int8(
                            tiles, u_q, w_s, in_s, None, spec=spec,
                            geom=geom, hadamard_bits=bits))
                    for dd, dm in meshes:
                        mesh = Mesh(np.array(
                            jax.devices()[:dd * dm]).reshape(dd, dm),
                            ("data", "model"))
                        y = np.asarray(execute_int8_sharded(
                            tiles, u_q, w_s, in_s, h_amax, spec=spec,
                            geom=geom, mesh=mesh, hadamard_bits=bits,
                            model_axis="model"))
                        assert np.array_equal(y, ref), \\
                            ("calibrated", m, base, bits, dd, dm,
                             np.abs(y - ref).max())
                        if bits is not None:
                            yd = np.asarray(execute_int8_sharded(
                                tiles, u_q, w_s, in_s, None, spec=spec,
                                geom=geom, mesh=mesh, hadamard_bits=bits,
                            model_axis="model"))
                            assert np.array_equal(yd, ref_dyn), \\
                                ("dynamic", m, base, bits, dd, dm,
                                 np.abs(yd - ref_dyn).max())
        print("OK")
    """, timeout=560)
    assert "OK" in out


def test_tp_f63_and_small_slab_regression():
    """F(6,3) through the 2-D TP executor (both bases, 9-bit requant,
    2×2 mesh) — plus the small-slab regression: a (4, 2) mesh leaves
    each device a 5-row tile slab, which once compiled the output
    transform at a different pallas grid shape than the full-tensor
    reference and broke dynamic exactness in the last fp32 bit
    (fixed by the transform's shape-stability contract; see
    ``wino_transform.output_transform``)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.quantization import QuantConfig
        from repro.core.winograd import WinogradSpec
        from repro.kernels.ops import (_extract, _geometry,
                                       _tiles_abs_max, execute_int8,
                                       execute_int8_sharded,
                                       prepare_weights_int8,
                                       scales_from_abs_max)

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 12, 4))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 8)) * 0.2

        cases = ([(6, base, 9, (2, 2)) for base in
                  ("canonical", "legendre")]
                 + [(4, "legendre", 8, (4, 2))])
        for m, base, bits, (dd, dm) in cases:
            spec = WinogradSpec(m=m, r=3, base=base,
                                quant=QuantConfig(hadamard_bits=bits))
            u_q, w_s = prepare_weights_int8(w, spec)
            tiles = _extract(x, m, 3, spec.n, "same")
            geom = _geometry(x.shape, m, 3, "same")
            in_s = scales_from_abs_max(_tiles_abs_max(tiles, spec))
            _, amax = execute_int8(tiles, u_q, w_s, in_s, spec=spec,
                                   geom=geom, hadamard_bits=bits,
                                   with_stats=True)
            h_amax = amax.reshape(-1, 1)
            ref = np.asarray(execute_int8(
                tiles, u_q, w_s, in_s, h_amax, spec=spec, geom=geom,
                hadamard_bits=bits, fused=True))
            ref_dyn = np.asarray(execute_int8(
                tiles, u_q, w_s, in_s, None, spec=spec, geom=geom,
                hadamard_bits=bits))
            mesh = Mesh(np.array(jax.devices()[:dd * dm]).reshape(dd, dm),
                        ("data", "model"))
            y = np.asarray(execute_int8_sharded(
                tiles, u_q, w_s, in_s, h_amax, spec=spec, geom=geom,
                mesh=mesh, hadamard_bits=bits,
                model_axis="model"))
            assert np.array_equal(y, ref), (m, base, bits, dd, dm)
            yd = np.asarray(execute_int8_sharded(
                tiles, u_q, w_s, in_s, None, spec=spec, geom=geom,
                mesh=mesh, hadamard_bits=bits,
                model_axis="model"))
            assert np.array_equal(yd, ref_dyn), (m, base, bits, dd, dm)
        print("OK")
    """, timeout=560)
    assert "OK" in out
