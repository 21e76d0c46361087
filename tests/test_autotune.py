"""Per-(spec, shape) block autotuner: candidate generation, the timed
search, the ConvEngine(autotune=True) lifecycle with its checkpoint
round-trip, early blocks validation, and block-independence of serving
numerics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import restore, save
from repro.conv import ConvEngine, ConvPolicy
from repro.conv.autotune import (VMEM_BUDGET_BYTES, autotune_blocks,
                                 candidate_blocks, clear_cache)
from repro.core.quantization import QuantConfig
from repro.core.winograd import WinogradSpec
from repro.kernels.ops import execute_int8, winograd_conv2d_int8
from repro.kernels.wino_gemm import (MAX_BLOCK, default_blocks,
                                     validate_blocks)

KEY = jax.random.PRNGKey(0)

#: Cheap search settings for tests — one timed iter, few candidates.
FAST = dict(iters=1, warmup=1, max_candidates=3)


def _spec(m=4, bits=9):
    return WinogradSpec(m=m, r=3, base="legendre",
                        quant=QuantConfig(hadamard_bits=bits))


# -- candidate generation ----------------------------------------------------

def test_candidates_clamped_dedup_and_feasible():
    P, m = 64, 6
    cands = candidate_blocks(P, m, T=128, cin=64, cout=64)
    assert cands and len(set(cands)) == len(cands)
    for bm, bn, bk in cands:
        assert 1 <= bm <= 128 and 1 <= bn <= 64 and 1 <= bk <= 64
        # the VMEM model holds for every candidate except (at most) the
        # always-included default
        scratch = P * bm * bn * 4
        assert scratch <= VMEM_BUDGET_BYTES


def test_candidates_include_spec_default():
    for P, m, T, c in [(36, 4, 200, 128), (64, 6, 50, 16)]:
        d = default_blocks(P)
        clamped = (min(d[0], T), min(d[1], c), min(d[2], c))
        assert clamped in candidate_blocks(P, m, T, c, c)


def test_f63_default_blocks_shrink_scratch():
    """At P = 64 the (128, 128) MXU default would pin a 4 MiB int32
    scratch; the spec default halves bm."""
    assert default_blocks(36) == (128, 128, 256)
    bm, bn, bk = default_blocks(64)
    assert 64 * bm * bn * 4 <= 2 * 1024 * 1024


# -- the timed search --------------------------------------------------------

def test_autotune_picks_a_candidate_and_caches():
    clear_cache()
    spec = _spec(4)
    res = autotune_blocks(spec, 40, 8, 8, hadamard_bits=9, **FAST)
    assert res.blocks in [c for c, _ in res.timings]
    assert res.us <= res.default_us + 1e-9 or res.blocks == res.default_blocks
    assert res.us == res.timings[0][1]
    # memoised: the second call must return the identical result object
    assert autotune_blocks(spec, 40, 8, 8, hadamard_bits=9, **FAST) is res


def test_autotune_blocks_are_numerics_neutral():
    """Serving with any tuned/candidate block split reproduces the
    default-blocks output (integer pipeline exact, fp32 to rounding)."""
    spec = _spec(4)
    x = jax.random.normal(KEY, (2, 16, 16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2
    y_default = winograd_conv2d_int8(x, w, spec, hadamard_bits=9,
                                     fused=True)
    for blocks in [(8, 8, 8), (16, 12, 8)]:
        y = winograd_conv2d_int8(x, w, spec, hadamard_bits=9, fused=True,
                                 blocks=blocks)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_default),
                                   rtol=1e-4, atol=1e-4)


# -- engine lifecycle + checkpoint round-trip --------------------------------

def test_engine_autotune_lifecycle_and_checkpoint_bit_identity(tmp_path):
    """calibrate → autotune → export → restore → serve: the tuned
    (bm, bn, bk) ride the checkpoint and the restored engine serves
    bit-identically to the tuning engine (same compile units, same
    blocks — serving never re-tunes)."""
    spec = _spec(4)
    x = jax.random.normal(KEY, (2, 16, 16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2

    eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                     autotune=True, autotune_opts=FAST)
    eng.prepare([("c", w)])
    with eng.calibration():
        eng.conv2d(x, None, layer="c")
    pk = eng.packed["c"]
    assert pk.blocks is not None
    tuned = pk.block_tuple()
    assert validate_blocks(tuned) == tuned
    y_src = np.asarray(eng.conv2d(x, None, layer="c"))

    save(str(tmp_path), 0, eng.export_state())
    served = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
    served.prepare([("c", w)])
    tree, _ = restore(str(tmp_path), served.state_template())
    served.import_state(tree)
    assert served.packed["c"].block_tuple() == tuned
    y_served = np.asarray(served.conv2d(x, None, layer="c"))
    np.testing.assert_array_equal(y_src, y_served)

    # stripping the tuned blocks serves the spec default — same numbers
    served.clear_tuned_blocks()
    assert served.packed["c"].blocks is None
    y_def = np.asarray(served.conv2d(x, None, layer="c"))
    np.testing.assert_allclose(y_def, y_served, rtol=1e-4, atol=1e-4)


def test_untuned_engine_checkpoint_roundtrips_sentinel(tmp_path):
    """An engine that never autotuned exports the blocks sentinel and
    restores to blocks=None — tuned and untuned checkpoints share one
    tree structure."""
    spec = _spec(4)
    x = jax.random.normal(KEY, (2, 12, 12, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2
    eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
    eng.prepare([("c", w)])
    with eng.calibration():
        eng.conv2d(x, None, layer="c")
    save(str(tmp_path), 0, eng.export_state())
    served = ConvEngine(spec, ConvPolicy(backend="winograd_int8"))
    served.prepare([("c", w)])
    tree, _ = restore(str(tmp_path), served.state_template())
    served.import_state(tree)
    assert served.packed["c"].blocks is None


def test_repack_preserves_tuned_blocks():
    """Blocks depend on the (spec, shape) only, so a weight-update
    re-pack keeps them while (as before) dropping hadamard_amax."""
    spec = _spec(4)
    x = jax.random.normal(KEY, (2, 12, 12, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.2
    eng = ConvEngine(spec, ConvPolicy(backend="winograd_int8"),
                     autotune=True, autotune_opts=FAST)
    eng.prepare([("c", w)])
    with eng.calibration():
        eng.conv2d(x, None, layer="c")
    tuned = eng.packed["c"].block_tuple()
    assert tuned is not None
    eng.prepare([("c", w * 1.7)])               # real weight update
    assert eng.packed["c"].hadamard_amax is None
    assert eng.packed["c"].block_tuple() == tuned


# -- early blocks validation -------------------------------------------------

@pytest.mark.parametrize("bad", [
    (0, 8, 8), (8, -1, 8), (8, 8), (8, 8, 8, 8), (8, 8, MAX_BLOCK + 1),
    ("a", 8, 8), (8.0, 8, 8), 7,
])
def test_bad_blocks_rejected_at_engine_and_execute(bad):
    spec = _spec(4)
    with pytest.raises(ValueError):
        ConvEngine(spec, blocks=bad)
    x = jax.random.normal(KEY, (1, 8, 8, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 4)) * 0.2
    with pytest.raises(ValueError):
        winograd_conv2d_int8(x, w, spec, hadamard_bits=9, blocks=bad)


def test_valid_blocks_pass_validation():
    assert validate_blocks(None) is None
    assert validate_blocks((8, 16, 32)) == (8, 16, 32)
    assert validate_blocks([np.int64(8), 16, 32]) == (8, 16, 32)
