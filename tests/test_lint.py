"""The hazard linter's rules, fixtures, and the clean-tree contract
(repro.analysis.lint)."""
import textwrap
from pathlib import Path

import pytest

from repro.analysis.lint import RULES, lint_file, lint_paths, lint_source

REPO = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).parent / "lint_fixtures"


def _rules(findings, waived=False):
    return sorted(f.rule for f in findings if f.waived == waived)


# ---------------------------------------------------------------------------
# fixture corpus: one known-bad snippet per rule class
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture,rule", [
    ("bad_jit_flavor.py", "jit-arg-flavor"),
    ("bad_shard_map_flavor.py", "jit-arg-flavor"),
    ("bad_cached_arrays.py", "cached-array-args"),
    ("bad_unsynced_timing.py", "unsynced-timing"),
])
def test_fixture_flags_exactly_its_hazard(fixture, rule):
    findings = lint_file(FIXTURES / fixture)
    assert _rules(findings) == [rule]


def test_library_import_fixture_flags_only_as_library_code():
    src = (FIXTURES / "bad_library_import.py").read_text()
    assert _rules(lint_source(src, "x.py", is_repro=True)) == \
        ["repro-imports-benchmarks"]
    # the same import from harness code is the sanctioned direction
    assert not lint_source(src, "x.py", is_repro=False)


def test_near_miss_corpus_is_clean():
    findings = lint_file(FIXTURES / "clean_near_misses.py")
    assert not [f for f in findings if not f.waived]
    # ...including its one deliberately-waived window
    assert _rules(findings, waived=True) == ["unsynced-timing"]


# ---------------------------------------------------------------------------
# rule behavior details
# ---------------------------------------------------------------------------

def test_mixed_flavors_within_one_call_flagged():
    src = textwrap.dedent("""
        import jax, numpy as np
        @jax.jit
        def f(a, b):
            return a + b
        f(np.ones(3), jax.device_put(np.ones(3)))
    """)
    assert _rules(lint_source(src)) == ["jit-arg-flavor"]


def test_jit_assignment_form_is_tracked():
    src = textwrap.dedent("""
        import jax, numpy as np
        def f(a):
            return a
        g = jax.jit(f)
        g(np.ones(3))
        g(jax.device_put(np.ones(3)))
    """)
    assert _rules(lint_source(src)) == ["jit-arg-flavor"]


def test_shard_map_wrapped_callable_is_tracked():
    # The sharded serving executor idiom: a shard_map(_compat)-wrapped
    # body dispatches like a jitted callable, so cross-call-site flavor
    # mixing is the same hazard.
    src = textwrap.dedent("""
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        def f(a):
            return a
        g = jax.shard_map(f, mesh=None, in_specs=P(), out_specs=P())
        g(np.ones(3))
        g(jax.device_put(np.ones(3)))
    """)
    assert _rules(lint_source(src)) == ["jit-arg-flavor"]
    # single-flavor call sites stay clean
    src_ok = textwrap.dedent("""
        import jax, numpy as np
        from jax import shard_map
        def f(a):
            return a
        g = shard_map(f, mesh=None, in_specs=(), out_specs=())
        g(jax.device_put(np.ones(3)))
        g(jax.device_put(np.zeros(3)))
    """)
    assert not _rules(lint_source(src_ok))


def test_cached_function_with_hashable_annotations_passes():
    src = textwrap.dedent("""
        import functools
        @functools.lru_cache(maxsize=None)
        def mats(m: int, base: str) -> tuple:
            return (m, base)
    """)
    assert not lint_source(src)


def test_cached_function_with_arrayish_annotation_flagged():
    src = textwrap.dedent("""
        import functools
        import numpy as np
        @functools.lru_cache(maxsize=None)
        def gram(x: np.ndarray):
            return x @ x.T
    """)
    assert _rules(lint_source(src)) == ["cached-array-args"]


def test_local_sync_wrapper_counts_as_barrier():
    src = textwrap.dedent("""
        import time
        def _block(y):
            return y.block_until_ready()
        def bench(f, x):
            t0 = time.perf_counter()
            _block(f(x))
            return time.perf_counter() - t0
    """)
    assert not lint_source(src)


def test_module_level_timing_window_flagged():
    src = textwrap.dedent("""
        import time
        t0 = time.perf_counter()
        work()
        dt = time.perf_counter() - t0
    """)
    assert _rules(lint_source(src)) == ["unsynced-timing"]


def test_waiver_on_enclosing_def_line():
    src = textwrap.dedent("""
        import time
        def bench(f, x):  # lint: waive=unsynced-timing
            t0 = time.perf_counter()
            f(x)
            return time.perf_counter() - t0
    """)
    findings = lint_source(src)
    assert not [f for f in findings if not f.waived]
    assert _rules(findings, waived=True) == ["unsynced-timing"]


def test_waiver_is_rule_specific():
    src = textwrap.dedent("""
        import time
        def bench(f, x):  # lint: waive=cached-array-args
            t0 = time.perf_counter()
            f(x)
            return time.perf_counter() - t0
    """)
    assert _rules(lint_source(src)) == ["unsynced-timing"]


# ---------------------------------------------------------------------------
# the tree contract: make lint is green
# ---------------------------------------------------------------------------

def test_src_and_benchmarks_have_zero_unwaived_findings():
    findings = lint_paths([REPO / "src", REPO / "benchmarks"])
    active = [f for f in findings if not f.waived]
    assert not active, "\n".join(str(f) for f in active)


def test_rule_catalog_is_stable():
    # docs/analysis.md documents exactly these rules
    assert RULES == ("jit-arg-flavor", "cached-array-args",
                     "unsynced-timing", "repro-imports-benchmarks")
