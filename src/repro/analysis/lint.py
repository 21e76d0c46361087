"""Repo-specific static hazard linter for the JAX serving codebase.

Generic linters cannot see the failure modes that actually shipped here;
each rule below encodes one bug class this repo hit (or narrowly dodged)
and its post-mortem:

``jit-arg-flavor``
    A jitted callable invoked with *mixed argument flavors* — raw
    ``numpy`` arrays at one call site, ``jax.device_put``/``jnp`` arrays
    at another. Functionally identical, but each flavor populates its
    own entry in jit's C++ fast-path cache and retriggers dispatch work;
    in the serving batcher this silently doubled pre-compiled geometry
    warmup (the PR-6 bucket-executor bug). All call sites of one jitted
    function should commit to one flavor. ``shard_map``-wrapped
    callables (``jax.shard_map``) are tracked the same way
    — the sharded serving executor is exactly such a callable, and its
    dispatch cache doubles identically.

``cached-array-args``
    ``functools.lru_cache``/``cache`` (or a memo decorator) on a
    function that may take array arguments. Arrays are unhashable at
    best; under ``jit`` tracing they are *tracers*, and caching a tracer
    leaks it out of its trace — the classic "Leaked trace" crash a
    cached transform-matrix helper caused here before it was keyed on
    the hashable spec instead. The rule flags cached functions whose
    parameters are unannotated (unknown — prove hashability by
    annotating) or annotated array-ish.

``unsynced-timing``
    A ``t1 - t0`` elapsed-time window over async-dispatched JAX work
    with no ``block_until_ready`` in the enclosing scope. JAX returns
    futures; without a sync barrier the window times Python dispatch,
    not the computation — every benchmark in this repo learned this
    once (``benchmarks.common.time_fn`` exists for exactly this).

``repro-imports-benchmarks``
    ``repro.*`` (the library, under ``src/``) importing ``benchmarks.*``
    (the harness). The library must stay importable without the
    benchmark tree on ``PYTHONPATH`` (serving containers ship without
    it); the dependency only ever points the other way.

False-positive escape hatch: a ``# lint: waive=<rule>[,<rule>...]``
pragma on the flagged line or on the enclosing ``def``/``class`` line
waives the finding — *visibly*, in the diff, where review can push back.

Run as ``python -m repro.analysis.lint`` (the ``make lint`` target) over
``src/`` and ``benchmarks/``; exits non-zero on unwaived findings. The
fixture corpus in ``tests/lint_fixtures/`` pins one known-bad snippet
per rule so the rules themselves are regression-tested.
"""
from __future__ import annotations

import ast
import dataclasses
import sys
from pathlib import Path
from typing import Iterable, Optional

__all__ = ["Finding", "lint_source", "lint_file", "lint_paths", "RULES",
           "main"]

RULES = ("jit-arg-flavor", "cached-array-args", "unsynced-timing",
         "repro-imports-benchmarks")

WAIVE_TAG = "# lint: waive="

# Parameter annotations that prove hashability to cached-array-args.
_HASHABLE_ANNOTATIONS = {
    "int", "float", "str", "bool", "bytes", "complex", "tuple",
    "frozenset", "None", "Fraction", "Number", "Optional", "Union",
    "Literal", "Hashable",
}
_ARRAYISH_ANNOTATIONS = {"ndarray", "Array", "ArrayLike", "DeviceArray"}

_TIME_FUNCS = {"perf_counter", "monotonic", "time", "process_time",
               "perf_counter_ns", "monotonic_ns"}


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str
    message: str
    waived: bool = False

    def __str__(self) -> str:
        tag = " (waived)" if self.waived else ""
        return f"{self.path}:{self.line}: {self.rule}{tag}: {self.message}"


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression ('jax.jit', 'np.array')."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    if isinstance(node, ast.Call):
        return _dotted(node.func)
    return ""


_JIT_NAMES = ("jax.jit", "jit", "pjit", "jax.pjit")
#: Wrappers whose result dispatches like a jitted callable — shard_map
#: builds a traced, cached SPMD program, so mixed numpy/device argument
#: flavors at its call sites double the dispatch cache exactly like
#: plain jit. Matched on the trailing name, so ``jax.shard_map`` and a
#: bare imported ``shard_map`` both count.
_SHARD_MAP_NAMES = ("shard_map",)


def _is_jit_expr(node: ast.AST) -> bool:
    """Does this decorator/value expression produce a jitted callable?"""
    if isinstance(node, ast.Call):
        name = _dotted(node.func)
        if name in _JIT_NAMES \
                or name.rsplit(".", 1)[-1] in _SHARD_MAP_NAMES:
            return True
        if name.endswith("partial"):
            return any(_is_jit_expr(a) for a in node.args)
        return False
    return _dotted(node) in _JIT_NAMES


def _is_cache_expr(node: ast.AST) -> bool:
    name = _dotted(node)
    short = name.rsplit(".", 1)[-1]
    return short in ("lru_cache", "cache", "memoize", "memo")


def _annotation_kind(ann: Optional[ast.expr]) -> str:
    """'hashable' | 'arrayish' | 'unknown' | 'missing' for one param."""
    if ann is None:
        return "missing"
    names = {n.rsplit(".", 1)[-1]
             for n in (_dotted(x) for x in ast.walk(ann)) if n}
    if names & _ARRAYISH_ANNOTATIONS:
        return "arrayish"
    if isinstance(ann, ast.Constant) and ann.value is None:
        return "hashable"
    # Subscripted generics (Optional[int], tuple[int, ...]) walk down to
    # their element names; all-hashable elements prove the whole.
    if names and names <= (_HASHABLE_ANNOTATIONS | {"Sequence", "Iterable"}):
        return "hashable"
    # Unknown class annotation (e.g. a frozen dataclass): the author
    # named a type — treat as a hashability claim, don't flag.
    return "unknown"


def _arg_flavor(node: ast.expr, numpy_names: set[str],
                device_names: set[str]) -> Optional[str]:
    """Classify a call argument as 'numpy' / 'device' / None (unknown)."""
    for sub in ast.walk(node):
        name = _dotted(sub)
        if not name:
            continue
        root = name.split(".", 1)[0]
        if name.endswith("device_put") or root in ("jnp", "jax"):
            return "device"
        if root in ("np", "numpy"):
            return "numpy"
        if isinstance(sub, ast.Name):
            if sub.id in device_names:
                return "device"
            if sub.id in numpy_names:
                return "numpy"
    return None


class _ModuleLinter(ast.NodeVisitor):
    def __init__(self, path: str, is_repro: bool):
        self.path = path
        self.is_repro = is_repro
        self.findings: list[Finding] = []
        self.jitted: set[str] = set()
        self.jit_flavors: dict[str, tuple[str, int]] = {}
        self.numpy_names: set[str] = set()
        self.device_names: set[str] = set()
        self._scope: list[ast.AST] = []

    def add(self, line: int, rule: str, message: str):
        self.findings.append(Finding(self.path, line, rule, message))

    # -- rule: repro-imports-benchmarks ------------------------------------
    def _check_import(self, node, module: str):
        if self.is_repro and (module == "benchmarks"
                              or module.startswith("benchmarks.")):
            self.add(node.lineno, "repro-imports-benchmarks",
                     f"library module imports {module!r}; repro.* must not "
                     "depend on the benchmark harness")

    def visit_Import(self, node: ast.Import):
        for alias in node.names:
            self._check_import(node, alias.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        if node.module and node.level == 0:
            self._check_import(node, node.module)
        self.generic_visit(node)

    # -- rule: cached-array-args + jitted-def collection -------------------
    def _visit_funcdef(self, node):
        cache_dec = next((d for d in node.decorator_list
                          if _is_cache_expr(d)), None)
        if cache_dec is not None:
            a = node.args
            params = (a.posonlyargs + a.args + a.kwonlyargs
                      + ([a.vararg] if a.vararg else []))
            bad = [(p.arg, _annotation_kind(p.annotation)) for p in params
                   if _annotation_kind(p.annotation) in ("missing",
                                                         "arrayish")]
            if bad:
                what = ", ".join(f"{n} ({k} annotation)" for n, k in bad)
                self.add(node.lineno, "cached-array-args",
                         f"cached function {node.name!r} may take array "
                         f"arguments: {what}; arrays are unhashable and "
                         "cached tracers leak out of their trace — key the "
                         "cache on hashable metadata instead")
        if any(_is_jit_expr(d) for d in node.decorator_list):
            self.jitted.add(node.name)
        self._scope.append(node)
        self.generic_visit(node)
        self._scope.pop()

    visit_FunctionDef = _visit_funcdef
    visit_AsyncFunctionDef = _visit_funcdef

    # -- assignment tracking for flavor inference --------------------------
    def visit_Assign(self, node: ast.Assign):
        targets = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if targets:
            if _is_jit_expr(node.value):
                self.jitted.update(targets)
            name = _dotted(node.value)
            root = name.split(".", 1)[0]
            if isinstance(node.value, ast.Call):
                if name.endswith("device_put") or root in ("jnp", "jax"):
                    self.device_names.update(targets)
                elif root in ("np", "numpy"):
                    self.numpy_names.update(targets)
        self.generic_visit(node)

    # -- rule: jit-arg-flavor ----------------------------------------------
    def visit_Call(self, node: ast.Call):
        callee = _dotted(node.func)
        if callee in self.jitted:
            flavors = {f for f in
                       (_arg_flavor(a, self.numpy_names, self.device_names)
                        for a in list(node.args)
                        + [kw.value for kw in node.keywords])
                       if f}
            if len(flavors) > 1:
                self.add(node.lineno, "jit-arg-flavor",
                         f"call to jitted {callee!r} mixes raw-numpy and "
                         "device-put argument flavors in one call; each "
                         "flavor occupies its own jit dispatch-cache entry")
            elif len(flavors) == 1:
                flavor = flavors.pop()
                prev = self.jit_flavors.get(callee)
                if prev is not None and prev[0] != flavor:
                    self.add(node.lineno, "jit-arg-flavor",
                             f"jitted {callee!r} called with {flavor} "
                             f"arguments here but {prev[0]} arguments at "
                             f"line {prev[1]}; mixed flavors double the "
                             "jit dispatch cache and re-trigger warmup")
                else:
                    self.jit_flavors[callee] = (flavor, node.lineno)
        self.generic_visit(node)


class _TimingLinter(ast.NodeVisitor):
    """unsynced-timing: per-scope t1 - t0 windows with no sync barrier."""

    def __init__(self, path: str):
        self.path = path
        self.findings: list[Finding] = []
        self.sync_names: set[str] = set()   # module-local sync wrappers

    def _scan_scope(self, node, body):
        def is_time_call(n: ast.AST) -> bool:
            return (isinstance(n, ast.Call)
                    and _dotted(n.func).rsplit(".", 1)[-1] in _TIME_FUNCS
                    and _dotted(n.func).split(".", 1)[0]
                    in {"time"} | _TIME_FUNCS)

        # Pass 1: names bound to time calls, sync barriers (order-free —
        # a t0 assigned anywhere in the scope flavors every window).
        time_names: set[str] = set()
        has_sync = False
        nodes = list(body_walk(body))
        for sub in nodes:
            if isinstance(sub, ast.Assign) and is_time_call(sub.value):
                time_names.update(t.id for t in sub.targets
                                  if isinstance(t, ast.Name))
            if isinstance(sub, ast.Call):
                callee = _dotted(sub.func).rsplit(".", 1)[-1]
                if callee in {"block_until_ready", "time_fn",
                              "result"} | self.sync_names:
                    has_sync = True
            if isinstance(sub, ast.Attribute) \
                    and sub.attr == "block_until_ready":
                has_sync = True

        def time_flavored(n: ast.AST) -> bool:
            return is_time_call(n) or (isinstance(n, ast.Name)
                                       and n.id in time_names)

        # Pass 2: t1 - t0 windows (both operands time-flavored — a
        # one-sided `deadline - perf_counter()` is the serving-loop
        # idiom, not a measurement).
        subs = [sub.lineno for sub in nodes
                if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Sub)
                and time_flavored(sub.left) and time_flavored(sub.right)]

        if subs and not has_sync:
            line = min(subs)
            self.findings.append(Finding(
                self.path, line, "unsynced-timing",
                "elapsed-time window with no block_until_ready in scope; "
                "JAX dispatch is async — this times the Python call, not "
                "the computation (use benchmarks.common.time_fn)"))

    def _visit_funcdef(self, node):
        self._scan_scope(node, node.body)
        # nested defs get their own scope scan via generic_visit
        self.generic_visit(node)

    visit_FunctionDef = _visit_funcdef
    visit_AsyncFunctionDef = _visit_funcdef

    def scan_module(self, tree: ast.Module):
        # Resolve module-local sync wrappers first: a def whose body
        # touches block_until_ready, or `alias = jax.block_until_ready`,
        # counts as a sync barrier at its call sites (the serving loop's
        # `_block` idiom).
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(isinstance(s, ast.Attribute)
                       and s.attr == "block_until_ready"
                       for s in ast.walk(node)):
                    self.sync_names.add(node.name)
            elif isinstance(node, ast.Assign) \
                    and _dotted(node.value).endswith("block_until_ready"):
                self.sync_names.update(t.id for t in node.targets
                                       if isinstance(t, ast.Name))
        # module top level as a scope of its own (scripts time inline)
        self._scan_scope(tree, [n for n in tree.body
                                if not isinstance(n, (ast.FunctionDef,
                                                      ast.AsyncFunctionDef,
                                                      ast.ClassDef))])
        self.visit(tree)


def body_walk(body) -> Iterable[ast.AST]:
    """Walk statements without descending into nested function scopes."""
    stack = list(body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _apply_waivers(findings: list[Finding], source: str) -> list[Finding]:
    """Mark findings waived by a pragma on their line or an enclosing
    def/class line."""
    lines = source.splitlines()

    def waivers_on(lineno: int) -> set[str]:
        if 1 <= lineno <= len(lines):
            text = lines[lineno - 1]
            idx = text.find(WAIVE_TAG)
            if idx >= 0:
                spec = text[idx + len(WAIVE_TAG):].split("#", 1)[0]
                return {r.strip() for r in spec.split(",") if r.strip()}
        return set()

    # enclosing def/class lines per source line
    tree = ast.parse(source)
    enclosing: dict[int, list[int]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            end = getattr(node, "end_lineno", node.lineno)
            for ln in range(node.lineno, end + 1):
                enclosing.setdefault(ln, []).append(node.lineno)

    out = []
    for f in findings:
        cand = {f.line, *enclosing.get(f.line, [])}
        waived = any(f.rule in waivers_on(ln) or "all" in waivers_on(ln)
                     for ln in cand)
        out.append(dataclasses.replace(f, waived=True) if waived else f)
    return out


def lint_source(source: str, path: str = "<string>",
                is_repro: Optional[bool] = None) -> list[Finding]:
    """Lint one module's source; returns findings with waivers applied."""
    if is_repro is None:
        is_repro = "repro" in Path(path).parts
    tree = ast.parse(source, filename=path)
    mod = _ModuleLinter(path, is_repro=is_repro)
    mod.visit(tree)
    tim = _TimingLinter(path)
    tim.scan_module(tree)
    findings = sorted(mod.findings + tim.findings,
                      key=lambda f: (f.line, f.rule))
    return _apply_waivers(findings, source)


def lint_file(path: Path) -> list[Finding]:
    return lint_source(path.read_text(), str(path))


def lint_paths(paths: Iterable[Path]) -> list[Finding]:
    findings: list[Finding] = []
    for root in paths:
        files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
        for f in files:
            findings.extend(lint_file(f))
    return findings


def main(argv: Optional[list[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="Repo-specific JAX hazard linter (see module docs).")
    ap.add_argument("paths", nargs="*", default=["src", "benchmarks"],
                    help="files or directories to lint "
                         "(default: src benchmarks)")
    ap.add_argument("--show-waived", action="store_true",
                    help="also print waived findings")
    args = ap.parse_args(argv)

    findings = lint_paths([Path(p) for p in args.paths])
    active = [f for f in findings if not f.waived]
    waived = [f for f in findings if f.waived]
    for f in active:
        print(f)
    if args.show_waived:
        for f in waived:
            print(f)
    print(f"lint: {len(active)} finding(s), {len(waived)} waived, "
          f"rules: {', '.join(RULES)}")
    return 1 if active else 0


if __name__ == "__main__":
    sys.exit(main())
