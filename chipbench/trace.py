"""From a profiler trace to the device numbers: busy time (the union of
the intervals in which an operation ran), idle share, device time by class
(Mosaic kernels and the XLA glue around them), the kernels' call
signatures, and the breakdown of the longest device operations and idle
gaps.

On a TPU the device plane's ``XLA Ops`` line has one event per executed
HLO instruction, named by the instruction's text with its result and
operand shapes; a Mosaic kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"``. Control flow (a ``while`` and the
ops of its body) nests, so device time by class counts each event's self
time: its duration less that of the events inside it. The ``Async XLA
Ops`` line (DMAs in flight) is not counted as busy.

Only the traced window counts: the interval of the benchmark's own
``chipbench.window`` span on the host, on the device's clock in the
trace. Idle gaps are labelled by the benchmark's host span
(``chipbench.*``) that overlaps them most.

The program's own host spans (``repro.*``) are kept apart, and its scopes
come from the ``op_name`` metadata of each HLO instruction: the device
events name instructions only, so ``op_names`` reads the served
executable's compiled HLO text and ``scopes`` puts the window's device
time under each ``(layer, stage)`` scope (``Scopes``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
PROGRAM_PREFIX = "repro."
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MOSAIC = "tpu_custom_call"


def load(trace_dir: str):
    """The ProfileData of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{files}")
    return ProfileData.from_file(files[0])


@dataclasses.dataclass
class Op:
    text: str          # the instruction, as the trace names the event
    start: float       # seconds, clipped to the window
    end: float
    self_s: float = 0.0

    @property
    def name(self) -> str:
        """The instruction's name, e.g. ``fused_gemm_output.14``."""
        return self.text.split(" = ", 1)[0].lstrip("%")

    @property
    def mosaic(self) -> bool:
        return f'custom_call_target="{MOSAIC}"' in self.text

    @property
    def signature(self) -> str:
        """Result and operand shapes: the text up to the call target."""
        return self.text.split(", custom_call_target=", 1)[0]


def _union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduction:
    window: tuple            # (start, end), seconds on the trace's clock
    ops: list                # per device: list of Op
    spans: list              # (name, start, end) host spans of chipbench
    program_spans: list      # (name, start, end) host spans of the program

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    def busy_intervals(self, device: int = 0):
        return _union((o.start, o.end) for o in self.ops[device])

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return sum(sum(e - s for s, e in self.busy_intervals(d))
                   for d in range(self.n_devices)) / self.n_devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def time_s(self, mosaic: bool) -> float:
        """Device seconds of the Mosaic kernels (or of everything else):
        self time summed over events, averaged over devices."""
        return sum(o.self_s for ops in self.ops for o in ops
                   if o.mosaic == mosaic) / self.n_devices

    def kernels(self):
        return [o for ops in self.ops for o in ops if o.mosaic]

    def gaps(self, device: int = 0):
        """Idle intervals of one device inside the window."""
        edges = [self.window[0]]
        for s, e in self.busy_intervals(device):
            edges += [s, e]
        edges.append(self.window[1])
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def label(self, start: float, end: float) -> str:
        """The benchmark host span that overlaps ``[start, end]`` most."""
        best, name = 0.0, "no chipbench span"
        for n, s, e in self.spans:
            if n == WINDOW_SPAN:
                continue
            ov = min(e, end) - max(s, start)
            if ov > best:
                best, name = ov, n
        return name

    def breakdown(self, top: int = 10) -> dict:
        by_name: dict[str, float] = {}
        for ops in self.ops:
            for o in ops:
                by_name[o.name] = by_name.get(o.name, 0.0) + o.self_s
        device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(0), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s / self.n_devices]
                               for n, s in device_ops],
                "idle_gaps": [[self.label(s, e), e - s] for s, e in gaps]}


def _self_times(ops):
    """Set each op's self time: its duration less its children's, where a
    child is an op that lies inside it (the body of a ``while``)."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack = []
    for o in ops:
        o.self_s = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_s -= o.end - o.start
        stack.append(o)


def reduce(profile, n_devices: int) -> Reduction:
    """Reduce a ProfileData to the traced window on ``n_devices`` chips."""
    spans, program, window = [], [], None
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                span = (ev.name, s, s + ev.duration_ns * 1e-9)
                if ev.name.startswith(PROGRAM_PREFIX):
                    program.append(span)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append(span)
                    if ev.name == WINDOW_SPAN:
                        window = span[1:]
    if window is None:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} span")
    devices = sorted((p for p in profile.planes
                      if p.name.startswith(DEVICE_PLANE)),
                     key=lambda p: p.name)[:n_devices]
    if len(devices) != n_devices:
        raise RuntimeError(f"the trace holds {len(devices)} device planes, "
                           f"the run used {n_devices} chips")
    per_device = []
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                s, e = max(s, window[0]), min(e, window[1])
                if e <= s:
                    continue
                ops.append(Op(ev.name, s, e))
        _self_times(ops)
        per_device.append(ops)
    return Reduction(window, per_device, spans, program)


# -- the program's scopes -------------------------------------------------
#
# The yardstick parses the HLO text itself, rather than calling the
# program's parser (``repro.analysis.hlo_cost.op_names``): what a metric
# is measured with lies under the benchmark's paths, out of reach of a PR
# that changes the program.

_INSTRUCTION = re.compile(r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="([^"]*)"')
_FRAME = re.compile(r"^[\w.\-]+\(.*\)$")     # jit(f), jvp(...), vmap(...)


def op_names(hlo_text: str) -> dict:
    """Each instruction's ``op_name`` metadata ("" where it has none), by
    the instruction's name, over every computation of a compiled HLO
    module's text: what names a device event's instruction."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line.strip())
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op.group(1) if op else ""
    return out


def scope_path(op_name: str) -> tuple:
    """The scopes an instruction was traced under: the components of its
    ``op_name`` less the transformation frames (``jit(...)`` and the like)
    and the last one, which names the primitive."""
    return tuple(p for p in op_name.split("/")[:-1] if not _FRAME.match(p))


def scope_of(op_name: str) -> tuple:
    """``(layer, stage)`` of an ``op_name``: the innermost scope is the
    stage, the one around it the layer. Either may be None."""
    path = scope_path(op_name)
    return (path[-2] if len(path) > 1 else None,
            path[-1] if path else None)


def _clip(spans, window, names) -> list:
    """``(start, end)`` of the spans named in ``names``, cut to
    ``window``."""
    a, b = window
    return [(max(s, a), min(e, b)) for n, s, e in spans
            if n in names and min(e, b) > max(s, a)]


def _overlap(xs, ys) -> float:
    """Seconds in both of two lists of sorted, disjoint intervals."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        tot += max(0.0, min(xs[i][1], ys[j][1]) - max(xs[i][0], ys[j][0]))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def program_label(spans, start: float, end: float) -> str:
    """The program span whose own time overlaps ``[start, end]`` most: its
    overlap less that of the spans it holds, so a collection that stalls a
    dispatcher stage names the gap, and not the stage around it."""
    cut = [(n, max(s, start), min(e, end), s, e) for n, s, e in spans
           if min(e, end) > max(s, start)]
    best, name = 0.0, "no repro span"
    for n, a, b, s, e in cut:
        inner = _union((a2, b2) for n2, a2, b2, s2, e2 in cut
                       if s <= s2 and e2 <= e and (s2, e2) != (s, e))
        own = b - a - sum(y - x for x, y in inner)
        if own > best:
            best, name = own, n
    return name


@dataclasses.dataclass
class Scopes:
    """The program's scopes and host spans in one traced window."""
    device_s: dict           # op_name ("" for none): device self seconds,
    #                          averaged over the devices
    glue_s: float            # self seconds of the ops that are not Mosaic
    glue_named_s: float      # of which under some scope (``scope_path``)
    spans: list              # (name, start, end) host spans of the program
    window: tuple            # (start, end), seconds on the trace's clock
    gaps: list               # idle intervals of device 0 in the window
    images: int              # images answered in the window

    def scope_s(self, scope: str):
        """Device seconds of the instructions traced under ``scope``, a
        component of their ``op_name`` path (a stage such as
        ``wino_extract``, or a layer such as ``stem``); None where no
        instruction was."""
        t = [v for name, v in self.device_s.items()
             if scope in name.split("/")]
        return sum(t) if t else None

    def span_s(self, names):
        """Seconds of the program spans named in ``names`` inside the
        window, each counted whole (overlaps are not merged); None where
        none lies in it."""
        cut = _clip(self.spans, self.window, set(names))
        return sum(e - s for s, e in cut) if cut else None

    def idle_in_s(self, names):
        """Seconds in which device 0 is idle inside one of the program
        spans named in ``names``: 0 where none of them lies in the window,
        None where no program span does (a program that keeps none)."""
        if not _clip(self.spans, self.window,
                     {n for n, _, _ in self.spans}):
            return None
        cut = _clip(self.spans, self.window, set(names))
        return _overlap(self.gaps, _union(cut))

    def per_image_us(self, seconds):
        """``seconds`` per image answered in the window, in microseconds;
        None where either is missing."""
        if seconds is None or not self.images:
            return None
        return 1e6 * seconds / self.images

    def summary(self, top: int = 10) -> dict:
        """For the log: the share of the XLA glue that some scope names,
        device time per image by stage and by ``(layer, stage)``
        (``scope_of``), and the longest idle gaps labelled by the
        program's spans."""
        by_scope, stage = {}, {}
        for name, t in self.device_s.items():
            key = scope_of(name)
            by_scope[key] = by_scope.get(key, 0.0) + t
            stage[key[1]] = stage.get(key[1], 0.0) + t
        longest = sorted(self.gaps, key=lambda g: g[0] - g[1])[:top]
        return {
            "glue_named_share": (self.glue_named_s / self.glue_s
                                 if self.glue_s else None),
            "stage_us_per_image": {str(k): self.per_image_us(v) for k, v in
                                   sorted(stage.items(),
                                          key=lambda kv: -kv[1])},
            "device_scopes": [[layer, st, t] for (layer, st), t in sorted(
                by_scope.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps_program": [[program_label(self.spans, s, e), e - s]
                                  for s, e in longest]}


def scopes(red: Reduction, names: dict, images: int) -> Scopes:
    """The program's scopes in ``red``'s window: ``names`` maps each
    instruction to its ``op_name`` (``op_names``), ``images`` are the
    images answered in the window."""
    device_s, glue, named = {}, 0.0, 0.0
    for ops in red.ops:
        for o in ops:
            name = names.get(o.name, "")
            t = o.self_s / red.n_devices
            device_s[name] = device_s.get(name, 0.0) + t
            if not o.mosaic:
                glue += t
                named += t if scope_path(name) else 0.0
    return Scopes(device_s, glue, named, red.program_spans, red.window,
                  red.gaps(0), images)
