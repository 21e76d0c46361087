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
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
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
    spans, window = [], None
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    s = ev.start_ns * 1e-9
                    spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
                    if ev.name == WINDOW_SPAN:
                        window = (s, s + ev.duration_ns * 1e-9)
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
    return Reduction(window, per_device, spans)
