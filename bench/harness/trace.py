"""From the profiler's trace to numbers: the device's busy time in the
window, each kernel's device time, the top device operations and the
longest idle gaps, each named by the benchmark's host span around it.

The window is the ``bench_window`` host span; device operations are the
events of each device plane's ``XLA Ops`` line, named there by their HLO
instruction (``%paged_attention.11 = bf16[...] custom-call(...)``).  Ops
nest (a ``while`` holds its body's ops), so busy time is the union of
their intervals and an op's share of the breakdown is its self time.  A
Pallas kernel is found by its ``pallas_call`` name in the instruction name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench_window"
HOST_SPANS = ("handler_host", "clock_wait", "dispatch_")
OPS_LINE = "XLA Ops"
_INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.(?:\d+|clone))*\s*=")


@dataclasses.dataclass
class Op:
    name: str          # the HLO instruction's name, numbering dropped
    start: int         # ns
    end: int
    self_ns: int = -1  # time not covered by the ops nested in it


def instruction(event_name: str) -> str:
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name[:64]


def self_times(ops: List[Op]) -> None:
    """Set each op's self time: its duration less its direct children's."""
    stack: List[Op] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        o.self_ns = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_ns -= o.end - o.start
        stack.append(o)


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Summary:
    ops: List[Op]              # device ops clipped to the window
    spans: List[Span]          # host spans inside the window
    start: int
    end: int
    n_devices: int = 1

    @classmethod
    def build(cls, ops: List[Op], spans: List[Span], n_devices: int = 1):
        win = [s for s in spans if s.name == WINDOW_SPAN]
        if len(win) != 1:
            raise ValueError(f"trace holds {len(win)} '{WINDOW_SPAN}' spans")
        a, b = win[0].start, win[0].end
        clipped = [Op(o.name, max(o.start, a), min(o.end, b))
                   for o in ops if o.end > a and o.start < b]
        self_times(clipped)
        inner = [s for s in spans if s.name != WINDOW_SPAN
                 and s.end > a and s.start < b]
        return cls(clipped, inner, a, b, n_devices)

    @property
    def window_s(self) -> float:
        return (self.end - self.start) * 1e-9

    def busy(self) -> List[Tuple[int, int]]:
        return _union([(o.start, o.end) for o in self.ops])

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices."""
        return sum(e - s for s, e in self.busy()) * 1e-9 / self.n_devices

    def kernel_s(self, names: Sequence[str]) -> float:
        """Device seconds of the ops named after one of the kernels; 0.0
        when none ran."""
        return sum(o.end - o.start for o in self.ops
                   if any(n in o.name for n in names)) * 1e-9

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every stretch of the window with no device op, longest first,
        named by the innermost host span around its middle (or else the
        span that overlaps it most)."""
        gaps, t = [], self.start
        for s, e in self.busy() + [(self.end, self.end)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        out = []
        for s, e in gaps:
            mid = (s + e) // 2
            around = [sp for sp in self.spans if sp.start <= mid < sp.end]
            if around:
                name = min(around, key=lambda sp: sp.end - sp.start).name
            else:
                ov = [(min(e, sp.end) - max(s, sp.start), sp.name)
                      for sp in self.spans]
                best = max(ov, default=(0, "no_span"))
                name = best[1] if best[0] > 0 else "no_span"
            out.append((name, (e - s) * 1e-9))
        return sorted(out, key=lambda g: -g[1])

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, int] = {}
        for o in self.ops:
            tot[o.name] = tot.get(o.name, 0) + o.self_ns
        return [(k, v * 1e-9) for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def breakdown(self) -> Dict:
        return {"device_ops": [list(x) for x in self.top_ops(10)],
                "idle_gaps": [list(x) for x in self.idle_gaps()[:10]]}


def read_events(path: str) -> Tuple[List[Op], List[Span], int]:
    """Device ops, host spans and the device count of one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    n_dev = 0
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            n_dev += bool(lines)
            for line in lines:
                for ev in line.events:
                    name = instruction(ev.name)
                    start = int(ev.start_ns)
                    ops.append(Op(name, start, start + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN or ev.name.startswith(
                            HOST_SPANS):
                        start = int(ev.start_ns)
                        spans.append(Span(ev.name, start,
                                          start + int(ev.duration_ns)))
    return ops, spans, max(n_dev, 1)


def load(trace_dir: str) -> Summary:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} traces under {trace_dir}")
    ops, spans, n = read_events(files[0])
    return Summary.build(ops, spans, n)
