"""The program's own spans and names in the profiler's trace.

The serving program marks its host work with ``serve.*`` spans whose
metadata rides on the trace events as stats (``repro.core.tracing``):
``serve.arrivals`` (``n``), ``serve.admit`` (``rid``, ``cached``,
``prompt``), ``serve.step.build`` (``step``, ``joins``, ``sfx``, ``cow``),
``serve.dispatch`` (``label``, and for an engine step ``step``, ``rows``,
``steps``, ``slots``), ``serve.step.fold`` (``step``, ``finished``) and
``serve.wait``.  On the device plane of the same ``.xplane.pb``, each op
ran inside one run of a program on the ``XLA Modules`` line
(``jit_decode_window(<program id>)``); where the trace was taken with
``enable_hlo_proto``, that program's HLO gives the op's name, whose
``jax.named_scope`` path names the model phase (``decode``,
``prefill_chunk``, ``prefill``, ``kv_scatter``, ``copy_blocks``).

This reads both, clipped to the benchmark's ``bench_window`` span, and
reduces them: idle device time by the innermost host span around it, and
device self time by program and outer scope.  It reads the same trace as
``harness.trace`` and changes nothing there.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
import re
from typing import Dict, List, Sequence, Tuple

from harness import trace as tr

PREFIX = "serve."
WAIT = "serve.wait"
CLOCK_WAIT = "clock_wait"       # the benchmark's wait for the wall clock
NO_SPAN = "no_span"
MODULES_LINE = "XLA Modules"
# path components of an op name that are structure, not a scope
_STRUCTURE = {"while", "body", "cond", "closed_call", "checkpoint", "remat",
              "rematted_computation", "shard_map", "pjit", "scan"}
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class HostSpan:
    name: str
    start: int          # ns
    end: int
    meta: Dict


@dataclasses.dataclass
class DeviceOp:
    name: str           # the HLO instruction, numbering dropped
    start: int
    end: int
    program: str        # the XLA module, e.g. ``jit_decode_window``
    scope: str          # outer named scope, "" outside any
    self_ns: int = -1


def program_name(module: str) -> str:
    """``jit_decode_window(123)`` -> ``jit_decode_window``."""
    return _MODULE_ID.sub("", module.strip())


def scope_of(op_name: str) -> str:
    """The outermost named scope of an HLO op name: the first component
    of its path that is neither a ``jit(...)`` nor loop structure, when
    one lies above the op itself; "" otherwise.

    ``jit(mixed)/while/body/closed_call/decode/dot_general`` -> ``decode``
    """
    parts = [p for p in op_name.split("/")
             if p and not p.startswith(("jit(", "pjit("))
             and p not in _STRUCTURE]
    return parts[0] if len(parts) > 1 else ""


@dataclasses.dataclass
class ProgramTrace:
    ops: List[DeviceOp]        # device ops clipped to the window
    spans: List[HostSpan]      # serve.* and clock_wait spans in the window
    start: int
    end: int

    @classmethod
    def build(cls, ops: Sequence[DeviceOp], spans: Sequence[HostSpan]):
        win = [s for s in spans if s.name == tr.WINDOW_SPAN]
        if len(win) != 1:
            raise ValueError(f"trace holds {len(win)} '{tr.WINDOW_SPAN}' "
                             "spans")
        a, b = win[0].start, win[0].end
        clipped = [DeviceOp(o.name, max(o.start, a), min(o.end, b),
                            o.program, o.scope)
                   for o in ops if o.end > a and o.start < b]
        tr.self_times(clipped)
        inner = [s for s in spans if s.name != tr.WINDOW_SPAN
                 and s.end > a and s.start < b]
        return cls(clipped, inner, a, b)

    @property
    def window_s(self) -> float:
        return (self.end - self.start) * 1e-9

    def named(self, name: str) -> List[HostSpan]:
        return [s for s in self.spans if s.name == name]

    def idle(self) -> List[Tuple[int, int]]:
        """Stretches of the window with no device op."""
        out, t = [], self.start
        for s, e in tr._union([(o.start, o.end) for o in self.ops]) + [
                (self.end, self.end)]:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        return out

    def innermost(self) -> List[Tuple[int, int, str]]:
        """The window cut where the innermost host span changes: (start,
        end, name), ``no_span`` where none is open.  Spans on one thread
        nest, so the innermost is the open span that opened last."""
        a, b = self.start, self.end
        spans = sorted(self.spans, key=lambda s: (s.start, -s.end))
        cuts = sorted({a, b} | {t for s in spans for t in (s.start, s.end)
                                if a < t < b})
        heap: List[Tuple[int, int, int]] = []   # (-start, end, index)
        out, k = [], 0
        for p, q in zip(cuts, cuts[1:]):
            while k < len(spans) and spans[k].start <= p:
                heapq.heappush(heap, (-spans[k].start, spans[k].end, k))
                k += 1
            while heap and heap[0][1] <= p:
                heapq.heappop(heap)
            out.append((p, q, spans[heap[0][2]].name if heap else NO_SPAN))
        return out

    def idle_by_span(self) -> Dict[str, float]:
        """Idle device seconds by the innermost host span around them, the
        most first: ``serve.*`` spans, ``clock_wait`` and ``no_span``.
        They add up to the window's idle seconds."""
        tot: Dict[str, int] = {}
        segs = self.innermost()
        j = 0
        for s, e in self.idle():
            while j < len(segs) and segs[j][1] <= s:
                j += 1
            i = j
            while i < len(segs) and segs[i][0] < e:
                p, q, name = segs[i]
                tot[name] = tot.get(name, 0) + min(e, q) - max(s, p)
                i += 1
        return {k: v * 1e-9 for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])}

    def device_by_program(self) -> Dict[str, float]:
        """Device self seconds by program and outer scope
        (``jit_mixed/decode``; the program alone outside any scope), the
        most first."""
        tot: Dict[str, int] = {}
        for o in self.ops:
            key = f"{o.program}/{o.scope}" if o.scope else o.program
            tot[key] = tot.get(key, 0) + o.self_ns
        return {k: v * 1e-9 for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])}

    def breakdown(self, n: int = 10) -> Dict:
        return {"idle_by_span": [[k, v] for k, v in
                                 list(self.idle_by_span().items())[:n]],
                "device_by_program": [[k, v] for k, v in
                                      list(self.device_by_program().items())
                                      [:n]]}


def _within(modules: List[Tuple[int, int, str]], starts: List[int],
            t0: int, t1: int) -> str:
    """The module whose run, of ``modules`` sorted by start, holds
    [t0, t1); "" when none does."""
    i = bisect.bisect_right(starts, t0) - 1
    return modules[i][2] if i >= 0 and modules[i][1] >= t1 else ""


def _varint(b, i: int) -> Tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return r, i


def _fields(b, want: int):
    """The length-delimited values of field ``want`` of one protobuf
    message's bytes (every other field skipped)."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            _, i = _varint(b, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = _varint(b, i)
            if field == want:
                yield b[i:i + n]
            i += n
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")


def _str(msg, field: int) -> str:
    return bytes(next(_fields(msg, field), b"")).decode()


def hlo_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{module run name: {instruction: HLO op name}} from the HLO protos
    that a trace holds when taken with ``enable_hlo_proto``; {} without.

    ``ProfileData`` does not show them, so the ``/host:metadata`` plane is
    read from the ``.xplane.pb`` itself: each event metadata there is a
    module (``jit_mixed(<program id>)``, as the device's ``XLA Modules``
    line names its runs) with an ``xla.HloProto`` stat, whose instructions
    carry ``metadata.op_name``."""
    with open(path, "rb") as f:
        raw = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in _fields(raw, 1):                  # XSpace.planes
        if _str(plane, 2) != "/host:metadata":     # XPlane.name
            continue
        for entry in _fields(plane, 4):            # XPlane.event_metadata
            for meta in _fields(entry, 2):         # map value
                names: Dict[str, str] = {}
                for stat in _fields(meta, 5):      # XEventMetadata.stats
                    for proto in _fields(stat, 6):     # XStat.bytes_value
                        for module in _fields(proto, 1):   # HloProto
                            for comp in _fields(module, 3):
                                for ins in _fields(comp, 2):
                                    op = next(_fields(ins, 7), b"")
                                    names[_str(ins, 1)] = _str(op, 2)
                out[_str(meta, 2)] = names
    return out


_INSTRUCTION = re.compile(r"^%?([\w.\-]+)\s*=")


def read_events(path: str) -> Tuple[List[DeviceOp], List[HostSpan]]:
    """Device ops with their program and scope, and the ``serve.*``,
    ``clock_wait`` and window spans with their stats, of one
    ``.xplane.pb``.  An op's program is the ``XLA Modules`` run around
    it; its scope comes from the trace's HLO protos, "" without them."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    scopes = {module: {ins: scope_of(op) for ins, op in names.items()}
              for module, names in hlo_op_names(path).items()}
    ops: List[DeviceOp] = []
    spans: List[HostSpan] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            modules = sorted(
                (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
                 ev.name)
                for ln in lines if ln.name == MODULES_LINE
                for ev in ln.events)
            starts = [m[0] for m in modules]
            for ln in lines:
                if ln.name != tr.OPS_LINE:
                    continue
                for ev in ln.events:
                    start = int(ev.start_ns)
                    end = start + int(ev.duration_ns)
                    module = _within(modules, starts, start, end)
                    m = _INSTRUCTION.match(ev.name)
                    scope = scopes.get(module, {}).get(
                        m.group(1) if m else "", "")
                    ops.append(DeviceOp(tr.instruction(ev.name), start, end,
                                        program_name(module), scope))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    name = ev.name
                    if (name.startswith(PREFIX) or name == CLOCK_WAIT
                            or name == tr.WINDOW_SPAN):
                        start = int(ev.start_ns)
                        spans.append(HostSpan(name, start,
                                              start + int(ev.duration_ns),
                                              dict(ev.stats)))
    return ops, spans


def load(trace_dir: str) -> ProgramTrace:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} traces under {trace_dir}")
    ops, spans = read_events(files[0])
    return ProgramTrace.build(ops, spans)

