"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy time,
per-program and per-operation device time, and idle gaps labelled by the
harness span that was open on the host.

Planes whose name starts with ``/device:`` are devices.  On each, the
``XLA Ops`` line holds one event per operation executed and the
``XLA Modules`` line one per program execution.  Host spans are the
events of the host planes whose names the harness writes with
``jax.profiler.TraceAnnotation``.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "harness.window"
HOST_SPANS = ("online.step", "harness.refill", "submit", "readback",
              WINDOW_SPAN)
# operations that contain other operations' events on the same line
CONTAINERS = ("while", "conditional", "call")
# the Pallas paged decode-attention kernel, by the name of its jitted entry
PAGED_KERNEL = "paged_decode_attention"


def op_name(name: str) -> str:
    """The instruction name of an ``XLA Ops`` event (a TPU trace names an
    operation by its whole HLO instruction: ``%fusion.3 = bf16[...]``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_paged_kernel(event) -> bool:
    return PAGED_KERNEL in op_name(event.name)


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    text: str = ""              # the event's string stats, for matching

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_xplane(path: str) -> List[Event]:
    """Every event of every plane of one trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        keep_text = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                text = ""
                if keep_text:
                    text = " ".join(str(v) for _, v in ev.stats
                                    if isinstance(v, str))
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 text))
    return out


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {paths}")
    return paths[0]


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merged, sorted [start, end] intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: List[List[float]], lo: float, hi: float
         ) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def overlap(a: List[List[float]], b: List[List[float]]) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class Reduction:
    window_ns: Tuple[float, float]
    devices: List[str]
    busy_intervals: Dict[str, List[List[float]]]  # per device, merged
    programs: Dict[str, List[float]]              # name -> [ns, count]
    ops: Dict[str, float]                         # name -> ns (all devices)
    op_events: List[Event] = field(default_factory=list)
    module_events: List[Event] = field(default_factory=list)
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    idle_by_span: Dict[str, float] = field(default_factory=dict)

    @property
    def busy_ns(self) -> Dict[str, float]:
        """Busy nanoseconds per device."""
        return {d: sum(e - s for s, e in iv)
                for d, iv in self.busy_intervals.items()}

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Device busy seconds, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.devices) * 1e-9

    def kernel_s(self, match) -> Tuple[float, int]:
        """Seconds and count of the operations ``match(event)`` selects."""
        sel = [e for e in self.op_events if match(e)]
        return sum(e.dur_ns for e in sel) * 1e-9, len(sel)

    def program_s(self, names) -> Tuple[float, int]:
        """Seconds and executions of the programs named in ``names``."""
        ns = n = 0
        for name in names:
            t, c = self.programs.get(name, (0.0, 0))
            ns += t
            n += c
        return ns * 1e-9, int(n)

    def programs_running(self, match) -> List[str]:
        """Programs any of whose executions contain an operation that
        ``match`` selects on the same device."""
        ops: Dict[str, list] = defaultdict(list)
        for e in self.op_events:
            if match(e):
                ops[e.plane].append((e.start_ns, e.end_ns))
        for v in ops.values():
            v.sort()
        out = set()
        for m in self.module_events:
            on = ops.get(m.plane, [])
            i = bisect.bisect_left(on, (m.start_ns,))
            if i < len(on) and on[i][1] <= m.end_ns:
                out.add(m.name)
        return sorted(out)

    def breakdown(self, k: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, t * 1e-9] for n, t in ops],
                "idle_gaps": [[lab, t] for lab, t in self.gaps[:k]]}


class _Spans:
    """Innermost open host span at a time (spans nest or are disjoint)."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted(spans, key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.spans]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        for j in range(i, max(-1, i - 64), -1):
            if self.spans[j].end_ns >= t:
                return self.spans[j].name
        return "outside harness spans"


def reduce(events: Sequence[Event], window_ns: Optional[Tuple] = None
           ) -> Reduction:
    """Busy union per device, program and operation times and labelled idle
    gaps, all clipped to the window (the harness's window span unless
    given)."""
    spans = [e for e in events if not e.plane.startswith(DEVICE_PREFIX)
             and e.name in HOST_SPANS]
    if window_ns is None:
        win = [e for e in spans if e.name == WINDOW_SPAN]
        if len(win) != 1:
            raise RuntimeError(f"expected one {WINDOW_SPAN!r} span, found "
                               f"{len(win)}")
        window_ns = (win[0].start_ns, win[0].end_ns)
    lo, hi = window_ns
    devices = sorted({e.plane for e in events
                      if e.plane.startswith(DEVICE_PREFIX)
                      and e.line == OPS_LINE})
    busy: Dict[str, List[List[float]]] = {}
    ops: Dict[str, float] = defaultdict(float)
    op_events: List[Event] = []
    gaps: List[Tuple[str, float]] = []
    label = _Spans([s for s in spans if s.name != WINDOW_SPAN])
    idle_by = defaultdict(float)
    for dev in devices:
        evs = [e for e in events if e.plane == dev and e.line == OPS_LINE
               and e.end_ns > lo and e.start_ns < hi]
        merged = clip(union((e.start_ns, e.end_ns) for e in evs), lo, hi)
        busy[dev] = merged
        for e in evs:
            name = op_name(e.name)
            if not name.startswith(CONTAINERS):
                ops[name] += min(e.end_ns, hi) - max(e.start_ns, lo)
        op_events.extend(evs)
        if dev == devices[0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    name = label.at((s + e) / 2)
                    idle_by[name] += (e - s) * 1e-9
                    gaps.append((name, (e - s) * 1e-9))
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    module_events = [e for e in events if e.plane.startswith(DEVICE_PREFIX)
                     and e.line == MODULES_LINE
                     and lo <= (e.start_ns + e.end_ns) / 2 <= hi]
    for e in module_events:
        programs[e.name][0] += e.dur_ns
        programs[e.name][1] += 1
    gaps.sort(key=lambda g: -g[1])
    return Reduction(window_ns=(lo, hi), devices=devices,
                     busy_intervals=busy,
                     programs=dict(programs), ops=dict(ops),
                     op_events=op_events, module_events=module_events,
                     gaps=gaps[:64],
                     idle_by_span=dict(idle_by))


def describe(events: Sequence[Event], k: int = 12) -> dict:
    """Planes, lines, event counts and the most frequent names: what to
    look at before writing a matcher against a new trace."""
    by: Dict[Tuple[str, str], Dict[str, int]] = defaultdict(
        lambda: defaultdict(int))
    for e in events:
        by[(e.plane, e.line)][e.name] += 1
    out = {}
    for (plane, line), names in sorted(by.items()):
        top = sorted(names.items(), key=lambda kv: -kv[1])[:k]
        out[f"{plane} | {line}"] = {"events": sum(names.values()),
                                    "top": top}
    return out
