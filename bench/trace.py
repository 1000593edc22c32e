"""Reduce a profiler trace to busy and idle time, per-span device time,
per-module time, and idle gaps attributed to what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` alone.  Device operations are the events
on the ``XLA Ops`` line of each ``/device:`` plane, named by the module
event (``XLA Modules`` line) that contains them.  On a host without an
accelerator the CPU client's operations (events with an ``hlo_op`` stat on
the host plane) stand in for them, so the same code can be tested on a
trace recorded on the CPU.  Host spans are the ``TraceAnnotation`` events
whose names start with one of ``SPAN_PREFIXES``: the benchmark's own spans
around each call into the program, and the program's own.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["Trace", "load", "merge", "covered", "find_xplane"]

SPAN_PREFIXES = ("bench.", "fl.", "serve.")
CONTAINERS = ("%while", "%conditional", "%cond", "%call", "while", "conditional")
HOST_CALL_PREFIX = "PjitFunction("

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    start: float  # ns
    end: float
    name: str
    module: str
    device: str


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Tuple[float, float, str]]  # host annotations, ns
    host_calls: List[Tuple[float, float, str]]  # jitted-call dispatches, ns
    devices: List[str]
    modules: List[Tuple[float, float, str]] = dataclasses.field(default_factory=list)

    # ----------------------------------------------------------- intervals
    def busy(self, device: Optional[str] = None) -> List[Interval]:
        """Merged intervals in which an operation ran (on ``device``, or on
        any device)."""
        cache = self.__dict__.setdefault("_busy", {})
        if device not in cache:
            cache[device] = merge((o.start, o.end) for o in self.ops
                                  if device is None or o.device == device)
        return cache[device]

    def span_intervals(self, name: str) -> List[Interval]:
        return merge((s, e) for s, e, n in self.spans if n == name)

    def window(self, name: str = "bench.window") -> Interval:
        """The traced window: the benchmark's window span, else the extent
        of the device operations."""
        w = [(s, e) for s, e, n in self.spans if n == name]
        if w:
            return w[0]
        if not self.ops:
            raise ValueError("trace holds no window span and no device operation")
        return min(o.start for o in self.ops), max(o.end for o in self.ops)

    # ------------------------------------------------------------- metrics
    def busy_seconds(self, lo: float, hi: float) -> float:
        """Device-busy seconds in [lo, hi], averaged over the devices."""
        devs = self.devices or [None]
        return sum(covered(self.busy(d), [(lo, hi)]) for d in devs) / len(devs) / 1e9

    def busy_in_spans(self, name: str) -> float:
        """Device-busy seconds inside the host spans called ``name``,
        averaged over the devices."""
        spans = self.span_intervals(name)
        devs = self.devices or [None]
        return sum(covered(self.busy(d), spans) for d in devs) / len(devs) / 1e9

    def module_seconds(self, patterns: Sequence[str]) -> Dict[str, float]:
        """Device seconds of the executions of the modules whose name
        contains one of ``patterns``, keyed by the pattern (summed over
        devices, then averaged).  Without module events (the CPU), the
        union of the modules' operations."""
        out = {p: 0.0 for p in patterns}
        for p in patterns:
            if self.modules:
                out[p] = sum(e - s for s, e, n in self.modules if p in n) / 1e9
            else:
                out[p] = sum(e - s for s, e in merge(
                    (o.start, o.end) for o in self.ops if p in o.module)) / 1e9
        n = max(1, len(self.devices))
        return {p: v / n for p, v in out.items()}

    def modules_in_spans(self, name: str) -> List[str]:
        """Names of the modules whose operations ran inside spans ``name``."""
        spans = self.span_intervals(name)
        starts = [s for s, _ in spans]
        seen = set()
        for o in self.ops:
            i = bisect.bisect_right(starts, o.start) - 1
            if i >= 0 and o.start < spans[i][1]:
                seen.add(o.module)
        return sorted(seen)

    def top_ops(self, lo: float, hi: float, n: int = 10) -> List[list]:
        """The ``n`` device operations (module:op) that took most time; a
        loop or conditional, which holds other operations, is not one."""
        acc: Dict[str, float] = defaultdict(float)
        for o in self.ops:
            if o.end > lo and o.start < hi and not o.name.startswith(CONTAINERS):
                acc[f"{o.module}:{o.name}"] += (min(o.end, hi) - max(o.start, lo)) / 1e9
        n_dev = max(1, len(self.devices))
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / n_dev] for k, v in top]

    def idle_gaps(self, lo: float, hi: float, n: int = 10) -> List[list]:
        """Idle device time in [lo, hi], summed by what the host was doing
        during each gap (innermost span, then the jitted call in flight at
        the gap's middle), the ``n`` largest."""
        acc: Dict[str, float] = defaultdict(float)
        devs = self.devices or [None]
        spans = Timeline([x for x in self.spans if x[2] != "bench.window"])
        calls = Timeline(self.host_calls)
        for d in devs:
            for a, b in gaps(self.busy(d), lo, hi):
                t = (a + b) / 2
                span, call = spans.at(t) or "no span", calls.at(t)
                acc[f"{span}/{call}" if call else span] += (b - a) / 1e9
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / len(devs)] for k, v in top]


class Timeline:
    """The innermost (shortest) of possibly nested named intervals at any
    time, looked up by bisection."""

    def __init__(self, intervals: Sequence[Tuple[float, float, str]]):
        points = sorted({p for s, e, _ in intervals for p in (s, e)})
        starts = defaultdict(list)
        ends = defaultdict(list)
        for iv in intervals:
            starts[iv[0]].append(iv)
            ends[iv[1]].append(iv)
        active: Dict[Tuple[float, float, str], int] = {}
        self.points, self.labels = points, []
        for p in points:
            for iv in ends[p]:
                active.pop(iv, None)
            for iv in starts[p]:
                if iv[1] > iv[0]:
                    active[iv] = 1
            self.labels.append(min(active, key=lambda iv: iv[1] - iv[0])[2]
                               if active else None)

    def at(self, t: float) -> Optional[str]:
        i = bisect.bisect_right(self.points, t) - 1
        return self.labels[i] if i >= 0 else None


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(busy: List[Interval], spans: List[Interval]) -> float:
    """Length of the intersection of two unions of disjoint sorted
    intervals (ns)."""
    total, i, j = 0.0, 0, 0
    spans = merge(spans)
    while i < len(busy) and j < len(spans):
        lo = max(busy[i][0], spans[j][0])
        hi = min(busy[i][1], spans[j][1])
        if hi > lo:
            total += hi - lo
        if busy[i][1] < spans[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] between the busy ones."""
    out, t = [], lo
    for s, e in busy:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or the directory the profiler wrote)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops: List[Op] = []
    modules: List[Tuple[float, float, str]] = []
    spans: List[Tuple[float, float, str]] = []
    calls: List[Tuple[float, float, str]] = []
    devices: List[str] = []
    host_ops: List[Op] = []
    # a device plane without operations (a TPU library loaded in a process
    # whose work ran on the CPU) is no accelerator
    accelerator = any(
        p.name.startswith("/device:") and not p.name.startswith("/device:CPU")
        and any(line.name == "XLA Ops" and any(True for _ in line.events) for line in p.lines)
        for p in data.planes)
    for plane in data.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            lines = {line.name: line for line in plane.lines}
            if not accelerator or "XLA Ops" not in lines:
                continue
            devices.append(plane.name)
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, _short_module(e.name))
                          for e in (lines["XLA Modules"].events if "XLA Modules" in lines else ()))
            modules.extend(mods)
            mod_starts = [m[0] for m in mods]
            for e in lines["XLA Ops"].events:
                s = e.start_ns
                i = bisect.bisect_right(mod_starts, s) - 1
                module = mods[i][2] if i >= 0 and s < mods[i][1] else ""
                ops.append(Op(s, s + e.duration_ns, _short_op(e.name), module, plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    s, d = e.start_ns, e.duration_ns
                    if name.startswith(SPAN_PREFIXES):
                        spans.append((s, s + d, name))
                    elif name.startswith(HOST_CALL_PREFIX):
                        calls.append((s, s + d, name[len(HOST_CALL_PREFIX):-1]))
                    elif not accelerator and d > 0 and not name.startswith("end: "):
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            host_ops.append(Op(s, s + d, str(stats["hlo_op"]),
                                               str(stats.get("hlo_module", "")),
                                               "/host:CPU"))
    if not devices and host_ops:
        ops, devices = host_ops, ["/host:CPU"]
    return Trace(ops=ops, spans=sorted(spans), host_calls=sorted(calls), devices=devices,
                 modules=sorted(modules))


def _short_module(name: str) -> str:
    """``jit_f(1234)`` -> ``jit_f``: the program's name without its hash."""
    return name.split("(", 1)[0]


def _short_op(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0]
