"""Device time by the program's own stages, and the program's compile
markers, read from the raw trace a traced run writes.

``bench/trace.py`` reduces the ``.xplane.pb`` to busy and idle time, host
spans, jitted-call dispatches and modules.  This module reads the same
file for what that reduction leaves out:

* each device operation's **scope**: the innermost ``fl.*`` name on its
  op-name path, which ``jax.named_scope`` inside the program puts there
  (``jit(fl_run_many)/vmap()/while/body/.../fl.select/...``).  The
  operation events carry no such path (a TPU v5e's ``XLA Ops`` events
  name their HLO instruction, the CPU client's events their instruction
  and program), so it is looked up in the HLO of the operation's program,
  which the profiler stores in the trace's ``/host:metadata`` plane;
* the program's **compile markers** (``obs.compile`` host events, one per
  backend compile or compile-cache load).

Readers find the file where ``bench/run.py`` records it
(:data:`TRACE_DIR`) and leaves it until the metrics are read.  Times share
``bench/trace.py``'s clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import os
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace as trace_lib

__all__ = ["Scopes", "load", "of", "hlo_op_names", "scope_of",
           "dispatches_in_spans", "span_idle_seconds", "TRACE_DIR", "MARKER"]

TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"
MARKER = "obs.compile"
HLO_STAT = "Hlo Proto"
CONTAINER_OPCODES = ("while", "conditional", "call")
_SCOPE = re.compile(r"fl\.[A-Za-z_]+")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``fl.*`` scope on an op-name path, or None."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


@dataclasses.dataclass
class Scopes:
    ops: List[Tuple[float, float, Optional[str], str]]  # start, end, scope, device
    devices: List[str]
    markers: List[float]  # start of each compile marker, ns

    def seconds(self, scope: Optional[str], lo: float, hi: float) -> float:
        """Device seconds in [lo, hi] of the operations in ``scope`` (any
        ``fl.*`` scope for None), averaged over the devices."""
        devs = self.devices or [None]
        total = 0.0
        for d in devs:
            busy = trace_lib.merge((s, e) for s, e, sc, dev in self.ops
                                   if sc is not None and (scope is None or sc == scope)
                                   and (d is None or dev == d))
            total += trace_lib.covered(busy, [(lo, hi)])
        return total / len(devs) / 1e9

    def scoped(self) -> bool:
        """Whether any operation carries an ``fl.*`` scope (a program
        without the named scopes has none)."""
        return any(sc is not None for _, _, sc, _ in self.ops)

    def markers_in(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.markers)


def load(path) -> Scopes:
    """Read an ``.xplane.pb`` (or the directory the profiler wrote)."""
    from jax.profiler import ProfileData

    path = str(path)
    if os.path.isdir(path):
        path = trace_lib.find_xplane(path)
    raw = Path(path).read_bytes()
    data = ProfileData.from_serialized_xspace(raw)
    hlo = hlo_op_names(raw)

    def lookup(program: str, instruction: str):
        instruction = instruction.lstrip("%")
        # the program itself, else any of its name: a trace can store one
        # HLO for programs that differ only in their arguments' signature
        for key in (program, program.split("(", 1)[0]):
            if (key, instruction) in hlo:
                return hlo[(key, instruction)]
        return "", ""

    accelerator = any(
        p.name.startswith("/device:") and not p.name.startswith("/device:CPU")
        and any(line.name == "XLA Ops" and any(True for _ in line.events) for line in p.lines)
        for p in data.planes)
    ops, devices, host_ops, markers = [], [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            lines = {line.name: line for line in plane.lines}
            if not accelerator or "XLA Ops" not in lines:
                continue
            devices.append(plane.name)
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in (lines["XLA Modules"].events if "XLA Modules" in lines
                                    else ()))
            starts = [m[0] for m in mods]
            for e in lines["XLA Ops"].events:
                s = e.start_ns
                i = bisect.bisect_right(starts, s) - 1
                program = mods[i][2] if i >= 0 and s < mods[i][1] else ""
                name = trace_lib._short_op(e.name)
                ops.append(_op(s, e.duration_ns, program, name, plane.name, lookup))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARKER:
                        markers.append(e.start_ns)
                    elif not accelerator and e.duration_ns > 0:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            host_ops.append(_op(e.start_ns, e.duration_ns,
                                                f"{stats.get('hlo_module', '')}"
                                                f"({stats.get('program_id', '')})",
                                                str(stats["hlo_op"]), "/host:CPU", lookup))
    if not devices and host_ops:
        ops, devices = host_ops, ["/host:CPU"]
    return Scopes(ops=[o for o in ops if o is not None], devices=devices,
                  markers=sorted(markers))


def _op(start, duration, program, name, device, lookup):
    """One leaf operation as (start, end, scope, device); None for a loop
    or conditional, whose time is its body's."""
    opcode, op_name = lookup(program, name)
    if opcode in CONTAINER_OPCODES or (not opcode and name.startswith(trace_lib.CONTAINERS)):
        return None
    return (start, start + duration, scope_of(op_name), device)


# ------------------------------------------------ the trace's stored HLO


def hlo_op_names(xspace: bytes) -> Dict[Tuple[str, str], Tuple[str, str]]:
    """(program, instruction name) -> (opcode, op_name) for every
    instruction of every program whose HLO the trace stores (an
    :data:`HLO_STAT` stat on the ``/host:metadata`` plane's event
    metadata).  A program is keyed both as its module events name it
    (``jit_f(1234)``) and by its module's name alone (``jit_f``)."""
    out: Dict[Tuple[str, str], Tuple[str, str]] = {}
    for num, value in _fields(memoryview(xspace)):
        if num != 1:  # XSpace.planes
            continue
        plane = list(_fields(value))
        if not any(n == 2 and bytes(v) == b"/host:metadata" for n, v in plane):
            continue
        stat_names = {}
        for n, v in plane:  # XPlane.stat_metadata: map<int64, XStatMetadata>
            if n == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[meta.get(1)] = bytes(meta.get(2, b"")).decode()
        for n, v in plane:  # XPlane.event_metadata: map<int64, XEventMetadata>
            if n != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            program = bytes(dict(meta).get(2, b"")).decode()
            for m, stat in meta:
                if m != 5:  # XEventMetadata.stats
                    continue
                fields = dict(_fields(stat))
                if stat_names.get(fields.get(1)) == HLO_STAT and 6 in fields:
                    for name, opcode, op_name in _instructions(fields[6]):
                        out[(program, name)] = (opcode, op_name)
                        out.setdefault((program.split("(", 1)[0], name), (opcode, op_name))
    return out


def _instructions(hlo_proto) -> Iterator[Tuple[str, str, str]]:
    """(name, opcode, op_name) of each instruction of an ``HloProto``.  A
    fusion that carries no op name of its own (the CPU compiler's
    single-operation fusions) takes its fused computation's: the root's,
    else the last one named there."""
    module = dict(_fields(hlo_proto)).get(1, b"")  # HloProto.hlo_module
    insts, named = [], {}  # named: computation id -> its op name
    for n, comp in _fields(module):
        if n != 3:  # HloModuleProto.computations
            continue
        fields = list(_fields(comp))
        head = dict(fields)  # HloComputationProto: id 5, root_id 6
        last = root = ""
        for m, inst in fields:
            if m != 2:  # HloComputationProto.instructions
                continue
            name = opcode = op_name = ""
            ident, called = None, []
            for k, v in _fields(inst):
                if k == 1:
                    name = bytes(v).decode()
                elif k == 2:
                    opcode = bytes(v).decode()
                elif k == 7:  # OpMetadata; op_name is its field 2
                    op_name = bytes(dict(_fields(v)).get(2, b"")).decode()
                elif k == 35:
                    ident = v
                elif k == 38:  # called_computation_ids, packed or not
                    called += [v] if isinstance(v, int) else _packed(v)
            insts.append((name, opcode, op_name, called))
            last = op_name or last
            if ident is not None and ident == head.get(6):
                root = op_name
        named[head.get(5)] = root or last
    for name, opcode, op_name, called in insts:
        if not op_name and opcode == "fusion" and called:
            op_name = named.get(called[0], "")
        yield name, opcode, op_name


def _packed(buf) -> List[int]:
    out, i = [], 0
    while i < len(buf):
        value, i = _varint(buf, i)
        out.append(value)
    return out


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one serialized protobuf message: an int for
    a varint, a memoryview of the bytes otherwise."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value, i = buf[i:i + width], i + width
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


# ----------------------------------------------------- for the readers


def of(ctx) -> Optional[Scopes]:
    """The scopes and markers of the metric context's trace, read once per
    context; None where the raw trace is not to be found."""
    cache = ctx.__dict__
    if "scopes" not in cache:
        try:
            cache["scopes"] = load(TRACE_DIR)
        except FileNotFoundError:
            cache["scopes"] = None
    return cache["scopes"]


def dispatches_in_spans(trace, name: str) -> int:
    """Jitted calls the host dispatched inside the host spans called
    ``name``, each counted once: a dispatch can show as nested
    ``PjitFunction`` events, so only those outside another count."""
    spans = trace.span_intervals(name)
    starts = [s for s, _ in spans]
    count, end = 0, float("-inf")
    for s, e, _ in sorted(trace.host_calls):
        if s < end:
            continue
        end = e
        i = bisect.bisect_right(starts, s) - 1
        count += i >= 0 and s < spans[i][1]
    return count


def span_idle_seconds(trace, name: str) -> float:
    """Device-idle seconds inside the host spans called ``name``, averaged
    over the devices."""
    spans = trace.span_intervals(name)
    return sum(e - s for s, e in spans) / 1e9 - trace.busy_in_spans(name)
