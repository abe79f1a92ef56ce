"""From a profiler trace to device busy time, idle gaps and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX's
own reader: the operations each device ran (its ``XLA Ops`` line) and the
host spans the benchmark opened with ``jax.profiler.TraceAnnotation``.
Both are on one clock.  The rest works on those plain lists:

* busy time is the union of the device's operation intervals inside the
  window; the idle share is one minus busy over the window;
* an idle gap is a stretch of the window with no operation on the device,
  labelled by the innermost benchmark span that covers its middle;
* a kernel's time is the summed duration of its events.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[str, float, float]  # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
WINDOW = "window"
SPANS = ("admit", "prefill", "decode", "read_token", "tune", "clear_caches")


@dataclass
class Trace:
    devices: Dict[str, List[Interval]] = field(default_factory=dict)
    spans: List[Interval] = field(default_factory=list)

    def window(self) -> Tuple[float, float]:
        ws = [s for s in self.spans if s[0] == WINDOW]
        if len(ws) != 1:
            raise ValueError(f"expected one {WINDOW!r} span, found {len(ws)}")
        return ws[0][1], ws[0][2]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {trace_dir}")
    return paths[0]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def load(path: str, span_names: Iterable[str] = (WINDOW,) + SPANS) -> Trace:
    """Device operations and benchmark spans of one trace.  On the CPU
    backend, which has no device plane, the operations are the host events
    that name an XLA module."""
    from jax.profiler import ProfileData

    names = set(span_names)
    out = Trace()
    planes = list(ProfileData.from_file(path).planes)
    cpu_backend = not any(_is_device(p.name) and any(
        line.name == OPS_LINE for line in p.lines) for p in planes)
    cpu_ops: List[Interval] = []
    for plane in planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.devices[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        out.spans.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
                    elif cpu_backend and any(k == "hlo_module"
                                             for k, _ in e.stats):
                        cpu_ops.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
    if cpu_ops:
        out.devices["/host:CPU"] = cpu_ops
    return out


def clip(events: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: Sequence[Interval]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(events, lo, hi)))


def gaps(events: Sequence[Interval], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_by_span(events, spans, lo, hi) -> Dict[str, float]:
    """Idle seconds inside [lo, hi), summed by what the host was doing:
    the benchmark span that covers a gap's middle.  The benchmark's spans
    inside the window follow one another and never nest."""
    inner = sorted((sp for sp in spans if sp[0] != WINDOW),
                   key=lambda sp: sp[1])
    starts = [sp[1] for sp in inner]
    out: Dict[str, float] = defaultdict(float)
    for s, e in gaps(events, lo, hi):
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid) - 1
        name = (inner[i][0] if i >= 0 and mid < inner[i][2]
                else "outside_spans")
        out[name] += (e - s) * 1e-9
    return dict(out)


#: operations whose events enclose the events of the operations they run
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """A TPU trace names an operation by its whole HLO instruction
    (``%fusion.12 = bf16[8,128]{...} fusion(...), ...``): keep the
    instruction's name and the type of its result.  One instruction of a
    layer scan's body runs once per layer under the same name."""
    lhs, _, rhs = event_name.partition(" = ")
    name = lhs.lstrip("%")
    if not rhs:
        return name
    result = rhs.split("{", 1)[0].split(" ", 1)[0]
    return f"{name} {result}" if not result.startswith("(") else name


def op_seconds(events, lo, hi) -> Dict[str, float]:
    """Device seconds inside [lo, hi) by operation, leaving out control
    flow, whose events enclose the operations they run."""
    out: Dict[str, float] = defaultdict(float)
    for n, s, e in clip(events, lo, hi):
        name = op_name(n)
        if re.sub(r"\.\d+$", "", name.split(" ")[0]) not in CONTAINERS:
            out[name] += (e - s) * 1e-9
    return dict(out)


def matching_seconds(events, lo, hi, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(e - s for n, s, e in clip(events, lo, hi)
               if rx.search(n)) * 1e-9


@dataclass
class Reduced:
    """What the per-layer readers and the breakdown take from a trace."""
    window_s: float
    busy_s: float           # averaged over the devices
    device_events: List[Interval]  # the first device's, inside the window
    lo: float
    hi: float
    idle_by_span: Dict[str, float]
    op_seconds: Dict[str, float]

    def seconds_matching(self, pattern: str) -> float:
        return matching_seconds(self.device_events, self.lo, self.hi, pattern)


def align(trace: Trace, lo: float) -> Dict[str, List[Interval]]:
    """The device events on the host's clock.  In a TPU trace the device's
    clock runs a millisecond or so behind the host's, so a step's device
    events can appear before the host dispatched it.  Nothing runs on the
    device before the window opens (the benchmark starts the window on an
    idle device, right after starting the trace), so the events are moved
    later, where need be, until the first starts with the window."""
    first = min(ev[1] for evs in trace.devices.values() for ev in evs)
    shift = max(0.0, lo - first)
    return {d: [(n, s + shift, e + shift) for n, s, e in evs]
            for d, evs in trace.devices.items()}


def reduce(trace: Trace) -> Reduced:
    if not any(trace.devices.values()):
        raise ValueError("the trace holds no device operations")
    lo, hi = trace.window()
    devices = align(trace, lo)
    busy = [busy_ns(ev, lo, hi) for ev in devices.values()]
    events = clip(devices[sorted(devices)[0]], lo, hi)
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9,
                   device_events=events, lo=lo, hi=hi,
                   idle_by_span=idle_by_span(events, trace.spans, lo, hi),
                   op_seconds=op_seconds(events, lo, hi))


def breakdown(r: Reduced, n: int = 10) -> Dict[str, List[List]]:
    ops = sorted(r.op_seconds.items(), key=lambda kv: -kv[1])[:n]
    idle = sorted(r.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
