"""Reduction of a profiler trace (``.xplane.pb``) to device intervals.

``read(path)`` turns the trace into plain events: per device plane the XLA
module executions and the XLA ops, and every host thread's spans. The rest
are pure functions of those events, so a test can check them on a small
recorded trace and every later run computes each number the same way.

Times are nanoseconds on the trace's one clock. A module is found by its XLA
module name (``jit_<function>``), with the program id suffix dropped.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    module: str = ""        # enclosing XLA module of a device op


@dataclasses.dataclass
class Trace:
    modules: List[Event]    # device module executions, all device planes
    ops: List[Event]        # device ops, all device planes
    host: List[Event]       # host-thread spans
    n_devices: int


def clean(name: str) -> str:
    """``jit_local_update(123)`` -> ``jit_local_update``."""
    return re.sub(r"\(\d+\)$", "", name.strip())


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CUSTOM" not in plane_name


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    modules, ops, host = [], [], []
    n_dev = 0
    for plane in data.planes:
        if _is_device(plane.name):
            n_dev += 1
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    modules += [Event(clean(e.name), e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
                elif line.name in OP_LINES:
                    ops += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.duration_ns > 0]
    shift = clock_offset(modules, host)
    for e in modules + ops:
        e.start -= shift
        e.end -= shift
    _assign_modules(ops, modules)
    return Trace(modules=modules, ops=ops, host=host, n_devices=max(n_dev, 1))


def clock_offset(modules: Sequence[Event], host: Sequence[Event]) -> float:
    """Device clock minus host clock, in ns. A program starts on the device
    only after the host launched it, so for every program launched as often
    as it ran (host ``PjitFunction(f)``, device ``jit_f``), device start minus
    launch start bounds the offset from above; the least such bound is taken
    (it overstates the offset by the shortest launch latency)."""
    launches: Dict[str, List[float]] = {}
    ends: Dict[str, float] = {}
    for h in sorted(host, key=lambda h: (h.start, -h.end)):
        m = re.fullmatch(r"PjitFunction\((.+)\)", h.name)
        if m and h.end > ends.get(h.name, -1.0):     # one launch, however nested
            launches.setdefault(f"jit_{m.group(1)}", []).append(h.start)
            ends[h.name] = h.end
    runs: Dict[str, List[float]] = {}
    for e in modules:
        runs.setdefault(e.name, []).append(e.start)
    bounds = [d - h for name, hs in launches.items()
              if len(runs.get(name, ())) == len(hs)
              for d, h in zip(sorted(runs[name]), sorted(hs))]
    return min(bounds) if bounds else 0.0


def _assign_modules(ops: List[Event], modules: List[Event]) -> None:
    """Name each op's enclosing module execution (by time containment)."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    import bisect
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and mods[i].end >= op.end:
            op.module = mods[i].name


def union(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """Disjoint sorted union of ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which some device op ran, on any device plane
    (the union over planes: exact for the one-chip cells here)."""
    return sum(e - s for s, e in union(((o.start, o.end) for o in trace.ops), lo, hi))


def module_time(trace: Trace, names: Sequence[str], lo: float, hi: float) -> Tuple[float, int]:
    """(device ns, executions) of the modules whose name contains any of
    ``names``, counting executions that start inside [lo, hi]."""
    total, n = 0.0, 0
    for m in trace.modules:
        if lo <= m.start < hi and any(k in m.name for k in names):
            total += min(m.end, hi) - m.start
            n += 1
    return total, n


def window(trace: Trace, step_name: str = "round") -> Optional[Interval]:
    """[first start, last end] of the host spans named ``step_name``."""
    spans = [h for h in trace.host if h.name == step_name]
    if not spans:
        return None
    return min(h.start for h in spans), max(h.end for h in spans)


def short(op_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return op_name.split(" = ")[0].strip().lstrip("%")


def leaf_ops(ops: Sequence[Event]) -> List[Event]:
    """The ops that hold no other op: a loop's op spans its body's ops."""
    ordered = sorted(ops, key=lambda o: (o.start, -o.end))
    return [o for o, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or not (nxt.start >= o.start and nxt.end <= o.end)]


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> List[List]:
    """The k device ops that took most time, as [module:op, seconds]."""
    tot: Dict[str, float] = {}
    for o in leaf_ops(trace.ops):
        if lo <= o.start < hi:
            key = f"{o.module}:{short(o.name)}" if o.module else short(o.name)
            tot[key] = tot.get(key, 0.0) + (min(o.end, hi) - o.start)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in best]


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10,
              ignore: Sequence[str] = ("round",)) -> List[List]:
    """The k longest stretches with no device op, each named by the host
    span that overlaps it most among those not much longer than the gap,
    as [host span, seconds]."""
    busy = union(((o.start, o.end) for o in trace.ops), lo, hi)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    host = sorted((h for h in trace.host if h.name not in ignore), key=lambda h: h.start)
    out = []
    for s, e in gaps:
        best, best_ov = "untraced", 0.0
        for h in host:
            if h.start >= e:
                break
            ov = min(h.end, e) - max(h.start, s)
            if ov > best_ov and (h.end - h.start) <= 10 * (e - s):
                best, best_ov = h.name, ov
        out.append([best, (e - s) * 1e-9])
    return out
