"""The device's idle time split among the program's own host spans.

The program names its phases with profiler annotations: ``fl.round`` around
each round and ``phase.*`` around each host phase (``repro.obs.telemetry``),
nested as the calls nest. Each instant of a span belongs to the innermost
program span open then (its self time). Every stretch of ``[lo, hi]`` in
which no device op runs goes to the program span that owned that instant,
or to none, and the spans are grouped into the host layers that the
per-layer ``*_idle_ms`` metrics report. The layers partition the window's
idle time (the union of device ops over all device planes: exact for one
chip, as ``idle_pct``).

Pure functions of ``Trace.host`` and ``Trace.ops``: ``device_trace.read``
already puts the device's events on the host spans' clock. A window that
holds no program span gives ``None``.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from device_trace import Event, Interval, union

ROUND_SPAN = "fl.round"
PHASE_PREFIX = "phase."
NO_SPAN = "(none)"

# host layer -> the program spans whose self time is that layer's; every
# other program span, fl.round's own self time and idle under no program
# span belong to the round loop
LAYERS: Dict[str, Tuple[str, ...]] = {
    "local_update": ("phase.local_update",),
    "encode": ("phase.uplink", "phase.uplink_decode"),
    "aggregate": ("phase.aggregate", "phase.compensatory", "phase.weight_solve",
                  "phase.accumulate", "phase.flush"),
}
LOOP = "loop"


def is_program(name: str) -> bool:
    return name == ROUND_SPAN or name.startswith(PHASE_PREFIX)


def program_spans(host: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """The program's spans that overlap [lo, hi]."""
    return [h for h in host if is_program(h.name) and h.end > lo and h.start < hi]


def self_intervals(spans: Sequence[Event]) -> List[Tuple[str, float, float]]:
    """(name, start, end) stretches in which each span is the innermost open
    one: a span minus the parts its children cover. The stretches are
    disjoint, in order, and cover exactly the union of the spans; where two
    spans overlap without nesting, the later one owns the overlap."""
    out: List[Tuple[str, float, float]] = []
    stack: List[Event] = []
    cursor = float("-inf")

    def own(name: str, end: float) -> None:
        nonlocal cursor
        if end > cursor:
            out.append((name, cursor, end))
            cursor = end

    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= sp.start:
            top = stack.pop()
            own(top.name, top.end)
        if stack:
            own(stack[-1].name, sp.start)
        cursor = max(cursor, sp.start)
        stack.append(sp)
    while stack:
        top = stack.pop()
        own(top.name, top.end)
    return out


def idle_intervals(ops: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] in which no device op runs."""
    out, prev = [], lo
    for s, e in union(((o.start, o.end) for o in ops), lo, hi):
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        out.append((prev, hi))
    return out


def idle_by_span(host: Iterable[Event], ops: Iterable[Event], lo: float,
                 hi: float) -> Optional[Dict[str, float]]:
    """Device-idle ns in [lo, hi] by the program span that owned each idle
    instant (``NO_SPAN`` where none was open), or None without program
    spans."""
    spans = program_spans(host, lo, hi)
    if not spans:
        return None
    owned = [(n, max(s, lo), min(e, hi)) for n, s, e in self_intervals(spans)
             if min(e, hi) > max(s, lo)]
    out: Dict[str, float] = {NO_SPAN: 0.0}
    i = 0
    for s, e in idle_intervals(ops, lo, hi):
        rest = e - s
        while i < len(owned) and owned[i][2] <= s:
            i += 1
        j = i
        while j < len(owned) and owned[j][1] < e:
            name, a, b = owned[j]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                rest -= ov
            j += 1
        out[NO_SPAN] += rest
    return out


def layer_idle_ns(host: Iterable[Event], ops: Iterable[Event], lo: float,
                  hi: float) -> Optional[Dict[str, float]]:
    """Device-idle ns in [lo, hi] by host layer (``LAYERS`` and ``LOOP``)."""
    by_span = idle_by_span(host, ops, lo, hi)
    if by_span is None:
        return None
    layer_of = {name: layer for layer, names in LAYERS.items() for name in names}
    out = dict.fromkeys(list(LAYERS) + [LOOP], 0.0)
    for name, ns in by_span.items():
        out[layer_of.get(name, LOOP)] += ns
    return out


def layer_idle_ms(ctx, layer: str) -> Optional[float]:
    """A metric reader's value: device-idle ms per traced round in ``layer``."""
    by_layer = layer_idle_ns(ctx.trace.host, ctx.trace.ops, ctx.lo, ctx.hi)
    if by_layer is None or not ctx.rounds:
        return None
    return by_layer[layer] * 1e-6 / ctx.rounds
