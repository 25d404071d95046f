"""Device idle time split by the program layer that held it.

The program records host spans (``src/repro/core/spans.py``) named
``<layer>.<what>``, on the profiler's clock. This reduction reads the host
events whose base name (the text before the first ``#``, where TraceMe puts
its arguments) starts with one of :data:`LAYERS` and a dot, and sweeps
every device-idle interval of the window: each idle instant goes to the
*innermost* program span open at it, the one that started last among the
spans that contain it, on any host line. Instants that no program span
covers are ``idle_untraced_s``.

The window, the device planes and busy time are ``chipbench/xplane.py``'s,
so on one trace the layers and the untraced rest sum to ``idle_share *
window_s`` of :func:`xplane.reduce` (each device's idle time is split, then
the devices are averaged, as busy time is). A trace without program spans,
such as one of a program that records none, reads all its idle time as
untraced.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

from . import xplane

# the program's layers (src/repro/core/spans.py LAYERS), copied: the
# benchmark imports the program only in drive.py
LAYERS = ("driver", "consumer", "engine", "completion")

Span = Tuple[float, float, str]       # start ns, end ns, base name


@dataclasses.dataclass
class LayerSplit:
    """Seconds of one window; idle times are means over the devices."""

    window_s: float
    idle_s: float
    idle_by_layer: Dict[str, float]
    idle_untraced_s: float
    idle_by_span: Dict[str, float]     # innermost span name -> idle s
    count: Dict[str, int]              # span name -> spans in the window
    self_s: Dict[str, float]           # span name -> own s (less children)


def base_name(event_name: str) -> str:
    """``engine.dispatch#relation=VT#`` -> ``engine.dispatch``."""
    return event_name.split("#", 1)[0]


def layer_of(name: str) -> Optional[str]:
    """The layer of a program span's base name, None for other events."""
    head, dot, _ = name.partition(".")
    return head if dot and head in LAYERS else None


def innermost_timeline(spans: Sequence[Span]) -> List[Span]:
    """Disjoint sorted ``(a, b, name)`` pieces: over each, the innermost
    open span is ``name``. Instants no span covers are left out."""
    events = sorted({t for a, b, _ in spans for t in (a, b)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][0])
    heap: List[Tuple[float, float, int]] = []   # (-start, end, index)
    out: List[Span] = []
    j = 0
    for t, t_next in zip(events, events[1:]):
        while j < len(order) and spans[order[j]][0] <= t:
            a, b, _ = spans[order[j]]
            heapq.heappush(heap, (-a, b, order[j]))
            j += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if heap:
            name = spans[heap[0][2]][2]
            if out and out[-1][2] == name and out[-1][1] == t:
                out[-1] = (out[-1][0], t_next, name)
            else:
                out.append((t, t_next, name))
    return out


def attribute(idle: Sequence[xplane.Interval], timeline: Sequence[Span]
              ) -> Tuple[Dict[str, float], float]:
    """Split disjoint sorted ``idle`` intervals over an innermost
    ``timeline``: ns per span name, and the ns no piece covers."""
    by: Dict[str, float] = {}
    covered = 0.0
    k = 0
    for a, b in idle:
        while k < len(timeline) and timeline[k][1] <= a:
            k += 1
        i = k
        while i < len(timeline) and timeline[i][0] < b:
            lo, hi = max(a, timeline[i][0]), min(b, timeline[i][1])
            if hi > lo:
                name = timeline[i][2]
                by[name] = by.get(name, 0.0) + (hi - lo)
                covered += hi - lo
            i += 1
    return by, sum(b - a for a, b in idle) - covered


def self_times(lines: Sequence[Sequence[Span]]) -> Dict[str, float]:
    """ns per span name of each span's duration less what its program-span
    children on the same host line cover (one line's spans nest)."""
    out: Dict[str, float] = {}
    for line in lines:
        stack: List[List] = []    # [end, name, own]
        for a, b, name in sorted(line, key=lambda s: (s[0], -s[1])):
            while stack and stack[-1][0] <= a:
                _, n, own = stack.pop()
                out[n] = out.get(n, 0.0) + own
            if stack:
                stack[-1][2] -= min(b, stack[-1][0]) - a
            stack.append([b, name, b - a])
        for _, n, own in stack:
            out[n] = out.get(n, 0.0) + own
    return out


def split(pd) -> Optional[LayerSplit]:
    """Reduce a ``jax.profiler.ProfileData``; None where the trace holds no
    device plane or no device event inside the window (as
    :func:`xplane.reduce`)."""
    host_lines = [list(xplane._events(line)) for p in pd.planes
                  if p.name.startswith("/host:") for line in p.lines]
    passes = [(a, b) for line in host_lines for n, a, b in line
              if n == xplane.PASS_SPAN]
    devices = []
    for p in pd.planes:
        if xplane.DEVICE_PLANE.match(p.name):
            lines = {line.name: list(xplane._events(line))
                     for line in p.lines}
            if xplane.MODULES in lines or xplane.OPS in lines:
                devices.append(lines)
    if not devices:
        return None
    if passes:
        lo, hi = min(a for a, _ in passes), max(b for _, b in passes)
    else:
        evs = [e for d in devices for line in d.values() for e in line]
        lo, hi = min(a for _, a, _ in evs), max(b for _, _, b in evs)
    lines = [[(max(a, lo), min(b, hi), base_name(n)) for n, a, b in line
              if layer_of(base_name(n)) and b > lo and a < hi]
             for line in host_lines]
    spans = [s for line in lines for s in line]
    timeline = innermost_timeline(spans)
    by_span: Dict[str, float] = {}
    idle = untraced = 0.0
    for d in devices:
        ops, mods = ([(a, b) for _, a, b in d.get(k, ()) if b > lo and a < hi]
                     for k in (xplane.OPS, xplane.MODULES))
        busy = xplane.union(xplane.clip(ops or mods, lo, hi))
        gaps = xplane.gaps_of(busy, lo, hi)
        by, rest = attribute(gaps, timeline)
        for n, t in by.items():
            by_span[n] = by_span.get(n, 0.0) + t
        idle += sum(b - a for a, b in gaps)
        untraced += rest
    if idle >= len(devices) * (hi - lo):
        return None
    scale = 1e-9 / len(devices)
    by_layer = {layer: 0.0 for layer in LAYERS}
    for n, t in by_span.items():
        by_layer[layer_of(n)] += t * scale
    count: Dict[str, int] = {}
    for _, _, n in spans:
        count[n] = count.get(n, 0) + 1
    return LayerSplit(
        window_s=(hi - lo) * 1e-9, idle_s=idle * scale,
        idle_by_layer=by_layer, idle_untraced_s=untraced * scale,
        idle_by_span={n: t * scale for n, t in by_span.items()},
        count=count,
        self_s={n: t * 1e-9 for n, t in self_times(lines).items()})
