"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy time,
per-program device time and labelled idle gaps.

Device planes are those named ``/device:TPU:<n>``. On each, a program run is
an event of the ``XLA Modules`` line, named after the jitted function
(``jit__relation_block_fused(<id>)`` for ``_relation_block_fused``); busy
time is the union of the ``XLA Ops`` events (of the modules where a plane
has no op line), clipped to the window. The window is the span of the
benchmark's own host annotation around the traced pass. An idle gap is
labelled by the shortest host event that covers it.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES = "XLA Modules"
OPS = "XLA Ops"
PASS_SPAN = "chipbench.pass"
_RUN_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Reduced:
    """What a trace says about one window. Times in seconds."""

    window_s: float
    busy_s: float                 # mean over the device planes
    n_devices: int
    program_s: Dict[str, float]   # function name -> summed device time
    op_s: Dict[str, float]        # "program:op" -> summed device time
    gaps: List[Tuple[str, float]]  # longest idle gaps, labelled

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_of(self, names: Iterable[str]) -> Optional[float]:
        """Summed device time of the programs named ``names`` (function
        names, without the ``jit_`` prefix), or None where none ran."""
        hit = [self.program_s[n] for n in names if n in self.program_s]
        return sum(hit) if hit else None


def program_name(event_name: str) -> str:
    """``jit__relation_block_fused(42)`` -> ``_relation_block_fused``."""
    name = _RUN_ID.sub("", event_name).strip()
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%fusion.8 = pred[262144]{...} fusion(...)`` -> ``fusion.8``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps_of(busy: Sequence[Interval], lo: float, hi: float
            ) -> List[Interval]:
    """Complement of disjoint sorted ``busy`` within ``[lo, hi]``."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def reduce(pd, n_gaps: int = 10) -> Optional[Reduced]:
    """Reduce a ``jax.profiler.ProfileData``; None where the trace holds no
    device plane or no device event inside the window."""
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    host_events = [ev for p in host for line in p.lines
                   for ev in _events(line)]
    spans = [(a, b) for n, a, b in host_events if n == PASS_SPAN]
    devices = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    per_dev = []
    for p in devices:
        lines = {line.name: list(_events(line)) for line in p.lines}
        if MODULES in lines or OPS in lines:
            per_dev.append(lines)
    if not per_dev:
        return None
    if spans:
        lo, hi = min(a for a, _ in spans), max(b for _, b in spans)
    else:
        evs = [e for d in per_dev for line in d.values() for e in line]
        lo, hi = min(a for _, a, _ in evs), max(b for _, _, b in evs)
    program_s: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    busy_total = 0.0
    all_busy: List[List[Interval]] = []
    for lines in per_dev:
        mods = [(n, a, b) for n, a, b in lines.get(MODULES, ())
                if b > lo and a < hi]
        ops = [(n, a, b) for n, a, b in lines.get(OPS, ())
               if b > lo and a < hi]
        for n, a, b in mods:
            k = program_name(n)
            program_s[k] = program_s.get(k, 0.0) + (b - a) * 1e-9
        starts = [a for _, a, _ in sorted(mods, key=lambda m: m[1])]
        by_start = sorted(mods, key=lambda m: m[1])
        for n, a, b in ops:
            k = op_name(n)
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and by_start[i][2] >= b:
                k = f"{program_name(by_start[i][0])}:{k}"
            op_s[k] = op_s.get(k, 0.0) + (b - a) * 1e-9
        busy = union(clip([(a, b) for _, a, b in (ops or mods)], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        all_busy.append(busy)
    if busy_total <= 0:
        return None
    # gaps where no device of the window was busy
    idle = gaps_of(union(iv for b in all_busy for iv in b), lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for a, b in idle[:n_gaps]:
        cover = [(e - s, n) for n, s, e in host_events
                 if s <= a and e >= b]
        labelled.append((min(cover)[1] if cover else "untraced",
                         (b - a) * 1e-9))
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total / len(per_dev) * 1e-9,
                   n_devices=len(per_dev), program_s=program_s, op_s=op_s,
                   gaps=labelled)


def top(d: Dict[str, float], n: int = 10) -> List[Tuple[str, float]]:
    return sorted(d.items(), key=lambda kv: -kv[1])[:n]
