"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: per chip the union of device-op intervals inside the measured
window (busy), the idle share, time per device op, and the idle gaps
named by the host span that was open during each.

The window is the host span ``bench.window`` that ``cellrun.py`` opens
around the measured calls. Device ops are the events of each TPU
plane's ``XLA Ops`` line. Host spans are the library's own
TraceAnnotations (``accl.<op>``, ``req.<op>.wait``, ...) and the
benchmark's, on the host plane; a gap is named by the innermost span
that holds its middle: ``bench.window`` where the benchmark's own loop
ran between calls, ``untraced`` outside every span.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: host spans that can name an idle gap
SPAN_PREFIXES = ("accl.", "req.", "xsend.", "xrecv.", "cmdlist.",
                 "fabric.", "bench.")
TOP = 10

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    window_s: float
    #: chip index -> seconds in which an op ran, inside the window
    busy_s: Dict[int, float]
    #: op name -> seconds, summed over chips
    op_s: Dict[str, float] = field(default_factory=dict)
    #: host span -> idle seconds, summed over chips
    gap_s: Dict[str, float] = field(default_factory=dict)
    #: host span -> calls of that span inside the window
    span_count: Dict[str, int] = field(default_factory=dict)

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / max(self.chips, 1)

    @property
    def busy_max_s(self) -> float:
        return max(self.busy_s.values(), default=0.0)

    def idle_share(self, busiest: bool = False) -> float:
        busy = self.busy_max_s if busiest else self.busy_mean_s
        return 1.0 - busy / self.window_s

    def breakdown(self) -> dict:
        c = max(self.chips, 1)
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v / c] for k, v in ops],
                "idle_gaps": [[k, v / c] for k, v in gaps]}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class SpanIndex:
    """Innermost host span holding a time, for properly nested spans
    (one thread's TraceAnnotations)."""

    def __init__(self, spans: List[Tuple[float, float, str]]):
        # by start, the outer of two spans that start together first
        self.spans = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
        self.starts = [s for s, _, _ in self.spans]
        self.parent: List[int] = []
        stack: List[int] = []
        for i, (s, e, _) in enumerate(self.spans):
            while stack and self.spans[stack[-1]][1] < s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def name_at(self, t: float) -> str:
        # the latest span to start by t; if it is over, every span that
        # holds t holds it too, so walk up its parents
        j = bisect.bisect_right(self.starts, t) - 1
        while j >= 0 and self.spans[j][1] < t:
            j = self.parent[j]
        return self.spans[j][2] if j >= 0 else "untraced"


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(hlo: str) -> str:
    """``%copy.1 = f32[1,64]{1,0:T(1,128)} copy(...)`` -> ``copy.1
    f32[1,64]``: the op and its result type, without layouts."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    rest = _LAYOUT.sub("", rest)
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if ch == " " and depth == 0:
            end = i
            break
    return f"{head.lstrip('%')} {rest[:end]}"


def reduce_profile(pd, chips: Optional[int] = None) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData``."""
    window: Optional[Interval] = None
    spans: List[Tuple[float, float, str]] = []
    devices: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW_SPAN:
                        window = (s, e)
                    if name.startswith(SPAN_PREFIXES):
                        spans.append((s, e, name))
        elif plane.name.startswith(DEVICE_PREFIX):
            try:
                idx = int(plane.name[len(DEVICE_PREFIX):])
            except ValueError:
                continue
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[idx] = [(op_name(n), s, e)
                                    for n, s, e in _events(line)]
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span on the host plane")
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane with an "
                         f"{OP_LINE!r} line")
    lo, hi = window
    used = sorted(devices)
    if chips is not None:
        used = [d for d in used if devices[d]][:chips] or used[:chips]
    index = SpanIndex([sp for sp in spans if sp[1] >= lo and sp[0] <= hi])
    summary = TraceSummary(window_s=(hi - lo) * 1e-9, busy_s={})
    for _, _, name in index.spans:
        summary.span_count[name] = summary.span_count.get(name, 0) + 1
    for d in used:
        evs = devices[d]
        busy = union(clip(((s, e) for _, s, e in evs), lo, hi))
        summary.busy_s[d] = sum(e - s for s, e in busy) * 1e-9
        for name, s, e in evs:
            for cs, ce in clip([(s, e)], lo, hi):
                summary.op_s[name] = summary.op_s.get(name, 0.0) + \
                    (ce - cs) * 1e-9
        for gs, ge in gaps(busy, lo, hi):
            who = index.name_at((gs + ge) / 2)
            summary.gap_s[who] = summary.gap_s.get(who, 0.0) + \
                (ge - gs) * 1e-9
    return summary


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"{len(found)} .xplane.pb files under {trace_dir}")
    return found[0]


def reduce_file(path: str, chips: Optional[int] = None) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), chips)


def reduce_dir(trace_dir: str, chips: Optional[int] = None) -> TraceSummary:
    return reduce_file(find_xplane(trace_dir), chips)
