"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device activity is read from the planes named ``/device:<KIND>:<n>``: the
``XLA Ops`` line holds one event per executed operation, the ``XLA
Modules`` line one per executed program.  Host spans are the
``jax.profiler.TraceAnnotation`` events the harness writes on the host
plane; the span named ``WINDOW_SPAN`` marks the measured window.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# idle gaps shorter than this sit between the ops of one program
MIN_GAP_NS = 50_000


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(path: str) -> List[Event]:
    """Every event of every plane of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def op_label(name: str, width: int = 72) -> str:
    """An HLO op event's name without layouts, cut to ``width``: the op and
    the shape of what it produces (``%fusion.3 = bf16[8,128] fusion(...``)."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted union of ``(start, end)`` intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


@dataclass
class Reduction:
    window_s: float
    busy_s: float                         # union of device ops, mean over chips
    chips: int
    module_s: Dict[str, float]            # device time per program name
    module_calls: Dict[str, int]
    top_ops: List[Tuple[str, float]]      # device time per op name, largest first
    idle_by_host: List[Tuple[str, float]]  # idle device time by host span
    host_s: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, needle: str) -> Tuple[float, int]:
        """Device seconds and calls of the programs whose name holds
        ``needle``."""
        s = sum(v for k, v in self.module_s.items() if needle in k)
        n = sum(v for k, v in self.module_calls.items() if needle in k)
        return s, n


def reduce_events(events: Sequence[Event], host_spans: Sequence[str],
                  top: int = 10) -> Reduction:
    """Reduce a trace to the window's device busy time, per-program device
    time, the heaviest device ops, and the device's idle time attributed to
    the innermost host span (of ``host_spans``) running at each idle
    moment; idle time under none of them is ``"host: other"``."""
    win = [e for e in events if e.name == WINDOW_SPAN and not is_device_plane(e.plane)]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = win[0].start_ns, win[0].end_ns
    dev = [e for e in events if is_device_plane(e.plane)
           and e.end_ns > lo and e.start_ns < hi]
    planes = sorted({e.plane for e in dev if e.line == OPS_LINE})
    if not planes:
        raise ValueError("no device operations inside the window")
    busy = []
    ops: Dict[str, float] = defaultdict(float)
    unions = {}
    for p in planes:
        iv = _clip([(e.start_ns, e.end_ns) for e in dev
                    if e.plane == p and e.line == OPS_LINE], lo, hi)
        unions[p] = union_ns(iv)
        busy.append(sum(e - s for s, e in unions[p]))
    for e in dev:
        if e.line == OPS_LINE:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            ops[op_label(e.name)] += (t - s) / 1e9
    mod_s: Dict[str, float] = defaultdict(float)
    mod_n: Dict[str, int] = defaultdict(int)
    for e in dev:
        if e.line == MODULES_LINE and e.plane == planes[0]:
            name = e.name.split("(")[0]
            mod_s[name] += (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
            mod_n[name] += 1

    # idle gaps of the first chip, attributed to the innermost host span
    spans = sorted((e for e in events if e.name in host_spans
                    and not is_device_plane(e.plane)
                    and e.end_ns > lo and e.start_ns < hi),
                   key=lambda e: e.start_ns)
    host_s: Dict[str, float] = defaultdict(float)
    for sp in spans:
        host_s[sp.name] += (min(sp.end_ns, hi) - max(sp.start_ns, lo)) / 1e9
    idle: Dict[str, float] = defaultdict(float)
    gaps, cur = [], lo
    for s, e in unions[planes[0]]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    st = np.array([sp.start_ns for sp in spans])
    en = np.array([sp.end_ns for sp in spans])
    for g0, g1 in gaps:
        if g1 - g0 < MIN_GAP_NS:
            idle["device: between ops"] += (g1 - g0) / 1e9
            continue
        # split the gap at span boundaries; each piece goes to the shortest
        # span covering it (spans nest, so the shortest is the innermost)
        near = [spans[i] for i in np.flatnonzero((st < g1) & (en > g0))]
        cuts = sorted({g0, g1} | {t for sp in near
                                  for t in (sp.start_ns, sp.end_ns)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            cover = [sp for sp in near if sp.start_ns <= mid < sp.end_ns]
            name = (min(cover, key=lambda sp: sp.dur_ns).name if cover
                    else "host: other")
            idle[name] += (b - a) / 1e9
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy) / 1e9,
        chips=len(planes), module_s=dict(mod_s), module_calls=dict(mod_n),
        top_ops=sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        idle_by_host=sorted(idle.items(), key=lambda kv: -kv[1])[:top],
        host_s=dict(host_s))


def reduce_file(path: str, host_spans: Sequence[str], top: int = 10) -> Reduction:
    return reduce_events(load_events(path), host_spans, top)
