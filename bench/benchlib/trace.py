"""Reduce a ``jax.profiler`` trace to what the per-layer metrics read.

The trace is flattened to events (plane, line, name, start, duration,
stats).  A device plane is one whose name starts with ``/device:``; its
``XLA Ops`` line holds one event per operation that ran and its
``XLA Modules`` line one per program execution.  The host's main thread
carries the client's ``TraceAnnotation`` spans, among them ``bench.window``,
which bounds the traced window on the trace's own clock.

- busy: the union of a device's operation intervals inside the window
  (averaged over the devices used), so overlapping operations count once;
- idle gaps: the holes in that union, each named by what the host's main
  thread was doing at the gap's middle (its outermost ``bench.*`` span and
  its innermost event);
- time by operation or program name, for kernels and steps.
"""

from __future__ import annotations

import glob
import os
import re
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
CONTAINERS = {"while", "conditional", "call"}


def op_stem(name: str) -> str:
    """``%bitcast_add_fusion.3 = bf16[...] fusion(...)`` → ``bitcast_add_fusion``."""
    head = name.split("=", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head) or name


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start: float      # ns on the trace's clock
    dur: float        # ns
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur

    def text(self) -> str:
        """The name and every string stat, for matching by pattern."""
        return " ".join([self.name] + [str(v) for v in self.stats.values()
                                       if isinstance(v, str)])


def load_xplane(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)   # pybind stat objects
        for plane in pd.planes:
            for line in plane.lines:
                for ev in line.events:
                    stats = {k: v for k, v in ev.stats}
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns), float(ev.duration_ns), stats))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
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
class Reduced:
    window: Tuple[float, float]
    devices: List[str]
    busy_ns: float                       # mean over devices
    ops: List[Event]                     # device operations inside the window
    modules: List[Event]                 # device program executions inside it
    gaps: List[Tuple[str, float]]        # (what the host did, ns), longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def op_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(e.dur for e in self.ops if rx.search(e.text())) / 1e9

    def module_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(e.dur for e in self.modules if rx.search(e.text())) / 1e9

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device time by operation, named by its HLO instruction stem
        (``%copy.105 = ...`` counts as ``copy``); loops and calls, which
        contain other operations, are left out so nothing counts twice."""
        tot: Dict[str, float] = {}
        for e in self.ops:
            key = op_stem(e.name)
            if key in CONTAINERS:
                continue
            tot[key] = tot.get(key, 0.0) + e.dur / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        return [(name, ns / 1e9) for name, ns in self.gaps[:n]]


def _host_main(events: List[Event]) -> List[Event]:
    """Events of the host thread that carries the window annotation."""
    win = [e for e in events if e.name == WINDOW]
    if not win:
        return []
    plane, line = win[0].plane, win[0].line
    return [e for e in events if e.plane == plane and e.line == line]


def _name_gap(host: List[Event], t: float) -> str:
    cover = [e for e in host if e.start <= t <= e.end and e.name != WINDOW]
    if not cover:
        return "host:none"
    outer = [e for e in cover if e.name.startswith("bench.")]
    inner = min(cover, key=lambda e: e.dur)
    head = max(outer, key=lambda e: e.dur).name if outer else "host"
    return head if inner.name == head else f"{head}:{inner.name}"


def reduce(events: List[Event], op_line: str = "XLA Ops",
           module_line: str = "XLA Modules") -> Optional[Reduced]:
    """The reduction of one traced window; None when the trace holds no
    window annotation or no device operation."""
    win = [e for e in events if e.name == WINDOW]
    if not win:
        return None
    lo, hi = win[0].start, win[0].end
    devices = sorted({e.plane for e in events if e.plane.startswith("/device:")})
    ops, modules, busy, holes = [], [], [], []
    for dev in devices:
        dev_ops = [e for e in events if e.plane == dev and e.line == op_line]
        ops += [e for e in dev_ops if e.end > lo and e.start < hi]
        modules += [e for e in events if e.plane == dev and e.line == module_line
                    and e.end > lo and e.start < hi]
        merged = _clip(_union([(e.start, e.end) for e in dev_ops]), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        holes += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                  if edges[i + 1] > edges[i]]
    if not ops:
        return None
    host = _host_main(events)
    gaps = sorted(((_name_gap(host, (s + e) / 2), e - s) for s, e in holes),
                  key=lambda g: -g[1])
    return Reduced(window=(lo, hi), devices=devices,
                   busy_ns=sum(busy) / len(devices), ops=ops, modules=modules,
                   gaps=gaps)
