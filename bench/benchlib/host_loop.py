"""The engine's phase spans inside the traced window, for the host-loop
metrics (``metrics/host_ms_per_step.py``, ``metrics/host_idle_ms_per_step.py``).

The engine writes each phase (``engine.step`` and its ``engine.*``
children) as a profiler annotation and into the process's flight recorder,
``repro.serve.trace.recent_spans()``, on ``time.perf_counter_ns()``: the
client's clock.  The traced window is stamped on both clocks, as
``bench.window`` on the trace's (``Reduced.window``) and as the client's
readings just inside it (``Ctx.trace_t0``, ``trace_t1``), so the line
through those two pairs carries a span onto the trace's clock, within the
few µs between an annotation and the client's reading.

Nothing is read (None) where the run has no trace, the program keeps no
flight recorder, the recorder no longer reaches back to the window's start,
or the two clocks disagree in rate.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from benchlib.trace import _union

STEP = "engine.step"
WAIT = ".wait"            # a phase that blocks on the device
RATE = 1e-3               # largest disagreement of the two clocks' rates,
SLACK = 1e6               # beside the ns between an annotation and a reading


@dataclass
class Step:
    start: float          # ns on the trace's clock
    end: float
    wait_ns: float        # covered by its ``*.wait`` phases

    @property
    def dur(self) -> float:
        return self.end - self.start


def program_spans() -> Optional[List[Tuple[str, int, int]]]:
    """The program's flight recorder, or None where it keeps none."""
    try:
        from repro.serve.trace import recent_spans
    except ImportError:
        return None
    return recent_spans()


def steps(ctx) -> Optional[List[Step]]:
    """The ``engine.step`` spans that lie wholly inside the traced window,
    on the trace's clock, each with the time its ``*.wait`` phases cover."""
    spans = program_spans()
    if not spans or ctx.trace is None or ctx.trace_t0 is None or ctx.trace_t1 is None:
        return None
    c0, c1 = ctx.trace_t0 * 1e9, ctx.trace_t1 * 1e9
    lo, hi = ctx.trace.window
    if c1 <= c0 or abs((hi - lo) - (c1 - c0)) > RATE * (c1 - c0) + SLACK:
        return None
    # the recorder keeps the spans that closed last: it covers the window
    # only if one of them closed before the window opened
    if min(e for _, _, e in spans) > c0:
        return None
    scale = (hi - lo) / (c1 - c0)
    inside = sorted((s, e) for name, s, e in spans
                    if name == STEP and s >= c0 and e <= c1)
    waits = [(s, e) for name, s, e in spans
             if name.endswith(WAIT) and s >= c0 and e <= c1]
    return [Step(lo + (s - c0) * scale, lo + (e - c0) * scale,
                 sum(we - ws for ws, we in waits if ws >= s and we <= e) * scale)
            for s, e in inside]


class Idle:
    """Device-idle time inside any interval of the traced window: the holes
    in each device's union of operations there, averaged over the devices
    as ``Reduced.busy_ns`` is."""

    def __init__(self, reduced):
        per: Dict[str, List[Tuple[float, float]]] = {d: [] for d in reduced.devices}
        for e in reduced.ops:
            per.setdefault(e.plane, []).append((e.start, e.end))
        self.busy = {d: _union(iv) for d, iv in per.items()}
        self.ends = {d: [e for _, e in iv] for d, iv in self.busy.items()}

    def ns(self, lo: float, hi: float) -> float:
        if not self.busy:
            return 0.0
        idle = []
        for d, iv in self.busy.items():
            busy, i = 0.0, bisect.bisect_right(self.ends[d], lo)
            while i < len(iv) and iv[i][0] < hi:
                busy += min(iv[i][1], hi) - max(iv[i][0], lo)
                i += 1
            idle.append((hi - lo) - busy)
        return sum(idle) / len(idle)
