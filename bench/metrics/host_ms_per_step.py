"""host_ms_per_step: the host's own work in an engine step, averaged over
the ``engine.step`` spans that lie wholly inside the traced sub-window: a
step's duration less the time its ``engine.*.wait`` phases, the calls that
block on the device, cover (``benchlib/host_loop.py`` reads the engine's
phases).  Moves output_tok_s (closed loop) or tpot_p95_ms (open).  Read
beside host_idle_ms_per_step: a change that removes host work lowers both,
one that overlaps it with the device lowers only that one.  The traced
run's Python profiler inflates host time."""

from benchlib import host_loop


def read(ctx):
    steps = host_loop.steps(ctx)
    if not steps:
        return None
    return sum(s.dur - s.wait_ns for s in steps) / len(steps) / 1e6
