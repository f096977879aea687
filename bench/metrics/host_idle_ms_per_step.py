"""host_idle_ms_per_step: the device's idle time inside an engine step (the
holes in the union of its operations there, averaged over devices as the
busy time is), averaged over the ``engine.step`` spans that lie wholly
inside the traced sub-window (``benchlib/host_loop.py``): what the host
loop cost the device.  Moves output_tok_s (closed loop) or tpot_p95_ms
(open)."""

from benchlib import host_loop


def read(ctx):
    steps = host_loop.steps(ctx)
    if not steps:
        return None
    idle = host_loop.Idle(ctx.trace)
    return sum(idle.ns(s.start, s.end) for s in steps) / len(steps) / 1e6
