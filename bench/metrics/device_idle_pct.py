"""device_idle_pct: the share of the traced sub-window in which no
operation ran on the device (one minus the union of operation intervals
over the window).  Moves output_tok_s (closed loop) or tpot_p95_ms
(open)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
