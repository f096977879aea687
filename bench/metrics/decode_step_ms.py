"""decode_step_ms: device time of the fused decode-window program over the
traced sub-window, per decode tick it ran there (the engine's ``tick``
counter advances by the window's length).  Moves output_tok_s in a
closed-loop cell and tpot_p95_ms in an open-loop one."""


def read(ctx):
    if ctx.trace is None:
        return None
    ticks = ctx.trace_counters.get("ticks", 0)
    dev = ctx.trace.module_seconds(r"fused_decode")
    return dev * 1e3 / ticks if ticks and dev > 0 else None
