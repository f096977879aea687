"""prefill_ms_per_ktok: device time of the paged prefill program over the
traced sub-window, per 1000 prompt tokens it computed there (the engine's
``prefill_tokens`` counter).  Moves ttft_p95_ms."""


def read(ctx):
    if ctx.trace is None:
        return None
    tokens = ctx.trace_counters.get("prefill_tokens", 0)
    dev = ctx.trace.module_seconds(r"paged_prefill")
    return dev * 1e3 / (tokens / 1e3) if tokens and dev > 0 else None
