"""prefix_hit_pct: prompt tokens served from the KV pool's prefix cache,
over all prompt tokens admitted, in the measured window (the engine's
``prefix_hit_tokens`` and ``prefill_tokens`` counters).  Moves
ttft_p95_ms."""


def read(ctx):
    hit = ctx.counters.get("prefix_hit_tokens", 0)
    done = ctx.counters.get("prefill_tokens", 0)
    return 100.0 * hit / (hit + done) if hit + done > 0 else None
