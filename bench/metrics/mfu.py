"""mfu: the whole step's share of the chip's bf16 peak over the traced
sub-window: the model operations of every token processed there, over the
window's seconds times the peak.

Counted from shapes by the cell's architecture module (``ctx.work``,
``benchlib/layer.py``): each decode token 2·params through every layer and
the LM head plus attention over its context; each prompt token the engine
computed (its ``prefill_tokens`` counter) 2·params through the layers, plus
attention at the mean context of the prompts whose prefill finished in the
window (positions past what a prefix cache could reuse), and one LM-head
row per first token.  Moves output_tok_s (closed loop) or tpot_p95_ms
(open)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    w = ctx.work
    decode = sum(w.token_matmul_flops(head=True) + w.attention_flops(c)
                 for c in ctx.decode_contexts())
    firsts = ctx.first_tokens_in_trace()
    hits = ctx.ideal_hits()
    spans = [(hits[r.rid], len(r.prompt)) for r in firsts]
    n = sum(p - h for h, p in spans)
    mean_ctx = (sum((h + p + 1) * (p - h) / 2 for h, p in spans) / n) if n else 0.0
    tokens = ctx.trace_counters.get("prefill_tokens", 0)
    prefill = tokens * (w.token_matmul_flops(head=False)
                        + w.attention_flops(1) * mean_ctx)
    prefill += len(firsts) * w.head_flops()
    total = decode + prefill
    if total <= 0:
        return None
    return 100.0 * total / (ctx.trace.window_s * ctx.peaks["bf16_flops"])
