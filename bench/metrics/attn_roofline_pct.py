"""attn_roofline_pct: the paged int8 decode-attention kernel's share of its
roofline over the traced sub-window.

The least time is the larger of two bounds on the work the decode steps
needed: the bytes of the live tokens' K/V codes and scales plus query,
output and block table (``decode_attention_bytes`` of ``ctx.work``, the
cell's architecture module's counts over all layers) over the HBM
bandwidth, and 4·ctx·heads·hd operations per row and layer over the bf16
peak.  It is divided by the summed device time of the kernel's operations
(``flash_attention`` custom calls).  Only the tokens that reached the
client count: rows that were idle or had finished inside a window are not
needed work.  Moves output_tok_s (closed loop) or tpot_p95_ms (open)."""

KERNEL = r"flash_attention"


def bound(ctx):
    w, ctxs = ctx.work, ctx.decode_contexts()
    bytes_ = sum(w.decode_attention_bytes(c, ctx.nbmax) for c in ctxs)
    flops = sum(w.decode_attention_flops(c) for c in ctxs)
    t_mem = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    t_ops = flops / ctx.peaks["bf16_flops"]
    return max(t_mem, t_ops), ("bytes" if t_mem >= t_ops else "flops")


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = ctx.trace.op_seconds(KERNEL)
    need, _ = bound(ctx)
    return 100.0 * need / kernel_s if kernel_s > 0 and need > 0 else None
