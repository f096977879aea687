"""queue_wait_p95_ms: 95th percentile of the engine's own admission wait,
t_admit - t_submit (its ``Request`` stamps), over the requests submitted in
the measured window and admitted by its end.  Moves ttft_p95_ms."""

from benchlib.e2e import p95


def read(ctx):
    waits = [(r.req.t_admit - r.req.t_submit) * 1e3 for r in ctx.records
             if r.submitted is not None and r.submitted < ctx.t_end
             and r.req.t_admit is not None and r.req.t_submit is not None]
    return p95(waits) if waits else None
