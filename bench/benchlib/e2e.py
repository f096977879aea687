"""End-to-end metrics from the client's own stamps, over the whole window:
every request due in it and every token that reached the client in it."""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, float), 95))


def end_to_end(records: List, t0: float, t_end: float) -> Dict[str, float]:
    seconds = t_end - t0
    due = [r for r in records if r.due < t_end]
    tokens = sum(sum(1 for t in r.token_t if t0 <= t <= t_end) for r in records)
    ttft = []
    for r in due:
        first = r.token_t[0] if r.token_t else None
        ttft.append(((first if first is not None and first <= t_end else t_end)
                     - r.due) * 1e3)
    tpot = []
    for r in records:
        inside = [t for t in r.token_t if t0 <= t <= t_end]
        if len(inside) >= 2:
            tpot.append((inside[-1] - inside[0]) / (len(inside) - 1) * 1e3)
    return {
        "output_tok_s": tokens / seconds,
        "ttft_p95_ms": p95(ttft) if ttft else float("nan"),
        "tpot_p95_ms": p95(tpot) if tpot else float("nan"),
        "_tokens": tokens,
    }


def attempted_failed(records: List, t_end: float):
    due = [r for r in records if r.due < t_end]
    failed = [r for r in due if r.done and r.finish not in ("length", "eos", "stop")]
    return len(due), len(failed)
