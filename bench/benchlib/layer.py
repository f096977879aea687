"""What a per-layer metric's reader (``metrics/<name>.py``) is given.

``read(ctx)`` returns a number, or None when the run holds nothing for it
to read; the harness then leaves the metric out of the result line.  A
reader never returns 0 for a share of a roofline or of a peak.

``ctx.work`` is ``work(model)`` of the cell's architecture module,
``reference/<architecture>.py`` (``benchlib/spec.py``): whole-model
integer counts, summed over the layers by the module, so a reader never
multiplies by a layer count:

- ``token_matmul_flops(head)``: one token through every layer, plus one
  row of logits with ``head``;
- ``attention_flops(ctx)``: one query over ``ctx`` keys in every layer;
- ``decode_attention_bytes(ctx, nbmax)``, ``decode_attention_flops(ctx)``:
  what the decode-attention kernel must move and compute for one row over
  all layers;
- ``head_flops()``: one row of logits;
- ``kv_bytes_per_token()``, ``weight_bytes()``."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class Ctx:
    model: dict
    work: object                  # the architecture module's work(model)
    peaks: dict
    settings: dict
    records: List
    t0: float                     # the measured window, client clock
    t_end: float
    compile_s: float              # JAX compile and compile-cache reads in set-up
    block_size: int
    nbmax: int
    counters: Dict[str, float] = field(default_factory=dict)   # whole window
    trace: Optional[object] = None                              # trace.Reduced
    trace_t0: Optional[float] = None      # traced sub-window, client clock
    trace_t1: Optional[float] = None
    trace_counters: Dict[str, float] = field(default_factory=dict)

    def decode_contexts(self) -> List[int]:
        """Keys attended by each decode step whose token reached the client
        inside the traced sub-window: output token k >= 1 of a request was
        computed from its prompt and k served tokens, so it attends
        ``prompt + k`` positions (token 0 comes from the prefill)."""
        out = []
        for r in self.records:
            p = len(r.prompt)
            for k, t in enumerate(r.token_t):
                if k and self.trace_t0 <= t <= self.trace_t1:
                    out.append(p + k)
        return out

    def first_tokens_in_trace(self) -> List:
        return [r for r in self.records if r.token_t
                and self.trace_t0 <= r.token_t[0] <= self.trace_t1]

    def ideal_hits(self) -> Dict[int, int]:
        """For every request, the longest block-aligned prefix of its prompt
        (at most ``prompt - 1`` tokens) that an earlier request's prompt or
        served tokens already wrote: the prefill a prefix cache of
        unlimited size would skip."""
        bs, seen, hits = self.block_size, set(), {}
        for r in sorted(self.records, key=lambda r: r.rid):
            h, n = 0, 0
            for j in range((len(r.prompt) - 1) // bs):
                h = hash((h, tuple(r.prompt[j * bs:(j + 1) * bs])))
                if h not in seen:
                    break
                n += bs
            hits[r.rid] = n
            seq = list(r.prompt) + list(r.out)
            h = 0
            for j in range(len(seq) // bs):
                h = hash((h, tuple(seq[j * bs:(j + 1) * bs])))
                seen.add(h)
        return hits
