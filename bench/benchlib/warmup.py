"""Compile every program the cell's window can call, before the window,
through the engine's public ``submit``/``step`` path alone.

The engine compiles lazily, on the first call of each shape: a prefill per
power-of-two chunk bucket (8 up to the chunk), with the pool's prefix
joined in or not, the sampler, the decode window at its usual length and
at 1 tick (the window it steps down to under pool pressure), and the
eager scatters that free a slot.  Warm-up serves one short request per
shape, one at a time, so that each wave holds that shape alone:

- a prompt of ``s`` tokens is a first chunk with no cached prefix, bucket
  ``s`` (only buckets the mix's shortest first prompt can reach: a wave
  runs without the prefix join only when every row starts at 0); the one
  of ``chunk`` tokens is the *base*;
- the base and ``s`` more tokens hit the base's blocks in the prefix cache
  and prefill ``s`` tokens joined to them, bucket ``s`` (with the prefix
  cache off, the same prompt ends in a continuation chunk of ``s``, which
  joins the prefix alike);
- one request is served while the engine's pressure watermarks are set to
  0, so its windows step down to 1 tick; the watermarks are then restored
  and an idle step lifts the engine out of that state.

Each request decodes one full window.  A mesh engine compiles a window
once for each way its inputs arrive (uploaded from the host, or left on
the device by the last call, with its sharding), so there each request
decodes two windows, and one more times its second window to take a new
pool block, whose table is then uploaded.

Warm-up prompts repeat token ids of their own, so the pool blocks they
leave in the prefix cache never match the traffic's prompts (whose tokens
are drawn at random); they count as free and are evicted first.  Warm-up
requests take negative ids, apart from the client's."""

from __future__ import annotations


def buckets(chunk: int):
    b, out = 8, []
    while True:
        out.append(b)
        if b >= chunk:
            return out
        b *= 2


def _crossing(first: int, ticks: int, bs: int) -> int:
    """A prompt length of at least ``first`` whose second decode window is
    the first to need a new pool block."""
    return -(-(first + ticks) // bs) * bs - ticks


def warm(eng, request_cls, sampling_cls, min_prompt: int) -> int:
    """Serve the warm-up requests; returns how many it served."""
    chunk = min(eng.prefill_chunk or eng.max_len, eng.max_len // 2)
    ticks = eng.draft_k if eng.spec_decode else eng.decode_ticks
    windows = 1 if eng.mesh is None else 2
    bs = getattr(eng, "block_size", None)
    served = []

    def serve(prompt):
        req = request_cls(rid=-1 - len(served), prompt=prompt,
                          sampling=sampling_cls(temperature=0.0,
                                                max_new=windows * ticks + 1))
        eng.submit(req)
        while len(eng.scheduler) or any(s is not None for s in eng.slots):
            eng.step()
        served.append(req)

    def fresh(n):                          # a prompt of a token id no other uses
        return [len(served) + 1] * n

    first = buckets(min(min_prompt, chunk))[-1]
    for s in buckets(chunk)[:-1]:
        if s >= first:
            serve(fresh(s))                # cold first chunk, bucket s
    base = fresh(chunk)
    serve(base)                            # cold, the chunk's bucket
    for s in buckets(chunk):
        serve(base + fresh(s))             # s tokens joined to the base's prefix
    if windows > 1 and bs:
        serve(fresh(_crossing(first, ticks, bs)))   # a second window takes a block
    high, low = eng.degrade_high, eng.degrade_low
    eng.degrade_high = eng.degrade_low = 0.0
    serve(fresh(_crossing(first, 1, bs) if windows > 1 and bs else first))
    eng.degrade_high, eng.degrade_low = high, low
    eng.step()                             # idle: pressure clears
    eng.reset_stats()
    return len(served)
