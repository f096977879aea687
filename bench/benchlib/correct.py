"""Whether what the timed path served is right: a sample of the requests
the window finished, drawn from the seed and holding the longest, is run
through the plain float32 reference over each prompt with its served
tokens.  The reference is the ``logits_at`` of the cell's architecture
module, ``reference/<architecture>.py`` (``forward`` where the
configuration names none; ``benchlib/spec.py`` lists what the module
gives).  For every served token the gap is how far its reference logit
lies below the reference's best at that position; the widest gap is
compared with the cell's limit.  Greedy decoding makes that a fair test:
a served token that is not the reference's best (or within rounding of
it) is a wrong token.

The same positions can also be read under the reference's lower-precision
control: the gap of the token the control puts first.  Set-up of a limit
runs both (``calibrate.py``); a benchmark run reads only the program."""

from __future__ import annotations

from typing import List

import numpy as np


def sample(records: List, seed: int, min_tokens: int) -> List:
    """Finished requests, the longest (prompt + served) first, then others
    in an order drawn from ``seed`` until ``min_tokens`` served tokens."""
    done = [r for r in records if r.done and r.finish == "length" and r.out]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 11]).permutation(len(rest))
    picked, n = [longest], len(longest.out)
    for i in order:
        if n >= min_tokens:
            break
        picked.append(rest[i])
        n += len(rest[i].out)
    return picked


def _padded(rec, max_len: int, rows_max: int):
    seq = list(rec.prompt) + list(rec.out)
    p, n = len(rec.prompt), len(rec.out)
    tokens = np.zeros(max_len, np.int32)
    tokens[: len(seq) - 1] = seq[:-1]
    rows = np.full(rows_max, p - 1, np.int32)
    rows[:n] = np.arange(p - 1, p - 1 + n)
    return tokens, rows, np.asarray(rec.out, np.int32), n


def gaps(arch, weights, model: dict, recs: List, max_len: int, rows_max: int,
         control: bool = False):
    """Widest gap of the served tokens (and, with ``control``, of the
    control's first choices) over the sampled requests, by the reference of
    the architecture module ``arch``; tokens compared."""
    served_gap, control_gap, n_tok = 0.0, 0.0, 0
    for rec in recs:
        tokens, rows, out, n = _padded(rec, max_len, rows_max)
        served = np.zeros(rows_max, np.int32)
        served[:n] = out
        g, c = (np.asarray(x) for x in _row_gaps(weights, tokens, rows, served,
                                                 arch=arch, model_items=_items(model),
                                                 control=control))
        served_gap = max(served_gap, float(g[:n].max()))
        control_gap = max(control_gap, float(c[:n].max()))
        n_tok += n
    return served_gap, (control_gap if control else None), n_tok


def _items(model: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def _row_gaps_impl(weights, tokens, rows, served, *, arch, model_items, control):
    import jax.numpy as jnp

    model = dict(model_items)
    ref = arch.logits_at(weights, model, tokens, rows)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    alt = best
    if control:
        pick = jnp.argmax(arch.logits_at(weights, model, tokens, rows, control=True), axis=-1)
        alt = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return best - got, best - alt


def _row_gaps(*args, arch, model_items, control):
    import jax

    global _ROW_GAPS
    if _ROW_GAPS is None:
        _ROW_GAPS = jax.jit(_row_gaps_impl,
                            static_argnames=("arch", "model_items", "control"))
    return _ROW_GAPS(*args, arch=arch, model_items=model_items, control=control)


_ROW_GAPS = None


def verdict(gap: float, n_tokens: int, limit: float) -> bool:
    return bool(n_tokens > 0 and np.isfinite(gap) and gap <= limit)
