"""The one general traffic generator: every mix is a data file
(``traffic/<mix>.json``) of the parameters read here.

A mix is a stream of *sessions*.  A session is one or more turns; turn k's
prompt is the session's system prompt, then every earlier turn's user
tokens and served reply, then its own user tokens.  A closed-loop mix is
served by a fixed number of clients, each starting its next session as soon
as the last one's final reply is in; an open-loop mix starts sessions at
Poisson arrival times at the cell's rate, and sends each later turn a think
time after the reply it answers.

Sizes and gaps are random draws from the mix's distributions, made once
from a fixed generator that no ``--seed`` changes: a synthetic trace.
Sessions come in blocks of ``block`` (a mix parameter); block ``b`` of the
trace is drawn from its own fixed stream, so it holds the same sessions and
the same arrival gaps (unit exponentials, divided by the cell's rate) for
every seed.  The seed permutes the sessions within each block, orders each
block's gaps, and draws the token ids.  So every seed offers the same work
in another order, as the benchmark's bounds require, and the arrivals stay
a Poisson process: a random order of independent exponential gaps is one.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

TRACE = 0x7ACE      # the fixed stream of the trace; never a run's seed


def draw(spec: dict, rng: np.random.Generator) -> float:
    """One draw of the distribution ``spec``, clipped to its ``lo``/``hi``."""
    dist = spec["dist"]
    if dist == "lognormal":            # by its median, or by its mean
        sigma = float(spec["sigma"])
        median = (spec["median"] if "median" in spec
                  else spec["mean"] * math.exp(-sigma * sigma / 2))
        v = float(round(median * math.exp(sigma * rng.standard_normal())))
    elif dist == "geometric":          # 1, 2, ... with the given mean
        v = float(rng.geometric(1.0 / float(spec["mean"])))
    elif dist == "uniform":            # whole numbers lo..hi
        v = float(rng.integers(int(spec["lo"]), int(spec["hi"]) + 1))
    elif dist == "exponential":
        v = float(rng.exponential(float(spec["mean"])))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    lo, hi = spec.get("lo", -math.inf), spec.get("hi", math.inf)
    return min(max(v, lo), hi)


@dataclass
class Shape:
    """A session's sizes, as the trace holds them (no token ids yet)."""

    system: int                              # index of its system prompt, or -1
    users: List[int]                         # user tokens per turn
    replies: List[int]                       # reply length (max_new) per turn
    thinks: List[float]                      # seconds before turns 2..


@dataclass
class Session:
    sid: int
    arrival: Optional[float]                 # seconds after the window opens (open loop)
    system: List[int]
    users: List[List[int]]
    replies: List[int]                       # reply length (max_new) per turn
    thinks: List[float] = field(default_factory=list)   # before turns 2..


class Traffic:
    """Sessions of one mix at one load, from one seed."""

    def __init__(self, mix: dict, load: dict, vocab: int, seed: int):
        self.mix, self.load, self.vocab, self.seed = mix, load, vocab, seed
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.block = int(mix["block"])
        if self.loop == "closed":
            self.clients = int(load["clients"])
        else:
            self.rate = float(load["session_rate_per_s"])
            self._arrivals = [0.0]
        self.max_context = int(mix.get("max_context", 1 << 30))
        self._user = mix.get("user") or mix["prompt"]
        sp = mix.get("system_prompts")
        self.system_prompts: List[List[int]] = []
        if sp:
            rng = np.random.default_rng([seed, 6])
            self.system_prompts = [rng.integers(1, vocab, int(sp["tokens"])).tolist()
                                   for _ in range(int(sp["count"]))]
            w = 1.0 / np.arange(1, len(self.system_prompts) + 1) ** float(sp.get("zipf", 0.0))
            self._system_cdf = np.cumsum(w / w.sum())
        self._shapes: dict = {}
        self._gaps: dict = {}
        self._perms: dict = {}

    # ----------------------------------------------------------- the trace

    def _shape(self, rng: np.random.Generator) -> Shape:
        mix = self.mix
        system = -1
        if self.system_prompts:
            system = min(len(self.system_prompts) - 1,
                         int(np.searchsorted(self._system_cdf, rng.random())))
        turns = int(draw(mix["turns"], rng)) if "turns" in mix else 1
        users, replies, thinks = [], [], []
        for j in range(turns):
            users.append(int(draw(self._user, rng)))
            replies.append(int(draw(mix["reply"], rng)))
            if j:
                thinks.append(draw(mix["think_s"], rng) if "think_s" in mix else 0.0)
        # a first turn longer than the context keeps its last tokens, as a
        # deployment with this context cuts a document
        n_sys = len(self.system_prompts[system]) if system >= 0 else 0
        users[0] = max(1, min(users[0], self.max_context - n_sys - replies[0]))
        return Shape(system, users, replies, thinks)

    def block_shapes(self, b: int) -> List[Shape]:
        """Block ``b`` of the trace, in the trace's own order."""
        if b not in self._shapes:
            rng = np.random.default_rng([TRACE, 0, b])
            self._shapes[b] = [self._shape(rng) for _ in range(self.block)]
        return self._shapes[b]

    def _block_gaps(self, b: int) -> np.ndarray:
        if b not in self._gaps:
            self._gaps[b] = np.random.default_rng([TRACE, 1, b]).exponential(1.0, self.block)
        return self._gaps[b]

    def _perm(self, tag: int, b: int):
        key = (tag, b)
        if key not in self._perms:
            self._perms[key] = np.random.default_rng(
                [self.seed, tag, b]).permutation(self.block)
        return self._perms[key]

    # --------------------------------------------------------------- a run

    def min_prompt(self) -> int:
        """The shortest first prompt the mix can send."""
        sp = self.mix.get("system_prompts")
        return (int(sp["tokens"]) if sp else 0) + int(self._user.get("lo", 1))

    def arrival(self, k: int) -> float:
        """Open loop: when session ``k`` starts, in seconds from the window's
        opening (session 0 at 0; each block's gaps are the trace's, divided
        by the cell's rate, in an order drawn from the seed)."""
        while len(self._arrivals) <= k:
            g = len(self._arrivals) - 1
            b = g // self.block
            i = self._perm(1, b)[g % self.block]
            self._arrivals.append(self._arrivals[-1] + self._block_gaps(b)[i] / self.rate)
        return self._arrivals[k]

    def session(self, k: int) -> Session:
        b = k // self.block
        shape = self.block_shapes(b)[int(self._perm(0, b)[k % self.block])]
        rng = np.random.default_rng([self.seed, 8, k])
        users = [rng.integers(1, self.vocab, n).tolist() for n in shape.users]
        system = self.system_prompts[shape.system] if shape.system >= 0 else []
        return Session(sid=k, arrival=self.arrival(k) if self.loop == "open" else None,
                       system=system, users=users, replies=list(shape.replies),
                       thinks=list(shape.thinks))
