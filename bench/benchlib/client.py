"""The in-process client: it offers one mix to ``repro.serve.Engine``
through ``submit``/``step`` for a fixed window and stamps, on its own clock,
when each request was due and when each token reached it (through the
request's ``stream`` callback, so a 4-tick window's tokens arrive together,
as they do for a user)."""

from __future__ import annotations

import contextlib
import heapq
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from benchlib.traffic import Session, Traffic


@dataclass
class Record:
    """One request as the client saw it."""

    rid: int
    sid: int
    turn: int
    due: float
    prompt: List[int]
    max_new: int
    token_t: List[float] = field(default_factory=list)
    submitted: Optional[float] = None
    done: bool = False
    finish: Optional[str] = None
    out: List[int] = field(default_factory=list)
    req: object = None                        # the engine's Request
    state: object = None                      # the session it belongs to


@dataclass
class _SessionState:
    session: Session
    turn: int = 0
    history: List[int] = field(default_factory=list)


class Client:
    """Drive ``engine`` with ``traffic`` for ``seconds``; ``hooks`` are
    called between engine steps with the clock's reading (the traced run
    starts and stops the profiler there)."""

    def __init__(self, engine, traffic: Traffic, *, request_cls, sampling_cls,
                 annotate: Optional[Callable[[str], object]] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.eng, self.traffic = engine, traffic
        self.Request, self.Sampling = request_cls, sampling_cls
        self.clock = clock
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.records: List[Record] = []          # indexed by request id
        self._heap: list = []
        self._seq = 0
        self._next_session = 0
        self._harvested = len(engine.finished)   # warm-up requests are not ours
        self.t0 = self.t_end = None

    # -------------------------------------------------------------- schedule

    def _push(self, due: float, state: _SessionState):
        heapq.heappush(self._heap, (due, self._seq, state))
        self._seq += 1

    def _new_session(self, due: float):
        k = self._next_session
        self._next_session += 1
        self._push(due, _SessionState(self.traffic.session(k)))

    def _submit(self, due: float, st: _SessionState):
        s = st.session
        user = s.users[st.turn]
        prompt = (list(s.system) if st.turn == 0 else st.history) + user
        max_new = s.replies[st.turn]
        if len(prompt) + max_new > self.traffic.max_context:
            self._session_over(due)
            return
        st.history = prompt
        rid = len(self.records)
        rec = Record(rid=rid, sid=s.sid, turn=st.turn, due=due, prompt=prompt,
                     max_new=max_new, state=st)
        self.records.append(rec)

        def stream(req, tok, rec=rec):
            rec.token_t.append(self.clock())

        req = self.Request(rid=rid, prompt=prompt, stream=stream,
                           sampling=self.Sampling(temperature=0.0, max_new=max_new,
                                                  seed=rid))
        rec.req = req
        with self.annotate("bench.submit"):
            self.eng.submit(req)
        rec.submitted = self.clock()

    def _session_over(self, t: float):
        if self.traffic.loop == "closed":
            self._new_session(t)            # the client starts its next one

    def _harvest(self):
        fin = self.eng.finished
        while self._harvested < len(fin):
            req = fin[self._harvested]
            self._harvested += 1
            rec = self.records[req.rid]
            rec.done, rec.finish, rec.out = True, req.finish_reason, list(req.out)
            st = rec.state
            t_last = rec.token_t[-1] if rec.token_t else self.clock()
            st.history = st.history + rec.out
            st.turn += 1
            if st.turn < len(st.session.users) and req.finish_reason == "length":
                self._push(t_last + st.session.thinks[st.turn - 1], st)
            else:
                self._session_over(t_last)

    # ------------------------------------------------------------------ run

    def busy(self) -> bool:
        return len(self.eng.scheduler) > 0 or any(s is not None for s in self.eng.slots)

    def run(self, seconds: float, hooks=()):
        self.t0 = t0 = self.clock()
        self.t_end = t_end = t0 + seconds
        if self.traffic.loop == "closed":
            for _ in range(self.traffic.clients):
                self._new_session(t0)
        else:
            self._arrival_next = 0
        while True:
            now = self.clock()
            for h in hooks:
                h(now)
            if now >= t_end:
                break
            if self.traffic.loop == "open":
                while t0 + self.traffic.arrival(self._arrival_next) <= now:
                    self._new_session(t0 + self.traffic.arrival(self._arrival_next))
                    self._arrival_next += 1
            while self._heap and self._heap[0][0] <= now:
                due, _, st = heapq.heappop(self._heap)
                self._submit(due, st)
            if self.busy():
                with self.annotate("bench.step"):
                    self.eng.step()
                self._harvest()
                continue
            nxt = t_end
            if self._heap:
                nxt = min(nxt, self._heap[0][0])
            if self.traffic.loop == "open":
                nxt = min(nxt, t0 + self.traffic.arrival(self._arrival_next))
            with self.annotate("bench.wait"):
                time.sleep(max(0.0, min(nxt - self.clock(), 0.05)))
        return self.records
