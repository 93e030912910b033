"""Open-loop driver over the token cascade.

``TokenEngine.serve`` takes a closed list and has no clock, so the clock
lives here. The driver steps the engine one token boundary at a time,
exactly as ``serve`` does: for each stage, ``_admit`` (the boundary's
prefill) and then, if rows are active, ``_step_fused`` (one fused decode
step). Between boundaries it moves the requests that have come due into
stage 0's queue, and when nothing is active or waiting it sleeps until
the next one is due. This is the one place the benchmark reaches into the
engine; a public clocked entry would replace it.

Every call is stamped with ``time.perf_counter()`` after it returns, and
records the change it made to each numeric field of its stage engine's
``stats`` (``Call.counts``): whatever an engine counts, such as prompts
prefilled or, in an engine that routes, the experts a step reads, reaches
the family's counts with no edit here. Both
calls end in ``np.asarray`` on the device's outputs, so the stamps follow
the device. Host phases are wrapped in ``jax.profiler.TraceAnnotation`` so
that a trace can name what the host was doing in each idle gap of the
device; an engine call's annotation carries its boundary (``b``), so that
a trace's device events can be matched to the call they ran for.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from traffic import Arrival


@dataclass
class Record:
    """One request as the client sees it."""
    arrival: Arrival
    due: float                               # absolute, perf_counter
    stamps: Dict[int, List[float]] = field(default_factory=dict)
    admit_start: Dict[int, float] = field(default_factory=dict)
    done: Optional[float] = None
    resolver: int = -1
    tokens: List[int] = field(default_factory=list)
    gaps: List[float] = field(default_factory=list)


@dataclass
class Call:
    """One engine call: an admit (prefill) or a fused decode step."""
    kind: str                 # "admit" | "decode"
    stage: int
    t0: float
    t1: float
    rows: int                 # prompts admitted, or rows active
    prompt_lens: List[int] = field(default_factory=list)
    padded_rows: int = 0      # rows the executable ran (batch bucket)
    depths: List[int] = field(default_factory=list)   # decode: per row
    boundary: int = -1
    counts: Dict[str, float] = field(default_factory=dict)


def numbers(stats) -> Dict[str, float]:
    """The numeric fields of an engine's ``stats`` (sets left out)."""
    return {k: v for k, v in vars(stats).items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def change(before: Dict[str, float], stats) -> Dict[str, float]:
    return {k: v - before.get(k, 0) for k, v in numbers(stats).items()}


class OpenLoop:
    """Drives ``te`` (a fused ``TokenEngine``, ``spec_k`` 1) over
    ``arrivals`` on the host clock from ``t_start``."""

    def __init__(self, te, arrivals: List[Arrival], t_start: float,
                 clock: Callable[[], float] = time.perf_counter):
        from repro.serving.token_engine import TokenRequest, TokenResult
        self.te = te
        self.clock = clock
        self.t_start = t_start
        self.records: Dict[int, Record] = {}
        self.pending = deque(arrivals)
        self.waiting = [deque() for _ in te.stages]
        self.act = [[] for _ in te.stages]
        self.calls: List[Call] = []
        self.queue_len: List[tuple] = []     # (time, waiting per stage)
        self.boundary = 0
        self._req = TokenRequest
        self._res = TokenResult
        self.hooks: List[tuple] = []         # (time, callback), run once

    # ---------------------------------------------------------- arrivals
    def _release(self, now: float) -> None:
        while self.pending and self.t_start + self.pending[0].due <= now:
            a = self.pending.popleft()
            rec = Record(a, self.t_start + a.due)
            self.records[a.rid] = rec
            self.waiting[0].append((self._req(a.rid, a.prompt, a.max_new),
                                    self._res(rid=a.rid)))

    def busy(self) -> bool:
        return any(self.waiting) or any(self.act)

    def next_due(self) -> Optional[float]:
        return self.t_start + self.pending[0].due if self.pending else None

    # ---------------------------------------------------------- one step
    def step(self) -> None:
        """One token boundary over every stage (or one idle wait)."""
        te, clock = self.te, self.clock
        now = clock()
        while self.hooks and self.hooks[0][0] <= now:
            self.hooks.pop(0)[1]()
        with TraceAnnotation("arrivals"):
            self._release(now)
        if not self.busy():
            nxt = self.next_due()
            nxt = now + 0.001 if nxt is None else nxt
            with TraceAnnotation("wait_arrival"):
                time.sleep(max(0.0, min(nxt, self._hook_time()) - now))
            return
        self.queue_len.append((now, tuple(len(w) for w in self.waiting)))
        for si, eng in enumerate(te.stages):
            queue = list(self.waiting[si])
            before = numbers(eng.stats)
            t0 = clock()
            with TraceAnnotation(f"admit.s{si}", b=self.boundary):
                te._admit(si, eng, self.waiting, self.act, self.boundary)
            t1 = clock()
            joined = queue[:len(queue) - len(self.waiting[si])]
            if joined:
                self.calls.append(Call(
                    "admit", si, t0, t1, len(joined),
                    prompt_lens=[int(r.prompt.size) for r, _ in joined],
                    padded_rows=eng._batch_bucket(len(joined)),
                    boundary=self.boundary,
                    counts=change(before, eng.stats)))
                for req, _ in joined:
                    rec = self.records[req.rid]
                    rec.admit_start[si] = t0
                    rec.stamps[si] = [t1]
            if not self.act[si]:
                continue
            rows = list(self.act[si])
            depths = [int(eng.pos[a.slot]) for a in rows]
            before = numbers(eng.stats)
            t0 = clock()
            with TraceAnnotation(f"decode.s{si}", b=self.boundary):
                te._step_fused(si, eng, self.waiting, self.act,
                               self.boundary)
            t1 = clock()
            with TraceAnnotation("bookkeeping"):
                self.calls.append(Call("decode", si, t0, t1, len(rows),
                                       padded_rows=eng.n_slots,
                                       depths=depths,
                                       boundary=self.boundary,
                                       counts=change(before, eng.stats)))
                for a in rows:
                    rec = self.records[a.req.rid]
                    rec.stamps[si].append(t1)
                    if a.res.resolver == si and rec.done is None:
                        rec.done, rec.resolver = t1, si
                        rec.tokens = list(a.res.tokens)
                        rec.gaps = list(a.res.gaps)
        self.boundary += 1

    def _hook_time(self) -> float:
        return self.hooks[0][0] if self.hooks else float("inf")

    def run(self, until: Callable[[float], bool]) -> None:
        """Step until ``until(now)`` holds at a boundary."""
        while not until(self.clock()):
            self.step()

    def drained(self) -> bool:
        return not self.busy()
