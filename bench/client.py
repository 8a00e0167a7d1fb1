"""The load generator: drives one engine with one mix, from one thread.

The client submits requests through ``DecodeEngine.submit``, advances
the engine through ``DecodeEngine.step("continuous")``, and after every
step reads each request's progress (``engine_view``) on its own clock.
Each of the three phases is a ``jax.profiler.TraceAnnotation`` span, so
a trace can say what the host was doing while the device sat idle.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from bench.engine_view import EngineView
from bench.traffic import Traffic

SPANS = ("client.submit", "engine.step", "client.harvest", "client.idle")


@dataclasses.dataclass
class Record:
    """One request as the client saw it. Times are client-clock
    seconds; counts are output tokens (``c``) and prompt tokens the
    engine has consumed (``p``)."""
    uid: int
    prompt: np.ndarray
    max_new: int
    due: Optional[float]          # open loop: when it was due
    t_sub: float                  # when it was submitted
    in_window: bool = False       # submitted inside the window
    t_first: Optional[float] = None
    c_last: int = 0
    p_last: int = 0
    c_t0: int = 0                 # counts when the window opened
    p_t0: int = 0
    c_t1: int = 0                 # counts when it closed
    p_t1: int = 0
    t_tok: Optional[float] = None  # last observation that brought tokens
    itl: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    #   inside the window, for each observation that brought tokens after
    #   the first: (seconds per token since the one before, tokens)
    status: Optional[str] = None
    tokens: Optional[np.ndarray] = None


class Client:
    def __init__(self, engine, traffic: Traffic, clock=time.perf_counter):
        self.engine = engine
        self.traffic = traffic
        self.clock = clock
        self.view = EngineView(engine)
        self.records: Dict[int, Record] = {}
        self.backlog = int(traffic.mix.get("backlog_per_slot", 0)
                           * engine.n_slots)
        self.t_start: Optional[float] = None
        self.in_window = False
        self._pending = None

    # -- submission ---------------------------------------------------

    def _submit_one(self, req, due: Optional[float]) -> None:
        uid = self.engine.submit(req.prompt, req.max_new_tokens)
        self.records[uid] = Record(
            uid=uid, prompt=req.prompt, max_new=req.max_new_tokens,
            due=due, t_sub=self.clock(), in_window=self.in_window)

    def _submit_due(self, now: float) -> None:
        """Closed loop: top the queue up to the backlog. Open loop:
        submit every request due at or before ``now``."""
        if self.traffic.closed:
            while self.engine.queue_depth() < self.backlog:
                self._submit_one(self.traffic.next(), None)
            return
        while self._next_due() <= now:
            self._submit_one(self._pending, self._next_due())
            self._pending = None

    def _next_due(self) -> float:
        if self._pending is None:
            self._pending = self.traffic.next()
        return self.t_start + self._pending.due_s

    # -- observation --------------------------------------------------

    def _observe(self, t: float) -> None:
        seen, done = self.view.poll()
        recs = self.records
        for uid, (c, p) in seen.items():
            r = recs[uid]
            if c > r.c_last:
                if r.t_first is None:
                    r.t_first = t
                elif self.in_window:
                    r.itl.append(((t - r.t_tok) / (c - r.c_last),
                                  c - r.c_last))
                r.t_tok = t
            r.c_last, r.p_last = c, p
        for comp in done:
            r = recs[comp.uid]
            r.status, r.tokens = comp.status, comp.tokens

    # -- driving ------------------------------------------------------

    def start(self) -> None:
        self.t_start = self.clock()

    def run_until(self, t_stop: float) -> float:
        """Drive the engine until an observation at or after
        ``t_stop``; returns that observation's time."""
        engine, clock = self.engine, self.clock
        while True:
            with jax.profiler.TraceAnnotation("client.submit"):
                self._submit_due(clock())
            if not engine.has_work():
                with jax.profiler.TraceAnnotation("client.idle"):
                    wait = min(self._next_due(), t_stop) - clock()
                    if wait > 0:
                        time.sleep(wait)
                t = clock()
                if t >= t_stop:
                    return t
                continue
            with jax.profiler.TraceAnnotation("engine.step"):
                engine.step("continuous")
            t = clock()
            with jax.profiler.TraceAnnotation("client.harvest"):
                self._observe(t)
            if t >= t_stop:
                return t

    def open_window(self, t0: float) -> None:
        """Start counting at observation time ``t0``."""
        self.in_window = True
        for r in self.records.values():
            r.c_t0, r.p_t0 = r.c_last, r.p_last

    def close_window(self, t1: float) -> None:
        """Stop counting at ``t1``. Requests due by then but not yet
        sent are sent now, so that each counts in the tails."""
        if not self.traffic.closed:
            self._submit_due(t1)
        self.in_window = False
        for r in self.records.values():
            r.c_t1, r.p_t1 = r.c_last, r.p_last

    def drain(self, n_finished: int, t_stop: float) -> None:
        """After the window: step without submitting until
        ``n_finished`` requests have finished, the engine is idle, or
        ``t_stop``, so that the check has finished requests to read
        even where a window is shorter than one request."""
        while (sum(r.tokens is not None for r in self.records.values())
               < n_finished and self.engine.has_work()
               and self.clock() < t_stop):
            self.engine.step("continuous")
            self._observe(self.clock())

    # -- what the window saw ------------------------------------------

    def window_records(self) -> List[Record]:
        return list(self.records.values())

    def output_tokens(self) -> int:
        return sum(r.c_t1 - r.c_t0 for r in self.records.values())

    def attempted(self, t0: float, t1: float) -> List[Record]:
        """Requests the window is answerable for: those it submitted or
        served, by a prompt token consumed or an output token emitted
        (closed loop), or those due in it (open loop). A closed loop
        whose requests outlast the window submits none in it."""
        if self.traffic.closed:
            return [r for r in self.records.values()
                    if r.in_window or r.c_t1 > r.c_t0 or r.p_t1 > r.p_t0]
        return [r for r in self.records.values()
                if r.due is not None and t0 <= r.due <= t1]
