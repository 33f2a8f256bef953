"""The client: sends a traffic source's requests into the engine and
stamps what it sees, on the host's clock.

One thread does everything, in a loop: send every request that is due,
run one ``Engine.tick`` (one admission pass, a burst of up to
``sync_every`` fused steps, one poll), then read which requests got new
tokens. A token is stamped with the end of the tick that harvested it:
that is the moment the client could first see it.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

STEP_KINDS = ("plain", "mixed", "admit", "spec")
OK_REASONS = ("length", "eos")


@dataclass
class Record:
    """One request, on the client's clock (``time.perf_counter``)."""
    uid: int
    phase: str
    due: float
    prompt_len: int
    max_new: int
    prompt: Optional[np.ndarray] = None
    submit: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    n: int = 0
    finished: Optional[float] = None
    reason: str = ""
    tokens: Optional[np.ndarray] = None

    @property
    def ok(self) -> bool:
        return self.finished is not None and self.reason in OK_REASONS


@dataclass
class Tick:
    """One ``Engine.tick``: its span, the steps it ran by kind, the tokens
    it harvested, and per request ``(uid, tokens before, tokens after)``."""
    start: float
    end: float
    steps: int
    kinds: Dict[str, int]
    tokens: int
    emits: List[Tuple[int, int, int]] = field(default_factory=list)
    waiting: int = 0             # sent requests still without a token


def _null(_name):
    return contextlib.nullcontext()


class Driver:
    def __init__(self, engine, source, *, annotate: Callable = _null):
        self.eng = engine
        self.source = source
        self.annotate = annotate
        self.t0 = 0.0
        self.records: Dict[int, Record] = {}
        self.live: List[int] = []
        self.ticks: List[Tick] = []

    def start(self, t0: float) -> None:
        """Traffic starts: due times count from ``t0``."""
        self.t0 = t0

    def _counter(self, name: str) -> int:
        return self.eng.metrics.counter(name).value

    def _record(self, a) -> Record:
        rec = Record(uid=a.uid, phase=a.phase, due=self.t0 + a.due,
                     prompt_len=len(a.prompt), max_new=a.max_new,
                     prompt=a.prompt)
        self.records[a.uid] = rec
        return rec

    def _send(self, now: float) -> None:
        new = self.source.poll(now - self.t0, len(self.live))
        if not new:
            return
        from repro.serving.request import Request
        with self.annotate("bench:submit"):
            for a in new:
                rec = self._record(a)
                self.eng.submit(Request(
                    uid=a.uid, prompt=a.prompt, max_new_tokens=a.max_new))
                rec.submit = time.perf_counter()
                self.live.append(a.uid)

    def _tick(self) -> Tick:
        kinds0 = {k: self._counter("steps_" + k) for k in STEP_KINDS}
        tok0 = self._counter("tokens_emitted")
        start = time.perf_counter()
        with self.annotate("bench:tick"):
            steps = self.eng.tick()
        end = time.perf_counter()
        tick = Tick(start=start, end=end, steps=steps,
                    kinds={k: self._counter("steps_" + k) - v
                           for k, v in kinds0.items()},
                    tokens=self._counter("tokens_emitted") - tok0)
        still = []
        for uid in self.live:
            rec = self.records[uid]
            resp = self.eng.responses[uid]
            n = len(resp.tokens)
            if n > rec.n:
                if rec.n == 0:
                    rec.first = end
                rec.last = end
                tick.emits.append((uid, rec.n, n))
                rec.n = n
            if resp.finished:
                rec.finished = end
                rec.reason = resp.finish_reason
                rec.tokens = np.asarray(resp.tokens, np.int32)
            else:
                still.append(uid)
                tick.waiting += rec.n == 0
        self.live = still
        self.ticks.append(tick)
        return tick

    def run_until(self, t_stop: float,
                  after_tick: Optional[Callable[[Tick], None]] = None
                  ) -> None:
        """Serve until the first tick boundary at or after ``t_stop``."""
        while True:
            now = time.perf_counter()
            if now >= t_stop:
                return
            self._send(now)
            if self.eng.has_work:
                tick = self._tick()
                if after_tick is not None:
                    after_tick(tick)
                continue
            nxt = self.source.next_due()
            until = t_stop if nxt is None else min(t_stop, self.t0 + nxt)
            with self.annotate("bench:wait"):
                time.sleep(max(0.0, until - time.perf_counter()))

    def drain(self) -> None:
        """Serve what was sent until nothing is left (set-up only)."""
        while self.eng.has_work:
            self._tick()

    def close_window(self, t_close: float) -> None:
        """Record the requests that fell due but were never sent before
        ``t_close``: they count in the latency tail with their wait. A
        closed loop has no such requests (``next_due`` is None)."""
        if self.source.next_due() is None:
            return
        for a in self.source.poll(t_close - self.t0, len(self.live)):
            if a.phase == "window":
                self._record(a)
