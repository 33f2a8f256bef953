"""Open loop: independent users send requests on a Poisson schedule at a
fixed rate, whether or not earlier requests have been served.

Arrival logic follows ``benchmarks/bench_load.make_workload`` (cumulative
exponential gaps); lengths are clipped lognormals instead of uniform, and
every draw is stratified (``common``). The warm-up and the window get
their own blocks of requests. The schedule (gaps, lengths and their
order) comes from the mix's ``schedule_seed``, the prompt ids from the
run's seed: every seed offers the same work at the same moments.

Parameters (the mix file): ``rate_hz``, ``schedule_seed``, ``prompt`` and
``output`` as ``{"median", "sigma", "lo", "hi"}``.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from bench.traffic import common


class OpenPoisson:
    def __init__(self, params: dict, vocab: int, seed: int,
                 warmup_s: float, seconds: float):
        rate = float(params["rate_hz"])
        self.arrivals: List[common.Arrival] = []
        uid = 0
        for block, (t0, span, phase) in enumerate(
                ((0.0, warmup_s, "warmup"),
                 (warmup_s, seconds, "window"))):
            if span <= 0:
                continue
            plan = common.rng_for(params["schedule_seed"], 1, block)
            rng = common.rng_for(seed, 1, block)
            n = common.count_in(span, rate)
            gaps = common.exponential_gaps(plan, n, rate)
            p_len = common.lognormal(plan, n, **params["prompt"])
            o_len = common.lognormal(plan, n, **params["output"])
            due = t0 + np.cumsum(gaps)
            for i in range(n):
                if due[i] >= t0 + span:
                    continue          # falls after its phase: not sent
                self.arrivals.append(common.Arrival(
                    uid=uid, prompt=common.prompt_tokens(rng, p_len[i], vocab),
                    max_new=int(o_len[i]), due=float(due[i]), phase=phase))
                uid += 1
        self._next = 0

    def poll(self, now: float, outstanding: int) -> List[common.Arrival]:
        """Every request due by ``now`` (seconds since the traffic
        started) that has not been sent yet."""
        out = []
        while self._next < len(self.arrivals) \
                and self.arrivals[self._next].due <= now:
            out.append(self.arrivals[self._next])
            self._next += 1
        return out

    def next_due(self) -> Optional[float]:
        if self._next < len(self.arrivals):
            return self.arrivals[self._next].due
        return None


def make(params: dict, vocab: int, seed: int, warmup_s: float,
         seconds: float) -> OpenPoisson:
    return OpenPoisson(params, vocab, seed, warmup_s, seconds)
