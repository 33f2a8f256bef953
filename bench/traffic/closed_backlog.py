"""Closed loop: a backlog of ``concurrency`` requests is always
outstanding; a new one is sent as soon as one finishes. This is an offline
batch job over a user's documents, where the client waits for nothing but
free capacity.

Lengths come in stratified blocks of ``block`` requests (``common``), in
an order drawn from the mix's ``schedule_seed``; the prompt ids come from
the run's seed. Every seed sends the same lengths in the same order.

Parameters (the mix file): ``concurrency``, ``block``, ``schedule_seed``,
``prompt`` and ``output`` as ``{"median", "sigma", "lo", "hi"}``.
"""
from __future__ import annotations

from typing import List, Optional

from bench.traffic import common


class ClosedBacklog:
    def __init__(self, params: dict, vocab: int, seed: int,
                 warmup_s: float, seconds: float):
        self.params = params
        self.vocab = vocab
        self.seed = seed
        self.warmup_s = warmup_s
        self.concurrency = int(params["concurrency"])
        self.block = int(params["block"])
        self._lens: List[tuple] = []
        self._uid = 0

    def _lengths(self, i: int) -> tuple:
        while i >= len(self._lens):
            b = len(self._lens) // self.block
            rng = common.rng_for(self.params["schedule_seed"], 2, b)
            p = common.lognormal(rng, self.block, **self.params["prompt"])
            o = common.lognormal(rng, self.block, **self.params["output"])
            self._lens.extend(zip(p.tolist(), o.tolist()))
        return self._lens[i]

    def poll(self, now: float, outstanding: int) -> List[common.Arrival]:
        """Top the backlog up to ``concurrency``: each new request is due
        now, the moment its client became free."""
        out = []
        for _ in range(max(0, self.concurrency - outstanding)):
            p_len, o_len = self._lengths(self._uid)
            rng = common.rng_for(self.seed, 3, self._uid)
            out.append(common.Arrival(
                uid=self._uid,
                prompt=common.prompt_tokens(rng, p_len, self.vocab),
                max_new=int(o_len), due=now,
                phase="window" if now >= self.warmup_s else "warmup"))
            self._uid += 1
        return out

    def next_due(self) -> Optional[float]:
        return None


def make(params: dict, vocab: int, seed: int, warmup_s: float,
         seconds: float) -> ClosedBacklog:
    return ClosedBacklog(params, vocab, seed, warmup_s, seconds)
