"""What the generators share: the request record and seeded draws.

Every draw is stratified: ``n`` values are taken at the quantiles
``(i + 0.5) / n`` of the distribution and put in an order drawn from a
seed. The generators draw that order from the mix's ``schedule_seed`` and
only the prompt ids from the run's seed, so the work in a run, and when
it arrives, does not change with the seed: near capacity the order of
arrivals moves a latency tail by a fifth, which would hide any change
smaller than that.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


@dataclass
class Arrival:
    """One request as the client sends it. ``due`` is the second, counted
    from the start of the traffic, at which the client wants it sent;
    ``phase`` is "warmup" or "window"."""
    uid: int
    prompt: np.ndarray
    max_new: int
    due: float
    phase: str


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal(rng: np.random.Generator, n: int, median: float,
              sigma: float, lo: int, hi: int) -> np.ndarray:
    """``n`` integer lengths from a lognormal with the given median and
    log-space ``sigma``, clipped to ``[lo, hi]``, in seeded order."""
    z = np.array([_NORMAL.inv_cdf(u) for u in quantiles(n)])
    vals = np.clip(np.rint(median * np.exp(sigma * z)), lo, hi)
    return rng.permutation(vals.astype(np.int64))


def exponential_gaps(rng: np.random.Generator, n: int,
                     rate_hz: float) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process at ``rate_hz``."""
    gaps = -np.log1p(-quantiles(n)) / rate_hz
    return rng.permutation(gaps)


def prompt_tokens(rng: np.random.Generator, length: int,
                  vocab: int) -> np.ndarray:
    """Prompt ids drawn uniformly over the whole vocabulary."""
    return rng.integers(0, vocab, int(length), dtype=np.int64).astype(
        np.int32)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent numpy generator per (seed, stream) pair; accepts any
    non-negative seed, including ones wider than 32 bits."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), *stream])


def count_in(seconds: float, rate_hz: float) -> int:
    return max(1, int(math.floor(seconds * rate_hz + 0.5)))
