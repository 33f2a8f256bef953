"""Serving telemetry: the unified metrics registry, shared percentile
helpers, the recorder interface, and the recompile watchdog.

The engine used to keep ad-hoc host-side lists (``step_times``,
``step_kinds``, ``_spec_emitted``, per-request ITL dicts) and rebuild
``latency_stats()`` from them by hand; benchmarks grew their own copies
of the percentile math. This module centralises all of it:

* :class:`MetricsRegistry` — named counters, gauges, bounded-reservoir
  histograms, and aligned series. The engine owns one registry and
  every stat it reports (``latency_stats()``, bench snapshots, the
  serve driver's periodic summary) is derived from it. Components that
  already keep their own counters (``PrefixCache``, ``PagedKVState``)
  are attached as *collectors*: ``snapshot()`` pulls their live
  ``stats()`` dicts without double-counting.
* :func:`pct_stats` / :func:`percentile` — the one percentile
  implementation (same keys, same empty-sample omission contract as
  PR 5: a stream with no samples contributes *no* keys, never a
  fabricated 0.0).
* :class:`Recorder` — the request-lifecycle event interface. The base
  class is the no-op default: every hook is ``pass``, ``enabled`` is
  False, and the engine's disabled path does zero per-step device work
  and no per-event allocation beyond the call itself.
  ``serving/tracing.Tracer`` is the recording implementation.
* :class:`CompileWatchdog` + :class:`RecompileWarning` — every XLA
  compile observed through ``Engine._jit`` is recorded (program name,
  elapsed wall); once the watchdog is *armed* (``Engine.reset_stats``
  after warmup, or ``Engine.mark_steady()``), any further compile is a
  steady-state recompile: a structured warning at runtime and a
  ``steady_compiles`` counter benchmarks fail CI on. This turns the
  test-only ``program_cache_sizes()`` guard into an always-on signal.

Everything here is host-side and cheap: no jax imports, no device work.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "percentile", "pct_stats",
    "Counter", "Gauge", "Histogram", "Series", "MetricsRegistry",
    "Recorder", "RecompileWarning", "CompileWatchdog",
]


# --------------------------------------------------------------------- #
# percentile math (the single implementation)
# --------------------------------------------------------------------- #
def percentile(samples: Sequence[float], p: float) -> float:
    """Linear-interpolation percentile over raw samples (the numpy
    default — the same basis every stats key in this repo has always
    used). Raises on an empty sample set: callers decide the empty
    contract (``pct_stats`` omits keys)."""
    return float(np.percentile(np.asarray(samples, np.float64), p))


def pct_stats(stats: Dict[str, float], prefix: str, samples,
              pcts: Tuple[int, ...]) -> None:
    """Add ``{prefix}_mean`` / ``{prefix}_p{p}`` keys (in ms, samples in
    seconds) for one latency stream — only when it actually produced
    samples. An empty stream contributes *no* keys (rather than
    fabricated 0.0 latencies that would poison benchmark artifacts):
    consumers treat a missing key as "no data"."""
    arr = np.asarray(samples, np.float64)
    if arr.size == 0:
        return
    stats[f"{prefix}_mean"] = float(arr.mean() * 1e3)
    for p in pcts:
        stats[f"{prefix}_p{p}"] = float(np.percentile(arr, p) * 1e3)


# --------------------------------------------------------------------- #
# metric primitives
# --------------------------------------------------------------------- #
class Counter:
    """Monotonic counter. ``persist=True`` survives ``registry.reset()``
    (e.g. total compiles — warmup history must not be erasable by a
    benchmark's stats reset)."""
    __slots__ = ("value", "persist")

    def __init__(self, persist: bool = False):
        self.value = 0
        self.persist = persist

    def inc(self, n: int = 1) -> None:
        self.value += n

    def reset(self) -> None:
        if not self.persist:
            self.value = 0


class Gauge:
    """Last-sampled value (active slots, free pages, ...)."""
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)

    def reset(self) -> None:
        self.value = 0.0


class Histogram:
    """Bounded-reservoir sample store (Vitter's algorithm R past the
    cap, deterministic seed): percentiles are exact until ``cap``
    samples, an unbiased reservoir estimate beyond — memory stays O(cap)
    over unbounded serving runs."""
    __slots__ = ("cap", "samples", "count", "_rng", "_seed")

    def __init__(self, cap: int = 8192, seed: int = 0):
        self.cap = int(cap)
        self.samples: List[float] = []
        self.count = 0
        self._seed = seed
        self._rng = np.random.default_rng(seed)

    def observe(self, v: float) -> None:
        self.count += 1
        if len(self.samples) < self.cap:
            self.samples.append(float(v))
            return
        j = int(self._rng.integers(0, self.count))
        if j < self.cap:
            self.samples[j] = float(v)

    @property
    def values(self) -> List[float]:
        return self.samples

    def summary(self, pcts: Tuple[int, ...] = (50, 95, 99)
                ) -> Dict[str, float]:
        out: Dict[str, float] = {"count": self.count}
        if self.samples:
            arr = np.asarray(self.samples, np.float64)
            out["mean"] = float(arr.mean())
            out["max"] = float(arr.max())
            for p in pcts:
                out[f"p{p}"] = float(np.percentile(arr, p))
        return out

    def reset(self) -> None:
        self.samples = []
        self.count = 0
        self._rng = np.random.default_rng(self._seed)


class Series:
    """Aligned append-only store — the registry home of per-step records
    whose *order* matters (step wall times aligned with step kinds, the
    compile log). ``values`` is the live list: the engine mutates it in
    place (burst averaging rewrites entries), so it is the same object
    across reads."""
    __slots__ = ("values",)

    def __init__(self):
        self.values: List[Any] = []

    def append(self, v: Any) -> None:
        self.values.append(v)

    def __len__(self) -> int:
        return len(self.values)

    def reset(self) -> None:
        self.values.clear()


class MetricsRegistry:
    """Named metric store with get-or-create accessors. ``snapshot()``
    renders everything JSON-serializable (the ``BENCH_*.json``
    ``telemetry`` section and the serve driver's JSONL records);
    ``reset()`` clears non-persistent state (the
    ``Engine.reset_stats()`` contract: forget timing history, keep
    compiled-program facts)."""

    def __init__(self):
        self.counters: Dict[str, Counter] = {}
        self.gauges: Dict[str, Gauge] = {}
        self.histograms: Dict[str, Histogram] = {}
        self.series: Dict[str, Series] = {}
        self._collectors: List[Callable[[], Dict[str, Any]]] = []

    # -- get-or-create ------------------------------------------------ #
    def counter(self, name: str, persist: bool = False) -> Counter:
        c = self.counters.get(name)
        if c is None:
            c = self.counters[name] = Counter(persist=persist)
        return c

    def gauge(self, name: str) -> Gauge:
        g = self.gauges.get(name)
        if g is None:
            g = self.gauges[name] = Gauge()
        return g

    def histogram(self, name: str, cap: int = 8192) -> Histogram:
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram(cap=cap)
        return h

    def get_series(self, name: str) -> Series:
        s = self.series.get(name)
        if s is None:
            s = self.series[name] = Series()
        return s

    def add_collector(self, fn: Callable[[], Dict[str, Any]]) -> None:
        """Attach a live stats source (e.g. ``PrefixCache.stats``):
        called at every ``snapshot()`` and merged under ``collected``.
        Collectors own their counters — the registry never copies or
        resets them."""
        self._collectors.append(fn)

    # -- output -------------------------------------------------------- #
    def snapshot(self) -> Dict[str, Any]:
        snap: Dict[str, Any] = {
            "counters": {k: c.value for k, c in sorted(
                self.counters.items())},
            "gauges": {k: g.value for k, g in sorted(self.gauges.items())},
            "histograms": {k: h.summary() for k, h in sorted(
                self.histograms.items())},
            "series": {},
        }
        for k, s in sorted(self.series.items()):
            vals = s.values
            if vals and all(isinstance(v, (int, float)) for v in vals):
                arr = np.asarray(vals, np.float64)
                snap["series"][k] = {
                    "count": len(vals), "mean": float(arr.mean()),
                    "p50": float(np.percentile(arr, 50)),
                    "p99": float(np.percentile(arr, 99)),
                    "max": float(arr.max())}
            else:
                snap["series"][k] = {"count": len(vals),
                                     "values": list(vals[-64:])}
        collected: Dict[str, Any] = {}
        for fn in self._collectors:
            collected.update(fn())
        snap["collected"] = collected
        return snap

    def reset(self) -> None:
        for group in (self.counters, self.gauges, self.histograms,
                      self.series):
            for m in group.values():
                m.reset()


# --------------------------------------------------------------------- #
# recorder interface (no-op default)
# --------------------------------------------------------------------- #
class Recorder:
    """Request-lifecycle event sink. This base class *is* the disabled
    path: every hook is a no-op and ``enabled`` is False, so the engine
    skips the (tiny) host work of assembling event payloads that need
    it. ``serving/tracing.Tracer`` subclasses it to build Chrome-trace
    timelines. All timestamps are ``time.perf_counter()`` seconds."""
    enabled = False

    def on_submit(self, req) -> None:
        pass

    def on_admission(self, req, slot: int, base: int, kind: str) -> None:
        """Request leaves the queue: ``kind`` is "chunked" (the fused
        mixed path every admission takes; ``base`` > 0 on a
        prefix-cache hit) or "fallback" (defensive-only: a stack with
        no ``extend_into_cache``, counted and rejected)."""

    def on_chunk(self, req, slot: int, lo: int, hi: int,
                 last: bool) -> None:
        """One admission chunk ``prompt[lo:hi)`` dispatched."""

    def on_first_token(self, req, ts: float) -> None:
        pass

    def on_emit(self, req, slot: int, n: int, ts: float) -> None:
        """``n`` tokens of ``req`` harvested at a poll."""

    def on_finish(self, req, reason: str, ts: float) -> None:
        pass

    def on_preempt(self, req, slot: int, ts: float) -> None:
        """``req`` evicted from ``slot`` and requeued (it will resume by
        replaying its generated prefix — docs/robustness.md)."""

    def on_fault(self, site: str, step: int, ts: float) -> None:
        """A scheduled fault fired at ``site`` (serving/faults.py)."""

    def on_poll(self, ts: float, active: int,
                stats: Dict[str, float]) -> None:
        """Periodic host sync: live occupancy / pool sample."""

    def on_compile(self, name: str, elapsed_s: float, steady: bool,
                   ts: float) -> None:
        pass


# --------------------------------------------------------------------- #
# recompile watchdog
# --------------------------------------------------------------------- #
class RecompileWarning(UserWarning):
    """A jitted engine program compiled a new specialization after the
    engine was marked steady — in serving, a silent latency cliff
    (~100ms+ per occurrence) that ``program_cache_sizes()`` could only
    catch in tests. Carries the program name and observed elapsed wall
    (trace + compile, measured around the dispatch call)."""

    def __init__(self, program: str, elapsed_s: float, step: int):
        self.program = program
        self.elapsed_s = elapsed_s
        self.step = step
        super().__init__(
            f"steady-state XLA recompile of {program!r} at engine step "
            f"{step} ({elapsed_s * 1e3:.1f} ms) — an input's "
            f"shape/layout/sharding is churning; see "
            f"docs/observability.md#recompile-watchdog")


class CompileWatchdog:
    """Records every XLA compile observed by ``Engine._jit`` wrappers
    into the registry (``compiles_total`` / ``steady_compiles``
    persistent counters plus a ``compiles`` series of per-event dicts)
    and raises :class:`RecompileWarning` for compiles after ``arm()``.

    Warmup compiles are expected (first call of every program); a
    *steady-state* compile is always a regression. Arming is explicit:
    ``Engine.reset_stats()`` (the warm-then-measure benchmark contract)
    or ``Engine.mark_steady()``."""

    def __init__(self, registry: MetricsRegistry,
                 recorder: Optional[Recorder] = None):
        self.registry = registry
        self.recorder = recorder or Recorder()
        self.steady = False
        self._total = registry.counter("compiles_total", persist=True)
        self._steady_c = registry.counter("steady_compiles", persist=True)
        self._log = registry.get_series("compiles")

    def arm(self) -> None:
        self.steady = True

    def record(self, name: str, elapsed_s: float, step: int,
               ts: float) -> None:
        self._total.inc()
        self._log.append({"program": name,
                          "elapsed_ms": round(elapsed_s * 1e3, 3),
                          "step": step, "steady": self.steady})
        self.recorder.on_compile(name, elapsed_s, self.steady, ts)
        if self.steady:
            self._steady_c.inc()
            warnings.warn(RecompileWarning(name, elapsed_s, step),
                          stacklevel=3)
