"""Request-lifecycle tracing: Chrome trace-event export for serving runs,
and the engine's host spans in the profiler's own trace.

:class:`Tracer` is the recording :class:`~repro.serving.telemetry.Recorder`
implementation: the engine feeds it span events for every request
(enqueued → admission chunks → first token → per-poll emissions →
finished/evicted), poll-time pool samples and compile events, all
host-side. ``export_chrome_trace`` renders the collected run as Chrome
trace-event JSON — open it at ``ui.perfetto.dev`` (or
``chrome://tracing``) and the run reads as:

* one lane per batch **slot** (``slot 0..B-1``): a complete span per
  request occupying it, with instants for admission chunks, the first
  token, and each poll's token emissions;
* a **queue** lane: per-request wait between ``submit`` and admission;
* a **compiles** lane: every XLA compile with its elapsed wall
  (steady-state ones flagged — the recompile watchdog's signal);
* counter tracks for **active slots** and **page-pool occupancy**
  (live/free pages), sampled at every poll.

What each engine step did on the host and the device is in the
``jax.profiler`` trace instead: :func:`span` opens the engine's
``engine:<phase>`` host spans there (always on; without a profiler
session a span is a constructor and an atomic check), beside the
device ops of the same run.

One clock: the profiler stamps with the system clock (``time.time_ns``,
nanoseconds since the epoch; an ``.xplane.pb`` stores its times as
offsets from the session's ``profile_start_time``). The tracer keeps
its events on that clock: it stamps them with ``time.time_ns`` or, for
the engine's ``time.perf_counter`` stamps, converts each as it arrives
(:func:`profiler_ns`), so a step or slew of the system clock moves the
tracer's later events as it moves the profiler's. Its ``ts`` are
microseconds on the profiler's clock: every replica's export and a
profile of the same run share one time base, and :func:`profile_trace`
renders a profile on it for :func:`merge_chrome_traces`.

The tracer is pure host bookkeeping: it never touches device state, so
a traced run's greedy outputs and compiled-program counts are
bit-identical to an untraced one (asserted in
``tests/test_telemetry.py``).
"""
from __future__ import annotations

import glob
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.serving.telemetry import Recorder

__all__ = ["Tracer", "span", "profiler_ns", "newest_profile",
           "profile_trace", "validate_chrome_trace", "complete_spans",
           "merge_chrome_traces"]

SPAN_PREFIX = "engine:"

# fixed thread-lane ids (slot lanes are 1..max_batch)
QUEUE_TID = 0
COMPILE_TID = 901
FAULT_TID = 902
_PID = 1


def span(phase: str) -> jax.profiler.TraceAnnotation:
    """The host span ``engine:<phase>`` in the ``jax.profiler`` trace,
    as a context manager."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + phase)


def profiler_ns(t: float) -> int:
    """A ``time.perf_counter`` stamp as nanoseconds on the profiler's
    clock, through a reading of both clocks taken now: convert a stamp
    soon after it is taken."""
    return time.time_ns() - round((time.perf_counter() - t) * 1e9)


def _us(ns: int) -> float:
    return round(ns * 1e-3, 1)


class Tracer(Recorder):
    enabled = True

    def __init__(self):
        self.t0 = time.time_ns()
        # uid -> lifecycle record (insertion order = submit order)
        self.requests: Dict[int, Dict[str, Any]] = {}
        # stamps in ns on the profiler's clock
        self.polls: List[Tuple[int, int, Dict[str, float]]] = []
        self.compiles: List[Tuple[int, str, float, bool]] = []
        self.faults: List[Tuple[int, str, int]] = []

    # -- Recorder hooks ------------------------------------------------ #
    def on_submit(self, req) -> None:
        self.requests[req.uid] = {
            "uid": req.uid, "prompt_len": len(req.prompt),
            "submitted": time.time_ns(), "admitted": None,
            "slot": None, "kind": "", "base": 0, "chunks": [],
            "first_token": None, "emits": [], "finished": None,
            "reason": "", "generated": 0, "preempts": []}

    def on_admission(self, req, slot: int, base: int, kind: str) -> None:
        r = self.requests.get(req.uid)
        if r is None:
            return
        r["admitted"] = time.time_ns()
        r["slot"] = slot
        r["kind"] = kind
        r["base"] = base

    def on_chunk(self, req, slot: int, lo: int, hi: int,
                 last: bool) -> None:
        r = self.requests.get(req.uid)
        if r is not None:
            r["chunks"].append((time.time_ns(), lo, hi, last))

    def on_first_token(self, req, ts: float) -> None:
        r = self.requests.get(req.uid)
        if r is not None and r["first_token"] is None:
            r["first_token"] = profiler_ns(ts)

    def on_emit(self, req, slot: int, n: int, ts: float) -> None:
        r = self.requests.get(req.uid)
        if r is not None and n:
            r["emits"].append((profiler_ns(ts), n))
            r["generated"] += n

    def on_finish(self, req, reason: str, ts: float) -> None:
        r = self.requests.get(req.uid)
        if r is not None:
            r["finished"] = profiler_ns(ts)
            r["reason"] = reason

    def on_preempt(self, req, slot: int, ts: float) -> None:
        r = self.requests.get(req.uid)
        if r is not None:
            r["preempts"].append((profiler_ns(ts), slot))

    def on_fault(self, site: str, step: int, ts: float) -> None:
        self.faults.append((profiler_ns(ts), site, step))

    def on_poll(self, ts: float, active: int,
                stats: Dict[str, float]) -> None:
        self.polls.append((profiler_ns(ts), active, dict(stats)))

    def on_compile(self, name: str, elapsed_s: float, steady: bool,
                   ts: float) -> None:
        self.compiles.append((profiler_ns(ts), name, elapsed_s, steady))

    # -- export -------------------------------------------------------- #
    def export_chrome_trace(self, path: Optional[str] = None
                            ) -> Dict[str, Any]:
        """Render the collected run as a Chrome trace-event object
        (``{"traceEvents": [...]}``); write JSON to ``path`` when given.
        Requests still running (or never admitted) at export time get an
        open-ended span cut at "now" with reason ``evicted``."""
        now = time.time_ns()
        ev: List[Dict[str, Any]] = []

        def meta(tid: int, name: str) -> None:
            ev.append({"name": "thread_name", "ph": "M", "ts": 0,
                       "pid": _PID, "tid": tid,
                       "args": {"name": name}})

        ev.append({"name": "process_name", "ph": "M", "ts": 0,
                   "pid": _PID, "tid": 0,
                   "args": {"name": "serving engine"}})
        meta(QUEUE_TID, "queue")
        slots = sorted({r["slot"] for r in self.requests.values()
                       if r["slot"] is not None})
        for b in slots:
            meta(1 + b, f"slot {b}")
        meta(COMPILE_TID, "compiles")
        if self.faults:
            meta(FAULT_TID, "faults")

        for r in self.requests.values():
            uid = r["uid"]
            adm = r["admitted"]
            end = r["finished"] if r["finished"] is not None else now
            # queue lane: submit -> admission (or still waiting)
            ev.append({"name": f"queue u{uid}", "ph": "X",
                       "ts": _us(r["submitted"]),
                       "dur": max(0.0, _us(
                           (adm if adm is not None else end)
                           - r["submitted"])),
                       "pid": _PID, "tid": QUEUE_TID,
                       "args": {"uid": uid,
                                "prompt_len": r["prompt_len"]}})
            if adm is None:
                continue
            tid = 1 + r["slot"]
            # the request's complete span on its slot lane
            ev.append({"name": f"req {uid}", "ph": "X",
                       "ts": _us(adm),
                       "dur": max(0.0, _us(end - adm)),
                       "pid": _PID, "tid": tid,
                       "args": {"uid": uid,
                                "prompt_len": r["prompt_len"],
                                "admission": r["kind"],
                                "prefix_reused": r["base"],
                                "generated": r["generated"],
                                "preemptions": len(r["preempts"]),
                                "finish": r["reason"] or "evicted"}})
            for (t, pslot) in r["preempts"]:
                ev.append({"name": "preempt", "ph": "i",
                           "ts": _us(t), "pid": _PID,
                           "tid": 1 + pslot, "s": "t",
                           "args": {"uid": uid}})
            for (t, lo, hi, last) in r["chunks"]:
                ev.append({"name": f"chunk {lo}:{hi}", "ph": "i",
                           "ts": _us(t), "pid": _PID, "tid": tid,
                           "s": "t",
                           "args": {"uid": uid, "last": bool(last)}})
            if r["first_token"] is not None:
                ev.append({"name": "first_token", "ph": "i",
                           "ts": _us(r["first_token"]),
                           "pid": _PID, "tid": tid, "s": "t",
                           "args": {"uid": uid}})
            for (t, n) in r["emits"]:
                ev.append({"name": f"emit {n}", "ph": "i",
                           "ts": _us(t), "pid": _PID, "tid": tid,
                           "s": "t", "args": {"uid": uid, "n": n}})
            if r["finished"] is not None:
                ev.append({"name": f"finish:{r['reason']}", "ph": "i",
                           "ts": _us(r["finished"]), "pid": _PID,
                           "tid": tid, "s": "t", "args": {"uid": uid}})

        for (t, site, step) in self.faults:
            ev.append({"name": f"fault {site}", "ph": "i",
                       "ts": _us(t), "pid": _PID, "tid": FAULT_TID,
                       "s": "t", "args": {"site": site, "step": step}})
        for (t, name, elapsed, steady) in self.compiles:
            ev.append({"name": f"compile {name}", "ph": "X",
                       "ts": _us(max(t, self.t0)),
                       "dur": round(elapsed * 1e6, 1),
                       "pid": _PID, "tid": COMPILE_TID,
                       "args": {"steady": bool(steady)}})
        for (t, active, stats) in self.polls:
            ev.append({"name": "active_slots", "ph": "C",
                       "ts": _us(t), "pid": _PID,
                       "args": {"active": active}})
            if "kv_pages_live" in stats:
                ev.append({"name": "page_pool", "ph": "C",
                           "ts": _us(t), "pid": _PID,
                           "args": {"live": stats["kv_pages_live"],
                                    "free": stats["kv_pages_free"]}})

        trace = {"traceEvents": ev, "displayTimeUnit": "ms"}
        if path:
            with open(path, "w") as f:
                json.dump(trace, f)
        return trace


# --------------------------------------------------------------------- #
# a profile on the same clock, and the multi-process merge
# --------------------------------------------------------------------- #
def newest_profile(trace_dir: str) -> "jax.profiler.ProfileData":
    """The newest ``.xplane.pb`` under ``trace_dir``, loaded."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(
        max(paths, key=os.path.getmtime))


def profile_trace(trace_dir: str) -> Dict[str, Any]:
    """The newest ``jax.profiler`` session under ``trace_dir`` (an
    engine's ``trace_dir=`` / ``serve.py --trace-dir``) as a Chrome
    trace-event object on the profiler's clock, the time base of a
    :class:`Tracer` export: one lane per line of each plane (host
    threads with their ``engine:*`` spans, each device's XLA ops and
    modules), thread names ``<plane> <line>``."""
    prof = newest_profile(trace_dir)
    origin = None
    for plane in prof.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                origin = int(value)
    if origin is None:
        raise ValueError(f"{trace_dir}: the profile has no "
                         f"profile_start_time")
    ev: List[Dict[str, Any]] = []
    tid = 0
    for plane in prof.planes:
        for line in plane.lines:
            tid += 1
            ev.append({"name": "thread_name", "ph": "M", "ts": 0,
                       "pid": _PID, "tid": tid,
                       "args": {"name": f"{plane.name} {line.name}"}})
            ev.extend({"name": e.name, "ph": "X",
                       "ts": round((origin + e.start_ns) * 1e-3, 3),
                       "dur": round(e.duration_ns * 1e-3, 3),
                       "pid": _PID, "tid": tid} for e in line.events)
    return {"traceEvents": ev, "displayTimeUnit": "ms"}


def merge_chrome_traces(parts, extra=None, extra_label: str = "fleet",
                        extra_pid: int = 99,
                        path: Optional[str] = None) -> Dict[str, Any]:
    """Merge Chrome traces on the profiler's clock (replica tracers,
    :func:`profile_trace`) into one multi-process trace.

    ``parts`` is a list of ``(label, pid, trace)`` tuples: every event in
    ``trace`` is rewritten onto process ``pid`` (named ``label``); its
    ``ts`` stays as it is, since every part shares the clock. ``extra``
    is an optional list of ready-made events for an orchestration lane
    on ``extra_pid`` (health transitions, failovers, hedges). Rejoined
    replicas carry a fresh tracer; the merge simply reflects whatever
    each current tracer recorded."""
    ev: List[Dict[str, Any]] = []
    for label, pid, trace in parts:
        ev.append({"name": "process_name", "ph": "M", "ts": 0,
                   "pid": pid, "tid": 0, "args": {"name": label}})
        for e in trace.get("traceEvents", ()):
            if e.get("ph") == "M" and e.get("name") == "process_name":
                continue
            ev.append(dict(e, pid=pid))
    if extra:
        ev.append({"name": "process_name", "ph": "M", "ts": 0,
                   "pid": extra_pid, "tid": 0,
                   "args": {"name": extra_label}})
        ev.extend(extra)
    merged = {"traceEvents": ev, "displayTimeUnit": "ms"}
    if path:
        with open(path, "w") as f:
            json.dump(merged, f)
    return merged


# --------------------------------------------------------------------- #
# validation (tests + CI)
# --------------------------------------------------------------------- #
_PHASES = {"X", "i", "C", "M"}


def validate_chrome_trace(trace: Any) -> List[str]:
    """Structural validation of a Chrome trace-event object (or a path
    to one): returns a list of problems, empty when the trace is
    loadable by Perfetto / chrome://tracing. Used by
    ``tests/test_telemetry.py`` and the CI telemetry check."""
    if isinstance(trace, str):
        with open(trace) as f:
            trace = json.load(f)
    errs: List[str] = []
    if not isinstance(trace, dict) or "traceEvents" not in trace:
        return ["not a dict with a 'traceEvents' key"]
    events = trace["traceEvents"]
    if not isinstance(events, list) or not events:
        return ["'traceEvents' is not a non-empty list"]
    try:
        json.dumps(trace)
    except (TypeError, ValueError) as e:
        errs.append(f"not JSON-serializable: {e}")
    for i, e in enumerate(events):
        where = f"event {i}"
        if not isinstance(e, dict):
            errs.append(f"{where}: not an object")
            continue
        if not isinstance(e.get("name"), str) or not e["name"]:
            errs.append(f"{where}: missing/empty 'name'")
        ph = e.get("ph")
        if ph not in _PHASES:
            errs.append(f"{where}: bad phase {ph!r}")
        if not isinstance(e.get("ts"), (int, float)):
            errs.append(f"{where}: missing numeric 'ts'")
        if not isinstance(e.get("pid"), int):
            errs.append(f"{where}: missing integer 'pid'")
        if ph == "X":
            d = e.get("dur")
            if not isinstance(d, (int, float)) or d < 0:
                errs.append(f"{where}: 'X' event needs dur >= 0")
        if ph == "C" and not isinstance(e.get("args"), dict):
            errs.append(f"{where}: counter event needs numeric args")
    return errs


def complete_spans(trace: Dict[str, Any], prefix: str = "req "
                   ) -> Dict[str, Dict[str, Any]]:
    """Complete ('X') events whose name starts with ``prefix``, keyed by
    name — the per-request span lookup tests assert on."""
    return {e["name"]: e for e in trace.get("traceEvents", ())
            if e.get("ph") == "X" and str(e.get("name", "")
                                          ).startswith(prefix)}
