"""Reduce a ``jax.profiler`` trace of the window to device busy time,
per-operation time and idle gaps named by what the host was doing.

``load`` turns the ``.xplane.pb`` file into plain data:

    {"device": {plane: [[name, start_ns, dur_ns], ...]},   # op events
     "host": [[name, start_ns, dur_ns], ...]}               # bench:* spans

taking, on each TPU plane, the events of its ``XLA Ops`` line, and on the
host the benchmark's own ``bench:tick`` / ``bench:submit`` /
``bench:wait`` annotations (``drive.py``). Both sides are on the
profiler's clock. ``reduce`` works on that data alone, so it is tested on
a small recorded trace (``tests/data``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PREFIX = "bench:"
TOP = 10


def load(trace_dir: str) -> Dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    device: Dict[str, List] = {}
    host: List = []
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend([e.name, e.start_ns, e.duration_ns]
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, e.start_ns, e.duration_ns])
    host.sort(key=lambda e: e[1])
    return {"device": device, "host": host}


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a: Sequence[List[float]], lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in a)


def _intersect(a: Sequence, b: Sequence) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class Reduced:
    """What one traced window shows. Times in seconds."""
    window_s: float
    busy_s: float                  # union of op intervals, mean over chips
    tick_s: float                  # host time inside ticks that ran steps
    busy_in_ticks_s: float
    ops: Dict[str, float]          # short op name -> device self seconds
    kernels: List[Tuple[str, float]]    # each Pallas call: full name, s
    gaps: List[Tuple[str, float]]  # longest idle gaps, by host span


_KIND = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
_SHAPE = re.compile(r"[a-z0-9]+\[[0-9,]*\]")
PALLAS = 'custom_call_target="tpu_custom_call"'


def short_name(name: str) -> str:
    """``%fusion.12 fusion bf16[8,1536]`` from an HLO instruction's text:
    the instruction, its opcode and its first result shape."""
    lhs, _, rhs = name.partition(" = ")
    kind = _KIND.search(" " + rhs)
    shape = _SHAPE.search(rhs)
    out = lhs
    if kind:
        out += " " + kind.group(1)
    if PALLAS in rhs:
        out += "(pallas)"
    if shape:
        out += " " + shape.group(0)
    return out


def _self_times(evs: Sequence, lo: float, hi: float) -> Dict[str, float]:
    """Each op's device time in the window minus that of the ops nested in
    it (a while loop's event spans its body's), summed by short name."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [end, name, self time]

    def close(item):
        n = short_name(item[1])
        out[n] = out.get(n, 0.0) + item[2] * 1e-9
    clipped = [(max(s, lo), min(s + d, hi), name) for name, s, d in evs]
    for a, b, name in sorted((c for c in clipped if c[1] > c[0]),
                             key=lambda c: (c[0], c[0] - c[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= b - a
        stack.append([b, name, b - a])
    while stack:
        close(stack.pop())
    return out


def reduce(data: Dict, tick_steps: Sequence[int]) -> Optional[Reduced]:
    """``tick_steps``: the steps each traced tick ran, in order. The
    profiler stops after the last traced tick, so the last
    ``len(tick_steps)`` ``bench:tick`` spans of the trace are those ticks;
    earlier spans belong to the warm-up the profiler started in. The
    window runs from the first traced tick's start to the last one's end.
    Returns None when the trace holds no tick or no device op."""
    ticks = [(s, s + d) for n, s, d in data["host"] if n == "bench:tick"]
    ticks = ticks[-len(tick_steps):] if tick_steps else []
    tick_steps = list(tick_steps)[len(tick_steps) - len(ticks):]
    if not ticks or not data["device"]:
        return None
    lo, hi = ticks[0][0], ticks[-1][1]
    busy_sets, ops, kernels = [], {}, []
    for plane, evs in sorted(data["device"].items()):
        iv = []
        for name, s, d in evs:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            iv.append((a, b))
            if PALLAS in name:
                kernels.append((name, (b - a) * 1e-9))
        busy_sets.append(_union(iv))
        for n, t in _self_times(evs, lo, hi).items():
            ops[n] = ops.get(n, 0.0) + t
    if not any(busy_sets):
        return None
    n_dev = len(busy_sets)
    busy = sum(_overlap(u, lo, hi) for u in busy_sets) / n_dev
    working = [t for t, n in zip(ticks, tick_steps) if n > 0]
    tick_ns = sum(e - s for s, e in working)
    in_ticks = sum(_intersect(u, working) for u in busy_sets) / n_dev
    u = busy_sets[0]
    edges = [lo] + [x for iv in u for x in iv] + [hi]
    holes = sorted(((e - s, s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s), reverse=True)[:TOP]
    starts = [s for _, s, _ in data["host"]]
    gaps = []
    for length, s, e in holes:
        mid = 0.5 * (s + e)
        i = bisect.bisect_right(starts, mid) - 1
        name = "other"
        if i >= 0:
            n, a, d = data["host"][i]
            if mid <= a + d:
                name = n
        gaps.append((name, length * 1e-9))
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
                   tick_s=tick_ns * 1e-9, busy_in_ticks_s=in_ticks * 1e-9,
                   ops=ops, kernels=kernels, gaps=gaps)


def breakdown(red: Reduced) -> Dict[str, List]:
    ops = sorted(red.ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in red.gaps]}
