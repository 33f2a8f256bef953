"""The trace reducer, on a hand-built trace with known answers and on a
slice of a trace recorded on the chip (``data/``)."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace
from bench.kernels import ssd_extend

DATA = Path(__file__).resolve().parent / "data"
KERNEL = ('%closed_call.1 = (f32[2,4,8,16]{3,2,1,0}, f32[2,4,16,32]{3,2,1,0})'
          ' custom-call(f32[2,4,16,32]{3,2,1,0} %a, f32[2,4,8,16]{3,2,1,0} %b,'
          ' f32[2,4,8,128]{3,2,1,0} %c, f32[4]{0} %d, f32[2,1,8,32]{3,2,1,0}'
          ' %e, f32[2,1,8,32]{3,2,1,0} %f, f32[4]{0} %g),'
          ' custom_call_target="tpu_custom_call"')


def _hand_trace():
    ms = 1_000_000
    dev = [["%while.1 = (s32[]) while(s32[] %x)", 0 * ms, 6 * ms],
           ["%fusion.2 = bf16[8,16]{1,0} fusion(bf16[8,16] %y)", 1 * ms,
            2 * ms],
           [KERNEL, 3 * ms, 2 * ms],
           ["%copy.3 = f32[4]{0} copy(f32[4]{0} %z)", 8 * ms, 1 * ms]]
    host = [["bench:tick", 0, 7 * ms], ["bench:submit", 7 * ms, 2 * ms],
            ["bench:tick", 9 * ms, 3 * ms]]
    return {"device": {"/device:TPU:0": dev}, "host": host}


def test_reduce_hand_built_trace():
    red = trace.reduce(_hand_trace(), [8, 0])
    assert red.window_s == pytest.approx(12e-3)
    assert red.busy_s == pytest.approx(7e-3)          # 0-6 and 8-9 ms
    assert red.tick_s == pytest.approx(7e-3)          # the tick with steps
    assert red.busy_in_ticks_s == pytest.approx(6e-3)
    # the while loop's own time excludes the fusion and kernel inside it
    assert red.ops["%while.1 while s32[]"] == pytest.approx(2e-3)
    assert red.ops["%fusion.2 fusion bf16[8,16]"] == pytest.approx(2e-3)
    assert sum(red.ops.values()) == pytest.approx(7e-3)
    assert red.gaps[0] == ("bench:tick", pytest.approx(3e-3))   # 9-12 ms
    assert ("bench:submit", pytest.approx(2e-3)) in red.gaps    # 6-8 ms
    assert [s for _, s in red.kernels] == [pytest.approx(2e-3)]
    assert ssd_extend.shapes(red.kernels[0][0]) == (2, 8, 4, 16, 1, 32)
    bd = trace.breakdown(red)
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert len(bd["device_ops"]) <= trace.TOP


def test_reduce_reads_the_last_ticks_of_the_trace():
    # the profiler started a tick before the window: the window is the
    # trace's last tick
    t = _hand_trace()
    t["device"]["/device:TPU:0"].append(
        ["%copy.4 = f32[4]{0} copy(f32[4]{0} %z)", 10_000_000, 1_000_000])
    red = trace.reduce(t, [8])
    assert red.window_s == pytest.approx(3e-3)        # 9-12 ms
    assert red.busy_s == pytest.approx(1e-3)          # 10-11 ms only
    assert red.tick_s == pytest.approx(3e-3)
    assert red.kernels == []


def test_reduce_without_ticks_or_ops_reads_nothing():
    t = _hand_trace()
    assert trace.reduce({"device": t["device"], "host": []}, [1]) is None
    assert trace.reduce({"device": {}, "host": t["host"]}, [1, 1]) is None


def _brute_busy(evs, lo, hi):
    grid = np.zeros(int(hi - lo), bool)
    for _, s, d in evs:
        a, b = int(max(s, lo) - lo), int(min(s + d, hi) - lo)
        if b > a:
            grid[a:b] = True
    return grid.sum() * 1e-9


def test_reduce_recorded_chip_trace_slice():
    data = json.loads((DATA / "trace_mamba2_chat_slice.json").read_text())
    evs = data["device"]["/device:TPU:0"]
    _, lo, d = data["host"][0]
    red = trace.reduce(data, [8])
    assert red.window_s == pytest.approx(d * 1e-9)
    assert red.busy_s == pytest.approx(_brute_busy(evs, lo, lo + d),
                                       abs=2e-9)
    assert 0 < red.busy_s <= red.window_s
    assert sum(red.ops.values()) <= red.busy_s * (1 + 1e-9)
    found = [ssd_extend.shapes(n) for n, _ in red.kernels]
    # the decode rows of a fused mixed step: 64 slots, one token each
    assert found and set(found) == {(64, 1, 48, 64, 1, 128)}
    assert all(s is None for n, _, _ in evs
               if "tpu_custom_call" not in n
               for s in [ssd_extend.shapes(n)])
