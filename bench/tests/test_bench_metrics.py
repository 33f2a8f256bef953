"""Per-layer readers on hand-built windows and traces: each reads what
its docstring says, and reads nothing (None) where there is nothing."""
import json
from pathlib import Path

import pytest

import test_bench_trace
from bench import cells, drive, harness, trace
from bench.flops import ssm

BENCH = Path(__file__).resolve().parents[1]
MAMBA = json.loads((BENCH / "configs" / "mamba2-780m.json").read_text())
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(ticks, records=(), red=None, peaks=PEAKS):
    w = harness.Window(records={r.uid: r for r in records}, ticks=ticks,
                       t_open=0.0, t_close=10.0, setup_s=1.0)
    return harness.Ctx(cell=None, config=MAMBA, traffic={}, peaks=peaks,
                       window=w, ticks=ticks, red=red,
                       flops=cells.flops(MAMBA))


def _rec(uid, due, submit, plen=100):
    return drive.Record(uid=uid, phase="window", due=due, prompt_len=plen,
                        max_new=8, submit=submit)


def test_step_ms_is_tick_wall_over_steps():
    ticks = [drive.Tick(0.0, 0.4, 8, {}, 0), drive.Tick(0.5, 0.7, 2, {}, 0)]
    assert cells.reader("step_ms.chat").read(_ctx(ticks)) == \
        pytest.approx(1e3 * 0.6 / 10)
    assert cells.reader("step_ms.docs").read(_ctx([])) is None


def test_gen_lag_takes_requests_due_in_the_traced_ticks():
    ticks = [drive.Tick(1.0, 2.0, 8, {}, 0)]
    recs = [_rec(i, 1.0 + 0.01 * i, 1.0 + 0.01 * i + 0.002)
            for i in range(50)] + [_rec(99, 5.0, 9.0)]      # outside
    assert cells.reader("gen_lag_p99_ms.chat").read(_ctx(ticks, recs)) == \
        pytest.approx(2.0)


def test_mfu_counts_prompts_at_first_token_and_each_decoded_token():
    t = drive.Tick(0.0, 1.0, 8, {}, 0, emits=[(0, 0, 3), (1, 5, 7)])
    recs = [_rec(0, 0, 0, plen=200), _rec(1, 0, 0, plen=50)]
    want = (ssm.prompt_flops(MAMBA, 200) + 4 * ssm.decode_flops(MAMBA, 1))
    got = cells.reader("mfu.chat").read(_ctx([t], recs))
    assert got == pytest.approx(100 * want / 197e12)
    assert cells.reader("mfu.chat").read(_ctx([t], recs, peaks={})) is None


def test_idle_and_roofline_read_the_trace():
    red = trace.reduce(test_bench_trace._hand_trace(), [8, 0])
    ctx = _ctx([drive.Tick(0.0, 0.007, 8, {}, 0)], red=red)
    assert cells.reader("idle_frac.chat").read(ctx) == \
        pytest.approx(100 * (1 - 6 / 7))
    # one call (b, T, h, p, g, n) = (2, 8, 4, 16, 1, 32) in 2 ms
    from bench.kernels import ssd_extend as k
    least = max(k.flops(2, 8, 4, 16, 1, 32) / 197e12,
                k.bytes_moved(2, 8, 4, 16, 1, 32) / 819e9)
    assert cells.reader("ssd_extend_roofline.chat").read(ctx) == \
        pytest.approx(100 * least / 2e-3)
    assert cells.reader("idle_frac.docs").read(_ctx([])) is None
    assert cells.reader("ssd_extend_roofline.chat").read(_ctx([])) is None
