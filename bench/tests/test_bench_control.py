"""The control of the correctness check, at a size a test run holds: the
program served in bfloat16 (its configured precision) passes, while the
reference computed in float8 and put in the program's place — the lower
precision a later change might be tempted by — fails the same limit. On
the chip the same comparison runs at the cells' own sizes
(``calibrate.py --control``)."""
import time

import jax
import numpy as np
import pytest

import bench_tiny
from bench import harness

BF16 = dict(bench_tiny.SSM, dtype="bfloat16", param_dtype="bfloat16",
            d_model=128, n_layers=4)
BF16_MOE = dict(bench_tiny.MOE, dtype="bfloat16", param_dtype="bfloat16",
                d_model=128, n_layers=4)
# longer answers and a larger sample, so that a run checks some hundreds
# of served tokens
LONGER = {"check_requests": 12,
          "output": {"median": 24, "sigma": 0.5, "lo": 12, "hi": 48}}
CHAT = dict(bench_tiny.CHAT, **LONGER,
            engine=dict(bench_tiny.CHAT["engine"], cache_len=112))
DOCS = dict(bench_tiny.DOCS, **LONGER,
            engine=dict(bench_tiny.DOCS["engine"], cache_len=128))
# Between the two readings at this size (CPU, seeds 1, 2**33+7, 3-6): the
# program's widest gap 0.0032 to 0.0079 (ssm) and 0.0015 to 0.0083 (moe)
# over 300-370 served tokens, the control's 0.060 to 0.104 (ssm) and
# 0.042 to 0.065 (moe).
LIMIT = 0.02


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "")
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


@pytest.mark.parametrize("cfg,traffic,name", [
    (BF16, CHAT, "mamba2-780m.chat"),
    (BF16_MOE, DOCS, "granite-moe-3b-a800m.docs")],
    ids=["ssm", "moe"])
@pytest.mark.parametrize("seed", [1, 2 ** 33 + 7, 3])
def test_control_fails_where_the_program_passes(cfg, traffic, name, seed):
    cell = bench_tiny.cell(cfg, traffic, name)
    cell.limits["max_logit_gap"]["limit"] = LIMIT
    extra = {"control": True}
    res = harness.run(cell, seed, 2.0, False, t_process=time.perf_counter(),
                      require_tpu=False, extra=extra)
    served, control = extra["served"], extra["control"]
    assert res["correct"], res["checks"]
    assert served.max() <= LIMIT < control.max()
    assert control.max() >= 3 * max(served.max(), 1e-6)
