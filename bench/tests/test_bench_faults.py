"""A whole run of the harness (no chip: the look for one is skipped) with
the timed path broken underneath: the check must read ``correct`` false
for each fault a serving cell can have, and true for the sound path."""
import jax
import numpy as np
import pytest

import bench_tiny
from bench import harness

CELLS = [(bench_tiny.SSM, bench_tiny.CHAT, "mamba2-780m.chat"),
         (bench_tiny.MOE, bench_tiny.DOCS, "granite-moe-3b-a800m.docs")]


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Keep the CPU test runs out of the checkout's compilation cache."""
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "")
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


def _run(cfg, traffic, name, seed=5):
    import time
    return harness.run(bench_tiny.cell(cfg, traffic, name), seed, 1.0,
                       False, t_process=time.perf_counter(),
                       require_tpu=False)


def _second_best(monkeypatch):
    """A token altered where it is produced: the sampler returns the
    runner-up instead of the greedy choice."""
    import repro.serving.engine as E
    orig = E._guarded_sample

    def wrong(sampler, key, logits):
        top = jax.numpy.argmax(logits, axis=-1)
        masked = logits.at[jax.numpy.arange(logits.shape[0]), top].set(
            -jax.numpy.inf)
        return orig(sampler, key, masked)
    monkeypatch.setattr(E, "_guarded_sample", wrong)


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged: admission and decode give
    logits but leave the cache as it was."""
    import repro.models.transformer as T
    ext, dec = T.extend_step, T.decode_step

    def extend(params, cfg, tokens, cache, **kw):
        return ext(params, cfg, tokens, cache, **kw)[0], cache

    def decode(params, cfg, token, cache):
        return dec(params, cfg, token, cache)[0], cache
    monkeypatch.setattr(T, "extend_step", extend)
    monkeypatch.setattr(T, "decode_step", decode)


def _half_left_out(monkeypatch):
    """Half of the batch left out: each multi-token extend advances its
    rows by half of their tokens."""
    import repro.models.transformer as T
    ext = T.extend_step

    def extend(params, cfg, tokens, cache, lengths=None, **kw):
        if lengths is not None and tokens.shape[1] > 1:
            lengths = (lengths + 1) // 2
        return ext(params, cfg, tokens, cache, lengths=lengths, **kw)
    monkeypatch.setattr(T, "extend_step", extend)


@pytest.mark.parametrize("cfg,traffic,name", CELLS,
                         ids=[c[2] for c in CELLS])
def test_sound_run_is_correct(cfg, traffic, name):
    res = _run(cfg, traffic, name)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["checks"]["tokens_checked"]["value"] >= 10


@pytest.mark.parametrize("fault", [_second_best, _state_unchanged,
                                   _half_left_out],
                         ids=["token_altered", "state_unchanged",
                              "half_left_out"])
@pytest.mark.parametrize("cfg,traffic,name", CELLS,
                         ids=[c[2] for c in CELLS])
def test_fault_reads_incorrect(cfg, traffic, name, fault, monkeypatch):
    fault(monkeypatch)
    res = _run(cfg, traffic, name)
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]
