"""The plain float32 references against the program, on the CPU at a
small width: the same weights (made by the benchmark from the seed), the
same tokens, logits from the program's cached admission and decode paths
and from its serving engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import cells, check, drive, harness
from bench.traffic import common

FAMILIES = [bench_tiny.SSM, bench_tiny.MOE]
# float32 on both sides; what is left is summation order
LOGIT_RTOL = 1e-4


def _setup(config, seed=3):
    from repro.models.model import build
    ref = cells.reference(config)
    model = build(harness.program_config(config))
    params = harness.make_weights(ref, config, seed)
    return ref, model, params


@pytest.mark.parametrize("config", FAMILIES, ids=lambda c: c["family"])
def test_weights_have_the_program_layout(config):
    ref, model, params = _setup(config)
    harness._check_layout(model, params)


@pytest.mark.parametrize("config", FAMILIES, ids=lambda c: c["family"])
def test_reference_matches_cached_admission_and_decode(config):
    ref, model, params = _setup(config)
    rng = common.rng_for(11)
    T = 20
    toks = common.prompt_tokens(rng, T + 1, config["vocab"])[None]
    cache = model.make_cache(1, 32)
    # the program: chunked admission (two masked extends), then one decode
    lg1, cache = model.extend_into_cache(params, jnp.asarray(toks[:, :12]),
                                         cache, jnp.array([12]))
    lg2, cache = model.extend_into_cache(params, jnp.asarray(toks[:, 12:T]),
                                         cache, jnp.array([T - 12]))
    lg3, _ = model.decode_step(params, jnp.asarray(toks[:, T:T + 1]), cache)
    prog = np.concatenate([np.asarray(lg1), np.asarray(lg2),
                           np.asarray(lg3)], axis=1)[0]
    want = np.asarray(ref.logits(params, config, toks,
                                 np.arange(T + 1)[None]))[0]
    scale = np.abs(want).max()
    assert np.abs(prog - want).max() <= LOGIT_RTOL * scale
    assert np.array_equal(prog.argmax(-1), want.argmax(-1))


@pytest.mark.parametrize("config", FAMILIES, ids=lambda c: c["family"])
def test_engine_serves_the_reference_greedy_tokens(config):
    from repro.serving.engine import Engine
    from repro.serving.request import Request
    ref, model, params = _setup(config)
    eng = Engine(model, params, max_batch=2, cache_len=64, prefill_chunk=16,
                 sync_every=4, faults=False)
    rng = common.rng_for(12)
    recs = []
    for uid, (plen, n) in enumerate([(9, 6), (30, 5), (21, 7)]):
        p = common.prompt_tokens(rng, plen, config["vocab"])
        eng.submit(Request(uid=uid, prompt=p, max_new_tokens=n))
        recs.append(drive.Record(uid=uid, phase="window", due=0.0,
                                 prompt_len=plen, max_new=n, prompt=p))
    eng.run()
    for r in recs:
        r.tokens = np.asarray(eng.responses[r.uid].tokens, np.int32)
        r.n = len(r.tokens)
        assert r.n == r.max_new
    gaps = check.gaps(ref, params, config, recs, 64)["served"]
    assert gaps.size == 18 and gaps.max() <= 1e-4


def test_sample_takes_the_longest_and_repeats():
    recs = [drive.Record(uid=i, phase="window", due=0.0, prompt_len=10 + i,
                         max_new=4, n=4) for i in range(20)]
    a = check.sample(recs, 4, 2 ** 40 + 3)
    b = check.sample(list(reversed(recs)), 4, 2 ** 40 + 3)
    assert [r.uid for r in a] == [r.uid for r in b]
    assert a[0].uid == 19 and len({r.uid for r in a}) == 4
    assert check.sample([], 4, 1) == []
