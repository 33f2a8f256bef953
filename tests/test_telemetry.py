"""Serving telemetry contracts: registry primitives, percentile
helpers, Chrome trace schema + per-request spans, counter/engine
agreement across serving modes, no-op recorder invisibility, artifact
schema validation, and the recompile watchdog."""
import json
import warnings

import jax
import numpy as np
import pytest

from benchmarks import schema
from repro.configs import get_arch
from repro.models.model import build
from repro.serving import telemetry, tracing
from repro.serving.engine import Engine
from repro.serving.request import Request
from repro.serving.sampler import Sampler

_CFG = get_arch("llama3.2-1b", variant="reduced")
_MODEL = build(_CFG)
_PARAMS = _MODEL.init(jax.random.PRNGKey(0))


def _engine(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("sampler", Sampler())
    return Engine(_MODEL, _PARAMS, **kw)


def _stream(eng, n=4, max_new=5, seed=0):
    rng = np.random.default_rng(seed)
    for uid in range(n):
        L = int(rng.integers(3, 20))
        eng.submit(Request(uid=uid, prompt=rng.integers(0, _CFG.vocab, L),
                           max_new_tokens=max_new))
    return eng.run()


# ------------------------------------------------------------------ #
# registry primitives (no model)
# ------------------------------------------------------------------ #
def test_percentile_and_pct_stats_contract():
    xs = [0.001 * i for i in range(1, 101)]            # 1..100 ms
    assert telemetry.percentile(xs, 50) == pytest.approx(0.0505)
    st = {}
    telemetry.pct_stats(st, "lat_ms", xs, (50, 99))
    assert set(st) == {"lat_ms_mean", "lat_ms_p50", "lat_ms_p99"}
    assert st["lat_ms_p50"] == pytest.approx(50.5)     # seconds -> ms
    empty = {}
    telemetry.pct_stats(empty, "lat_ms", [], (50,))
    assert empty == {}                                  # no fake zeros
    with pytest.raises(Exception):
        telemetry.percentile([], 50)


def test_registry_reset_and_persist():
    reg = telemetry.MetricsRegistry()
    reg.counter("tokens").inc(5)
    reg.counter("compiles", persist=True).inc(2)
    reg.gauge("active").set(3)
    reg.histogram("ttft").observe(0.5)
    reg.get_series("wall").append(1.0)
    reg.reset()
    snap = reg.snapshot()
    assert snap["counters"]["tokens"] == 0
    assert snap["counters"]["compiles"] == 2            # persists
    assert snap["gauges"]["active"] == 0.0
    assert snap["histograms"]["ttft"]["count"] == 0
    json.dumps(snap)                                    # serializable


def test_histogram_reservoir_bounded_and_deterministic():
    h1 = telemetry.Histogram(cap=64)
    h2 = telemetry.Histogram(cap=64)
    for i in range(1000):
        h1.observe(float(i))
        h2.observe(float(i))
    assert h1.count == 1000 and len(h1.samples) == 64
    assert h1.samples == h2.samples                     # seeded
    assert "p50" in h1.summary((50,))


def test_validate_payload():
    pl = schema.payload("x", run={"smoke": True},
                        metrics=[schema.metric("a", "u", 1.0)],
                        data={}, telemetry={"counters": {}, "gauges": {},
                                            "histograms": {}})
    assert schema.validate_payload(pl) == []
    assert pl["schema_version"] == 2
    v1 = {"bench": "x", "schema_version": 1, "run": {}, "metrics": [],
          "data": {}}
    assert schema.validate_payload(v1) == []            # v1 still valid
    bad = dict(pl, telemetry={"counters": []})
    assert schema.validate_payload(bad)
    assert schema.validate_payload({"bench": ""})


def test_watchdog_arms_and_warns():
    reg = telemetry.MetricsRegistry()
    wd = telemetry.CompileWatchdog(reg, telemetry.Recorder())
    with warnings.catch_warnings():
        warnings.simplefilter("error")                  # warmup is silent
        wd.record("step", 0.1, step=0, ts=0.0)
    wd.arm()
    with pytest.warns(telemetry.RecompileWarning, match="mixed"):
        wd.record("mixed", 0.2, step=5, ts=1.0)
    snap = reg.snapshot()
    assert snap["counters"]["compiles_total"] == 2
    assert snap["counters"]["steady_compiles"] == 1
    logged = reg.get_series("compiles").values
    assert [e["steady"] for e in logged] == [False, True]


# ------------------------------------------------------------------ #
# engine integration
# ------------------------------------------------------------------ #
def test_trace_schema_and_request_spans(tmp_path):
    eng = _engine(recorder=True, prefill_chunk=4)
    resp = _stream(eng, n=4, max_new=5)
    path = str(tmp_path / "trace.json")
    eng.export_trace(path)
    assert tracing.validate_chrome_trace(path) == []
    with open(path) as f:
        trace = json.load(f)
    spans = tracing.complete_spans(trace)
    assert len(spans) == 4                      # one complete span/request
    for uid, r in resp.items():
        span = spans[f"req {uid}"]
        assert span["args"]["generated"] == len(r.tokens)
        assert span["args"]["finish"] == r.finish_reason
    lanes = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"queue", "slot 0", "compiles"} <= lanes
    assert any(e["ph"] == "C" and e["name"] == "active_slots"
               for e in trace["traceEvents"])


def test_export_trace_requires_recorder():
    eng = _engine()
    with pytest.raises(RuntimeError, match="recorder=True"):
        eng.export_trace()


@pytest.mark.parametrize("kw", [
    {},                                                   # plain
    {"prefill_chunk": 4},                                 # chunked
    {"prefill_chunk": 4, "prefix_cache_tokens": 256},     # prefix
    {"paged": True, "page_size": 8},                      # paged
    {"draft": "fp@1", "spec_gamma": 2},                   # speculative
], ids=["plain", "chunked", "prefix", "paged", "spec"])
def test_registry_counters_match_engine_outputs(kw):
    eng = _engine(max_batch=1 if "draft" in kw else 2, **kw)
    resp = _stream(eng, n=3, max_new=4)
    st = eng.latency_stats()
    c = eng.metrics.snapshot()["counters"]
    assert c["tokens_emitted"] == st["tokens_generated"] \
        == sum(len(r.tokens) for r in resp.values())
    assert c["steps_total"] == eng._steps == sum(
        v for k, v in c.items() if k.startswith("steps_")
        and k != "steps_total")
    if eng.prefill_chunk:
        assert c["chunked_admissions"] == st["chunked_admissions"] > 0
    if eng.spec_gamma:
        assert c["spec_tokens_emitted"] > 0
        assert st["spec_tokens_per_step"] == pytest.approx(
            c["spec_tokens_emitted"] / c["spec_active_steps"])
    collected = eng.metrics.snapshot()["collected"]
    if eng.paged:
        assert collected["kv_pages_live"] == 0           # all harvested
    if "prefix_cache_tokens" in kw:
        assert "prefix_hits" in collected


def test_noop_recorder_is_invisible():
    """Default (no-op) telemetry must not change greedy output or the
    set/size of compiled programs vs a tracing engine."""
    out, progs = [], []
    for rec in (None, True):
        eng = _engine(prefill_chunk=4, recorder=rec)
        resp = _stream(eng, n=3, max_new=4, seed=3)
        out.append({u: list(r.tokens) for u, r in resp.items()})
        progs.append(eng.program_cache_sizes())
    assert out[0] == out[1]
    assert progs[0] == progs[1]


def test_latency_stats_keys_preserved():
    eng = _engine(prefill_chunk=4)
    _stream(eng, n=3, max_new=4)
    st = eng.latency_stats()
    for k in ("n_finished", "tokens_generated", "decode_steps",
              "fallback_admissions", "chunked_admissions",
              "decode_ms_mean", "decode_ms_p50", "decode_ms_p99",
              "ttft_ms_mean", "ttft_ms_p50", "ttft_ms_p95", "ttft_ms_p99",
              "itl_ms_mean", "itl_ms_p50", "itl_ms_p95", "itl_ms_p99"):
        assert k in st, k
    # token-id requests never leave the fast path
    assert st["fallback_admissions"] == 0


def test_steady_state_recompile_warns():
    """After reset_stats() (the warmed-bench boundary) the first request
    to hit a still-cold program — here the ``materialize`` slot program
    a prefix-cache hit compiles on first use — must raise
    RecompileWarning and count as a steady compile."""
    eng = _engine(max_batch=1, prefix_cache_tokens=64, prefill_chunk=4)
    prompt = np.arange(12) % _CFG.vocab
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=3))
    eng.run()                  # warm the step/mixed/reset programs and
    eng.reset_stats()          # publish the prefix; arm the watchdog
    eng.submit(Request(uid=1, prompt=prompt, max_new_tokens=3))
    with pytest.warns(telemetry.RecompileWarning, match="materialize"):
        eng.run()              # prefix hit -> cold materialize program
    c = eng.metrics.snapshot()["counters"]
    assert c["steady_compiles"] >= 1
    assert c["compiles_total"] > c["steady_compiles"]   # warmup counted too


@pytest.mark.parametrize("site", ["start_trace", "stop_trace"])
def test_trace_dir_profiler_failure_fails_run(monkeypatch, tmp_path, site):
    """A device trace was asked for: a profiler that cannot start or stop
    fails the run instead of leaving it to exit cleanly with no trace."""
    def broken(*a, **k):
        raise RuntimeError(f"profiler {site} failed")
    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: started.append(d))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(jax.profiler, site, broken)
    eng = _engine(trace_dir=str(tmp_path), profile_steps=1)
    with pytest.raises(RuntimeError, match=f"profiler {site} failed"):
        _stream(eng)
