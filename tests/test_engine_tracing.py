"""The engine in the profiler's own trace: ``engine:*`` host spans around
the phases of ``Engine.tick``, the clock the Tracer's export shares with
the profiler, the named scopes in the compiled programs, and the
``queue_wait_s`` histogram."""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.model import build
from repro.serving import tracing
from repro.serving.engine import Engine
from repro.serving.request import Request
from repro.serving.sampler import Sampler

_CFG = get_arch("llama3.2-1b", variant="reduced")
_MODEL = build(_CFG)
_PARAMS = _MODEL.init(jax.random.PRNGKey(0))
BLOCK_SCOPES = {"attention", "ssm", "mlp", "moe_ffn"}


def _engine(model=_MODEL, params=_PARAMS, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("sampler", Sampler())
    return Engine(model, params, **kw)


def _request(uid, n=6, max_new=12, **kw):
    rng = np.random.default_rng(uid)
    return Request(uid=uid, prompt=rng.integers(0, _CFG.vocab, n),
                   max_new_tokens=max_new, **kw)


def _host_spans(prof, prefix):
    """``[(name, start_ns, end_ns)]`` of the host events whose name
    starts with ``prefix``, in start order (offsets from the session's
    start, as the ``.xplane.pb`` holds them)."""
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for p in prof.planes if p.name.startswith("/host:")
           for line in p.lines for e in line.events
           if e.name.startswith(prefix)]
    return sorted(out, key=lambda s: s[1])


def _profile_start_ns(prof):
    return next(int(v) for p in prof.planes for k, v in p.stats
                if k == "profile_start_time")


def _traced_tick(eng, trace_dir):
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("caller:tick"):
            steps = eng.tick()
    finally:
        jax.profiler.stop_trace()
    return steps, tracing.newest_profile(str(trace_dir))


def test_tick_spans_in_order_inside_callers_span(tmp_path):
    eng = _engine()
    eng.submit(_request(0))
    eng.run()                                  # compile both programs
    eng.submit(_request(1))
    steps, prof = _traced_tick(eng, tmp_path)
    assert steps == eng.sync_every
    (caller,) = _host_spans(prof, "caller:")
    spans = _host_spans(prof, tracing.SPAN_PREFIX)
    names = [n for n, _, _ in spans]
    # one admission chunk (prompt <= prefill_chunk), then plain decode
    assert names == (["engine:admit", "engine:mixed"]
                     + ["engine:plain"] * (steps - 1)
                     + ["engine:sync", "engine:poll"])
    for _, s, e in spans:
        assert caller[1] <= s <= e <= caller[2]
    for (_, _, e0), (_, s1, _) in zip(spans, spans[1:]):
        assert e0 <= s1                        # siblings, not nested


def test_tracer_stamps_on_the_profilers_clock(tmp_path):
    """An event the Tracer records inside a span, from a
    ``perf_counter`` stamp as the engine hands it over, falls inside that
    span in its export as the ``.xplane.pb`` holds it: the profiler
    stamps with the system clock (``time.time_ns``), its times counted
    from ``profile_start_time``."""
    tracer = tracing.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with tracing.span(f"clock{i}"):
            time.sleep(0.002)
            tracer.on_fault(f"clock{i}", i, time.perf_counter())
            time.sleep(0.002)
    jax.profiler.stop_trace()
    prof = tracing.newest_profile(str(tmp_path))
    origin = _profile_start_ns(prof)
    spans = _host_spans(prof, "engine:clock")
    faults = [e for e in tracer.export_chrome_trace()["traceEvents"]
              if e["name"].startswith("fault clock")]
    assert len(spans) == len(faults) == 3
    for f, (_, s, e) in zip(faults, spans):
        ns = 1e3 * f["ts"] - origin
        assert s < ns < e
        # well inside: the two clocks agree to far better than the 2 ms
        assert min(ns - s, e - ns) > 1e6


def test_tracer_follows_a_step_of_the_system_clock(monkeypatch):
    """The Tracer converts each stamp as it arrives, so a step of the
    system clock (as NTP makes in a long-running server) moves the
    events recorded after it, as it moves the profiler's stamps, and not
    those recorded before it."""
    tracer = tracing.Tracer()
    tracer.on_fault("before", 0, time.perf_counter())
    system_ns = time.time_ns
    monkeypatch.setattr(tracing.time, "time_ns",
                        lambda: system_ns() + 10**9)
    tracer.on_fault("after", 1, time.perf_counter())
    ts = {e["name"]: e["ts"] for e in tracer.export_chrome_trace()[
        "traceEvents"] if e["name"].startswith("fault ")}
    assert 1e6 <= ts["fault after"] - ts["fault before"] < 1.1e6


def test_tracer_export_and_profile_line_up(tmp_path):
    """A request's queue span in the Tracer's export ends (its
    admission) inside the ``engine:admit`` span of the same tick in the
    profile, once both are merged on the one clock."""
    eng = _engine(recorder=True)
    eng.submit(_request(0))
    eng.run()
    eng.submit(_request(1))
    _traced_tick(eng, tmp_path)
    merged = tracing.merge_chrome_traces([
        ("engine", 1, eng.export_trace()),
        ("profile", 2, tracing.profile_trace(str(tmp_path)))])
    assert tracing.validate_chrome_trace(merged) == []
    ev = merged["traceEvents"]
    (queue,) = [e for e in ev if e["name"] == "queue u1"]
    admitted = queue["ts"] + queue["dur"]
    (admit,) = [e for e in ev
                if e["pid"] == 2 and e["name"] == "engine:admit"]
    assert admit["ts"] <= admitted <= admit["ts"] + admit["dur"]
    # the steps lane is gone: per-step spans live in the profile
    assert not any(e.get("args", {}).get("name") == "steps" for e in ev)


def _scopes(compiled_text):
    names = re.findall(r'op_name="([^"]*)"', compiled_text)
    return {c for n in names for c in n.split("/")}


@pytest.mark.parametrize("arch,blocks", [
    ("granite-moe-3b-a800m", {"attention", "moe_ffn"}),
    ("mamba2-780m", {"ssm"}),
], ids=["attn-moe", "ssm"])
def test_programs_carry_named_scopes(arch, blocks):
    """The compiled plain and mixed programs keep the engine's scopes
    (``decode``, ``prefill``, ``sample``) and the model's block scopes
    in their ops' metadata, where the profiler's ``tf_op`` reads them."""
    cfg = get_arch(arch, variant="reduced")
    model = build(cfg)
    eng = _engine(model, model.init(jax.random.PRNGKey(0)))
    state = (eng.params, eng.cache, eng.tokens, eng.remaining, eng.active,
             eng.eos, eng.key)
    plain = _scopes(eng._step_fn._jit.lower(*state, eng.poison)
                    .compile().as_text())
    assert {"decode", "sample"} | blocks <= plain
    assert "prefill" not in plain
    assert not (BLOCK_SCOPES - blocks) & plain
    chunk = (jnp.zeros((eng.prefill_chunk,), jnp.int32), jnp.int32(0),
             jnp.int32(3), jnp.asarray(True), jnp.int32(4), jnp.int32(-1))
    mixed = _scopes(eng._get_mixed()._jit.lower(*state, *chunk, eng.poison)
                    .compile().as_text())
    assert {"decode", "prefill", "sample"} | blocks <= mixed


def test_queue_wait_once_per_first_admission():
    """``queue_wait_s`` takes one sample when a request is first
    admitted and none when a preempted request is admitted again."""
    eng = _engine(max_batch=1)
    assert not any(k.startswith("queue_wait_ms")
                   for k in eng.latency_stats())
    eng.submit(_request(0, max_new=24, priority=0))
    for _ in range(2):
        eng.tick(2)                            # 0 is live mid-stream
    h = eng.metrics.histogram("queue_wait_s")
    assert h.count == 1
    eng.submit(_request(1, max_new=4, priority=5))
    t_submit = eng.requests[1].submitted_s
    resp = eng.run()
    assert resp[0].ok and resp[1].ok
    assert eng.requests[0].preemptions >= 1    # 0 was admitted again
    assert eng.latency_stats()["chunked_admissions"] >= 3
    assert h.count == 2
    assert h.values[1] == pytest.approx(
        eng.requests[1].started_s - t_submit)
    st = eng.latency_stats()
    assert {"queue_wait_ms_mean", "queue_wait_ms_p50", "queue_wait_ms_p95",
            "queue_wait_ms_p99"} <= set(st)
    eng.reset_stats()
    assert not any(k.startswith("queue_wait_ms")
                   for k in eng.latency_stats())
