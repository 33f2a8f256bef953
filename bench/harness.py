"""One run of one cell: set-up, the measured window, the metrics, and the
check that decides ``correct``.

Set-up (counted in ``setup_s``, from process start to the window's start):
weights from the seed in one jitted call on the device, the engine, one
request through admission and decode so that every program the window
runs is compiled (or loaded from the persistent cache), then
``warmup_s`` of the cell's own traffic so that occupancy is steady. Then
``Engine.reset_stats()`` and ``seconds`` of measurement, in which any
compile is an error. With ``trace`` the profiler starts ``TRACE_LEAD_S``
before the window (its start-up then falls in the warm-up), covers the
first ``TRACE_S`` seconds of the window, and the run reports per-layer
metrics.
After the window: peak device memory, the engine is freed, and the
served tokens are compared with the reference (``check.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from bench import cells, check, device, drive, trace
from bench.traffic import common

TRACE_S = 6.0
TRACE_LEAD_S = 3.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileInWindow(RuntimeError):
    """A program compiled (or was loaded) inside the measured window."""


class _CompileCounter:
    """Counts JAX backend compiles (persistent-cache loads included)."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == COMPILE_EVENT:
            self.count += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def _free(eng) -> None:
    """Release the device buffers the engine holds (cache, weights, decode
    state, token trace) before the reference runs beside them."""
    import jax
    for a in jax.tree.leaves(list(vars(eng).values())):
        if isinstance(a, jax.Array) and not a.is_deleted():
            a.delete()


def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` from a configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in config.items() if k in names}
    if kw.get("moe"):
        kw["moe"] = MoEConfig(**kw["moe"])
    if kw.get("ssm"):
        kw["ssm"] = SSMConfig(**kw["ssm"])
    return ModelConfig(**kw)


def make_weights(ref, config, seed: int):
    import jax
    from bench.reference import common as rc
    return jax.block_until_ready(
        jax.jit(lambda k: ref.init(config, k))(rc.seed_key(seed)))


def _check_layout(model, params) -> None:
    """The benchmark's weights have exactly the tree, shapes and types of
    the program's own initialisation."""
    import jax
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError("weights tree differs from the program's")
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        g = got
        for k in path:
            g = g[k.key]
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"weight {jax.tree_util.keystr(path)}: program "
                             f"wants {w.shape} {w.dtype}, got {g.shape} "
                             f"{g.dtype}")


@dataclass
class Window:
    """What the client saw in the window, on the host clock."""
    records: Dict[int, drive.Record]
    ticks: List[drive.Tick]
    t_open: float               # first window tick starts
    t_close: float              # last window tick ended
    setup_s: float


@dataclass
class Ctx:
    """What a per-layer metric reader may read."""
    cell: cells.Cell
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    peaks: Dict[str, float]
    window: Window
    ticks: List[drive.Tick]          # the traced ticks
    red: Optional[trace.Reduced]
    flops: Any = None


def _pct(vals, q) -> Optional[float]:
    return float(np.percentile(np.asarray(vals, np.float64), q)) \
        if len(vals) else None


def end_to_end(name: str, w: Window) -> Optional[float]:
    """The end-to-end metrics, by name. Latencies count from when a
    request was due; a request of the window still without a first token
    at the close counts with its wait so far."""
    recs = [r for r in w.records.values() if r.phase == "window"]
    if name == "setup_s":
        return w.setup_s
    if name == "ttft_p95_ms":
        vals = [((r.first if r.first is not None else w.t_close) - r.due)
                for r in recs]
        v = _pct(vals, 95)
        return None if v is None else 1e3 * v
    if name == "tpot_p95_ms":
        vals = [(r.last - r.first) / (r.n - 1)
                for r in w.records.values()
                if r.finished is not None and r.finished > w.t_open
                and r.n > 1]
        v = _pct(vals, 95)
        return None if v is None else 1e3 * v
    if name == "output_tok_s":
        if not w.ticks:
            return None
        span = w.ticks[-1].end - w.ticks[0].start
        return sum(t.tokens for t in w.ticks) / span
    raise KeyError(f"no end-to-end metric {name!r}")


def _compile_request(traffic, vocab: int, seed: int):
    """One request that goes through a chunked admission and then plain
    decode, so the mixed and plain programs both compile."""
    from repro.serving.request import Request
    e = traffic["engine"]
    rng = common.rng_for(seed, 5)
    return Request(uid=-1, prompt=common.prompt_tokens(
        rng, e["prefill_chunk"] + 1, vocab),
        max_new_tokens=2 * e.get("sync_every", 8) + 1)


def run(cell: cells.Cell, seed: int, seconds: float, trace_on: bool, *,
        t_process: float, require_tpu: bool = True,
        extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Run one cell once and return the result object. ``extra``
    (``calibrate.py``) receives the window and the raw readings, and with
    ``extra["control"]`` the float8 control's readings too."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import build
    from repro.serving.engine import Engine
    from repro.serving.sampler import Sampler

    dev = device.describe(cell.chips, require_tpu)
    peaks = device.peaks(dev["kind"]) if require_tpu else {}
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = _CompileCounter()

    config, traffic = cell.config, cell.traffic
    ref = cells.reference(config)
    model = build(program_config(config))
    params = make_weights(ref, config, seed)
    _check_layout(model, params)
    e = traffic["engine"]
    eng = Engine(model, params, max_batch=e["max_batch"],
                 cache_len=e["cache_len"], prefill_chunk=e["prefill_chunk"],
                 sync_every=e.get("sync_every", 8), sampler=Sampler(),
                 faults=False)
    warmup_s = float(traffic["warmup_s"])
    source = cells.generator(traffic).make(
        traffic, config["vocab"], seed, warmup_s, seconds)
    drv = drive.Driver(eng, source, annotate=jax.profiler.TraceAnnotation)
    log(f"weights and engine ready at {time.perf_counter() - t_process:.1f}s")

    eng.submit(_compile_request(traffic, config["vocab"], seed))
    drv.drain()
    log(f"programs compiled at {time.perf_counter() - t_process:.1f}s")
    t_traffic = time.perf_counter()
    drv.start(t_traffic)
    t_window = t_traffic + warmup_s
    tdir = tempfile.TemporaryDirectory(prefix="bench-trace-") \
        if trace_on else None
    profiling = False
    traced: List[drive.Tick] = []
    if tdir is not None:
        drv.run_until(t_window - TRACE_LEAD_S)
        t = time.perf_counter()
        jax.profiler.start_trace(tdir.name)
        profiling = True
        log(f"profiler started in {time.perf_counter() - t:.2f}s")
    drv.run_until(t_window)
    eng.reset_stats()
    n_warm = len(drv.ticks)

    def after_tick(tick):
        nonlocal profiling
        if profiling:
            traced.append(tick)
            if tick.end >= t_window + TRACE_S:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                profiling = False
                log(f"profiler stopped after {len(traced)} ticks, in "
                    f"{time.perf_counter() - t:.2f}s")

    compiles.armed = True
    drv.run_until(t_window + seconds, after_tick)
    compiles.close()
    if profiling:
        jax.profiler.stop_trace()
    steady = eng.metrics.counter("steady_compiles").value
    if compiles.count or steady:
        raise CompileInWindow(f"{compiles.count} compile(s) in the window "
                              f"(engine watchdog: {steady})")
    ticks = drv.ticks[n_warm:]
    t_close = ticks[-1].end if ticks else time.perf_counter()
    drv.close_window(t_close)
    w = Window(records=drv.records, ticks=ticks,
               t_open=ticks[0].start if ticks else t_window,
               t_close=t_close, setup_s=t_window - t_process)
    dev["memory_peak_bytes"] = device.memory_peak_bytes()
    log(f"window closed: {len(ticks)} ticks, "
        f"{sum(t.steps for t in ticks)} steps")

    result: Dict[str, Any] = {"correct": False}
    window_recs = [r for r in w.records.values() if r.phase == "window"]
    result["attempted"] = len(window_recs)
    result["failed"] = sum(1 for r in window_recs
                           if r.finished is not None and not r.ok)
    metrics: Dict[str, Any] = {}
    if extra is not None:
        extra["window"] = w
    if trace_on:
        data = trace.load(tdir.name)
        tdir.cleanup()
        red = trace.reduce(data, [t.steps for t in traced])
        ctx = Ctx(cell=cell, config=config, traffic=traffic, peaks=peaks,
                  window=w, ticks=traced, red=red,
                  flops=cells.flops(config))
        for m in cell.per_layer:
            v = cells.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            dev["busy_s"] = red.busy_s
            dev["window_s"] = red.window_s
            result["breakdown"] = trace.breakdown(red)
    else:
        for m in cell.end_to_end:
            v = end_to_end(m["name"], w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = dev

    # the check: the program's state goes first, then the reference
    finished = [r for r in w.records.values()
                if r.ok and r.finished > w.t_open]
    recs = check.sample(finished, int(traffic["check_requests"]), seed)
    _free(eng)
    drv.eng = eng = params = None
    gc.collect()
    t_ref = time.perf_counter()
    ref_params = make_weights(ref, config, seed)
    control = bool(extra and extra.get("control"))
    out = check.gaps(ref, ref_params, config, recs, e["cache_len"], control)
    g = out["served"]
    if extra is not None:
        extra.update(out)
        extra["sample"] = recs
    readings = {"max_logit_gap": float(g.max()) if g.size else float("inf"),
                "tokens_checked": int(g.size)}
    log(f"reference over {len(recs)} requests, {g.size} tokens, "
        f"{time.perf_counter() - t_ref:.1f}s")
    result["correct"], result["checks"] = check.verdict(readings, cell.limits)
    return result
