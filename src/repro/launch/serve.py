"""End-to-end serving driver: batched requests through the engine.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --variant reduced --requests 16 --max-new 24
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build
from repro.serving.engine import Engine
from repro.serving.faults import Faults
from repro.serving.request import Request
from repro.serving.sampler import Sampler


def _engine_kwargs(args):
    """Engine knobs shared by the single-engine and fleet paths (the
    fleet owns ``recorder``/``faults``/``trace_dir`` itself)."""
    return dict(max_batch=args.max_batch, cache_len=args.cache_len,
                sampler=Sampler(temperature=args.temperature, top_k=32),
                seed=args.seed, sync_every=args.sync_every,
                kv_cache_dtype=args.kv_cache_dtype,
                prefill_chunk=None if args.prefill_chunk < 0
                else args.prefill_chunk,
                prefix_cache_tokens=None if args.prefix_cache_tokens < 0
                else args.prefix_cache_tokens,
                paged=args.paged, page_size=args.page_size,
                num_pages=args.num_pages or None,
                mesh=args.mesh or None)


def synthetic_prompts(cfg, n, prompt_len, seed):
    """The seeded request mix ``main`` serves: ``n`` prompts of
    ``prompt_len // 2 .. prompt_len`` random tokens, each with random
    frontend embeddings when the arch has a frontend. Returns a list of
    ``(prompt, embeddings or None)``."""
    rng = np.random.default_rng(seed)
    fe = cfg.frontend
    out = []
    for _ in range(n):
        L = int(rng.integers(max(2, prompt_len // 2), prompt_len + 1))
        emb = None
        if fe is not None:
            emb = rng.normal(0, 1, (fe.n_tokens, fe.d_embed)).astype(
                np.float32)
        out.append((rng.integers(0, cfg.vocab, L), emb))
    return out


def bytes_by_device(tree):
    """Bytes of a pytree's arrays held on each device, keyed by device
    id — how params or a KV cache are spread over a mesh."""
    out = {}
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            d = shard.device.id
            out[d] = out.get(d, 0) + shard.data.nbytes
    return dict(sorted(out.items()))


def _parse_drains(spec):
    """'rid@seconds[,rid@seconds...]' -> [(seconds, rid)] sorted."""
    plan = []
    for part in filter(None, (p.strip() for p in spec.split(","))):
        rid, sep, at = part.partition("@")
        try:
            if not sep:
                raise ValueError(part)
            plan.append((float(at), int(rid)))
        except ValueError:
            raise SystemExit(f"--drain: bad entry {part!r}, want "
                             f"'rid@seconds' (e.g. '0@2.5')")
    return sorted(plan)


def _serve_fleet(args, cfg, model, params):
    """--replicas > 1: serve through the fault-tolerant Fleet
    (docs/robustness.md). Mirrors the single-engine loop but adds the
    --drain rolling-restart schedule and fleet-level reporting."""
    from repro.serving.fleet import DRAINED, Fleet

    if cfg.frontend is not None:
        raise SystemExit("--replicas > 1 serves token-only prompts; "
                         "frontend-embedding archs need the "
                         "single-engine path")
    fl = Fleet(model, params, replicas=args.replicas,
               engine_kwargs=_engine_kwargs(args),
               hedge=args.hedge, trace=bool(args.trace_out),
               faults=(Faults.parse(args.faults, seed=args.faults_seed)
                       if args.faults else None))
    t0 = time.perf_counter()
    for uid, (prompt, _) in enumerate(synthetic_prompts(
            cfg, args.requests, args.prompt_len, args.seed)):
        fl.submit(Request(uid=uid, prompt=prompt,
                          max_new_tokens=args.max_new,
                          deadline_s=args.deadline or None))
    logger = None
    if args.metrics_jsonl:
        from repro.training.metrics import MetricsLogger
        logger = MetricsLogger(args.metrics_jsonl,
                               run_name=f"serve-fleet-{cfg.name}")
    drains = _parse_drains(args.drain)
    draining = set()
    next_log = t0 + (args.log_every or 1.0)
    while fl.has_work:
        fl.tick(args.sync_every)
        elapsed = time.perf_counter() - t0
        while drains and elapsed >= drains[0][0]:
            _, rid = drains.pop(0)
            try:
                fl.drain(rid)
                draining.add(rid)
            except ValueError as err:   # already dead/drained: skip
                print(f"--drain: {err}")
        for rid in sorted(draining):
            if fl.replicas[rid].state == DRAINED:
                fl.rejoin(rid)          # rolling restart: fresh engine
                draining.discard(rid)
        if (args.log_every or logger is not None) \
                and time.perf_counter() >= next_log:
            snap = fl.metrics.snapshot()
            c, gz = snap["counters"], snap["gauges"]
            fields = dict(inflight=gz.get("fleet_inflight", 0),
                          queued=gz.get("fleet_queue_depth", 0),
                          dispatches=c.get("dispatches", 0),
                          failovers=c.get("failovers", 0),
                          hedges=c.get("hedges_issued", 0))
            if logger is not None:
                logger.log("fleet", **fields)
            if args.log_every:
                states = "".join(r.state[0] for r in fl.replicas)
                print(f"[{elapsed:6.1f}s] replicas={states} " +
                      " ".join(f"{k}={v}" for k, v in fields.items()))
            next_log = time.perf_counter() + (args.log_every or 1.0)
    responses = fl.responses
    wall = time.perf_counter() - t0
    stats = fl.latency_stats()
    if logger is not None:
        logger.log("final", wall_s=wall, **{
            k: v for k, v in stats.items()
            if isinstance(v, (int, float))})
        logger.close()
    if args.trace_out:
        fl.export_trace(args.trace_out)
        print(f"merged chrome trace written to {args.trace_out} "
              f"(one lane per replica + a fleet lane; open in "
              f"https://ui.perfetto.dev)")
    tokens = sum(len(r.tokens) for r in responses.values())
    n_ok = sum(1 for r in responses.values() if r.ok)
    print(f"arch={cfg.name} requests={args.requests} "
          f"replicas={args.replicas} batch={args.max_batch}"
          + (" hedge" if args.hedge else ""))
    print(f"finished={stats['n_finished']} ok={n_ok} tokens={tokens} "
          f"wall={wall:.2f}s ({tokens / wall:,.1f} tok/s)")
    g = lambda k: stats.get(k, float("nan"))  # noqa: E731
    print(f"fleet ttft ms: p50={g('fleet_ttft_ms_p50'):.1f} "
          f"p95={g('fleet_ttft_ms_p95'):.1f} "
          f"p99={g('fleet_ttft_ms_p99'):.1f}")
    print(f"routing: dispatches={stats.get('dispatches', 0)} "
          f"affinity_hits={stats.get('affinity_hits', 0)} "
          f"breaker_opens={stats.get('breaker_opens', 0)}")
    print(f"resilience: deaths={stats.get('replica_deaths', 0)} "
          f"failovers={stats.get('failovers', 0)} "
          f"migrated={stats.get('requests_migrated', 0)} "
          f"router_drops={stats.get('router_drops', 0)} "
          f"hedges won/wasted={stats.get('hedges_won', 0)}"
          f"/{stats.get('hedges_wasted', 0)} "
          f"drains={stats.get('drains', 0)} "
          f"rejoins={stats.get('rejoins', 0)} "
          f"timeouts={stats.get('fleet_timeouts', 0)}")
    for r in fl.replicas:
        ewma = f"{r.ewma_s * 1e3:.1f}ms" if r.ewma_s else "-"
        print(f"  replica {r.rid}: {r.state} ticks={r.ticks} "
              f"step_ewma={ewma}"
              + (f" ({r.death_reason})" if r.death_reason else ""))
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump({"arch": cfg.name, "wall_s": wall,
                       **{k: v for k, v in stats.items()
                          if isinstance(v, (int, float, str))}},
                      f, indent=2)
    return responses, stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="llama3.2-1b")
    ap.add_argument("--variant", default="reduced")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sync-every", type=int, default=8,
                    help="decode steps between finished-flag polls")
    ap.add_argument("--quant", choices=["", "none", "int8", "int4"],
                    default="",
                    help="weight-only PTQ of the served params: int8/int4 "
                         "override the config's cfg.quant knob, 'none' "
                         "forces full precision even for quantized "
                         "variants (e.g. edge), '' keeps the config's "
                         "setting")
    ap.add_argument("--kv-cache-dtype", choices=["", "int8"], default="",
                    help="int8 = quantized KV cache (edge memory profile)")
    ap.add_argument("--draft", default="",
                    help="speculative-decoding draft spec "
                         "'<prec>[@<blocks>]' (fp|int8|int4, e.g. "
                         "'int8@1' = first block, int8-quantized "
                         "self-draft) or 'ngram' (draft-free "
                         "prompt-lookup — works on every family, incl. "
                         "SSM/encdec); 'none' disables a config-set "
                         "draft (e.g. the spec variant); '' keeps the "
                         "config's cfg.draft")
    ap.add_argument("--spec-gamma", type=int, default=0,
                    help="draft tokens proposed per speculative step "
                         "(0 keeps cfg.spec_gamma; needs --draft or a "
                         "spec-variant config)")
    ap.add_argument("--prefill-chunk", type=int, default=-1,
                    help="continuous batching: fuse at most this many "
                         "prompt tokens of one admitting request into "
                         "every decode step (0 = a single max-size "
                         "chunk per admission — the whole prompt in one "
                         "fused extend; -1 keeps cfg.prefill_chunk; see "
                         "the 'continuous' variant)")
    ap.add_argument("--prefix-cache-tokens", type=int, default=-1,
                    help="shared-prefix KV reuse budget in tokens (LRU; "
                         "0 = off, -1 keeps cfg.prefix_cache_tokens; "
                         "non-speculative, attention-only stacks)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: fixed page pool + per-slot "
                         "block tables with copy-on-write prefix "
                         "sharing — KV memory scales with live tokens "
                         "(attention-only stacks, token-only prompts)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page with --paged")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV pool size with --paged (0 = capacity "
                         "parity with the contiguous layout + headroom)")
    ap.add_argument("--mesh", default="",
                    help="tensor-parallel serving mesh: 'dp,mp' (e.g. "
                         "'2,4' = 2-way data x 4-way model), 'auto' = "
                         "all local devices on the model axis, 'none' "
                         "forces single-device even for a sharded "
                         "variant, '' keeps cfg.mesh (see the 'sharded' "
                         "variant)")
    ap.add_argument("--json", default="",
                    help="optional path to dump latency stats as JSON")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON of the run "
                         "(request-lifecycle spans; load in Perfetto — "
                         "see docs/observability.md)")
    ap.add_argument("--metrics-jsonl", default="",
                    help="append periodic registry snapshots as JSONL "
                         "(training/metrics.MetricsLogger format)")
    ap.add_argument("--trace-dir", default="",
                    help="capture a jax.profiler device trace of the "
                         "first decode steps into this directory")
    ap.add_argument("--log-every", type=float, default=0.0,
                    help="seconds between one-line progress summaries "
                         "while serving (0 = off)")
    ap.add_argument("--faults", default="",
                    help="deterministic fault schedule, e.g. "
                         "'nan_logits@12/1,page_alloc@30x2' (grammar: "
                         "site[@step][/slot][xN][+delay][%%prob]; see "
                         "repro/serving/faults.py). '' defers to the "
                         "REPRO_FAULTS env var")
    ap.add_argument("--faults-seed", type=int, default=0,
                    help="seed for the --faults schedule's dice")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request deadline in seconds (0 = none); "
                         "expired requests finish with reason 'timeout'")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a fault-tolerant Fleet of this "
                         "many engine replicas (health-checked routing, "
                         "failover by replay, drain/rejoin — see "
                         "docs/robustness.md); 1 = single engine. "
                         "--faults may then also name fleet sites "
                         "(replica_crash/replica_hang/router_drop)")
    ap.add_argument("--hedge", action="store_true",
                    help="with --replicas > 1: duplicate slow-starting "
                         "requests to a second replica after the fleet's "
                         "p99 TTFT; first token wins, loser is cancelled")
    ap.add_argument("--drain", default="",
                    help="with --replicas > 1: rolling-restart schedule "
                         "'rid@seconds[,rid@seconds...]' — drain each "
                         "replica at that wall time, rejoin it once "
                         "drained")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch, variant=args.variant)
    if args.quant:
        cfg = cfg.replace(quant="" if args.quant == "none" else args.quant)
    if args.draft == "none":
        cfg = cfg.replace(draft="", spec_gamma=0)  # speculation fully off
    elif args.draft:
        cfg = cfg.replace(draft=args.draft)
    if args.spec_gamma:
        cfg = cfg.replace(spec_gamma=args.spec_gamma)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if cfg.quant:
        from repro.quant import quantize_for_cfg
        params = quantize_for_cfg(params, cfg)
    if args.replicas > 1:
        return _serve_fleet(args, cfg, model, params)
    engine = Engine(model, params, **_engine_kwargs(args),
                    recorder=bool(args.trace_out),
                    trace_dir=args.trace_dir,
                    faults=(Faults.parse(args.faults, seed=args.faults_seed)
                            if args.faults else None))

    t0 = time.perf_counter()
    for uid, (prompt, emb) in enumerate(synthetic_prompts(
            cfg, args.requests, args.prompt_len, args.seed)):
        engine.submit(Request(uid=uid, prompt=prompt,
                              max_new_tokens=args.max_new,
                              embeddings=emb,
                              deadline_s=args.deadline or None))
    logger = None
    if args.metrics_jsonl:
        from repro.training.metrics import MetricsLogger
        logger = MetricsLogger(args.metrics_jsonl,
                               run_name=f"serve-{cfg.name}")

    def _progress():
        snap = engine.metrics.snapshot()
        c, gz = snap["counters"], snap["gauges"]
        fields = dict(steps=c.get("steps_total", 0),
                      tokens=c.get("tokens_emitted", 0),
                      active=gz.get("active_slots", 0),
                      queued=gz.get("queue_depth", 0),
                      compiles=c.get("compiles_total", 0))
        if logger is not None:
            logger.log("serve", **fields)
        if args.log_every:
            dt = time.perf_counter() - t0
            print(f"[{dt:6.1f}s] steps={fields['steps']} "
                  f"tokens={fields['tokens']} active={fields['active']} "
                  f"queued={fields['queued']} "
                  f"compiles={fields['compiles']}")

    if args.log_every or logger is not None:
        # hand-rolled drain loop so we can emit periodic summaries
        next_log = t0 + (args.log_every or 1.0)
        while engine.has_work:
            engine.tick(args.sync_every)
            if time.perf_counter() >= next_log:
                _progress()
                next_log = time.perf_counter() + (args.log_every or 1.0)
        _progress()
    responses = engine.run()          # finalize (stops device profiler)
    wall = time.perf_counter() - t0
    stats = engine.latency_stats()
    stats["param_bytes_by_device"] = bytes_by_device(engine.params)
    stats["cache_bytes_by_device"] = bytes_by_device(engine.cache)
    if logger is not None:
        logger.log("final", wall_s=wall, **{
            k: v for k, v in stats.items()
            if isinstance(v, (int, float))})
        logger.close()
    if args.trace_out:
        engine.export_trace(args.trace_out)
        print(f"chrome trace written to {args.trace_out} "
              f"(open in https://ui.perfetto.dev)")
    print(f"arch={cfg.name} requests={args.requests} "
          f"batch={args.max_batch}")
    if engine.mesh is not None:
        shape = dict(zip(engine.mesh.axis_names,
                         engine.mesh.devices.shape))
        print(f"mesh: data={shape.get('data', 1)} "
              f"model={shape.get('model', 1)} "
              f"({engine.mesh.devices.size} devices)")
    mib = lambda by_dev: {d: round(b / 2**20, 1)  # noqa: E731
                          for d, b in by_dev.items()}
    print(f"placement MiB by device: "
          f"params={mib(stats['param_bytes_by_device'])} "
          f"kv cache={mib(stats['cache_bytes_by_device'])}")
    print(f"finished={stats['n_finished']} "
          f"tokens={stats['tokens_generated']} wall={wall:.2f}s "
          f"({stats['tokens_generated']/wall:,.1f} tok/s)")
    # latency keys are absent when a stream produced no samples (e.g.
    # --max-new 1 never decodes): print NaN rather than fake zeros
    g = lambda k: stats.get(k, float("nan"))  # noqa: E731
    print(f"decode ms/step: mean={g('decode_ms_mean'):.2f} "
          f"p50={g('decode_ms_p50'):.2f} p99={g('decode_ms_p99'):.2f}")
    print(f"ttft ms: mean={g('ttft_ms_mean'):.1f} "
          f"p50={g('ttft_ms_p50'):.1f} p95={g('ttft_ms_p95'):.1f} "
          f"p99={g('ttft_ms_p99'):.1f}")
    print(f"itl ms: mean={g('itl_ms_mean'):.2f} "
          f"p50={g('itl_ms_p50'):.2f} p95={g('itl_ms_p95'):.2f} "
          f"p99={g('itl_ms_p99'):.2f}")
    n_ok = sum(1 for r in responses.values() if r.ok)
    if n_ok != len(responses) or stats.get("preemptions") \
            or stats.get("faults_injected"):
        print(f"resilience: ok={n_ok}/{len(responses)} "
              f"timeouts={stats.get('timeouts', 0)} "
              f"cancelled={stats.get('cancellations', 0)} "
              f"errors={stats.get('slot_errors', 0)} "
              f"preemptions={stats.get('preemptions', 0)} "
              f"faults_injected={stats.get('faults_injected', 0)}")
    line = (f"continuous batching: chunk={stats['prefill_chunk']} "
            f"chunked admissions={stats['chunked_admissions']} "
            f"fallback admissions={stats['fallback_admissions']}")
    if "prefix_hits" in stats:
        line += (f" prefix hits={stats['prefix_hits']} "
                 f"reused tokens={stats['prefix_hit_tokens']}")
    print(line)
    if engine.spec_gamma:
        print(f"speculative: gamma={stats['spec_gamma']} "
              f"accept={stats['spec_acceptance_rate']:.2f} "
              f"tokens/step={stats['spec_tokens_per_step']:.2f}")
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump({"arch": cfg.name, "wall_s": wall, **stats}, f,
                      indent=2)
    return responses, stats


if __name__ == "__main__":
    main()
