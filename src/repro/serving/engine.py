"""Batched serving engine v3: continuous batching — bucketed prefill,
fused on-device decode, chunked prefill fused into the decode step,
shared-prefix KV reuse, and optional speculative decoding.

A fixed number of batch *slots* share one batched KV/SSM cache; each slot
runs an independent sequence at its own per-row ``step`` offset. When a
sequence finishes, the next queued request is admitted into the free slot
and the decode batch never drains — the serving analogue the paper's
Fig. 3 measures (stable per-token latency under a stream of
differently-sized requests). See ``docs/serving.md`` for the lifecycle
diagram and invariants.

What v3 changes over v2 (PR 1/3):

* **Fused mixed step (Sarathi-style chunked prefill)** — with
  ``prefill_chunk > 0``, a long prompt no longer monopolises the engine:
  every step is a single jitted, cache-donating program that decodes all
  active slots AND advances at most ``prefill_chunk`` tokens of one
  admitting request, via ``Model.extend_into_cache`` (per-row lengths:
  decode rows advance by 1, the admitting row by the chunk, idle rows by
  0). Decode never stalls behind prefill, so tail inter-token latency
  stays flat when long prompts arrive — the knob trades first-token
  latency of the admitting request for ITL of everyone else.
* **Shared-prefix KV reuse** — ``prefix_cache_tokens > 0`` keeps a
  host-side trie of recently admitted prompt prefixes (chunk-aligned;
  LRU-evicted under a token budget) whose device KV slices are
  materialised into a fresh slot with one on-device
  ``dynamic_update_slice`` copy; chunked prefill resumes after the reused
  prefix. Shared system prompts and few-shot headers cost one HBM copy
  instead of recomputation (``serving/prefix_cache.py``).
* **Percentile latency stats** — ``latency_stats`` now reports
  p50/p95/p99 TTFT and inter-token latency over per-request samples, the
  tail metrics ``benchmarks/bench_load.py`` tracks under Poisson load.

With ``paged=True`` the per-slot contiguous KV rings are replaced by a
fixed pool of fixed-size pages with per-slot block tables
(``serving/paged_kv.py``): KV memory scales with live tokens instead of
``max_batch x cache_len``, prefix-cache hits alias pages (refcount bump,
zero KV copies — the materialize/extract programs never run) and
admission applies backpressure when the pool is short. The decode /
mixed / speculative step programs are unchanged in shape; they write
through the block table via the paged attention path in
``models/layers.py``.

Chunked admission is the ONLY admission path: every family — dense,
MoE (dense routing in cached modes), SSM (``ssd_extend`` from the
cached state, in the chunked dual form for whole-block chunks on the
chip), hybrid, VLM (the frontend prefix enters as one embedding
chunk) and encoder–decoder (cross-attention memory encoded once at
admission, decoder ring chunked like any other) — flows through
``Model.extend_into_cache``. The v2 monolithic slot-direct prefill is
gone; ``prefill_chunk=0`` now means a single max-size chunk (the whole
prompt in one fused extend), not a separate program. The
``fallback_admissions`` counter observes any admission that cannot take
the fused path — structurally zero for every supported family, and
asserted zero by ``benchmarks/check_families.py``.

Retained from v2 (see the sections below and docs/serving.md): the
fused donated decode step with zero steady-state host<->device traffic,
the bounded ``_poll``/``_harvest`` trace contract, and the fused
draft–verify speculative step (``draft=``/``spec_gamma=``; chunked
admission then runs as its own extend program right before the spec
step, advancing target and draft caches in lockstep with the draft one
position behind). ``draft="ngram"`` replaces the draft model with a
prompt-lookup drafter (``serving/ngram_draft.py``) that proposes from
the request's own token history — no draft cache, works for every
family, and recurrent targets commit speculation through the rollback-
and-replay flow (``Model.rollback_needs_replay``).

Telemetry (``docs/observability.md``): every host-side stat lives in
one ``serving/telemetry.MetricsRegistry`` (``Engine.metrics``) —
counters (tokens emitted, steps by kind, admissions, spec
accept/emit), gauges sampled at each poll (active slots, free pages,
KV bytes per live token), bounded-reservoir histograms (queue wait,
TTFT, ITL) and the per-step wall/kind series ``latency_stats()`` is
built on. Request lifecycles route through a ``Recorder`` (no-op by
default; pass ``recorder=True`` for a ``serving/tracing.Tracer`` and
export a Perfetto-loadable Chrome trace with
``Engine.export_trace(path)``).
Every jitted program is watched for XLA compiles: after
``reset_stats()``/``mark_steady()`` arms the watchdog, a steady-state
compile raises ``telemetry.RecompileWarning`` and increments the
``steady_compiles`` counter CI fails on. ``trace_dir=`` additionally
captures a ``jax.profiler`` device trace over a short step window, in
which ``tick`` marks its phases with ``engine:*`` host spans
(``tracing.span``) and the programs' ops carry named scopes
(``decode``, ``prefill``, ``sample``; the model's blocks add
``attention``, ``ssm``, ``mlp``, ``moe_ffn``).

Resilience (``docs/robustness.md``): requests carry ``deadline_s`` and
``priority``; ``Engine.cancel(uid)`` and per-poll deadline enforcement
finish streams with ``finish_reason`` "cancelled"/"timeout", releasing
their slot and pages immediately. Under slot or page pressure the
engine preempts a victim (lowest priority, then latest deadline) and
requeues it; on re-admission the generated prefix is replayed through
the chunked-extend path, so the resumed stream's output is identical
to an unpreempted run. An on-device NaN/inf guard at every sampler
boundary contains a poisoned slot to a ``finish_reason="error"``
finish while the rest of the fused batch continues. All of it is
exercised by the deterministic fault registry in ``serving/faults.py``
(``Engine(faults=...)`` / ``REPRO_FAULTS``), a zero-overhead no-op by
default.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from repro.models.model import Model
from repro.serving import faults as faults_mod
from repro.serving import paged_kv, telemetry
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import Request, Response
from repro.serving.sampler import Sampler
from repro.serving.tracing import span

#: Sentinel "token" the fused steps emit for a slot whose sampler logits
#: were not finite (NaN/inf): the on-device guard deactivates only that
#: row, and the host harvest turns the sentinel into finish_reason
#: "error" without appending it. Real token ids are >= 0 and the no-EOS
#: sentinel is -1, so -2 is unambiguous.
ERR_TOKEN = -2


def _guarded_sample(sampler, key, logits):
    """NaN/inf containment at the sampler boundary. Rows whose logits
    are not finite emit :data:`ERR_TOKEN` instead of sampling garbage
    (argmax/categorical over NaN is undefined) and the caller marks only
    those rows done — the rest of the fused batch is unaffected (samples
    are per-row functions of per-row logits). Finite rows are
    bit-identical to an unguarded call: the ``where`` masks select the
    original logits elementwise."""
    with jax.named_scope("sample"):
        bad = ~jnp.all(jnp.isfinite(logits), axis=-1)            # (B,)
        safe = jnp.where(bad[:, None], 0.0, logits)
        nxt = jnp.where(bad, jnp.int32(ERR_TOKEN), sampler(key, safe))
    return nxt, bad


def _finite_rows(logits):
    """Replace non-finite logit rows with zeros (draft-side guard: the
    proposals sampled from a poisoned draft row are garbage, but the
    target verify rejects them — zeroing just keeps the sampling and
    accept-ratio math well-defined)."""
    ok = jnp.all(jnp.isfinite(logits), axis=-1, keepdims=True)
    return jnp.where(ok, logits, 0.0)


@dataclasses.dataclass
class _Admission:
    """One in-flight chunked admission: the effective token stream
    enters the cache ``prefill_chunk`` tokens per fused step, starting
    at ``base`` (> 0 when a prefix-cache hit pre-populated the slot).
    ``tokens`` is the prompt plus — when resuming a preempted request —
    the ``n_done`` tokens it had already generated: replaying them
    through the same extend path makes the resumed stream token-
    identical to an unpreempted run (greedy)."""
    req: Request
    slot: int
    base: int
    length: int
    tokens: np.ndarray = None
    n_done: int = 0
    resumed: bool = False


class Engine:
    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 cache_len: int = 512, sampler: Optional[Sampler] = None,
                 seed: int = 0, sync_every: int = 8,
                 donate: Optional[bool] = None,
                 kv_cache_dtype: str = "",
                 draft: Any = None, spec_gamma: int = 0,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache_tokens: Optional[int] = None,
                 mesh: Any = None,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 faults: Any = None,
                 recorder: Any = None, trace_dir: str = "",
                 profile_steps: int = 8):
        """``params`` may be a quantized tree (``quant.quantize_params``):
        projections route through the fused dequantize-matmul inside the
        same jitted prefill/decode programs, nothing else changes.

        ``kv_cache_dtype="int8"`` stores K/V as int8 with per-(slot, head)
        scales — quantize-on-write in the cache update, dequantize-in-
        attention on read — halving KV bytes per decode step (the
        memory-roofline cost at long cache lengths). "" keeps the model's
        own setting (``cfg.kv_quant``).

        ``draft`` enables speculative decoding: a self-draft spec string
        (``"int8@1"`` — see ``quant.self_draft``), the string
        ``"ngram"`` for the family-agnostic prompt-lookup drafter
        (``serving/ngram_draft.py`` — no draft model or cache; proposals
        come from the request's own token history), an explicit
        ``(draft_model, draft_params)`` pair, or None to follow
        ``cfg.draft``. ``spec_gamma`` is the number of draft tokens
        proposed per step (0 follows ``cfg.spec_gamma``, defaulting to 4
        once a draft is configured). Model drafts require caches that
        rewind without replay on both sides (attention-backed); the
        n-gram drafter serves every family — recurrent targets commit
        accepted tokens through checkpoint-restore + replay
        (``Model.rollback_needs_replay``).

        ``prefill_chunk`` sizes the chunked admission path — the ONLY
        admission path: each engine step decodes every active slot and
        advances at most this many prompt tokens of one admitting
        request through the fused mixed step. None follows
        ``cfg.prefill_chunk``; 0 means a single max-size chunk (the
        whole prompt enters through one fused extend — there is no
        separate monolithic prefill program). Every family supports the
        extend path; requests carrying frontend embeddings admit their
        prefix through one embedding chunk (VLM) or a one-shot encode
        of the cross-attention memory (encdec) before the token chunks.

        ``prefix_cache_tokens`` (with chunked prefill, non-speculative)
        caps the shared-prefix KV reuse budget in tokens; None follows
        ``cfg.prefix_cache_tokens``, 0 disables.

        ``mesh`` enables tensor-parallel sharded serving: a
        ``jax.sharding.Mesh`` with ("data", "model") axes, a spec string
        ("auto" = all local devices on the model axis, "dp,mp" e.g.
        "2,4" — see ``launch.mesh.make_serving_mesh``), or None to
        follow ``cfg.mesh`` ("" / "none" disables). Params are placed by
        ``param_shardings`` (attention/MLP weights split over the model
        axis), the KV cache by ``cache_shardings`` (heads on model,
        slots on data), decode state by ``batch_shardings``; every
        jitted program is built with explicit in/out shardings so
        donation still updates the cache in place and no per-step
        re-layout occurs. Host-side state (queue, trie, sampler knobs)
        stays replicated/host-resident. Pallas kernel ops fall back to
        their jnp references under a model axis > 1
        (``kernels.dispatch``).

        ``paged=True`` replaces the per-slot contiguous KV rings with a
        fixed pool of ``num_pages`` pages of ``page_size`` tokens each
        (``serving/paged_kv.py``): HBM scales with live tokens, prefix
        hits become block-table aliases (zero KV copies) and admission
        applies backpressure instead of assuming worst-case capacity.
        Requires a paged cache layout (attention-only stacks — SSM
        recurrent state has no per-position storage to page) and
        token-only prompts that fit the KV ring — ``submit`` rejects
        anything else. ``num_pages=None`` sizes the pool for capacity
        parity with the contiguous layout plus provisioning headroom.
        Composes with int8 KV, speculative decoding (the draft cache
        stays contiguous), chunked admission and mesh sharding.

        ``faults`` enables deterministic fault injection
        (``serving/faults.py``): a ``Faults`` schedule, a spec string
        for ``Faults.parse`` (``"nan_logits@12/1,page_alloc@30"``), or
        None to follow the ``REPRO_FAULTS`` env var. The default is the
        zero-overhead ``NoFaults`` no-op: programs, outputs and
        ``program_cache_sizes()`` are bit-identical with it (the NaN
        site injects through the always-present ``poison`` input, never
        a recompiled program variant).

        ``recorder`` enables request-lifecycle tracing: ``True`` builds
        a ``serving/tracing.Tracer`` (export with
        ``Engine.export_trace(path)``), or pass any
        ``telemetry.Recorder`` instance. None/False keeps the no-op
        default — host bookkeeping only, zero per-step device work, and
        greedy outputs / compiled-program counts bit-identical either
        way (the metrics registry itself is always on; it is pure host
        state). ``trace_dir`` additionally captures a ``jax.profiler``
        device trace of ``profile_steps`` engine steps (the window
        starts at step 1, after the first compile).
        """
        if kv_cache_dtype not in ("", "int8"):
            raise ValueError(f"unsupported kv_cache_dtype "
                             f"{kv_cache_dtype!r} (use '' or 'int8')")
        if kv_cache_dtype == "int8" and not model.cfg.kv_quant:
            from repro.models.model import build
            model = build(model.cfg.replace(kv_quant=True))
        self.model = model
        self.params = params
        self.kv_cache_dtype = "int8" if model.cfg.kv_quant else \
            model.cfg.dtype
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.sampler = sampler or Sampler()
        self.sync_every = max(1, sync_every)
        cfg = model.cfg
        # actual KV ring length (make_cache caps at the sliding window)
        self.kv_len = min(cache_len, cfg.sliding_window) \
            if cfg.sliding_window else cache_len
        # vlm prompts carry a frontend prefix in the same cache rows
        self._prefix = cfg.frontend.n_tokens \
            if (cfg.frontend is not None and cfg.family == "vlm") else 0
        # XLA ignores donation on CPU (and warns); only donate elsewhere
        self._donate = (jax.default_backend() != "cpu") if donate is None \
            else donate

        # --- tensor-parallel serving mesh ------------------------------ #
        mesh_src = cfg.mesh if mesh is None else mesh
        if isinstance(mesh_src, str):
            if mesh_src in ("", "none", "off"):
                mesh_src = None
            else:
                from repro.launch.mesh import make_serving_mesh
                mesh_src = make_serving_mesh(mesh_src)
        self.mesh = mesh_src
        self._param_sh = self._cache_sh = self._draft_param_sh = None
        self._draft_cache_sh = self._tok_sh = self._vec_sh = None
        self._repl = None
        if self.mesh is not None:
            from repro.distribution import sharding as _SH
            from repro.launch.mesh import batch_axes
            self._SH = _SH
            self._b_axes = batch_axes(self.mesh) or ("data",)
            self._act_rules = _SH.default_activation_rules(
                batch_axes=self._b_axes)
            self._repl = NamedSharding(self.mesh, PartitionSpec())
            # params placed once, by path rules; programs then pin the
            # same shardings via in_shardings so no call ever re-lays
            # them out
            self._param_sh = _SH.param_shardings(self.params, self.mesh)
            self.params = jax.device_put(self.params, self._param_sh)

        # --- telemetry -------------------------------------------------- #
        # the registry is the single host-side stats store: counters,
        # gauges, histograms and the aligned per-step series that
        # latency_stats()/benchmarks read (step_times/step_kinds below
        # are live views into it). The recorder is the request-lifecycle
        # event sink: a no-op by default, a tracing.Tracer on request.
        self.metrics = telemetry.MetricsRegistry()
        if recorder is True:
            from repro.serving.tracing import Tracer
            recorder = Tracer()
        self.recorder: telemetry.Recorder = recorder or telemetry.Recorder()
        self._watchdog = telemetry.CompileWatchdog(self.metrics,
                                                   self.recorder)
        self._step_series = self.metrics.get_series("step_wall_s")
        self._kind_series = self.metrics.get_series("step_kind")
        self._kinds_base = 0           # global step of step_kinds[0]
        self._c_tokens = self.metrics.counter("tokens_emitted")
        self._c_steps = self.metrics.counter("steps_total", persist=True)
        self._c_admissions = self.metrics.counter("chunked_admissions")
        # admissions that could not take the fused chunked path. The
        # refactor that retired the monolithic prefill made this
        # structurally zero for every supported family — the counter
        # (and its trace instant) exists so any reintroduced bypass is
        # observable, and benchmarks/check_families.py gates on it.
        self._c_fallback = self.metrics.counter("fallback_admissions")
        self._c_spec_emitted = self.metrics.counter("spec_tokens_emitted")
        self._c_spec_steps = self.metrics.counter("spec_active_steps")
        self._h_queue = self.metrics.histogram("queue_wait_s")
        self._h_ttft = self.metrics.histogram("ttft_s")
        self._h_itl = self.metrics.histogram("itl_s")
        self._c_preempt = self.metrics.counter("preemptions")
        self._c_timeout = self.metrics.counter("timeouts")
        self._c_cancel = self.metrics.counter("cancellations")
        self._c_faults = self.metrics.counter("faults_injected")
        self._c_errors = self.metrics.counter("slot_errors")
        self._trace_dir = trace_dir
        self._profile_steps = max(1, int(profile_steps))
        self._prof_on = self._prof_done = False
        self._prof_base = 0
        self._kv_nbytes = None         # lazy: KV bytes of the cache tree

        # --- fault injection (docs/robustness.md) --------------------- #
        # deterministic seeded schedule; the default NoFaults is a
        # zero-overhead no-op (same contract as the Recorder)
        if faults is None:
            faults = faults_mod.from_env()
        elif isinstance(faults, str):
            faults = faults_mod.Faults.parse(faults)
        elif faults is False:
            faults = faults_mod.NoFaults()
        self.faults = faults
        if self.faults.enabled:
            self.metrics.add_collector(self.faults.stats)

        # host-side scheduling state
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.requests: Dict[int, Request] = {}
        self.responses: Dict[int, Response] = {}
        self._deadline_armed = False   # any live request has deadline_s

        # device-resident decode state (never read back in steady state)
        self.key = jax.random.PRNGKey(seed)
        self.tokens = jnp.zeros((max_batch, 1), jnp.int32)
        self.prev = jnp.zeros((max_batch, 1), jnp.int32)   # spec: token
        # preceding the pending one (the draft cache lags by one position)
        self.remaining = jnp.zeros((max_batch,), jnp.int32)
        self.active = jnp.zeros((max_batch,), bool)
        self.eos = jnp.full((max_batch,), -1, jnp.int32)
        # fault-poison lane: an always-present additive input to every
        # step program's sampler logits (0.0 = exact identity for finite
        # values). The nan_logits site sets one row to NaN for one step;
        # because it is a program *input*, injection never recompiles
        # and a fault-free engine's programs are bit-identical.
        self.poison = self._poison_zero = jnp.zeros((max_batch,),
                                                    jnp.float32)

        # --- paged KV cache ------------------------------------------- #
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self._paged: Optional[paged_kv.PagedKVState] = None
        self._depth_ub = [0] * max_batch   # per-slot provisioned depth:
        # an upper bound on the device's committed depth, advanced ahead
        # of each dispatched step and corrected at every poll
        if self.paged:
            if not model.supports_paged:
                raise ValueError(
                    "paged KV requires the extend path (attention-only "
                    f"stacks); family {cfg.family!r} has none")
            n_blk = paged_kv.num_blocks(self.kv_len, self.page_size)
            # default: capacity parity with the contiguous layout, plus
            # headroom for provisioning drift (depth upper bounds run
            # ahead of the harvested truth between polls)
            self.num_pages = int(num_pages) if num_pages \
                else max_batch * n_blk + 2 * max_batch
            if self.num_pages < n_blk:
                # one full-length stream must always fit once the pool
                # drains, else admission backpressure can never clear
                raise ValueError(
                    f"num_pages={self.num_pages} cannot hold one full "
                    f"stream ({n_blk} blocks of {self.page_size} tokens)")
            self._paged = paged_kv.PagedKVState(
                max_batch, self.kv_len, self.page_size, self.num_pages)
            self.cache = model.make_paged_cache(
                max_batch, cache_len, page_size=self.page_size,
                num_pages=self.num_pages)
        else:
            self.num_pages = 0
            self.cache = model.make_cache(max_batch, cache_len)
        if self.mesh is not None:
            # KV cache: heads on the model axis, slots (batch) on data;
            # decode state: leading batch dim on data; PRNG key replicated
            self._cache_sh = self._SH.cache_shardings(
                self.cache, self.mesh, self._b_axes)
            self.cache = jax.device_put(self.cache, self._cache_sh)
            self._tok_sh = self._SH.batch_shardings(self.tokens, self.mesh,
                                                    self._b_axes)
            self._vec_sh = self._SH.batch_shardings(self.remaining,
                                                    self.mesh, self._b_axes)
            self.tokens = jax.device_put(self.tokens, self._tok_sh)
            self.prev = jax.device_put(self.prev, self._tok_sh)
            self.remaining = jax.device_put(self.remaining, self._vec_sh)
            self.active = jax.device_put(self.active, self._vec_sh)
            self.eos = jax.device_put(self.eos, self._vec_sh)
            self._poison_zero = jax.device_put(self._poison_zero,
                                               self._vec_sh)
            self.poison = self._poison_zero
            self.key = jax.device_put(self.key, self._repl)

        # per-step sampled-token trace: device arrays, harvested lazily.
        # Plain decode appends (B,) token vectors; mixed/spec/admission
        # steps append ((B, W) block, (B,) emit-count) pairs (W = 1 for
        # mixed and admission entries, gamma+1 for speculative entries).
        self._trace: List[Any] = []
        self._trace_base = 0                      # global step of _trace[0]
        self._slot_start = [0] * max_batch        # global step per slot
        self._steps = 0
        self._step_wall: List[float] = []         # per-step wall clock (for
        # inter-token gaps; assigned at burst sync, padded for raw
        # step(), pruned with the trace — _step_wall_base is the global
        # step index of entry 0)
        self._step_wall_base = 0
        self._await_first: List[Request] = []     # chunked admissions whose
        # first token exists on device but has no host timestamp yet
        self._drop_compile_step = True            # step_times[0] is compile

        # --- speculative decoding ------------------------------------- #
        draft_src = draft if draft is not None else (cfg.draft or None)
        gamma = spec_gamma or cfg.spec_gamma
        if draft_src is not None and gamma == 0:
            gamma = 4
        if gamma and draft_src is None:
            raise ValueError("spec_gamma set but no draft configured "
                             "(pass draft=... or set cfg.draft)")
        self.spec_gamma = gamma if draft_src is not None else 0
        self._ngram = isinstance(draft_src, str) \
            and draft_src.partition("@")[0] == "ngram"
        self._draft_model: Optional[Model] = None
        self._draft_params = None
        self.draft_cache = None
        self.hist = self.hist_len = None   # ngram drafter token history
        self._hist_sh = None
        if self.spec_gamma:
            if self.spec_gamma + 1 > self.kv_len:
                raise ValueError(
                    f"spec_gamma={self.spec_gamma} needs a verify window "
                    f"of {self.spec_gamma + 1} <= kv ring {self.kv_len}")
            if self._ngram:
                # family-agnostic prompt-lookup drafter: proposals come
                # from each slot's own effective token stream, kept on
                # device so the spec step stays sync-free. Sized for the
                # longest stream worth matching against; longer streams
                # keep their most recent window (serving/ngram_draft.py)
                H = 2 * self.kv_len
                self.hist = jnp.full((max_batch, H), -1, jnp.int32)
                self.hist_len = jnp.zeros((max_batch,), jnp.int32)
            else:
                if isinstance(draft_src, str):
                    from repro.quant.self_draft import make_self_draft
                    dmodel, dparams = make_self_draft(model, params,
                                                      draft_src)
                else:
                    dmodel, dparams = draft_src
                if model.rollback_needs_replay \
                        or dmodel.rollback_needs_replay:
                    raise ValueError(
                        "model-draft speculation requires caches that "
                        "rewind without replay on both sides (attention-"
                        f"backed); families {cfg.family!r} / "
                        f"{dmodel.cfg.family!r} carry recurrent state — "
                        "use draft='ngram' instead")
                if model.encode_memory is not None:
                    raise ValueError(
                        "model-draft speculation is not wired for "
                        "encoder-decoder stacks (the draft would need "
                        "its own cross-attention memory per request) — "
                        "use draft='ngram' instead")
                self._draft_model = dmodel
                self._draft_params = dparams
                self.draft_cache = dmodel.make_cache(max_batch, cache_len)
            if self.mesh is not None and self._draft_model is not None:
                # same rules as the target: the self-draft's params are
                # (slices of) the target's, so they shard identically
                self._draft_param_sh = self._SH.param_shardings(
                    self._draft_params, self.mesh)
                self._draft_params = jax.device_put(self._draft_params,
                                                    self._draft_param_sh)
                self._draft_cache_sh = self._SH.cache_shardings(
                    self.draft_cache, self.mesh, self._b_axes)
                self.draft_cache = jax.device_put(self.draft_cache,
                                                  self._draft_cache_sh)
            if self.mesh is not None and self._ngram:
                self._hist_sh = self._SH.batch_shardings(
                    self.hist, self.mesh, self._b_axes)
                self.hist = jax.device_put(self.hist, self._hist_sh)
                self.hist_len = jax.device_put(self.hist_len,
                                               self._vec_sh)
            # a spec step emits up to gamma+1 tokens per slot, so polls
            # must come ~(gamma+1)x as often to keep the post-finish
            # overshoot (device decoding an already-finished slot until
            # the next poll) the same number of *tokens* as plain decode
            self.sync_every = max(1, self.sync_every
                                  // (self.spec_gamma + 1))

        # --- continuous batching (the one admission path) -------------- #
        chunk = cfg.prefill_chunk if prefill_chunk is None \
            else prefill_chunk
        # 0 / unset = a single max-size chunk per admission: the whole
        # prompt enters through one fused extend. There is no separate
        # monolithic prefill program — every family admits through the
        # chunked path.
        self.prefill_chunk = min(int(chunk), self.kv_len) if chunk \
            else self.kv_len
        pct = cfg.prefix_cache_tokens if prefix_cache_tokens is None \
            else prefix_cache_tokens
        # prefix reuse stores target-cache slices only; in spec mode the
        # draft cache would still need recomputation, so it is disabled.
        # The extract/materialize slot programs slice KV rings, so the
        # trie is attention-only-stack scoped (recurrent state and
        # encoder memory have no per-position KV slices to share).
        self.prefix_cache: Optional[PrefixCache] = None
        if pct and not self.spec_gamma and model.supports_paged:
            if self.paged:
                # entries are page-index lists; bucketing on the page
                # size makes every hit a whole-page alias, and eviction
                # drops the entry's page references (the pages outlive
                # it while any live slot still aliases them)
                self.prefix_cache = PrefixCache(
                    pct, self.page_size,
                    on_evict=lambda e: self._paged.release_pages(e["kv"]))
            else:
                self.prefix_cache = PrefixCache(pct, self.prefill_chunk)
        self._admit: Optional[_Admission] = None

        if self.spec_gamma:
            self._step_fn = self._build_ngram_spec_step() if self._ngram \
                else self._build_spec_step()
        else:
            self._step_fn = self._build_step()
        self._mixed_fn = None          # fused decode+chunk, built lazily
        self._admit_chunk_fn = None    # spec-mode chunk program, lazy
        self._slot_jits: Dict[Tuple, Any] = {}   # reset/materialize/extract
        # live component stats surface through snapshot() collectors
        if self.prefix_cache is not None:
            self.metrics.add_collector(self.prefix_cache.stats)
        if self.paged:
            self.metrics.add_collector(self._paged.stats)

    # ------------------------------------------------------------ #
    # host-side step series (live views into the metrics registry)
    # ------------------------------------------------------------ #
    @property
    def step_times(self) -> List[float]:
        """Per-step wall clock, aligned with ``step_kinds``. The list is
        the registry's ``step_wall_s`` series storage itself — appends
        and in-place rewrites (burst averaging) hit the same object."""
        return self._step_series.values

    @step_times.setter
    def step_times(self, v) -> None:
        self._step_series.values[:] = list(v)

    @property
    def step_kinds(self) -> List[str]:
        """"plain"|"mixed"|"admit"|"spec" per step, aligned with
        ``step_times`` — lets benchmarks separate steady decode from
        steps that also carried admission work."""
        return self._kind_series.values

    @step_kinds.setter
    def step_kinds(self, v) -> None:
        self._kind_series.values[:] = list(v)

    def _record_step(self, kind: str) -> None:
        """One engine step happened: advance the global counter and the
        registry's per-kind counters + aligned kind series (the wall
        entry is appended by ``step()`` once timing is known)."""
        self._kind_series.append(kind)
        self.metrics.counter("steps_" + kind).inc()
        self._c_steps.inc()
        self._steps += 1

    # ------------------------------------------------------------ #
    # jitted programs
    # ------------------------------------------------------------ #
    def _jit(self, fn, donate=(), in_sh=None, out_sh=None, name=""):
        """``jax.jit`` with the engine's mesh wiring. Off-mesh this is a
        plain jit. On a mesh, every program gets explicit
        ``in_shardings``/``out_shardings`` (donated buffers keep their
        layout, so the cache is updated in place and nothing is
        re-laid-out between steps) and is *traced* inside the
        activation-rules context — ``shard_activation`` call sites in
        the models become real constraints and ``kernels.dispatch``
        routes Pallas ops to their partitionable jnp references.

        Every program is wrapped by the recompile watchdog: a call that
        grows the jit cache records a compile event (program ``name``,
        elapsed wall) into the registry, and — once the engine is
        steady (``reset_stats``/``mark_steady``) — raises a
        ``telemetry.RecompileWarning``."""
        if self.mesh is None:
            return self._watch(jax.jit(fn, donate_argnums=donate), name)
        jitted = jax.jit(fn, donate_argnums=donate,
                         in_shardings=in_sh, out_shardings=out_sh)
        mesh, rules = self.mesh, self._act_rules
        from repro.distribution.sharding import activation_sharding

        def wrapped(*args):
            with activation_sharding(mesh, rules):
                return jitted(*args)
        wrapped._jit = jitted        # compile-count introspection (tests)
        return self._watch(wrapped, name)

    def _watch(self, fn, name: str):
        """Recompile-watchdog wrapper: detect compiles by jit-cache
        growth around each call (a compile blocks the dispatching call,
        so its wall time is the observed elapsed). Adds two cache-size
        probes and two clock reads per call — host-only, no effect on
        the compiled programs themselves."""
        inner = getattr(fn, "_jit", fn)
        probe = getattr(inner, "_cache_size", None)
        if probe is None:            # jax without cache introspection
            return fn
        watchdog = self._watchdog

        def watched(*args):
            before = probe()
            t0 = time.perf_counter()
            out = fn(*args)
            if probe() > before:
                t1 = time.perf_counter()
                watchdog.record(name or getattr(fn, "__name__", "jit"),
                                t1 - t0, self._steps, t1)
            return out
        watched._jit = inner         # program_cache_sizes introspection
        return watched

    def program_cache_sizes(self) -> Dict[str, int]:
        """Compiled-specialization count per fused-step program. Under a
        mesh this is the no-recompile guard: steady-state serving must
        keep each program at one entry — a growing count means some
        input's sharding/layout is churning step to step."""
        out: Dict[str, int] = {}
        for name, fn in (("step", self._step_fn),
                         ("mixed", self._mixed_fn),
                         ("admit_chunk", self._admit_chunk_fn)):
            if fn is None:
                continue
            inner = getattr(fn, "_jit", fn)
            if hasattr(inner, "_cache_size"):
                out[name] = inner._cache_size()
        return out

    def _build_step(self):
        """Fused decode: model step + sampling + slot bookkeeping, with the
        cache and decode state donated so XLA updates them in place.

        Paged engines decode through a masked T=1 ``extend_into_cache``
        (bit-identical per row to ``decode_step``) so rows the device
        already finished neither scatter into pages nor advance their
        step — page provisioning stays an upper bound on real writes."""
        model, sampler = self.model, self.sampler

        if self.paged:
            def step(params, cache, tokens, remaining, active, eos, key,
                     poison):
                with jax.named_scope("decode"):
                    logits, cache = model.extend_into_cache(
                        params, tokens, cache, active.astype(jnp.int32),
                        last_only=True)
                key, sk = jax.random.split(key)
                nxt, bad = _guarded_sample(
                    sampler, sk,
                    logits[:, 0].astype(jnp.float32) + poison[:, None])
                done = active & (bad | (remaining <= 1) | (nxt == eos))
                new_active = active & ~done
                remaining = jnp.where(active, remaining - 1, remaining)
                new_tokens = jnp.where(active, nxt, tokens[:, 0])
                return (new_tokens[:, None], cache, remaining, new_active,
                        key)
        else:
            def step(params, cache, tokens, remaining, active, eos, key,
                     poison):
                with jax.named_scope("decode"):
                    logits, cache = model.decode_step(params, tokens, cache)
                key, sk = jax.random.split(key)
                nxt, bad = _guarded_sample(                        # (B,)
                    sampler, sk,
                    logits[:, -1].astype(jnp.float32) + poison[:, None])
                done = active & (bad | (remaining <= 1) | (nxt == eos))
                new_active = active & ~done
                remaining = jnp.where(active, remaining - 1, remaining)
                return nxt[:, None], cache, remaining, new_active, key

        donate = (1, 2, 3, 4) if self._donate else ()
        in_sh = out_sh = None
        if self.mesh is not None:
            r, tok, vec = self._repl, self._tok_sh, self._vec_sh
            in_sh = (self._param_sh, self._cache_sh, tok, vec, vec, vec, r,
                     vec)
            out_sh = (tok, self._cache_sh, vec, vec, r)
        return self._jit(step, donate, in_sh, out_sh, name="step")

    @staticmethod
    def _slot_extend(model, params, cache, slot, chunk, n, last_only=True,
                     paged=False):
        """Slot-direct chunk extend inside a jitted program: slice the
        admitting slot out of the batched cache (batch axis 1 under the
        block axis), advance it by ``n`` of the chunk's C tokens at
        batch 1, and write it back with ``dynamic_update_slice`` — the
        chunk costs C tokens at batch 1, NOT B·C. (An earlier design ran
        a (B, C) matrix through one extend; every decode row then paid
        the chunk's sequence length through all matmuls and tail ITL got
        *worse* than the stall baseline it was meant to fix.)

        Paged caches share their page pools across slots: only the
        per-slot leaves (block table / pos / step) are sliced and written
        back; the pools pass through whole and the chunk's KV scatters
        into them through the sliced block-table row."""
        if paged:
            def slc(node):
                return {k: (v if k in paged_kv.POOL_KEYS else
                            lax.dynamic_slice_in_dim(v, slot, 1, axis=1))
                        for k, v in node.items()}
            cache1 = paged_kv.walk_attn(cache, slc)
            logits, cache1 = model.extend_into_cache(
                params, chunk[None, :], cache1, n[None],
                last_only=last_only)

            def merge(full, upd):
                return {k: (upd[k] if k in paged_kv.POOL_KEYS else
                            lax.dynamic_update_slice_in_dim(
                                full[k], upd[k], slot, axis=1))
                        for k in full}
            return logits, paged_kv.walk_attn2(cache, cache1, merge)
        cache1 = jax.tree.map(
            lambda t: lax.dynamic_slice_in_dim(t, slot, 1, axis=1), cache)
        logits, cache1 = model.extend_into_cache(
            params, chunk[None, :], cache1, n[None], last_only=last_only)
        cache = jax.tree.map(
            lambda full, u: lax.dynamic_update_slice_in_dim(
                full, u, slot, axis=1), cache, cache1)
        return logits, cache

    def _build_mixed_step(self):
        """One fused decode + prefill-chunk program (static shapes):

        1. all active slots decode one token (a masked T=1
           ``extend_into_cache`` — bit-identical per row to the plain
           step, but the admitting and idle rows advance by 0 so nothing
           is speculated into a half-filled slot);
        2. the admitting slot is sliced out, advanced by up to
           ``prefill_chunk`` prompt tokens at batch 1, and written back
           (``_slot_extend``);
        3. one sampler call over each row's last-valid logits gives the
           decode rows their next token and — when the chunk completes
           the prompt (``a_last``) — the admitting row its *first*
           token, arming it on device (tokens/remaining/active/eos rows
           written in-program, no host round-trip).

        Emitted tokens flow through the same trace/poll contract as
        plain decode (W = 1 blocks with a per-row emit count)."""
        model, sampler = self.model, self.sampler
        is_paged = self.paged

        def mixed(params, cache, tokens, remaining, active, eos, key,
                  chunk, a_slot, a_len, a_last, a_rem, a_eos, poison):
            B = tokens.shape[0]
            bidx = jnp.arange(B)
            is_admit = bidx == a_slot
            with jax.named_scope("decode"):
                dec_logits, cache = model.extend_into_cache(
                    params, tokens, cache, active.astype(jnp.int32),
                    last_only=True)
            with jax.named_scope("prefill"):
                ch_logits, cache = self._slot_extend(
                    model, params, cache, a_slot, chunk, a_len,
                    paged=is_paged)
            logits = jnp.where(is_admit[:, None], ch_logits[0, 0][None],
                               dec_logits[:, 0])
            key, sk = jax.random.split(key)
            nxt, bad = _guarded_sample(                         # (B,)
                sampler, sk, logits.astype(jnp.float32) + poison[:, None])
            arm = is_admit & a_last
            emit = active | arm
            done = emit & (bad | (jnp.where(arm, a_rem, remaining) <= 1)
                           | (nxt == jnp.where(arm, a_eos, eos)))
            new_active = emit & ~done
            new_remaining = jnp.where(
                arm, a_rem - 1,
                jnp.where(active, remaining - 1, remaining))
            new_eos = jnp.where(arm, a_eos, eos)
            new_tokens = jnp.where(emit, nxt, tokens[:, 0])
            return (new_tokens[:, None], nxt[:, None],
                    emit.astype(jnp.int32), cache, new_remaining,
                    new_active, new_eos, key)

        donate = (1, 2, 3, 4, 5) if self._donate else ()
        in_sh = out_sh = None
        if self.mesh is not None:
            r, tok, vec = self._repl, self._tok_sh, self._vec_sh
            in_sh = (self._param_sh, self._cache_sh, tok, vec, vec, vec,
                     r, r, r, r, r, r, r, vec)
            out_sh = (tok, tok, vec, self._cache_sh, vec, vec, vec, r)
        return self._jit(mixed, donate, in_sh, out_sh, name="mixed")

    def _build_admit_chunk(self):
        """Spec-mode chunk program: advance one admitting request by up to
        C prompt tokens in the target cache and (one position behind) in
        the draft cache — both slot-direct at batch 1 — arming the slot
        on completion. Dispatched right before the fused spec step, so
        admission never stalls speculative decode of the other slots.
        The draft consumes the same chunk capped at L-1 total (its cache
        lags the committed depth by one: the last prompt token becomes
        ``prev`` and is re-consumed by the first draft verify window)."""
        model, draft = self.model, self._draft_model
        sampler = self.sampler
        is_paged = self.paged

        def admit(params, dparams, cache, dcache, tokens, prev, remaining,
                  active, eos, key, chunk, a_slot, a_len, d_len, a_last,
                  a_rem, a_eos, a_prev, poison):
            B = tokens.shape[0]
            bidx = jnp.arange(B)
            is_admit = bidx == a_slot
            with jax.named_scope("prefill"):
                logits, cache = self._slot_extend(
                    model, params, cache, a_slot, chunk, a_len,
                    paged=is_paged)
                _, dcache = self._slot_extend(
                    draft, dparams, dcache, a_slot, chunk, d_len)
            key, sk = jax.random.split(key)
            nxt, bad = _guarded_sample(                          # (1,)
                sampler, sk,
                logits[:, 0].astype(jnp.float32) + poison[a_slot])
            arm = is_admit & a_last
            done = arm & (bad[0] | (a_rem <= 1) | (nxt[0] == a_eos))
            new_active = active | (arm & ~done)
            new_remaining = jnp.where(arm, a_rem - 1, remaining)
            new_eos = jnp.where(arm, a_eos, eos)
            new_tokens = jnp.where(arm, nxt[0], tokens[:, 0])
            new_prev = jnp.where(arm, a_prev, prev[:, 0])
            return (new_tokens[:, None], new_prev[:, None],
                    new_tokens[:, None], arm.astype(jnp.int32), cache,
                    dcache, new_remaining, new_active, new_eos, key)

        donate = (2, 3, 4, 5, 6, 7, 8) if self._donate else ()
        in_sh = out_sh = None
        if self.mesh is not None:
            r, tok, vec = self._repl, self._tok_sh, self._vec_sh
            in_sh = (self._param_sh, self._draft_param_sh, self._cache_sh,
                     self._draft_cache_sh, tok, tok, vec, vec, vec, r,
                     r, r, r, r, r, r, r, r, vec)
            out_sh = (tok, tok, tok, vec, self._cache_sh,
                      self._draft_cache_sh, vec, vec, vec, r)
        return self._jit(admit, donate, in_sh, out_sh, name="admit_chunk")

    def _build_spec_step(self):
        """One fused draft–verify–accept program (static shapes):

        1. the draft proposes gamma tokens autoregressively. Its cache
           *lags the committed depth by one position* (see below), so the
           first proposal comes from a 2-token verify window
           ``[prev, pending]`` and the remaining gamma-1 from single-token
           decodes — gamma draft forwards total, and the draft cache
           never develops holes on full acceptance;
        2. the target scores all gamma+1 positions in one masked
           multi-token forward (``verify_step``) at each row's own offset;
        3. ``sampler.speculative`` accepts a per-row prefix and resamples
           the first rejection (greedy: emitted prefix == target argmax,
           so output is token-identical to non-speculative decode);
        4. both caches roll their per-row ``step`` back via
           ``Model.rollback`` — target to the committed depth, draft to
           committed-1 — and stored keys beyond it stay causally
           invisible;
        5. slot bookkeeping mirrors the plain step with a variable emit
           count ``n_emit in [1, gamma+1]`` per row.

        Lag invariant: entering a step with committed depth C, the target
        cache holds positions < C and the draft cache positions < C-1;
        ``prev`` is the token at C-1 and ``tokens`` the pending one at C.
        The draft's verify window rewrites C-1 and C, decodes write
        C+1..C+gamma-1, and the last proposal is *never* written — its
        position is re-consumed by the next step's verify window, so full
        acceptance leaves no hole.

        Every forward is an ``extend_into_cache`` masked by ``active``:
        inactive rows neither write keys nor advance their ``step``.
        Active rows are bit-identical either way (attention is per-row),
        but an *admitting* slot — mid-chunked-prefill while its
        neighbours keep speculating — must not have garbage speculated
        into the row between its chunks.
        """
        model, sampler = self.model, self.sampler
        draft, gamma = self._draft_model, self.spec_gamma

        def spec(params, dparams, cache, dcache, tokens, prev, remaining,
                 active, eos, key, poison):
            B = tokens.shape[0]
            act1 = active.astype(jnp.int32)
            # 1) draft proposals (and their full logit rows, for the
            #    stochastic accept ratio p/q). _finite_rows keeps a
            #    NaN-poisoned draft row well-defined — the target verify
            #    is the authority and simply rejects its proposals
            window = jnp.concatenate([prev, tokens], axis=1)   # (B, 2)
            dl, dcache = draft.extend_into_cache(dparams, window, dcache,
                                                 2 * act1)
            d_toks, d_logits = [], []
            cur_logits = _finite_rows(dl[:, -1].astype(jnp.float32))
            for i in range(gamma):
                key, sk = jax.random.split(key)
                t = sampler(sk, cur_logits)
                d_toks.append(t)
                d_logits.append(cur_logits)
                if i + 1 < gamma:
                    dl, dcache = draft.extend_into_cache(
                        dparams, t[:, None], dcache, act1)
                    cur_logits = _finite_rows(
                        dl[:, -1].astype(jnp.float32))
            draft_tokens = jnp.stack(d_toks, axis=1)          # (B, g)
            draft_logits = jnp.stack(d_logits, axis=1)        # (B, g, V)

            # 2) one masked multi-token target forward over
            #    [pending, draft_0..draft_{g-1}]
            seq = jnp.concatenate([tokens, draft_tokens], axis=1)
            t_logits, cache = model.extend_into_cache(
                params, seq, cache, (gamma + 1) * act1)

            # 3) accept prefix + resample first rejection (on device).
            #    A row whose target logits are not finite emits the
            #    single ERR_TOKEN sentinel (n_acc forced to 0) and is
            #    marked done below — containment mirrors _guarded_sample
            t32 = t_logits.astype(jnp.float32) + poison[:, None, None]
            bad = active & ~jnp.all(jnp.isfinite(t32), axis=(1, 2))
            key, sk = jax.random.split(key)
            block, n_acc = sampler.speculative(
                sk, draft_tokens, draft_logits,
                jnp.where(bad[:, None, None], 0.0, t32))
            block = jnp.where(bad[:, None], jnp.int32(ERR_TOKEN), block)
            n_acc = jnp.where(bad, 0, n_acc)
            n_emit = jnp.where(active, n_acc + 1, 0)          # (B,)

            # 4) per-row rollback to the accepted depth. verify advanced
            #    active targets by gamma+1; the committed depth is
            #    old_step + 1 + n_acc (pending + accepted drafts), i.e.
            #    current - gamma + n_acc. The draft sits at committed-1.
            #    Inactive rows did not move and must not be rolled.
            steps_now = model.cache_steps(cache)              # (B,)
            committed = jnp.where(active, steps_now - gamma + n_acc,
                                  steps_now)
            cache = model.rollback(cache, committed)
            dcache = draft.rollback(
                dcache, jnp.where(active, committed - 1,
                                  draft.cache_steps(dcache)))

            # 5) bookkeeping (same stop conditions as the plain step,
            #    with a variable emit count)
            idx = jnp.arange(gamma + 1)[None, :]
            emitted = idx < n_emit[:, None]
            eos_hit = jnp.any(emitted & (block == eos[:, None]), axis=1)
            done = active & (bad | (remaining <= n_emit) | eos_hit)
            new_active = active & ~done
            remaining = jnp.where(
                active, jnp.maximum(remaining - n_emit, 0), remaining)
            bidx = jnp.arange(B)
            last = block[bidx, jnp.maximum(n_emit, 1) - 1]
            nxt = jnp.where(active, last, tokens[:, 0])
            # token preceding the new pending one: the last accepted
            # draft, or the old pending token when nothing was accepted
            new_prev = jnp.where(
                n_acc > 0, block[bidx, jnp.maximum(n_acc, 1) - 1],
                tokens[:, 0])
            new_prev = jnp.where(active, new_prev, prev[:, 0])
            return (nxt[:, None], new_prev[:, None], block, n_emit,
                    cache, dcache, remaining, new_active, key)

        donate = (2, 3, 4, 5, 6, 7) if self._donate else ()
        in_sh = out_sh = None
        if self.mesh is not None:
            r, tok, vec = self._repl, self._tok_sh, self._vec_sh
            in_sh = (self._param_sh, self._draft_param_sh, self._cache_sh,
                     self._draft_cache_sh, tok, tok, vec, vec, vec, r,
                     vec)
            # tok's (batch, None) spec also covers the (B, gamma+1) block
            out_sh = (tok, tok, tok, vec, self._cache_sh,
                      self._draft_cache_sh, vec, vec, r)
        return self._jit(spec, donate, in_sh, out_sh, name="spec_step")

    def _build_ngram_admit_chunk(self):
        """n-gram-mode chunk program: advance one admitting request by up
        to C prompt tokens in the target cache (slot-direct at batch 1),
        arming the slot on completion — the drafter has no cache, so
        unlike the model-draft variant there is no lagging draft extend.
        The armed first token is appended to the slot's history row on
        device (it is part of the stream the drafter matches against)."""
        model, sampler = self.model, self.sampler
        is_paged = self.paged

        def admit(params, cache, tokens, hist, hist_len, remaining,
                  active, eos, key, chunk, a_slot, a_len, a_last, a_rem,
                  a_eos, poison):
            B = tokens.shape[0]
            H = hist.shape[1]
            bidx = jnp.arange(B)
            is_admit = bidx == a_slot
            with jax.named_scope("prefill"):
                logits, cache = self._slot_extend(
                    model, params, cache, a_slot, chunk, a_len,
                    paged=is_paged)
            key, sk = jax.random.split(key)
            nxt, bad = _guarded_sample(                          # (1,)
                sampler, sk,
                logits[:, 0].astype(jnp.float32) + poison[a_slot])
            arm = is_admit & a_last
            done = arm & (bad[0] | (a_rem <= 1) | (nxt[0] == a_eos))
            new_active = active | (arm & ~done)
            new_remaining = jnp.where(arm, a_rem - 1, remaining)
            new_eos = jnp.where(arm, a_eos, eos)
            new_tokens = jnp.where(arm, nxt[0], tokens[:, 0])
            wpos = jnp.where(a_last, hist_len[a_slot], H)   # H -> dropped
            hist = hist.at[a_slot, wpos].set(nxt[0], mode="drop")
            hist_len = jnp.where(
                is_admit & a_last,
                jnp.minimum(hist_len + 1, H), hist_len)
            return (new_tokens[:, None], new_tokens[:, None],
                    arm.astype(jnp.int32), cache, hist, hist_len,
                    new_remaining, new_active, new_eos, key)

        donate = (1, 2, 3, 4, 5, 6, 7) if self._donate else ()
        in_sh = out_sh = None
        if self.mesh is not None:
            r, tok, vec = self._repl, self._tok_sh, self._vec_sh
            in_sh = (self._param_sh, self._cache_sh, tok, self._hist_sh,
                     vec, vec, vec, vec, r, r, r, r, r, r, r, vec)
            out_sh = (tok, tok, vec, self._cache_sh, self._hist_sh, vec,
                      vec, vec, vec, r)
        return self._jit(admit, donate, in_sh, out_sh,
                         name="ngram_admit_chunk")

    def _build_ngram_spec_step(self):
        """One fused propose–verify–accept program with the prompt-lookup
        drafter (``serving/ngram_draft.py``) in place of a draft model:

        1. ``ngram_propose`` matches each row's recent history suffix
           against its own stream and proposes the gamma tokens that
           followed the most recent earlier occurrence (deterministic —
           no draft forward, no draft cache, no lag bookkeeping);
        2. the target scores all gamma+1 positions in one masked extend,
           exactly like the model-draft spec step;
        3. ``sampler.speculative`` accepts a per-row prefix against the
           drafter's one-hot proposal distribution (greedy output is
           token-identical to plain decode by the same argument: the
           emitted prefix is the target argmax);
        4. rollback is family-aware: attention-backed targets rewind
           ``step`` to the committed depth; recurrent targets
           (``Model.rollback_needs_replay``) restore the pre-verify
           checkpoint and *replay* the accepted prefix through the same
           extend — state after replay is bit-identical to having never
           speculated (tests/test_families.py);
        5. the emitted block is appended to the history rows on device,
           growing the drafter's corpus as the stream generates.
        """
        model, sampler = self.model, self.sampler
        gamma = self.spec_gamma
        vocab = self.model.cfg.vocab
        replay = self.model.rollback_needs_replay
        from repro.serving.ngram_draft import ngram_propose

        def spec(params, cache, tokens, hist, hist_len, remaining,
                 active, eos, key, poison):
            B = tokens.shape[0]
            H = hist.shape[1]
            act1 = active.astype(jnp.int32)
            # 1) proposals from each row's own emitted stream
            draft_tokens, draft_logits = ngram_propose(
                hist, hist_len, gamma=gamma, vocab=vocab)
            seq = jnp.concatenate([tokens, draft_tokens], axis=1)

            # 2) one masked multi-token target forward
            t_logits, cache = model.extend_into_cache(
                params, seq, cache, (gamma + 1) * act1)

            # 3) accept prefix + resample first rejection (on device);
            #    NaN/inf containment mirrors the model-draft step
            t32 = t_logits.astype(jnp.float32) + poison[:, None, None]
            bad = active & ~jnp.all(jnp.isfinite(t32), axis=(1, 2))
            key, sk = jax.random.split(key)
            block, n_acc = sampler.speculative(
                sk, draft_tokens, draft_logits,
                jnp.where(bad[:, None, None], 0.0, t32))
            block = jnp.where(bad[:, None], jnp.int32(ERR_TOKEN), block)
            n_acc = jnp.where(bad, 0, n_acc)
            n_emit = jnp.where(active, n_acc + 1, 0)          # (B,)

            # 4) family-aware rollback to the committed depth
            steps_now = model.cache_steps(cache)              # (B,)
            committed = jnp.where(active, steps_now - gamma + n_acc,
                                  steps_now)
            if replay:
                # recurrent state restores the checkpoint taken before
                # the verify advance, then re-absorbs exactly the
                # accepted prefix (pending + n_acc drafts). Attention
                # sub-caches in a hybrid stack rewrite the same K/V at
                # the same slots — bitwise a no-op for them.
                pre = jnp.where(active, steps_now - (gamma + 1),
                                steps_now)
                cache = model.rollback(cache, pre)
                _, cache = model.extend_into_cache(
                    params, seq, cache, jnp.where(active, n_acc + 1, 0),
                    last_only=True)
            else:
                cache = model.rollback(cache, committed)

            # 5) bookkeeping + history append
            idx = jnp.arange(gamma + 1)[None, :]
            emitted = idx < n_emit[:, None]
            eos_hit = jnp.any(emitted & (block == eos[:, None]), axis=1)
            done = active & (bad | (remaining <= n_emit) | eos_hit)
            new_active = active & ~done
            remaining = jnp.where(
                active, jnp.maximum(remaining - n_emit, 0), remaining)
            bidx = jnp.arange(B)
            last = block[bidx, jnp.maximum(n_emit, 1) - 1]
            nxt = jnp.where(active, last, tokens[:, 0])
            wpos = jnp.where(emitted & active[:, None],
                             hist_len[:, None] + idx, H)   # H -> dropped
            hist = hist.at[bidx[:, None], wpos].set(block, mode="drop")
            hist_len = jnp.minimum(hist_len + n_emit, H)
            return (nxt[:, None], block, n_emit, cache, hist, hist_len,
                    remaining, new_active, key)

        donate = (1, 2, 3, 4, 5, 6) if self._donate else ()
        in_sh = out_sh = None
        if self.mesh is not None:
            r, tok, vec = self._repl, self._tok_sh, self._vec_sh
            in_sh = (self._param_sh, self._cache_sh, tok, self._hist_sh,
                     vec, vec, vec, vec, r, vec)
            out_sh = (tok, tok, vec, self._cache_sh, self._hist_sh, vec,
                      vec, vec, r)
        return self._jit(spec, donate, in_sh, out_sh,
                         name="ngram_spec_step")

    def _get_embed_chunk(self, for_draft: bool = False):
        """VLM admission program: the request's frontend embeddings
        enter the admitting slot through the same masked extend as text
        — one embedding chunk (static length ``frontend.n_tokens``)
        before the token chunks, slot-direct at batch 1."""
        jkey = ("embed_chunk", for_draft)
        if jkey in self._slot_jits:
            return self._slot_jits[jkey]
        model = self._draft_model if for_draft else self.model

        def fn(params, emb, cache, b):
            cache1 = jax.tree.map(
                lambda t: lax.dynamic_slice_in_dim(t, b, 1, axis=1), cache)
            _, cache1 = model.extend_into_cache(params, None, cache1,
                                                embeddings=emb)
            return jax.tree.map(
                lambda full, u: lax.dynamic_update_slice_in_dim(
                    full, u, b, axis=1), cache, cache1)

        donate = (2,) if self._donate else ()
        in_sh = out_sh = None
        if self.mesh is not None:
            r = self._repl
            cache_sh = self._draft_cache_sh if for_draft else self._cache_sh
            in_sh = (self._draft_param_sh if for_draft else self._param_sh,
                     r, cache_sh, r)
            out_sh = cache_sh
        jitted = self._jit(fn, donate, in_sh, out_sh,
                           name=f"embed_chunk{'[d]' if for_draft else ''}")
        self._slot_jits[jkey] = jitted
        return jitted

    def _get_encode_fn(self):
        """Encoder–decoder admission program: encode the request's
        frontend frames once (``Model.encode_memory``) and write the
        per-layer cross-attention KV rows into the admitting slot. The
        memory is prefill-frozen — every later chunk and decode step
        reads it untouched, so the one-shot encode replaces the whole
        encoder half of the old monolithic prefill."""
        jkey = ("encode", 0)
        if jkey in self._slot_jits:
            return self._slot_jits[jkey]
        model = self.model

        def fn(params, frames, cache, b):
            xk, xv = model.encode_memory(params, frames)
            out = dict(cache)
            out["xk"] = lax.dynamic_update_slice_in_dim(
                cache["xk"], xk.astype(cache["xk"].dtype), b, axis=1)
            out["xv"] = lax.dynamic_update_slice_in_dim(
                cache["xv"], xv.astype(cache["xv"].dtype), b, axis=1)
            return out

        donate = (2,) if self._donate else ()
        in_sh = out_sh = None
        if self.mesh is not None:
            in_sh = (self._param_sh, self._repl, self._cache_sh,
                     self._repl)
            out_sh = self._cache_sh
        jitted = self._jit(fn, donate, in_sh, out_sh, name="encode")
        self._slot_jits[jkey] = jitted
        return jitted

    # ------------------------------------------------------------ #
    # slot programs (chunked admission + prefix reuse)
    # ------------------------------------------------------------ #
    def _walk_attn(self, node, fn):
        """Apply ``fn`` to every attention sub-cache dict (identified by
        its ``pos`` row). Non-attention nodes — SSM recurrent state
        dicts, the encdec cross-attention memory arrays — pass through
        untouched; callers that must also reset them walk separately
        (``_get_slot_fn('reset')``)."""
        if not isinstance(node, dict):
            return node
        if "pos" in node:
            return fn(node)
        return {k: self._walk_attn(v, fn) for k, v in node.items()}

    def _get_slot_fn(self, kind: str, P=0):
        """reset / materialize / extract programs for one slot row, jitted
        per (kind, length). Lengths are bucketed chunk multiples, so the
        jit cache stays small: extract is keyed on the stored prefix
        length, materialize on the hit length Q alone — a partial hit of
        a longer stored entry is sliced to Q eagerly in
        ``_start_chunked`` before reaching the program (exact by
        causality: K/V at p depends only on tokens <= p)."""
        jkey = (kind, P)
        if jkey in self._slot_jits:
            return self._slot_jits[jkey]

        def pos_row(node, b, upto):
            nb, _, S = node["pos"].shape
            ar = jnp.arange(S, dtype=jnp.int32)
            row = jnp.where(ar < upto, ar, -1)[None, None, :]
            out = dict(node)
            out["pos"] = lax.dynamic_update_slice(
                node["pos"], jnp.broadcast_to(row, (nb, 1, S)), (0, b, 0))
            out["step"] = lax.dynamic_update_slice(
                node["step"], jnp.full((nb, 1), upto, jnp.int32), (0, b))
            return out

        if kind == "reset":
            def fn(cache, b):
                # erase slot b: every position empty, depth 0 — a recycled
                # slot carries no stale keys from the previous occupant.
                # With P > 0 (the paged prefix-alias path) the first P
                # positions are stamped valid instead: the slot's block
                # table already points at fully-written shared pages, so
                # only the pos/step metadata needs populating. Non-
                # attention state is zeroed outright: SSM recurrent nodes
                # (state + checkpoints + step) and the encdec cross-
                # attention memory rows have no positional masking to
                # hide a previous occupant behind
                def walk(node):
                    if not isinstance(node, dict):
                        return node.at[:, b].set(0)
                    if "pos" in node:
                        return pos_row(node, b, P)
                    if "conv" in node and "ssm" in node:
                        return {k2: v2.at[:, b].set(0)
                                for k2, v2 in node.items()}
                    return {k2: walk(v2) for k2, v2 in node.items()}
                return walk(cache)
        elif kind == "materialize":
            def fn(cache, kv, b):
                # walk cache and entry trees in lockstep: write the P
                # stored K/V (+scale) positions, then stamp pos/step for
                # a slot whose first P positions are now populated
                def walk(c, e):
                    if isinstance(c, dict) and "pos" in c:
                        out = dict(c)
                        for k2, part in e.items():
                            idx = (0, b, 0) + (0,) * (c[k2].ndim - 3)
                            out[k2] = lax.dynamic_update_slice(
                                c[k2], part, idx)
                        return pos_row(out, b, P)
                    return {k2: walk(v2, e[k2]) for k2, v2 in c.items()}
                return walk(cache, kv)
        elif kind == "extract":
            def fn(cache, b):
                def ext(node):
                    out = {}
                    for k2 in ("k", "v", "k_scale", "v_scale"):
                        if k2 in node:
                            sl = lax.dynamic_slice_in_dim(node[k2], b, 1,
                                                          axis=1)
                            out[k2] = lax.slice_in_dim(sl, 0, P, axis=2)
                    return out
                return self._walk_attn(cache, ext)
        else:
            raise ValueError(kind)

        donate = (0,) if (self._donate and kind != "extract") else ()
        in_sh = out_sh = None
        if self.mesh is not None:
            r = self._repl
            if kind == "reset":
                in_sh, out_sh = (self._cache_sh, r), self._cache_sh
            elif kind == "materialize":
                in_sh = (self._cache_sh, self._kv_slice_shardings(P), r)
                out_sh = self._cache_sh
            else:  # extract: the stored slice keeps the cache's layout,
                # so a later materialize of the same entry is copy-only
                in_sh = (self._cache_sh, r)
                out_sh = self._kv_slice_shardings(P)
        jitted = self._jit(fn, donate, in_sh, out_sh,
                           name=f"{kind}[{P}]")
        self._slot_jits[jkey] = jitted
        return jitted

    def _kv_slice_shardings(self, P: int):
        """``cache_shardings`` for the (nb, 1, P, heads, hd) KV-slice
        pytree the extract/materialize slot programs exchange with the
        prefix cache — heads stay on the model axis, the single batch
        row is replicated."""
        def ext(node):
            out = {}
            for k2 in ("k", "v", "k_scale", "v_scale"):
                if k2 in node:
                    out[k2] = jax.ShapeDtypeStruct(
                        node[k2].shape[:1] + (1, P) + node[k2].shape[3:],
                        node[k2].dtype)
            return out
        shapes = self._walk_attn(self.cache, ext)
        return self._SH.cache_shardings(shapes, self.mesh, self._b_axes)

    # ------------------------------------------------------------ #
    # paged provisioning (host allocator <-> device page pools)
    # ------------------------------------------------------------ #
    def _provision(self, slot: int, start: int, n: int) -> bool:
        """Make the pages behind positions [start, start+n) of ``slot``
        privately writable before a dispatched step (allocate missing
        pages, CoW-split shared ones). Exhaustion — real or injected via
        the ``page_alloc`` fault site — degrades instead of crashing
        (docs/robustness.md): reclaim LRU prefix entries, then poll (a
        finished slot may be sitting on pages), then preempt-and-requeue
        the lowest-priority victim; only a pool that genuinely cannot
        hold the live set raises. Returns False when degradation polled
        or preempted: the poll's shrink may have reclaimed headroom
        provisioned for *other* slots this round, so callers must
        rebuild their provisioning pass."""
        clean, polled = True, False
        while True:
            forced = self.faults.enabled and self._fire(
                "page_alloc", step=self._steps, slot=slot)
            if not forced:
                try:
                    copies = self._paged.prepare_write(slot, start, n)
                    break
                except paged_kv.PagePoolExhausted:
                    pass
            if self.prefix_cache is not None \
                    and self.prefix_cache.drop_lru():
                continue
            clean = False
            if not polled:
                polled = True
                self._poll()
                continue
            if self._preempt_one(exclude={slot}):
                continue
            if forced:
                # the injected exhaustion outlived every degradation
                # rung; unlike a real one it freed nothing, so consult
                # the actual pool before declaring the ladder dead
                try:
                    copies = self._paged.prepare_write(slot, start, n)
                    break
                except paged_kv.PagePoolExhausted:
                    pass
            raise RuntimeError(
                f"KV page pool exhausted mid-decode (slot {slot}, "
                f"positions [{start}, {start + n})) with no resumable "
                f"victim to preempt")
        if copies:
            self._copy_pages(copies)
        return clean

    def _copy_pages(self, copies) -> None:
        """Copy-on-write splits: duplicate the shared pool pages on
        device *before* the write that would have mutated them through
        an alias (one jitted gather/scatter per split count)."""
        src = jnp.asarray([s for s, _ in copies], jnp.int32)
        dst = jnp.asarray([d for _, d in copies], jnp.int32)
        self.cache = self._get_page_copy(len(copies))(self.cache, src, dst)

    def _get_page_copy(self, k: int):
        jkey = ("pagecopy", k)
        if jkey in self._slot_jits:
            return self._slot_jits[jkey]

        def fn(cache, src, dst):
            def cp(node):
                out = dict(node)
                for k2 in paged_kv.POOL_KEYS:
                    if k2 in node:
                        out[k2] = node[k2].at[:, dst].set(node[k2][:, src])
                return out
            return self._walk_attn(cache, cp)

        donate = (0,) if self._donate else ()
        in_sh = out_sh = None
        if self.mesh is not None:
            in_sh = (self._cache_sh, self._repl, self._repl)
            out_sh = self._cache_sh
        jitted = self._jit(fn, donate, in_sh, out_sh,
                           name=f"pagecopy[{k}]")
        self._slot_jits[jkey] = jitted
        return jitted

    def _push_block_tables(self) -> None:
        """Sync the host-authoritative block tables into every attention
        sub-cache's ``bt`` leaf (dirty-flagged). The host copy is a tiny
        int32 array; the next jitted step places it on device (and, on a
        mesh, to the bt sharding) as a normal input upload."""
        if not self._paged.dirty:
            return
        bt = self._paged.block_tables

        def setbt(node):
            out = dict(node)
            out["bt"] = np.broadcast_to(bt[None], out["bt"].shape)
            return out
        self.cache = self._walk_attn(self.cache, setbt)
        self._paged.dirty = False

    def _admit_fits(self, req: Request) -> bool:
        """Paged admission backpressure: admit only when the pool can
        hold the whole prompt plus the first decode write (conservative:
        a prefix hit would need less). Reclaims LRU prefix entries
        first; on failure the request simply stays queued (FIFO order is
        preserved — nothing behind it is admitted either)."""
        if not self.paged:
            return True
        need = self._eff_len(req)
        while not self._paged.can_admit(need):
            if self.prefix_cache is None \
                    or not self.prefix_cache.drop_lru():
                return False
        return True

    def _get_mixed(self):
        if self._mixed_fn is None:
            self._mixed_fn = self._build_mixed_step()
        return self._mixed_fn

    def _get_admit_chunk(self):
        if self._admit_chunk_fn is None:
            self._admit_chunk_fn = self._build_ngram_admit_chunk() \
                if self._ngram else self._build_admit_chunk()
        return self._admit_chunk_fn

    # ------------------------------------------------------------ #
    # scheduling
    # ------------------------------------------------------------ #
    def submit(self, req: Request) -> None:
        """Validate and enqueue. Malformed requests raise ``ValueError``
        here with the violated constraint spelled out — never a shape
        error deep inside a jitted program or a silently wedged slot."""
        self._validate(req)
        req.submitted_s = time.perf_counter()
        if req.deadline_s is not None:
            self._deadline_armed = True
        if self.recorder.enabled:
            self.recorder.on_submit(req)
        self.queue.append(req)
        self.requests[req.uid] = req
        self.responses[req.uid] = Response(uid=req.uid,
                                           prompt_len=len(req.prompt))

    def _validate(self, req: Request) -> None:
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"request {req.uid}: prompt must be a non-empty 1-D "
                f"token array, got shape {prompt.shape}")
        if prompt.dtype.kind not in "iu":
            raise ValueError(
                f"request {req.uid}: prompt must hold integer token "
                f"ids, got dtype {prompt.dtype}")
        if req.max_new_tokens <= 0:
            raise ValueError(
                f"request {req.uid}: max_new_tokens must be positive, "
                f"got {req.max_new_tokens}")
        if req.deadline_s is not None and req.deadline_s <= 0:
            raise ValueError(
                f"request {req.uid}: deadline_s must be positive, got "
                f"{req.deadline_s}")
        old = self.responses.get(req.uid)
        if old is not None and not old.finished:
            raise ValueError(
                f"request uid {req.uid} is already in flight")
        L = int(prompt.size)
        cap = self.kv_len - self._prefix
        if L > cap and (self.paged or not self.model.cfg.sliding_window):
            # a sliding-window ring legitimately serves longer prompts:
            # chunks wrap the ring and the window mask hides overwritten
            # context. Full-attention and paged caches cannot — the
            # overwrite would silently drop attended positions
            raise ValueError(
                f"request {req.uid}: prompt of {L} tokens exceeds the "
                f"KV capacity of {cap} (cache_len={self.cache_len}"
                + (f" minus a {self._prefix}-token frontend prefix"
                   if self._prefix else "")
                + "); raise cache_len or shorten the prompt")
        if self.model.encode_memory is not None \
                and req.embeddings is None:
            raise ValueError(
                f"request {req.uid}: encoder-decoder serving requires "
                "frontend frame embeddings on every request (the "
                "cross-attention memory is encoded at admission)")
        if req.embeddings is not None:
            fe = self.model.cfg.frontend
            if fe is None:
                raise ValueError(
                    f"request {req.uid}: embeddings were supplied but "
                    "the model has no frontend to consume them")
            if self.paged:
                raise ValueError(
                    "paged KV serving is token-only: frontend "
                    "embeddings have no paged admission program")
            emb = np.asarray(req.embeddings)
            if emb.shape != (fe.n_tokens, fe.d_embed):
                raise ValueError(
                    f"request {req.uid}: embeddings must have shape "
                    f"({fe.n_tokens}, {fe.d_embed}) to match the "
                    f"frontend, got {emb.shape}")

    def _free_slot(self) -> Optional[int]:
        admitting = self._admit.slot if self._admit is not None else -1
        for b in range(self.max_batch):
            if self.slots[b] is None and b != admitting:
                return b
        return None

    def _eff_len(self, req: Request) -> int:
        """Length of the request's *effective* token stream: the prompt
        plus any tokens generated before a preemption (replayed through
        admission on resume)."""
        resp = self.responses.get(req.uid)
        if resp is None or resp.finished:
            return len(req.prompt)
        return len(req.prompt) + len(resp.tokens)

    def _fill_free_slots(self) -> None:
        """Admission scheduler (FIFO head): every request starts a
        chunked admission (at most one in flight — 'advance one
        admitting request per step'). A head-of-queue request that
        outranks a live stream may preempt it when the slot table or
        page pool is short — the victim requeues right *behind* the
        displacing request (never ahead: that would livelock) and
        resumes later with its output unchanged."""
        while self.queue:
            if self._admit is not None:
                return                # one chunked admission at a time
            req = self.queue[0]
            b = self._free_slot()
            if b is None:
                if self._outranked(req) and self._preempt_one(
                        below=req.priority, requeue_pos=1):
                    continue
                return
            if self.model.extend_into_cache is None:
                # defensively unreachable: every family builds the
                # extend path (``Model.supports_extend`` is universally
                # True since the admission unification). Counted and
                # traced so a facade regression is observable —
                # ``fallback_admissions`` is asserted zero by the family
                # gate (benchmarks/check_families.py) — then contained
                # as a per-request error instead of wedging the queue
                self.queue.popleft()
                self._c_fallback.inc()
                if self.recorder.enabled:
                    self.recorder.on_admission(req, b, 0, "fallback")
                self._finish_request(req, "error", time.perf_counter())
                continue
            if not self._admit_fits(req):
                # page backpressure: the head waits, unless it
                # outranks a live stream whose pages can serve it
                if self._outranked(req) and self._preempt_one(
                        below=req.priority, requeue_pos=1):
                    continue
                return
            self.queue.popleft()
            self._start_chunked(req, b)

    def _outranked(self, req: Request) -> bool:
        """Cheap pre-check (no device sync) for priority displacement:
        some occupied slot runs at strictly lower priority than ``req``.
        A chunked admission in flight blocks displacement — the head
        could not admit into the freed slot anyway until it drains."""
        if self._admit is not None:
            return False
        return any(r is not None and r.priority < req.priority
                   for r in self.slots)

    def _start_chunked(self, req: Request, b: int) -> None:
        """Begin a chunked admission: probe the prefix cache, then either
        materialise the hit into slot ``b`` (one on-device
        dynamic_update_slice copy) or reset the slot row; the fused mixed
        step takes it from there, ``prefill_chunk`` tokens per step.

        A preempted request re-admits through this same path: its
        effective stream is the prompt plus the tokens it had already
        generated, replayed chunk by chunk — teacher-forcing the model
        through its own earlier output, so the token sampled on arming
        (and every one after) matches the unpreempted run."""
        if not req.started_s:                 # first admission, not a resume
            req.started_s = time.perf_counter()
            self._h_queue.observe(req.started_s - req.submitted_s)
        done = self.responses[req.uid].tokens
        eff = np.asarray(req.prompt, np.int32)
        if done:
            eff = np.concatenate([eff, np.asarray(done, np.int32)])
        adm = _Admission(req=req, slot=b, base=0, length=len(eff),
                         tokens=eff, n_done=len(done),
                         resumed=bool(done))
        base, kv, ent_len = 0, None, 0
        if self.prefix_cache is not None and req.embeddings is None:
            # embeddings requests never touch the prefix cache: the
            # token stream alone does not key the slot's content (two
            # requests with identical prompts but different frames
            # would alias), so neither lookup nor publication applies
            kv, ent_len, base = self.prefix_cache.lookup(eff)
            adm.base = base
        bb = jnp.int32(b)
        if self._ngram:
            # seed the drafter's corpus with the effective stream (tail-
            # truncated to the history capacity): prompt n-grams are the
            # richest match source for the first generated tokens
            H = int(self.hist.shape[1])
            n = min(len(eff), H)
            row = np.full((H,), -1, np.int32)
            row[:n] = eff[-n:]
            self.hist = self.hist.at[b].set(jnp.asarray(row))
            self.hist_len = self.hist_len.at[b].set(n)
        if self.paged:
            # a prefix hit is a page alias: point the fresh slot's block
            # table at the entry's pages (host refcount bump — zero KV
            # copies, no materialize program) and stamp pos/step for the
            # covered positions; a partial hit just takes fewer pages
            self._paged.release_slot(b)
            if kv is not None:
                self._paged.alias_prefix(b, kv[:base // self.page_size])
            self.cache = self._get_slot_fn(
                "reset", base if kv is not None else 0)(self.cache, bb)
            if self._draft_model is not None:
                self.draft_cache = self._get_slot_fn("reset")(
                    self.draft_cache, bb)
            self._depth_ub[b] = base
            self._admit = adm
            if self.recorder.enabled:
                self.recorder.on_admission(req, b, base, "chunked")
            return
        if kv is not None:
            if base < ent_len:
                # partial hit: take the first Q positions of the longer
                # stored entry eagerly, so the materialize program is
                # keyed on the hit length alone
                kv = jax.tree.map(lambda t: t[:, :, :base], kv)
            self.cache = self._get_slot_fn("materialize", base)(
                self.cache, kv, bb)
        else:
            self.cache = self._get_slot_fn("reset")(self.cache, bb)
            if self._draft_model is not None:
                self.draft_cache = self._get_slot_fn("reset")(
                    self.draft_cache, bb)
        if req.embeddings is not None:
            emb = jnp.asarray(req.embeddings)[None]
            if self.model.encode_memory is not None:
                # encdec: one-shot encode of the frontend frames; the
                # per-layer cross KV rows land in the slot and stay
                # frozen for the request's whole lifetime
                self.cache = self._get_encode_fn()(
                    self.params, emb, self.cache, bb)
            else:
                # vlm: the frontend prefix enters through the same
                # masked extend as text — one embedding chunk before
                # the token chunks
                self.cache = self._get_embed_chunk()(
                    self.params, emb, self.cache, bb)
                if self._draft_model is not None:
                    self.draft_cache = self._get_embed_chunk(True)(
                        self._draft_params, emb, self.draft_cache, bb)
        self._admit = adm
        if self.recorder.enabled:
            self.recorder.on_admission(req, b, base, "chunked")

    # ------------------------------------------------------------ #
    # lifecycle control: cancel / deadlines / preempt-and-requeue
    # (docs/robustness.md)
    # ------------------------------------------------------------ #
    def cancel(self, uid: int) -> bool:
        """Cancel a request in any live state — queued, mid-chunked-
        admission, or actively decoding. Tokens already produced stay in
        the response; the slot and (paged) its pages are released
        immediately and ``finish_reason`` reads ``"cancelled"``. Returns
        True if the request was live, False when it is unknown or had
        already finished."""
        req = self.requests.get(uid)
        resp = self.responses.get(uid)
        if req is None or resp is None or resp.finished:
            return False
        now = time.perf_counter()
        if req in self.queue:
            self.queue.remove(req)
            self._finish_request(req, "cancelled", now)
            self._c_cancel.inc()
            return True
        if self._admit is not None and self._admit.req.uid == uid:
            self._abort_admission("cancelled", now)
            self._c_cancel.inc()
            return True
        for b, r in enumerate(self.slots):
            if r is not None and r.uid == uid:
                self._poll()       # commit tokens already produced...
                if resp.finished:  # ...which may have finished it first
                    return False
                self._release_active_slot(b)
                self._finish_request(req, "cancelled",
                                     time.perf_counter())
                self._c_cancel.inc()
                return True
        return False

    def _finish_request(self, req: Request, reason: str,
                        now: float) -> None:
        resp = self.responses[req.uid]
        resp.finished = True
        resp.finish_reason = reason
        req.finished_s = now
        if self.recorder.enabled:
            self.recorder.on_finish(req, reason, now)

    def _release_active_slot(self, b: int) -> None:
        """Host+device teardown of an occupied slot, keeping its
        harvested tokens: deactivate the device row (masked steps then
        neither write KV nor advance it), detach the request, and — when
        paged — return its pages to the pool immediately."""
        self.active = self.active.at[b].set(False)
        self.slots[b] = None
        self._slot_start[b] = self._steps
        if self.paged:
            self._paged.release_slot(b)
            self._depth_ub[b] = 0

    def _abort_admission(self, reason: str, now: float) -> None:
        """Tear down the in-flight chunked admission (its slot was never
        attached, so only provisioned pages need releasing)."""
        adm, self._admit = self._admit, None
        if self.paged:
            self._paged.release_slot(adm.slot)
            self._depth_ub[adm.slot] = 0
        self._finish_request(adm.req, reason, now)

    def _enforce_deadlines(self, include_active: bool = True) -> None:
        """Finish every request past its absolute deadline with
        ``finish_reason="timeout"`` (keeping partial tokens). Runs at
        tick boundaries: before admission with ``include_active=False``
        (queued/admitting only — an active slot may hold tokens not yet
        harvested) and right after each poll with the full sweep."""
        now = time.perf_counter()
        for req in [r for r in self.queue if r.deadline_abs() <= now]:
            self.queue.remove(req)
            self._finish_request(req, "timeout", now)
            self._c_timeout.inc()
        if self._admit is not None \
                and self._admit.req.deadline_abs() <= now:
            self._abort_admission("timeout", now)
            self._c_timeout.inc()
        if not include_active:
            return
        for b, r in enumerate(self.slots):
            if r is not None and r.deadline_abs() <= now:
                self._release_active_slot(b)
                self._finish_request(r, "timeout", now)
                self._c_timeout.inc()

    def _select_victim(self, exclude=(),
                       below: Optional[int] = None) -> Optional[int]:
        """Pick the slot to preempt: lowest priority first, then latest
        deadline (no deadline counts as latest — most slack), then
        lowest slot index. Only streams that can actually resume qualify
        (the effective stream must still fit the KV ring with room to
        decode). ``below`` restricts victims to priorities strictly
        below it (priority-displacement admission)."""
        best = None
        for b, r in enumerate(self.slots):
            if r is None or b in exclude:
                continue
            if below is not None and r.priority >= below:
                continue
            if self._eff_len(r) + 1 > self.kv_len - self._prefix:
                continue           # too long to replay: not resumable
            key = (r.priority, -r.deadline_abs(), b)
            if best is None or key < best[0]:
                best = (key, b)
        return None if best is None else best[1]

    def _preempt_one(self, exclude=(), below: Optional[int] = None,
                     requeue_pos: int = 0) -> bool:
        """Preempt-and-requeue one victim stream. Polls first so every
        token the device already produced is committed, then releases
        the victim's slot and pages and requeues it (position 0 = queue
        front; 1 = right behind a displacing higher-priority head). On
        re-admission the generated prefix is replayed, so the resumed
        stream's output is identical to an unpreempted run (greedy).
        Returns False when no resumable victim exists."""
        self._poll()
        b = self._select_victim(exclude=exclude, below=below)
        if b is None:
            return False
        req = self.slots[b]
        self._release_active_slot(b)
        req.preemptions += 1
        self._c_preempt.inc()
        if self.recorder.enabled:
            self.recorder.on_preempt(req, b, time.perf_counter())
        pos = min(requeue_pos, len(self.queue))
        if pos <= 0:
            self.queue.appendleft(req)
        else:
            self.queue.insert(pos, req)
        return True

    def _fire(self, site: str, **ctx):
        """Ask the fault registry whether ``site`` should fail here
        (None when nothing is scheduled). Fired faults count into the
        ``faults_injected`` counter and the recorder's fault lane."""
        spec = self.faults.fire(site, **ctx)
        if spec is not None:
            self._c_faults.inc()
            if self.recorder.enabled:
                self.recorder.on_fault(site, self._steps,
                                       time.perf_counter())
        return spec

    def _set_poison(self, b: int) -> None:
        """Arm the ``nan_logits`` fault: poison row ``b``'s sampler
        logits for the next dispatched step. Input-only — the step
        programs never recompile."""
        self.poison = self._poison_zero.at[b % self.max_batch].set(
            float("nan"))

    def _clear_poison(self) -> None:
        self.poison = self._poison_zero

    # ------------------------------------------------------------ #
    # decode
    # ------------------------------------------------------------ #
    def step(self) -> None:
        """One engine step (plain, mixed, or speculative — plus, in spec
        mode, the admission chunk program). Pure device work: tokens,
        finish flags, and counters all stay on device; nothing is
        transferred."""
        t0 = time.perf_counter()
        n0 = self._steps
        poisoned = False
        if self.faults.enabled:
            spec = self._fire("slow_step", step=self._steps)
            if spec is not None and spec.delay_s > 0:
                time.sleep(spec.delay_s)
            spec = self._fire("nan_logits", step=self._steps)
            if spec is not None:
                self._set_poison(spec.slot or 0)
                poisoned = True
        if self._admit is None and self.queue \
                and self.model.extend_into_cache is not None:
            # pipeline the next admission mid-burst: the head-of-queue
            # request starts its chunked admission without waiting for
            # the burst boundary
            b = self._free_slot()
            if b is not None and self._admit_fits(self.queue[0]):
                with span("admit"):
                    self._start_chunked(self.queue.popleft(), b)
        adm = self._admit
        if self.spec_gamma:
            if adm is not None:
                with span("admit_chunk"):
                    self._step_admit_chunk(adm)
            if adm is None or self.active_slots:
                with span("spec"):
                    self._step_spec()
        elif adm is not None:
            with span("mixed"):
                self._step_mixed(adm)
        else:
            with span("plain"):
                self._step_plain()
        if poisoned:
            self._clear_poison()
        made = self._steps - n0
        dt = (time.perf_counter() - t0) / max(made, 1)
        for _ in range(made):
            self.step_times.append(dt)

    def _provision_decode_rows(self, per_row: int) -> bool:
        """Provision ``per_row`` decode writes for every occupied slot
        (an upper bound — rows the device already finished write
        nothing; the poll's shrink reclaims the overshoot). A degraded
        ``_provision`` (poll/preempt inside its ladder) may have shrunk
        headroom provisioned earlier in the same pass, so one False
        aborts the round; callers loop until a round runs clean."""
        for b, r in enumerate(self.slots):
            if r is not None:
                if not self._provision(b, self._depth_ub[b], per_row):
                    return False
                self._depth_ub[b] += per_row
        return True

    def _step_plain(self) -> None:
        if self.paged:
            while not self._provision_decode_rows(1):
                pass
            self._push_block_tables()
        (self.tokens, self.cache, self.remaining, self.active,
         self.key) = self._step_fn(self.params, self.cache,
                                   self.tokens, self.remaining,
                                   self.active, self.eos, self.key,
                                   self.poison)
        self._trace.append(self.tokens[:, 0])
        self._record_step("plain")

    def _step_spec(self) -> None:
        if self.paged:
            # a spec step writes up to gamma+1 positions per active row
            # (verify window); rollback keeps the committed prefix and
            # the poll's shrink drops pages past it
            while not self._provision_decode_rows(self.spec_gamma + 1):
                pass
            self._push_block_tables()
        if self._ngram:
            (self.tokens, block, n_emit, self.cache, self.hist,
             self.hist_len, self.remaining, self.active,
             self.key) = self._step_fn(
                self.params, self.cache, self.tokens, self.hist,
                self.hist_len, self.remaining, self.active, self.eos,
                self.key, self.poison)
        else:
            (self.tokens, self.prev, block, n_emit, self.cache,
             self.draft_cache, self.remaining, self.active,
             self.key) = self._step_fn(
                self.params, self._draft_params, self.cache,
                self.draft_cache, self.tokens, self.prev, self.remaining,
                self.active, self.eos, self.key, self.poison)
        self._trace.append((block, n_emit))
        self._record_step("spec")

    def _chunk_args(self, adm: _Admission) -> Tuple[np.ndarray, int, bool]:
        C = self.prefill_chunk
        n = min(C, adm.length - adm.base)
        chunk = np.zeros((C,), np.int32)
        chunk[:n] = adm.tokens[adm.base:adm.base + n]
        return chunk, n, adm.base + n >= adm.length

    def _step_mixed(self, adm: _Admission) -> None:
        """Dispatch the fused decode + prefill-chunk program."""
        chunk, n, last = self._chunk_args(adm)
        req = adm.req
        if self.paged:
            while True:
                if not self._provision_decode_rows(1):
                    continue
                if self._provision(adm.slot, adm.base, n):
                    break
            self._depth_ub[adm.slot] = adm.base + n
            self._push_block_tables()
        (self.tokens, block, n_emit, self.cache, self.remaining,
         self.active, self.eos, self.key) = self._get_mixed()(
            self.params, self.cache, self.tokens, self.remaining,
            self.active, self.eos, self.key, jnp.asarray(chunk),
            jnp.int32(adm.slot), jnp.int32(n), jnp.asarray(bool(last)),
            jnp.int32(req.max_new_tokens - adm.n_done),
            jnp.int32(-1 if req.eos_id is None else int(req.eos_id)),
            self.poison)
        self._trace.append((block, n_emit))
        if self.recorder.enabled:
            self.recorder.on_chunk(req, adm.slot, adm.base, adm.base + n,
                                   bool(last))
        adm.base += n
        if last:
            self._complete_admission(adm)
        self._record_step("mixed")

    def _step_admit_chunk(self, adm: _Admission) -> None:
        """Dispatch the spec-mode admission chunk program (target +
        lagging draft for model drafts; target + history append for the
        n-gram drafter), then let the spec step decode the other
        slots."""
        chunk, n, last = self._chunk_args(adm)
        req = adm.req
        if self.paged:
            # target chunk only — the draft cache stays contiguous; the
            # spec step dispatched right after provisions decode rows
            # (including a slot this chunk just armed)
            while not self._provision(adm.slot, adm.base, n):
                pass
            self._depth_ub[adm.slot] = adm.base + n
            self._push_block_tables()
        if self._ngram:
            (self.tokens, block, n_emit, self.cache, self.hist,
             self.hist_len, self.remaining, self.active, self.eos,
             self.key) = self._get_admit_chunk()(
                self.params, self.cache, self.tokens, self.hist,
                self.hist_len, self.remaining, self.active, self.eos,
                self.key, jnp.asarray(chunk), jnp.int32(adm.slot),
                jnp.int32(n), jnp.asarray(bool(last)),
                jnp.int32(req.max_new_tokens - adm.n_done),
                jnp.int32(-1 if req.eos_id is None else int(req.eos_id)),
                self.poison)
        else:
            d_n = max(0, min(n, adm.length - 1 - adm.base))
            (self.tokens, self.prev, block, n_emit, self.cache,
             self.draft_cache, self.remaining, self.active, self.eos,
             self.key) = self._get_admit_chunk()(
                self.params, self._draft_params, self.cache,
                self.draft_cache, self.tokens, self.prev, self.remaining,
                self.active, self.eos, self.key, jnp.asarray(chunk),
                jnp.int32(adm.slot), jnp.int32(n), jnp.int32(d_n),
                jnp.asarray(bool(last)),
                jnp.int32(req.max_new_tokens - adm.n_done),
                jnp.int32(-1 if req.eos_id is None else int(req.eos_id)),
                jnp.int32(int(adm.tokens[-1])), self.poison)
        self._trace.append((block, n_emit))
        if self.recorder.enabled:
            self.recorder.on_chunk(req, adm.slot, adm.base, adm.base + n,
                                   bool(last))
        adm.base += n
        if last:
            self._complete_admission(adm)
        self._record_step("admit")

    def _complete_admission(self, adm: _Admission) -> None:
        """The chunk just dispatched covers the end of the prompt: the
        device sampled the first token and armed the slot in-program.
        Host-side: attach the request to the slot (its trace entries
        start at this step), queue the TTFT stamp for the next sync, and
        snapshot the prompt's prefix KV for reuse before any decode step
        can wrap the ring over it."""
        b = adm.slot
        self.slots[b] = adm.req
        self._slot_start[b] = self._steps
        self._await_first.append(adm.req)
        self._c_admissions.inc()
        self._admit = None
        # resumed admissions skip publication: their prompt prefix was
        # published (if wanted) on first admission, and the effective
        # stream's tail is request-specific output, not a shared prefix.
        # Embeddings requests skip it too — the token stream alone does
        # not key the slot's content (see _start_chunked)
        if self.prefix_cache is not None and not adm.resumed \
                and adm.req.embeddings is None:
            P = self.prefix_cache.wants(adm.req.prompt)
            if P and P <= self.kv_len:
                if self.paged:
                    # publication is a refcount pin on the slot's own
                    # pages — no extract program, no KV movement
                    pages = self._paged.snapshot_prefix(b, P)
                    self.prefix_cache.insert(adm.req.prompt, P, pages)
                else:
                    kv = self._get_slot_fn("extract", P)(self.cache,
                                                         jnp.int32(b))
                    self.prefix_cache.insert(adm.req.prompt, P, kv)

    def _stamp_first_tokens(self, now: float) -> None:
        for req in self._await_first:
            if not req.first_token_s:
                req.first_token_s = now
                self._h_ttft.observe(now - req.submitted_s)
                if self.recorder.enabled:
                    self.recorder.on_first_token(req, now)
        self._await_first.clear()

    def _poll(self) -> None:
        """The periodic host sync: harvest each occupied slot's new token
        block (one bounded transfer per entry, sliced on device) and
        prune the trace. Only the unconsumed suffix of the trace is ever
        touched, so poll cost is bounded by the tokens produced since the
        previous poll — it does not grow with trace (or sequence) length.
        Finish detection replays the device's own stop conditions on the
        harvested tokens, so host and device slot state agree by
        construction."""
        if not self._trace:
            self._sample_occupancy()
            return
        occupied = [(b, self._slot_start[b] - self._trace_base)
                    for b, r in enumerate(self.slots) if r is not None]
        starts = [s for _, s in occupied if s < len(self._trace)]
        if starts:
            lo = min(starts)
            suffix = self._trace[lo:]
            jax.block_until_ready(suffix[-1])
            # host-side conversion, entry by entry: each is a bounded
            # (B,)/(B, W) transfer. A device-side jnp.stack here would
            # trigger one XLA compile per distinct suffix length — a
            # recurring ~100ms latency spike that dwarfed the transfers
            # it saved. Entries are heterogeneous (plain (B,) vectors,
            # mixed/admission W=1 pairs, speculative W=gamma+1 pairs),
            # so they are normalised to (block, count) per entry.
            host = []
            for t in suffix:
                if isinstance(t, tuple):
                    host.append((np.asarray(t[0]), np.asarray(t[1])))
                else:
                    host.append((np.asarray(t)[:, None], None))
            for b, start in occupied:
                s = start - lo
                if s >= len(host):
                    continue                           # armed post-trace
                col: List[int] = []
                gaps: List[Optional[float]] = []
                for off in range(s, len(host)):
                    blk, cnt = host[off]
                    g = self._trace_base + lo + off    # global step index
                    w = g - self._step_wall_base
                    gap = None
                    if 0 < w < len(self._step_wall):
                        gap = self._step_wall[w] - self._step_wall[w - 1]
                    if cnt is None:
                        col.append(int(blk[b, 0]))
                        gaps.append(gap)
                        continue
                    c = int(cnt[b])
                    if self.spec_gamma \
                            and blk.shape[1] == self.spec_gamma + 1:
                        self._c_spec_emitted.inc(c)
                        self._c_spec_steps.inc(int(c > 0))
                    for tok in blk[b, :c]:
                        col.append(int(tok))
                        gaps.append(gap / c if gap is not None else None)
                self._harvest(b, col, gaps)
        # every occupied slot has now consumed the whole trace
        keep_from = min((self._slot_start[b] for b, r
                         in enumerate(self.slots) if r is not None),
                        default=self._steps)
        drop = keep_from - self._trace_base
        if drop > 0:
            del self._trace[:drop]
            self._trace_base = keep_from
        # prune wall stamps consumed by every slot (keep one entry before
        # the oldest live step: its gap needs the predecessor's stamp)
        wdrop = keep_from - 1 - self._step_wall_base
        if wdrop > 0:
            del self._step_wall[:wdrop]
            self._step_wall_base = keep_from - 1
        if self.paged:
            # the harvested trace reveals each live slot's true committed
            # depth (prompt + generated - 1 pending): release the pages
            # the provisioning upper bound ran ahead by
            for b, r in enumerate(self.slots):
                if r is not None:
                    nt = len(self.responses[r.uid].tokens)
                    if nt:
                        depth = len(r.prompt) + nt - 1
                        self._paged.shrink(b, depth)
                        self._depth_ub[b] = depth
            if __debug__:
                entries = None
                if self.prefix_cache is not None:
                    entries = [e["kv"] for e
                               in self.prefix_cache._entries.values()]
                self._paged.check_invariants(entries)
        self._sample_occupancy()

    def _sample_occupancy(self) -> None:
        """Refresh the poll-time gauges (live occupancy, pool pressure,
        KV bytes per live token) and feed the recorder's counter lanes.
        Host arithmetic only — the page allocator and slot table are
        host-authoritative, nothing is read back from device."""
        m = self.metrics
        active = self.active_slots
        m.gauge("active_slots").set(active)
        m.gauge("queue_depth").set(len(self.queue))
        pool: Dict[str, float] = {}
        if self.paged:
            ps = self._paged.stats()
            pool["kv_pages_live"] = ps["kv_pages_live"]
            pool["kv_pages_free"] = ps["kv_pages_free"]
            m.gauge("kv_pages_free").set(ps["kv_pages_free"])
            live_tok = ps["kv_pages_live"] * self.page_size
        else:
            live_tok = sum(
                len(r.prompt) + len(self.responses[r.uid].tokens)
                for r in self.slots if r is not None)
        if live_tok:
            if self._kv_nbytes is None:
                self._kv_nbytes = sum(
                    x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(self.cache))
            m.gauge("kv_bytes_per_live_token").set(
                self._kv_nbytes / live_tok)
        if self.recorder.enabled:
            self.recorder.on_poll(time.perf_counter(), active, pool)

    def _harvest(self, b: int, col: List[int],
                 gaps: Optional[List[Optional[float]]] = None) -> None:
        """Append slot ``b``'s sampled tokens host-side. The device kept
        decoding after the slot finished (it only learns at the next poll),
        so cut the column at the true stop condition — the same condition
        the fused step applied on device. ``gaps`` carries each token's
        inter-step wall gap for the ITL percentile stats (the first token
        of a request is TTFT, not ITL, and is skipped)."""
        req = self.slots[b]
        resp = self.responses[req.uid]
        done = False
        if gaps is None:
            gaps = [None] * len(col)
        n0 = len(resp.tokens)
        for tok, gap in zip(col, gaps):
            tok = int(tok)
            if tok == ERR_TOKEN:
                # the on-device NaN/inf guard tripped for this row: the
                # sentinel is not a real token — finish with "error";
                # everything harvested before it stands
                resp.finish_reason = "error"
                self._c_errors.inc()
                done = True
                break
            if resp.tokens and gap is not None:
                self._h_itl.observe(gap)
            resp.tokens.append(tok)
            if (req.eos_id is not None and tok == req.eos_id):
                resp.finish_reason = "eos"
                done = True
                break
            if len(resp.tokens) >= req.max_new_tokens:
                resp.finish_reason = "length"
                done = True
                break
        appended = len(resp.tokens) - n0
        if appended:
            self._c_tokens.inc(appended)
            if self.recorder.enabled:
                self.recorder.on_emit(req, b, appended,
                                      time.perf_counter())
        if done:
            resp.finished = True
            req.finished_s = time.perf_counter()
            if self.recorder.enabled:
                self.recorder.on_finish(req, resp.finish_reason,
                                        req.finished_s)
            self.slots[b] = None
            if self.paged:
                # the stream's pages return to the free list; pages a
                # prefix entry pinned stay live through the entry's own
                # references until it is evicted
                self._paged.release_slot(b)
                self._depth_ub[b] = 0
        else:
            self._slot_start[b] = self._steps              # all consumed

    @property
    def active_slots(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def has_work(self) -> bool:
        return bool(self.queue or self.active_slots
                    or self._admit is not None)

    def tick(self, steps: Optional[int] = None) -> int:
        """Advance the engine by one admission pass, one burst of up to
        ``steps`` fused steps (default ``sync_every``), and one poll.
        Returns the number of steps run — the open-loop driving primitive
        for callers that interleave submissions with service
        (``benchmarks/bench_load.py``); ``run`` is a drain loop on top."""
        k = self.sync_every if steps is None else max(1, steps)
        if self._deadline_armed:
            self._enforce_deadlines(include_active=False)
        with span("admit"):
            self._fill_free_slots()
        if not (self.active_slots or self._admit is not None):
            with span("poll"):
                self._poll()
            if self._deadline_armed:
                self._enforce_deadlines()
            return 0
        t0 = time.perf_counter()
        # steps run outside tick (raw .step() calls) have no wall stamp;
        # backfill so gap indexing stays aligned with the step counter
        while len(self._step_wall) + self._step_wall_base < self._steps:
            self._step_wall.append(t0)
        n0 = len(self.step_times)
        ran0 = self._steps
        while self._steps - ran0 < k:
            first_ever = self._steps == 0
            before = len(self.step_times)
            self.step()
            if first_ever:
                # isolate the fused-step compile in its own step_times
                # entries (latency_stats drops the first) so burst
                # averaging below never smears it over steady state
                jax.block_until_ready(self.tokens)
                now = time.perf_counter()
                made = len(self.step_times) - before
                for i in range(before, len(self.step_times)):
                    self.step_times[i] = (now - t0) / made
                self._step_wall.extend([now] * made)
                t0 = now
                n0 = len(self.step_times)
        with span("sync"):
            jax.block_until_ready(self.tokens)
        t1 = time.perf_counter()
        m = len(self.step_times) - n0
        if m > 0:
            # burst-average: per-step dispatch time plus its share of sync
            dt = (t1 - t0) / m
            for i in range(n0, len(self.step_times)):
                self.step_times[i] = dt
            for i in range(m):
                self._step_wall.append(t0 + dt * (i + 1))
        self._stamp_first_tokens(t1)
        with span("poll"):
            self._poll()
        if self._deadline_armed:
            self._enforce_deadlines()
        self._maybe_profile()
        return self._steps - ran0

    def run(self, max_steps: int = 100_000,
            sync_every: Optional[int] = None) -> Dict[int, Response]:
        k = self.sync_every if sync_every is None else max(1, sync_every)
        steps = 0
        while self.has_work and steps < max_steps:
            made = self.tick(min(k, max_steps - steps))
            steps += made
            if made == 0 and not self.has_work:
                break
        self._poll()   # partial tokens for interrupted slots
        self._stop_profile()
        return self.responses

    def reset_stats(self) -> None:
        """Forget timing and finished-request history (compiled programs,
        cache state and prefix-cache *entries* are kept) — for benchmarks
        that warm an engine up and then measure a fresh stream. Also
        *arms* the recompile watchdog: the warm-then-measure boundary is
        where steady state begins, so any later XLA compile raises
        ``telemetry.RecompileWarning``."""
        self.metrics.reset()
        self._kinds_base = self._steps
        self._drop_compile_step = False
        for uid in [u for u, r in self.responses.items() if r.finished]:
            del self.responses[uid]
            del self.requests[uid]
        if self.prefix_cache is not None:
            pc = self.prefix_cache
            pc.hits = pc.misses = pc.hit_tokens = pc.evictions = 0
        if self.paged:
            pk = self._paged
            pk.alias_pages = pk.cow_splits = pk.pages_released = 0
        self._watchdog.arm()

    def mark_steady(self) -> None:
        """Arm the recompile watchdog without touching stats: every
        later XLA compile is treated as a steady-state regression
        (structured ``RecompileWarning`` + ``steady_compiles`` counter).
        ``reset_stats()`` arms it implicitly."""
        self._watchdog.arm()

    # ------------------------------------------------------------ #
    # trace / profiler export
    # ------------------------------------------------------------ #
    def export_trace(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Export the recorded request-lifecycle trace as a Chrome
        trace-event object (written as JSON to ``path`` when given) —
        see ``serving/tracing.py`` for the lane layout. Requires a
        tracing recorder (``Engine(..., recorder=True)``)."""
        exp = getattr(self.recorder, "export_chrome_trace", None)
        if exp is None:
            raise RuntimeError(
                "export_trace needs a tracing recorder: build the "
                "engine with recorder=True (or a tracing.Tracer)")
        return exp(path)

    def _maybe_profile(self) -> None:
        """Drive the optional ``jax.profiler`` device-trace window
        (``trace_dir=``): start after the first step (so the first
        compile doesn't dominate the capture), stop after
        ``profile_steps`` steps. A trace was asked for, so a profiler
        that fails to start or stop fails the run."""
        if not self._trace_dir or self._prof_done:
            return
        if not self._prof_on:
            if self._steps >= 1:
                jax.profiler.start_trace(self._trace_dir)
                self._prof_on = True
                self._prof_base = self._steps
        elif self._steps - self._prof_base >= self._profile_steps:
            self._stop_profile()

    def _stop_profile(self) -> None:
        if self._prof_on:
            self._prof_on = False
            self._prof_done = True
            jax.block_until_ready(self.tokens)
            jax.profiler.stop_trace()

    # ------------------------------------------------------------ #
    @staticmethod
    def _pct_stats(stats: Dict[str, float], prefix: str, samples,
                   pcts: Tuple[int, ...]) -> None:
        """Delegates to :func:`telemetry.pct_stats` — the one percentile
        implementation (same keys, same empty-sample omission contract);
        kept as a method for callers that reach it through the engine."""
        telemetry.pct_stats(stats, prefix, samples, pcts)

    def latency_stats(self) -> Dict[str, float]:
        """Latency summary. The ``decode_ms_*`` / ``queue_wait_ms_*`` /
        ``ttft_ms_*`` / ``itl_ms_*`` keys are present only when the
        corresponding stream has at least one sample — a fresh (or
        reset) engine reports the counters alone."""
        drop = 1 if self._drop_compile_step else 0
        finished = [r for r in self.responses.values() if r.finished]
        stats: Dict[str, float] = {
            "n_finished": len(finished),
            "tokens_generated": sum(r.n_generated for r in finished),
            "fallback_admissions": self._c_fallback.value,
            "decode_steps": self._steps,
            "prefill_chunk": self.prefill_chunk,
            "chunked_admissions": self._c_admissions.value,
            "preemptions": self._c_preempt.value,
            "timeouts": self._c_timeout.value,
            "cancellations": self._c_cancel.value,
            "slot_errors": self._c_errors.value,
            "faults_injected": self._c_faults.value,
        }
        telemetry.pct_stats(stats, "decode_ms", self.step_times[drop:],
                            (50, 99))
        telemetry.pct_stats(stats, "queue_wait_ms", self._h_queue.values,
                            (50, 95, 99))
        telemetry.pct_stats(stats, "ttft_ms", self._h_ttft.values,
                            (50, 95, 99))
        telemetry.pct_stats(stats, "itl_ms", self._h_itl.values,
                            (50, 95, 99))
        if self.prefix_cache is not None:
            stats.update(self.prefix_cache.stats())
        if self.paged:
            stats.update(self._paged.stats())
        if self.spec_gamma:
            # every harvested (step, active slot) pair emitted 1 + n_acc
            # tokens; acceptance rate = mean(n_acc) / gamma
            emitted = self._c_spec_emitted.value
            steps = self._c_spec_steps.value
            n = max(steps, 1)
            stats["spec_gamma"] = self.spec_gamma
            stats["spec_tokens_per_step"] = emitted / n
            stats["spec_acceptance_rate"] = \
                (emitted - steps) / (self.spec_gamma * n)
        return stats
