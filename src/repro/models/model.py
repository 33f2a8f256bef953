"""Unified model facade: ``build(cfg)`` returns a ``Model`` exposing
init / train_loss / prefill / decode_step / make_cache / input_specs for
every assigned family. This is the object the service layer wraps."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec as ED
from repro.models import transformer as T


def lm_loss(logits, targets, mask=None):
    """Mean next-token cross entropy. logits: (B, L, V) f32."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@dataclasses.dataclass(frozen=True)
class Model:
    """Facade contract (what the serving engine relies on):

    * ``make_cache(batch, cache_len)`` leaves are ``[blocks, batch, ...]``
      with a *per-row* ``step`` in attention sub-caches, so slots at
      different sequence depths share one batched cache.
    * ``prefill(params, batch, cache)`` accepts an optional
      ``batch["length"]`` (B,) int32 of valid text tokens when
      ``batch["tokens"]`` is right-padded to a bucket length; the cache is
      written only for valid positions and logits are taken at the last
      valid position per row.
    * ``decode_step(params, token, cache)`` advances every row by one token
      at that row's own offset.
    * ``extend_into_cache(params, tokens, cache, lengths, last_only)``
      is the unified masked multi-token cached forward at per-row
      offsets, supported by EVERY family: row b consumes
      ``tokens[b, :lengths[b]]`` and advances its cache step by
      ``lengths[b]`` (0 = untouched; lengths=None = all rows advance by
      T). Speculative verify, chunked prefill and the serving engine's
      fused mixed (decode + prefill-chunk) step all share this one code
      path. Attention rings use the masked scatter, SSM mixers the
      ``ssd_extend`` recurrence from the cached state (token-serial for
      decode rows and verify windows, the chunked dual form for
      admission chunks on the chip), encdec the decoder ring with
      prefill-frozen cross-attention memory.
    * ``verify_step(params, tokens, cache)`` is extend with the full
      window (every row advances by T) — the speculative-decoding verify
      pass — and ``rollback(cache, steps)`` moves every sub-cache back
      to the accepted depth. Attention caches rewind by rewriting
      ``step`` (causal masking hides the speculated tail until its slots
      are rewritten); SSM sub-caches restore the checkpoint taken before
      the most recent advance, so when ``rollback_needs_replay`` is set
      the caller must roll back to the *pre-verify* depth and re-extend
      the accepted tokens (the engine's replay flow).
    * ``encode_memory(params, frames)`` (encdec only) encodes frontend
      frames once and returns the per-layer cross-attention KV rows the
      engine writes into a batch slot at admission.
    """

    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    train_loss: Callable[..., Any]        # (params, batch) -> (loss, metrics)
    prefill: Callable[..., Any]           # (params, batch, cache) -> (logits, cache)
    decode_step: Callable[..., Any]       # (params, token, cache) -> (logits, cache)
    make_cache: Callable[..., Any]        # (batch, cache_len) -> cache pytree
    cache_steps: Callable[..., Any] = lambda cache: None  # cache -> (B,) depths
    verify_step: Optional[Callable[..., Any]] = None  # (params, tokens (B,T), cache)
    rollback: Optional[Callable[..., Any]] = None     # (cache, steps (B,)) -> cache
    extend_into_cache: Optional[Callable[..., Any]] = None
    # (params, tokens (B,T), cache, lengths (B,), last_only) -> (logits, cache)
    make_paged_cache: Optional[Callable[..., Any]] = None
    # (batch, cache_len, *, page_size, num_pages) -> paged cache pytree
    encode_memory: Optional[Callable[..., Any]] = None
    # (params, frames (B, T_src, d_embed)) -> (xk, xv) per-layer cross KV
    rollback_needs_replay: bool = False
    # True for stacks with recurrent (SSM) state: rollback restores the
    # pre-advance checkpoint, so speculative accept must re-extend the
    # accepted tokens instead of just rewinding ``step``

    @property
    def supports_paged(self) -> bool:
        """Paged KV pools are attention-only — SSM recurrent state has
        no per-position storage to page."""
        return self.make_paged_cache is not None

    @property
    def supports_speculative(self) -> bool:
        return self.verify_step is not None

    @property
    def supports_extend(self) -> bool:
        """Whether the stack supports the per-row-length multi-token
        cached forward (chunked prefill / fused mixed step). True for
        every family — this is the one admission path the engine has."""
        return self.extend_into_cache is not None

    def cache_len(self, shape: ShapeConfig) -> int:
        if self.cfg.sliding_window:
            return min(shape.seq_len, self.cfg.sliding_window)
        return shape.seq_len

    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for one step at the given shape."""
        cfg = self.cfg
        B = shape.global_batch
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        fe = cfg.frontend

        if shape.mode == "train":
            L_tok = shape.seq_len - (fe.n_tokens if fe and cfg.family == "vlm"
                                     else 0)
            batch = {"tokens": sds((B, L_tok), i32)}
            if fe is not None:
                batch["embeddings"] = sds((B, fe.n_tokens, fe.d_embed),
                                          cfg.act_dtype)
            return {"batch": batch}

        if shape.mode == "prefill":
            L_tok = shape.seq_len - (fe.n_tokens if fe and cfg.family == "vlm"
                                     else 0)
            batch = {"tokens": sds((B, L_tok), i32)}
            if fe is not None:
                batch["embeddings"] = sds((B, fe.n_tokens, fe.d_embed),
                                          cfg.act_dtype)
            cache = jax.eval_shape(
                lambda: self.make_cache(B, self.cache_len(shape)))
            return {"batch": batch, "cache": cache}

        # decode: one token against a cache of seq_len
        cache = jax.eval_shape(
            lambda: self.make_cache(B, self.cache_len(shape)))
        return {"token": sds((B, 1), i32), "cache": cache}


# --------------------------------------------------------------------- #
def build(cfg: ModelConfig) -> Model:
    if cfg.family == "encdec":
        return _build_encdec(cfg)
    return _build_decoder(cfg)


def _build_decoder(cfg: ModelConfig) -> Model:
    fe = cfg.frontend

    def train_loss(params, batch):
        tokens = batch["tokens"]
        emb = batch.get("embeddings") if fe is not None else None
        logits, aux = T.forward_train(params, cfg, tokens, emb)
        P = fe.n_tokens if (fe is not None and cfg.family == "vlm") else 0
        text_logits = logits[:, P:][:, :-1]
        loss = lm_loss(text_logits, tokens[:, 1:]) + aux
        return loss, {"lm_loss": loss - aux, "aux_loss": aux}

    def prefill_fn(params, batch, cache):
        emb = batch.get("embeddings") if fe is not None else None
        length = batch.get("length")
        if length is not None and emb is not None and cfg.family == "vlm":
            # length counts text tokens; the cache also holds the frontend
            # prefix, so the total valid depth includes it
            length = length + fe.n_tokens
        return T.prefill(params, cfg, batch["tokens"], cache, emb,
                         length=length)

    def decode_fn(params, token, cache):
        return T.decode_step(params, cfg, token, cache)

    def make_cache(batch, cache_len, dtype=None):
        return T.make_cache(cfg, batch, cache_len, dtype)

    def verify_fn(params, tokens, cache):
        return T.verify_step(params, cfg, tokens, cache)

    def extend_fn(params, tokens, cache, lengths=None, last_only=False,
                  embeddings=None):
        return T.extend_step(params, cfg, tokens, cache, lengths=lengths,
                             last_only=last_only, embeddings=embeddings)

    def make_paged(batch, cache_len, *, page_size, num_pages, dtype=None):
        return T.make_paged_cache(cfg, batch, cache_len,
                                  page_size=page_size, num_pages=num_pages,
                                  dtype=dtype)

    # extend/verify/rollback are universal; paged pools stay attention-
    # only (SSM recurrent state has no per-position storage to page).
    # Recurrent mixers roll back by checkpoint restore, which commits
    # speculation through the engine's replay flow.
    attn_only = all(m == "attn" for m, _ in T.block_spec(cfg))
    has_ssm = any(m == "ssm" for m, _ in T.block_spec(cfg))

    return Model(cfg=cfg, init=lambda k: T.init_transformer(k, cfg),
                 train_loss=train_loss, prefill=prefill_fn,
                 decode_step=decode_fn, make_cache=make_cache,
                 cache_steps=T.cache_steps,
                 verify_step=verify_fn,
                 rollback=T.set_cache_steps,
                 extend_into_cache=extend_fn,
                 make_paged_cache=make_paged if attn_only else None,
                 rollback_needs_replay=has_ssm)


def _build_encdec(cfg: ModelConfig) -> Model:
    fe = cfg.frontend

    def train_loss(params, batch):
        logits, aux = ED.forward_train(params, cfg, batch["tokens"],
                                       batch["embeddings"])
        loss = lm_loss(logits[:, :-1], batch["tokens"][:, 1:]) + aux
        return loss, {"lm_loss": loss - aux, "aux_loss": aux}

    def prefill_fn(params, batch, cache):
        return ED.prefill(params, cfg, batch["tokens"], cache,
                          batch["embeddings"], length=batch.get("length"))

    def decode_fn(params, token, cache):
        return ED.decode_step(params, cfg, token, cache)

    def make_cache(batch, cache_len, dtype=None):
        return ED.make_encdec_cache(cfg, batch, cache_len, fe.n_tokens,
                                    dtype)

    def cache_steps(cache):
        return cache["self"]["step"][0]

    def extend_fn(params, tokens, cache, lengths=None, last_only=False):
        return ED.extend_step(params, cfg, tokens, cache, lengths=lengths,
                              last_only=last_only)

    def verify_fn(params, tokens, cache):
        return ED.extend_step(params, cfg, tokens, cache)

    def encode_memory(params, frames):
        memory = ED.encode(params, cfg, frames)
        return ED.cross_kv_all(params, cfg, memory)

    return Model(cfg=cfg, init=lambda k: ED.init_encdec(k, cfg),
                 train_loss=train_loss, prefill=prefill_fn,
                 decode_step=decode_fn, make_cache=make_cache,
                 cache_steps=cache_steps,
                 verify_step=verify_fn,
                 rollback=T.set_cache_steps,
                 extend_into_cache=extend_fn,
                 encode_memory=encode_memory)
