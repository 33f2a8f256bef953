"""Decoder stacks for all assigned families (dense / moe / ssm / hybrid /
vlm), built scan-over-layers so HLO size is depth-independent.

A *block* is the scan unit: one sublayer for homogeneous stacks, or a
super-block (e.g. Jamba's [1 attn + 7 mamba] with alternating MoE/MLP FFNs)
for hybrids. Params for all blocks are stacked on a leading axis via
``jax.vmap`` over init keys.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distribution.sharding import shard_activation
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


# --------------------------------------------------------------------- #
# block structure
# --------------------------------------------------------------------- #
def block_spec(cfg: ModelConfig) -> List[Tuple[str, Optional[str]]]:
    """Returns [(mixer, ffn)] per sublayer of the scan unit."""
    if cfg.family in ("dense", "vlm"):
        return [("attn", "mlp")]
    if cfg.family == "moe":
        return [("attn", "moe")]
    if cfg.family == "ssm":
        return [("ssm", None)]
    if cfg.family == "hybrid":
        every = max(1, cfg.moe.moe_every) if cfg.moe else 0
        spec = []
        for i in range(cfg.attn_every):
            mixer = "attn" if i == 0 else "ssm"
            ffn = "moe" if (cfg.moe and i % every == 0) else "mlp"
            spec.append((mixer, ffn))
        return spec
    raise ValueError(f"unknown family {cfg.family}")


def n_blocks(cfg: ModelConfig) -> int:
    k = len(block_spec(cfg))
    assert cfg.n_layers % k == 0, (cfg.n_layers, k)
    return cfg.n_layers // k


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #
def init_block(key, cfg: ModelConfig):
    spec = block_spec(cfg)
    p: Dict[str, Any] = {}
    keys = jax.random.split(key, len(spec))
    for i, (mixer, ffn) in enumerate(spec):
        sk = jax.random.split(keys[i], 4)
        sub: Dict[str, Any] = {"ln1": L.init_rms_norm(cfg.d_model, cfg.p_dtype)}
        if mixer == "attn":
            sub["attn"] = L.init_attention(sk[0], cfg)
        else:
            sub["ssm"] = S.init_ssm(sk[1], cfg)
        if ffn is not None:
            sub["ln2"] = L.init_rms_norm(cfg.d_model, cfg.p_dtype)
            if ffn == "mlp":
                sub["mlp"] = L.init_mlp(sk[2], cfg.d_model, cfg.d_ff,
                                        cfg.p_dtype)
            else:
                sub["moe"] = M.init_moe(sk[3], cfg)
        p[f"sub{i}"] = sub
    return p


def init_stack(key, cfg: ModelConfig):
    nb = n_blocks(cfg)
    keys = jax.random.split(key, nb)
    return jax.vmap(lambda k: init_block(k, cfg))(keys)


def init_transformer(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    p = {
        "embed": L.init_embedding(ks[0], cfg.vocab, cfg.d_model, cfg.p_dtype),
        "blocks": init_stack(ks[1], cfg),
        "ln_f": L.init_rms_norm(cfg.d_model, cfg.p_dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.init_linear(ks[2], cfg.d_model, cfg.vocab,
                                     cfg.p_dtype)
    if cfg.frontend is not None:
        p["frontend_proj"] = L.init_linear(ks[3], cfg.frontend.d_embed,
                                           cfg.d_model, cfg.p_dtype)
    return p


# --------------------------------------------------------------------- #
# cache
# --------------------------------------------------------------------- #
def _block_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    spec = block_spec(cfg)
    c: Dict[str, Any] = {}
    for i, (mixer, _) in enumerate(spec):
        if mixer == "attn":
            S_len = min(cache_len, cfg.sliding_window) if cfg.sliding_window \
                else cache_len
            c[f"sub{i}"] = L.make_kv_cache(batch, S_len, cfg.n_kv_heads,
                                           cfg.hd, dtype,
                                           quant=cfg.kv_quant)
        else:
            c[f"sub{i}"] = S.make_ssm_cache(batch, cfg, dtype)
    return c


def make_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None):
    """Stacked (per-block) decode cache pytree.

    Layout contract (relied on by the serving engine): every leaf carries
    the scanned block axis first and the batch axis second, i.e.
    ``[n_blocks, batch, ...]``, and attention sub-caches keep a *per-row*
    ``step`` offset — batch slot ``b`` can sit at any sequence depth
    independently of its neighbours, so one batched cache serves requests
    of different lengths."""
    dtype = dtype or cfg.act_dtype
    one = _block_cache(cfg, batch, cache_len, dtype)
    nb = n_blocks(cfg)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (nb,) + x.shape), one)


def make_paged_cache(cfg: ModelConfig, batch: int, cache_len: int, *,
                     page_size: int, num_pages: int, dtype=None):
    """Paged decode cache (see ``layers.make_paged_kv_cache`` /
    ``serving/paged_kv.py``): same ``[n_blocks, batch, ...]`` layout
    contract as ``make_cache`` for ``pos``/``step``/``bt`` leaves, while
    the K/V pool leaves carry ``[n_blocks, num_pages + 1, ...]`` — the
    pool replaces the per-slot ring as the storage axis. Attention-only
    stacks (SSM recurrent state has no paged analogue)."""
    if any(mixer != "attn" for mixer, _ in block_spec(cfg)):
        raise NotImplementedError(
            f"paged KV caches require attention-only stacks; family "
            f"{cfg.family!r} has SSM mixers")
    dtype = dtype or cfg.act_dtype
    spec = block_spec(cfg)
    S_len = min(cache_len, cfg.sliding_window) if cfg.sliding_window \
        else cache_len
    one = {f"sub{i}": L.make_paged_kv_cache(batch, S_len, cfg.n_kv_heads,
                                            cfg.hd, dtype,
                                            page_size=page_size,
                                            num_pages=num_pages,
                                            quant=cfg.kv_quant)
           for i in range(len(spec))}
    nb = n_blocks(cfg)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (nb,) + x.shape), one)


def cache_steps(cache):
    """Per-slot sequence depth (B,) from the first sub-cache that tracks
    one (every mixer does: attention rings and SSM recurrent state both
    carry a per-row ``step``)."""
    for sub in cache.values():
        if isinstance(sub, dict) and "step" in sub:
            return sub["step"][0]
    return None


# --------------------------------------------------------------------- #
# block apply
# --------------------------------------------------------------------- #
def apply_block(bp, x, cfg: ModelConfig, *, mode: str, cache=None,
                length=None):
    """mode: 'train' | 'prefill' | 'decode' | 'extend'. Returns
    (x, new_cache, aux). ``length``: optional (B,) counts — for 'prefill'
    the valid-token count of right-padded rows (bucketed serving
    prefill); for 'extend' the per-row advance (rows move by length[b]
    <= T tokens, None = all rows advance by T). 'extend' is the masked
    multi-token cached decode shared by speculative verify, chunked
    prefill and the engine's fused mixed step; every mixer supports it
    (attention via the masked ring scatter, SSM via the ``ssd_extend``
    recurrence from the cached state, with identity steps past each
    row's length; see ``ssm.ssm_block`` for its two forms). MoE FFNs route *densely* (per-token, capacity-free) in the
    cached serving modes so chunk padding and batch composition cannot
    distort expert assignment — see ``moe.moe_block``."""
    spec = block_spec(cfg)
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    for i, (mixer, ffn) in enumerate(spec):
        sp = bp[f"sub{i}"]
        h = L.rms_norm(sp["ln1"], x, cfg.norm_eps)
        with jax.named_scope("attention" if mixer == "attn" else "ssm"):
            if mixer == "attn":
                if mode == "train":
                    y, nc = L.attention_block(sp["attn"], h, cfg)
                elif mode == "prefill":
                    y, nc = L.prefill_into_cache(sp["attn"], h, cfg,
                                                 cache[f"sub{i}"],
                                                 length=length)
                elif mode == "extend":
                    y, nc = L.extend_into_cache(sp["attn"], h, cfg,
                                                cache[f"sub{i}"],
                                                lengths=length)
                else:
                    y, nc = L.attention_block(sp["attn"], h, cfg,
                                              cache=cache[f"sub{i}"])
            else:
                if mode == "train":
                    y, nc = S.ssm_block(sp["ssm"], h, cfg)
                elif mode == "prefill":
                    y, nc = S.ssm_block(sp["ssm"], h, cfg, return_cache=True,
                                        length=length)
                elif mode == "extend":
                    y, nc = S.ssm_block(sp["ssm"], h, cfg,
                                        cache=cache[f"sub{i}"],
                                        length=length, mode="extend")
                else:
                    y, nc = S.ssm_block(sp["ssm"], h, cfg,
                                        cache=cache[f"sub{i}"])
        if nc is not None:
            new_cache[f"sub{i}"] = nc
        x = x + y
        x = shard_activation(x, "act_btd")
        if ffn is not None:
            h = L.rms_norm(sp["ln2"], x, cfg.norm_eps)
            if ffn == "mlp":
                with jax.named_scope("mlp"):
                    y = L.mlp(sp["mlp"], h)
            else:
                with jax.named_scope("moe_ffn"):
                    y, moe_aux = M.moe_block(
                        sp["moe"], h, cfg,
                        dense=mode in ("decode", "extend"))
                aux = aux + moe_aux
            x = x + y
            x = shard_activation(x, "act_btd")
    return x, (new_cache or None), aux


# --------------------------------------------------------------------- #
# full forward passes
# --------------------------------------------------------------------- #
def _scan_blocks(params, x, cfg: ModelConfig, *, mode: str, cache=None,
                 length=None):
    block_fn = functools.partial(apply_block, cfg=cfg, mode=mode,
                                 length=length)
    if cfg.remat:
        block_fn = jax.checkpoint(block_fn)

    if cfg.unroll_layers:
        nb = jax.tree.leaves(params["blocks"])[0].shape[0]
        aux = jnp.zeros((), jnp.float32)
        new_caches = []
        for i in range(nb):
            bp = jax.tree.map(lambda t: t[i], params["blocks"])
            c = None if cache is None else \
                jax.tree.map(lambda t: t[i], cache)
            x, nc, a = block_fn(bp, x, cache=c)
            aux = aux + a
            if nc is not None:
                new_caches.append(nc)
        new_cache = None if not new_caches else \
            jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
        return x, new_cache, aux

    if mode == "train":
        def body(carry, bp):
            x, aux = carry
            x, _, a = block_fn(bp, x)
            return (x, aux + a), None
        (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
        return x, None, aux

    def body(carry, xs):
        x, aux = carry
        bp, c = xs
        x, nc, a = block_fn(bp, x, cache=c)
        return (x, aux + a), nc
    (x, aux), new_cache = lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (params["blocks"], cache))
    return x, new_cache, aux


def embed_inputs(params, cfg: ModelConfig, tokens=None, embeddings=None):
    """tokens: (B, Lt) ids; embeddings: (B, Le, d_embed) frontend stub
    output (VLM patches / audio frames). Returns (B, L, d)."""
    parts = []
    if embeddings is not None:
        parts.append(L.linear(params["frontend_proj"], embeddings))
    if tokens is not None:
        parts.append(L.embed(params["embed"], tokens))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return x.astype(cfg.act_dtype)


def logits_from(params, cfg: ModelConfig, x):
    x = L.rms_norm(params["ln_f"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        out = L.unembed(params["embed"], x)
    else:
        out = L.linear(params["lm_head"], x)
    return shard_activation(out.astype(jnp.float32), "logits")


def forward_train(params, cfg: ModelConfig, tokens, embeddings=None):
    """Returns (logits, aux_loss)."""
    x = embed_inputs(params, cfg, tokens, embeddings)
    x = shard_activation(x, "act_btd")
    x, _, aux = _scan_blocks(params, x, cfg, mode="train")
    return logits_from(params, cfg, x), aux


def last_valid(x, length):
    """x: (B, L, d); length: (B,) valid counts -> (B, 1, d) at the last
    valid position per row (the whole-sequence last position if None)."""
    if length is None:
        return x[:, -1:]
    idx = jnp.clip(length - 1, 0, x.shape[1] - 1).astype(jnp.int32)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)


def prefill(params, cfg: ModelConfig, tokens, cache, embeddings=None,
            length=None):
    """Populates cache; returns (last-valid-position logits, cache).

    ``length``: optional (B,) total valid positions (frontend tokens +
    text) when inputs are right-padded to a bucket length. Right padding +
    causal attention means valid positions are computed identically to an
    unpadded call; padded cache slots are marked empty (pos = -1) and the
    per-row ``step`` offset is set to ``length`` so decode resumes at the
    true depth. One caveat: MoE routing shares an expert-capacity budget
    across all (incl. padded) tokens, so padded prefill of MoE stacks is
    only capacity-approximate — the serving engine therefore pads only
    MoE-free models (exact for dense/ssm/hybrid-no-moe/vlm/encdec)."""
    x = embed_inputs(params, cfg, tokens, embeddings)
    x = shard_activation(x, "act_btd")
    x, new_cache, _ = _scan_blocks(params, x, cfg, mode="prefill",
                                   cache=cache, length=length)
    return logits_from(params, cfg, last_valid(x, length)), new_cache


def decode_step(params, cfg: ModelConfig, token, cache):
    """token: (B, 1) ids. Returns (logits (B, 1, V), new_cache)."""
    x = embed_inputs(params, cfg, token)
    x = shard_activation(x, "act_btd")
    x, new_cache, _ = _scan_blocks(params, x, cfg, mode="decode",
                                   cache=cache)
    return logits_from(params, cfg, x), new_cache


def extend_step(params, cfg: ModelConfig, tokens, cache, lengths=None,
                last_only=False, embeddings=None):
    """Masked multi-token cached forward at per-row offsets — the unified
    extend path behind speculative verify, chunked prefill, and the
    serving engine's fused mixed step. tokens: (B, T) ids; ``lengths``:
    optional (B,) per-row advance (row b consumes tokens[b, :lengths[b]]
    and its cache step moves by lengths[b]; 0 = row untouched; None = all
    rows advance by T). Returns (logits, new_cache) — logits (B, T, V)
    where ``logits[:, i]`` is the distribution after consuming
    tokens[:, :i+1], or (B, 1, V) at each row's last valid position when
    ``last_only`` (saves the (T-1)·V unembed when only the next-token
    distribution is needed, e.g. a prefill chunk). ``embeddings``:
    optional (B, T, d_embed) frontend output admitted *instead of*
    tokens (a VLM/audio prefix chunk flowing through the same masked
    extend as text)."""
    x = embed_inputs(params, cfg, tokens, embeddings)
    x = shard_activation(x, "act_btd")
    x, new_cache, _ = _scan_blocks(params, x, cfg, mode="extend",
                                   cache=cache, length=lengths)
    if last_only:
        x = last_valid(x, lengths)
    return logits_from(params, cfg, x), new_cache


def verify_step(params, cfg: ModelConfig, tokens, cache):
    """Speculative-decoding verify: score T tokens per row in one masked
    multi-token forward at each row's own cache offset. tokens: (B, T)
    ids — [pending token, draft proposals]. Returns (logits (B, T, V),
    new_cache with step += T); ``logits[:, i]`` is the target
    distribution after consuming tokens[:, :i+1]."""
    return extend_step(params, cfg, tokens, cache)


def set_cache_steps(cache, steps):
    """Per-row cache rollback: move every sub-cache to depth ``steps``
    (B,), family-aware.

    * Attention sub-caches (``pos`` leaf): rewrite ``step`` (leaves are
      (n_blocks, B)). ``pos`` entries beyond the new depth are left in
      place — causal masking keeps them invisible until the decode step
      that overwrites their ring slot (see ``layers.verify_into_cache``).
    * SSM sub-caches (``conv``/``ssm`` leaves): recurrent state cannot
      be rewound by masking, so rows with ``steps < step`` restore the
      ``*_ckpt`` snapshot taken before the most recent advance. The
      caller must target that snapshot's depth (the engine rolls back to
      the pre-verify depth and *replays* accepted tokens through
      ``extend_step`` — see ``Model.rollback_needs_replay``).

    Rows where ``steps`` equals the current depth are untouched
    bit-for-bit on both.
    """
    steps = steps.astype(jnp.int32)

    def walk(node):
        if not isinstance(node, dict):
            return node
        if "conv" in node and "ssm" in node:              # SSM sub-cache
            tgt = jnp.broadcast_to(steps[None, :], node["step"].shape)
            back = tgt < node["step"]                     # (n_blocks, B)

            def sel(cur, ck):
                m = back.reshape(back.shape + (1,) * (cur.ndim - back.ndim))
                return jnp.where(m, ck, cur)

            return {"conv": sel(node["conv"], node["conv_ckpt"]),
                    "ssm": sel(node["ssm"], node["ssm_ckpt"]),
                    "step": jnp.where(back, tgt, node["step"]),
                    "conv_ckpt": node["conv_ckpt"],
                    "ssm_ckpt": node["ssm_ckpt"],
                    "step_ckpt": node["step_ckpt"]}
        out = {}
        for k, v in node.items():
            if k == "step":
                out[k] = jnp.broadcast_to(steps[None, :], v.shape)
            else:
                out[k] = walk(v)
        return out

    return walk(cache)
