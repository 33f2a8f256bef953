"""Mamba-2 (SSD) mixer block: projections, causal conv, gated norm, and the
SSD scan (chunked dual form for train/prefill, recurrent step for decode)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models.layers import _normal, init_linear, linear, rms_norm
from repro.kernels.ssd_scan import ops as ssd_ops


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    return s, d_in, nh, conv_dim


def init_ssm(key, cfg: ModelConfig):
    s, d_in, nh, conv_dim = _dims(cfg)
    ks = jax.random.split(key, 4)
    d_proj = 2 * d_in + 2 * s.n_groups * s.d_state + nh
    return {
        "in_proj": init_linear(ks[0], cfg.d_model, d_proj, cfg.p_dtype),
        "conv_w": _normal(ks[1], (conv_dim, s.d_conv), cfg.p_dtype, 0.5),
        "conv_b": jnp.zeros((conv_dim,), cfg.p_dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": {"scale": jnp.ones((d_in,), cfg.p_dtype)},
        "out_proj": init_linear(ks[3], d_in, cfg.d_model, cfg.p_dtype),
    }


def make_ssm_cache(batch, cfg: ModelConfig, dtype):
    """Decode/extend cache for one mixer. ``step`` is the per-row depth
    (tokens absorbed into the state); the ``*_ckpt`` leaves hold the
    state as it was *before* the most recent advance — the restore point
    ``rollback`` returns to when speculation rejects drafts (recurrent
    state cannot be rewound by causal masking the way a KV ring can)."""
    s, d_in, nh, conv_dim = _dims(cfg)
    conv = jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype)
    ssm = jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32)
    step = jnp.zeros((batch,), jnp.int32)
    return {"conv": conv, "ssm": ssm, "step": step,
            "conv_ckpt": conv, "ssm_ckpt": ssm, "step_ckpt": step}


def _causal_conv(x, w, b):
    """Depthwise causal conv. x: (B, L, Cc), w: (Cc, K). Computed in
    float32, like the extend and decode steps' convs, so prefill agrees
    with the serving path that continues from it; in bf16 the two drift
    apart with depth."""
    f32 = jnp.float32
    K = w.shape[1]
    L = x.shape[1]
    xp = jnp.pad(x.astype(f32), ((0, 0), (K - 1, 0), (0, 0)))
    w = w.astype(f32)
    out = sum(xp[:, i:i + L] * w[None, None, :, i] for i in range(K))
    return out + b.astype(f32)


def _split_proj(zxbcdt, cfg: ModelConfig):
    s, d_in, nh, conv_dim = _dims(cfg)
    z = zxbcdt[..., :d_in]
    xBC = zxbcdt[..., d_in:d_in + conv_dim]
    dt = zxbcdt[..., d_in + conv_dim:]
    return z, xBC, dt


def _split_xbc(xBC, cfg: ModelConfig):
    s, d_in, nh, _ = _dims(cfg)
    gn = s.n_groups * s.d_state
    x = xBC[..., :d_in]
    B = xBC[..., d_in:d_in + gn]
    C = xBC[..., d_in + gn:]
    return x, B, C


def ssm_block(p, u, cfg: ModelConfig, *, cache=None, return_cache=False,
              length=None, mode=None):
    """u: (B, L, d). cache=None -> full sequence (chunked SSD); pass
    ``return_cache=True`` during prefill to also get the decode cache.
    cache given and L==1 -> recurrent decode step. cache given and
    ``mode="extend"`` -> multi-token cached recurrence at per-row
    offsets (the serving engine's chunked admission / speculative
    verify): every row advances by ``length[b] <= L`` tokens through
    ``ssd_extend``, masked positions are exact identity steps (dt = 0 ->
    decay 1, zero input) and the conv tail is gathered from the last
    valid inputs, so a length-0 row's cache is bit-untouched. On the
    token-serial form (the jnp reference; the Pallas kernel for L not a
    whole number of its blocks) chunked extends compose bitwise with a
    single whole-prompt extend; on the chunked form (Pallas, L a whole
    number of blocks, as the engine's admission chunks are) they agree
    numerically. Returns (y, new_cache).

    ``length``: optional (B,) int32 valid-token count when ``u`` is
    right-padded (bucketed prefill). Padded positions get ``dt = 0`` —
    decay 1, zero input — so the recurrent state after ``length`` tokens is
    exactly the unpadded state, and the conv tail is gathered from the last
    valid inputs rather than the padding."""
    s, d_in, nh, conv_dim = _dims(cfg)
    Bsz, L, _ = u.shape
    zxbcdt = linear(p["in_proj"], u)
    z, xBC, dt = _split_proj(zxbcdt, cfg)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    if length is not None:
        valid = jnp.arange(L)[None, :] < length[:, None]      # (B, L)
        dt = dt * valid[..., None]
    A = -jnp.exp(p["A_log"])

    if cache is None:
        xBC_raw = xBC
        xBC = jax.nn.silu(_causal_conv(xBC, p["conv_w"], p["conv_b"]))
        x, Bc, Cc = _split_xbc(xBC, cfg)
        xh = x.reshape(Bsz, L, nh, s.head_dim)
        Bg = Bc.reshape(Bsz, L, s.n_groups, s.d_state)
        Cg = Cc.reshape(Bsz, L, s.n_groups, s.d_state)
        # pad to a chunk multiple; dt=0 on padding -> decay 1, zero input,
        # so outputs and final state are unaffected
        chunk = min(s.chunk, max(16, 1 << (L - 1).bit_length()))
        pad = (-L) % chunk
        if pad:
            zp = lambda a: jnp.pad(a, [(0, 0), (0, pad)]
                                   + [(0, 0)] * (a.ndim - 2))
            xh, Bg, Cg, dt = zp(xh), zp(Bg), zp(Cg), zp(dt)
        y, final_state = ssd_ops.ssd(xh, dt, A, Bg, Cg, p["D"],
                                     chunk=chunk)
        y = y[:, :L]
        y = y.reshape(Bsz, L, d_in).astype(u.dtype)
        if return_cache:
            K = s.d_conv
            if length is not None:
                # last K-1 *valid* inputs per row; indices before the start
                # of the sequence read as zeros (same as fresh-cache pad)
                idx = length[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]
                in_range = idx >= 0                           # (B, K-1)
                g = jnp.take_along_axis(
                    xBC_raw, jnp.clip(idx, 0, L - 1)[..., None], axis=1)
                tail = jnp.where(in_range[..., None], g, 0)
            else:
                tail = xBC_raw[:, max(0, L - (K - 1)):]
                if tail.shape[1] < K - 1:
                    tail = jnp.pad(
                        tail, ((0, 0), (K - 1 - tail.shape[1], 0), (0, 0)))
            lens = (length if length is not None
                    else jnp.full((Bsz,), L, jnp.int32))
            tail = tail.astype(u.dtype)
            # fresh stream: the checkpoint is the state itself (there is
            # nothing earlier to restore to)
            new_cache = {"conv": tail, "ssm": final_state,
                         "step": lens.astype(jnp.int32),
                         "conv_ckpt": tail, "ssm_ckpt": final_state,
                         "step_ckpt": lens.astype(jnp.int32)}
        else:
            new_cache = None
    elif mode == "extend":
        # multi-token cached recurrence at per-row offsets. The conv
        # stream is [cached tail | raw new inputs]; token t's depthwise
        # window is conv_in[t : t+K], so positions < length[b] only ever
        # see valid inputs, and the new tail (last K-1 valid inputs)
        # is conv_in[length[b] : length[b]+K-1] — for length 0 that is
        # the old tail, bit-for-bit.
        K = s.d_conv
        step = cache["step"]
        conv_in = jnp.concatenate([cache["conv"], xBC], axis=1)
        widx = jnp.arange(L)[:, None] + jnp.arange(K)[None, :]   # (L, K)
        win = conv_in[:, widx]                                   # (B,L,K,Cc)
        conv_out = jnp.einsum("blkc,ck->blc", win.astype(jnp.float32),
                              p["conv_w"].astype(jnp.float32))
        xc = jax.nn.silu(conv_out + p["conv_b"].astype(jnp.float32))
        x, Bc, Cc = _split_xbc(xc, cfg)
        xh = x.reshape(Bsz, L, nh, s.head_dim)
        Bg = Bc.reshape(Bsz, L, s.n_groups, s.d_state)
        Cg = Cc.reshape(Bsz, L, s.n_groups, s.d_state)
        y, new_state = ssd_ops.ssd_extend(cache["ssm"], xh, dt, A,
                                          Bg, Cg, p["D"])
        y = y.reshape(Bsz, L, d_in).astype(u.dtype)
        lens = (length if length is not None
                else jnp.full((Bsz,), L, jnp.int32))
        tidx = lens[:, None] + jnp.arange(K - 1)[None, :]        # (B, K-1)
        tail = jnp.take_along_axis(conv_in, tidx[..., None], axis=1)
        new_cache = {"conv": tail.astype(cache["conv"].dtype),
                     "ssm": new_state,
                     "step": step + lens.astype(step.dtype),
                     "conv_ckpt": cache["conv"], "ssm_ckpt": cache["ssm"],
                     "step_ckpt": step}
    else:
        # single-token recurrence (L == 1)
        xBC1 = xBC[:, 0]                                  # (B, Cc)
        conv_full = jnp.concatenate([cache["conv"], xBC1[:, None]], axis=1)
        wc = p["conv_w"].astype(jnp.float32)              # (Cc, K)
        conv_out = jnp.einsum("bkc,ck->bc",
                              conv_full.astype(jnp.float32),
                              wc) + p["conv_b"].astype(jnp.float32)
        xBC1 = jax.nn.silu(conv_out)
        x, Bc, Cc = _split_xbc(xBC1, cfg)
        xh = x.reshape(Bsz, nh, s.head_dim)
        Bg = Bc.reshape(Bsz, s.n_groups, s.d_state)
        Cg = Cc.reshape(Bsz, s.n_groups, s.d_state)
        y1, new_state = ssd_ops.ssd_step(cache["ssm"], xh, dt[:, 0], A,
                                         Bg, Cg, p["D"])
        y = y1.reshape(Bsz, 1, d_in).astype(u.dtype)
        new_cache = {"conv": conv_full[:, 1:].astype(cache["conv"].dtype),
                     "ssm": new_state,
                     "step": cache["step"] + 1,
                     "conv_ckpt": cache["conv"], "ssm_ckpt": cache["ssm"],
                     "step_ckpt": cache["step"]}

    # gated RMSNorm (Mamba-2): norm(y * silu(z))
    y = rms_norm(p["norm"], y * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype),
                 cfg.norm_eps)
    return linear(p["out_proj"], y), new_cache
