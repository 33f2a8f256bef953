"""Mamba-2 (SSD) language model, written plainly in float32.

Each layer (Dao & Gu, arXiv:2405.21060, with the published mamba2
configs' choices: one B/C group, gated RMSNorm after the scan, no MLP):

    h            = RMSNorm(x) * ln1
    z, xBC, dt   = split(h @ W_in)
    dt           = softplus(dt + dt_bias)
    xBC          = silu(causal depthwise conv(xBC, K taps) + conv_b)
    x, B, C      = split(xBC)                   # x per head (P), B, C (N)
    s_t          = exp(dt_t * A) s_{t-1} + dt_t x_t B_t^T,   A = -exp(A_log)
    y_t          = s_t C_t + D x_t
    x            = x + (RMSNorm(y * silu(z)) * norm) @ W_out

The recurrence runs token by token (``lax.scan`` over time), which is the
definition rather than the chunked dual form the program's kernels use.

Weights follow the Mamba-2 reference initialisation (``mamba_ssm``):
embedding N(0, 0.02); input projection U(+-1/sqrt(d_model)); depthwise
conv U(+-1/sqrt(K)) with bias U(+-1/sqrt(K)); ``dt`` drawn log-uniform in
[1e-3, 1e-1] and stored as its inverse softplus; ``A`` uniform in [1, 16];
``D`` = 1; output projection U(+-1/sqrt(d_inner)) / sqrt(n_layers); norm
scales 1: random weights at the scales the published model starts
training from.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import common as c

ROWS = 8          # sequences per call: the scan's steps cost the same

def dims(config):
    s = config["ssm"]
    d = config["d_model"]
    d_in = s["expand"] * d
    heads = d_in // s["head_dim"]
    gn = s["n_groups"] * s["d_state"]
    return d, d_in, heads, s["head_dim"], s["n_groups"], s["d_state"], \
        d_in + 2 * gn, s["d_conv"]


def init(config, key):
    d, d_in, H, P, G, N, conv_dim, K = dims(config)
    L, V = config["n_layers"], config["vocab"]
    pt = jnp.dtype(config["param_dtype"])
    d_proj = 2 * d_in + 2 * G * N + H
    ks = jax.random.split(key, 9)
    dt = jnp.exp(jax.random.uniform(ks[5], (L, H), c.F32, math.log(1e-3),
                                    math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    params = {
        "embed": {"table": c.normal(ks[0], (V, d), 0.02, pt)},
        "blocks": {"sub0": {
            "ln1": {"scale": jnp.ones((L, d), pt)},
            "ssm": {
                "in_proj": {"w": c.uniform(ks[1], (L, d, d_proj),
                                           1 / math.sqrt(d), pt)},
                "conv_w": c.uniform(ks[2], (L, conv_dim, K),
                                    1 / math.sqrt(K), pt),
                "conv_b": c.uniform(ks[3], (L, conv_dim),
                                    1 / math.sqrt(K), pt),
                "A_log": jnp.log(jax.random.uniform(ks[4], (L, H), c.F32,
                                                    1.0, 16.0)),
                "D": jnp.ones((L, H), c.F32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": {"scale": jnp.ones((L, d_in), pt)},
                "out_proj": {"w": c.uniform(
                    ks[6], (L, d_in, d),
                    1 / math.sqrt(d_in) / math.sqrt(L), pt)},
            },
        }},
        "ln_f": {"scale": jnp.ones((d,), pt)},
    }
    if not config.get("tie_embeddings", False):
        params["lm_head"] = {"w": c.normal(ks[7], (d, V), 0.02, pt)}
    return params


def _mixer(p, h, config, mode):
    d, d_in, H, P, G, N, conv_dim, K = dims(config)
    B, T, _ = h.shape
    zxbcdt = c.mm(h, p["in_proj"]["w"], mode)
    z = zxbcdt[..., :d_in]
    xBC = zxbcdt[..., d_in:d_in + conv_dim]
    dt = jax.nn.softplus(zxbcdt[..., d_in + conv_dim:]
                         + p["dt_bias"].astype(c.F32))            # (B, T, H)
    w = p["conv_w"].astype(c.F32)                                 # (Cc, K)
    xp = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, k:k + T] * w[:, k] for k in range(K))
    xBC = c.silu(conv + p["conv_b"].astype(c.F32))
    x = xBC[..., :d_in].reshape(B, T, H, P)
    Bm = xBC[..., d_in:d_in + G * N].reshape(B, T, G, N)
    Cm = xBC[..., d_in + G * N:].reshape(B, T, G, N)
    rep = H // G
    Bm = jnp.repeat(Bm, rep, axis=2)                              # (B,T,H,N)
    Cm = jnp.repeat(Cm, rep, axis=2)
    A = -jnp.exp(p["A_log"].astype(c.F32))                        # (H,)
    Dv = p["D"].astype(c.F32)

    def step(s, inp):
        xt, dtt, bt, ct = inp                  # (B,H,P) (B,H) (B,H,N) x2
        s = s * jnp.exp(dtt * A)[..., None, None] \
            + (xt * dtt[..., None])[..., None] * bt[..., None, :]
        y = jnp.sum(s * ct[..., None, :], axis=-1) + Dv[:, None] * xt
        return s, y

    s0 = jnp.zeros((B, H, P, N), c.F32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm))
    _, ys = lax.scan(step, s0, xs, unroll=8)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, T, d_in)
    y = c.rms_norm(y * c.silu(z), p["norm"]["scale"], config["norm_eps"])
    return c.mm(y, p["out_proj"]["w"], mode)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, mode):
    config = json.loads(cfg_json)

    def layer(p, x):
        h = c.rms_norm(x, p["ln1"]["scale"], config["norm_eps"])
        return x + _mixer(p["ssm"], h, config, mode)

    def head(params, x, read_pos):
        return c.final_logits(params, config, x, read_pos, mode)

    return jax.jit(layer), jax.jit(head)


def logits(params, config, tokens, read_pos, mode="f32"):
    """tokens: (B, T) int32; read_pos: (B, R) int32. Returns the
    (B, R, vocab) float32 logits predicting the token after each read
    position, computed one layer at a time."""
    layer, head = _programs(json.dumps(config, sort_keys=True), mode)
    return c.run_layers(layer, head, params, config, tokens, read_pos)
