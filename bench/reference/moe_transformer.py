"""Decoder-only transformer with grouped-query attention, rotary
positions and a top-k mixture of SwiGLU experts, written plainly in
float32 (the granite-3.0 MoE family, ``GraniteMoeForCausalLM``):

    h = RMSNorm(x) * ln1
    q, k, v = h @ Wq, h @ Wk, h @ Wv          # Hq query heads, Hkv kv heads
    q, k = rope(q), rope(k)                   # half-split rotation, theta
    a = softmax(q k^T / sqrt(hd) + causal mask) v   # query head j reads
                                                    # kv head j // (Hq/Hkv)
    x = x + a @ Wo
    h = RMSNorm(x) * ln2
    p = softmax(h @ W_router); top-k of p, renormalised to sum 1
    x = x + sum_k p_k * (silu(h @ Wg_e) * (h @ Wi_e)) @ Wo_e

Attention runs over blocks of queries, so a layer fits beside the
weights at the cell's longest sequence; the experts run densely, each
weighted by its gate (0 where the router did not choose it).

Weights follow the published initialisation (``initializer_range``
0.02): every projection, expert, router and the embedding N(0, 0.02);
norm scales 1. The granite-3.0 scalar multipliers (embedding, attention,
residual, logits) are not modelled: the program has none, and the
reference computes what the program is meant to compute.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import common as c

Q_BLOCK = 512
ROWS = 1          # sequences per call: attention scores grow with it


def dims(config):
    d = config["d_model"]
    hq, hkv = config["n_heads"], config["n_kv_heads"]
    hd = config.get("head_dim") or d // hq
    return d, hq, hkv, hd


def init(config, key):
    d, hq, hkv, hd = dims(config)
    m = config["moe"]
    L, V, E, f = config["n_layers"], config["vocab"], m["n_experts"], \
        m["d_expert"]
    pt = jnp.dtype(config["param_dtype"])
    ks = jax.random.split(key, 10)
    params = {
        "embed": {"table": c.normal(ks[0], (V, d), 0.02, pt)},
        "blocks": {"sub0": {
            "ln1": {"scale": jnp.ones((L, d), pt)},
            "attn": {
                "wq": {"w": c.normal(ks[1], (L, d, hq * hd), 0.02, pt)},
                "wk": {"w": c.normal(ks[2], (L, d, hkv * hd), 0.02, pt)},
                "wv": {"w": c.normal(ks[3], (L, d, hkv * hd), 0.02, pt)},
                "wo": {"w": c.normal(ks[4], (L, hq * hd, d), 0.02, pt)},
            },
            "ln2": {"scale": jnp.ones((L, d), pt)},
            "moe": {
                "router": {"w": c.normal(ks[5], (L, d, E), 0.02, c.F32)},
                "wi": c.normal(ks[6], (L, E, d, f), 0.02, pt),
                "wg": c.normal(ks[7], (L, E, d, f), 0.02, pt),
                "wo": c.normal(ks[8], (L, E, f, d), 0.02, pt),
            },
        }},
        "ln_f": {"scale": jnp.ones((d,), pt)},
    }
    if not config.get("tie_embeddings", False):
        params["lm_head"] = {"w": c.normal(ks[9], (d, V), 0.02, pt)}
    return params


def rope(x, pos, theta):
    """x: (B, T, H, hd); pos: (T,). Rotates the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=c.F32) / half))
    ang = pos.astype(c.F32)[:, None] * freqs                   # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, hkv):
    """Causal GQA over query blocks. q: (B, T, Hq, hd); k, v: (B, T, Hkv,
    hd). Returns (B, T, Hq * hd)."""
    B, T, hq, hd = q.shape
    g = hq // hkv
    qg = q.reshape(B, T, hkv, g, hd)
    outs = []
    for s in range(0, T, Q_BLOCK):
        e = min(T, s + Q_BLOCK)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg[:, s:e], k[:, :e],
                        precision=c.HIGHEST) / jnp.sqrt(jnp.float32(hd))
        mask = jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None]
        sc = jnp.where(mask, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bhgqk,bkhd->bqhgd", pr, v[:, :e],
                               precision=c.HIGHEST))
    return jnp.concatenate(outs, axis=1).reshape(B, T, hq * hd)


def _moe(p, h, config, mode):
    """Each token's ``top_k`` experts, weighted by their renormalised
    router probabilities. Every expert is applied to every token and
    weighted by its gate, which is 0 for the experts a token was not
    routed to: the same sum as over the chosen experts alone, as dense
    matmuls (a grouped matmul at HIGHEST precision runs far slower)."""
    m = config["moe"]
    E, k = m["n_experts"], m["top_k"]
    B, T, d = h.shape
    x = h.reshape(B * T, d)
    probs = jax.nn.softmax(jnp.matmul(x, p["router"]["w"].astype(c.F32),
                                      precision=c.HIGHEST), axis=-1)
    top_v, top_i = lax.top_k(probs, k)                         # (N, k)
    top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(top_i, E, dtype=c.F32) * top_v[..., None],
                   axis=1)                                     # (N, E)
    he = c.silu(c.emm("nd,edf->nef", x, p["wg"], mode)) \
        * c.emm("nd,edf->nef", x, p["wi"], mode)
    y = c.emm("nef,efd->nd", he * gate[..., None], p["wo"], mode)
    return y.reshape(B, T, d)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, mode):
    config = json.loads(cfg_json)
    d, hq, hkv, hd = dims(config)
    eps, theta = config["norm_eps"], config["rope_theta"]

    def layer(p, x):
        B, T, _ = x.shape
        pos = jnp.arange(T)
        a = p["attn"]
        h = c.rms_norm(x, p["ln1"]["scale"], eps)
        q = rope(c.mm(h, a["wq"]["w"], mode).reshape(B, T, hq, hd), pos,
                 theta)
        k = rope(c.mm(h, a["wk"]["w"], mode).reshape(B, T, hkv, hd), pos,
                 theta)
        v = c.mm(h, a["wv"]["w"], mode).reshape(B, T, hkv, hd)
        x = x + c.mm(_attention(q, k, v, hkv), a["wo"]["w"], mode)
        h = c.rms_norm(x, p["ln2"]["scale"], eps)
        return x + _moe(p["moe"], h, config, mode)

    def head(params, x, read_pos):
        return c.final_logits(params, config, x, read_pos, mode)

    return jax.jit(layer), jax.jit(head)


def logits(params, config, tokens, read_pos, mode="f32"):
    """tokens: (B, T) int32; read_pos: (B, R) int32. Returns the
    (B, R, vocab) float32 logits predicting the token after each read
    position, computed one layer at a time."""
    layer, head = _programs(json.dumps(config, sort_keys=True), mode)
    return c.run_layers(layer, head, params, config, tokens, read_pos)
