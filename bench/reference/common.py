"""Arithmetic shared by the references: float32 matmuls at HIGHEST
precision, the float8 control's rounding, RMSNorm and the seeded key."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
MODES = ("f32", "fp8")


def seed_key(seed: int) -> jax.Array:
    """A threefry key from a seed of up to 64 bits."""
    s = int(seed) & (2 ** 64 - 1)
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], dtype=np.uint32))


def fp8_round(v, axis):
    """Round ``v`` to float8 e4m3 with one scale per slice along ``axis``
    (the largest magnitude maps to the format's largest value)."""
    v = v.astype(F32)
    amax = jnp.max(jnp.abs(v), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (v / scale).astype(FP8).astype(F32) * scale


def mm(x, w, mode):
    """``x @ w`` for a weight ``w`` (d_in, d_out): float32 at HIGHEST, or
    with both operands rounded to float8 first (activations per row,
    weights per output column) and accumulated in float32."""
    x = x.astype(F32)
    w = w.astype(F32)
    if mode == "fp8":
        x = fp8_round(x, axis=-1)
        w = fp8_round(w, axis=0)
    elif mode != "f32":
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def emm(spec, x, w, mode):
    """``einsum(spec, x, w)`` for expert weights ``w`` (experts, d_in,
    d_out): float32 at HIGHEST, or float8 as in ``mm`` (activations per
    row over ``d_in``, weights per output column of each expert)."""
    x = x.astype(F32)
    w = w.astype(F32)
    if mode == "fp8":
        x = fp8_round(x, axis=-1)
        w = fp8_round(w, axis=-2)
    elif mode != "f32":
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale.astype(F32)


def silu(x):
    return x * jax.nn.sigmoid(x)


def layer_slice(blocks, i):
    """Layer ``i`` of the stacked block weights."""
    return jax.tree.map(lambda a: a[i], blocks)


def run_layers(layer, head, params, config, tokens, read_pos):
    """Embed ``tokens``, apply the jitted ``layer`` once per layer with
    that layer's weights, and take ``head`` at ``read_pos``: one layer's
    activations live at a time, beside the weights."""
    x = jnp.take(params["embed"]["table"], jnp.asarray(tokens),
                 axis=0).astype(F32)
    blocks = params["blocks"]["sub0"]
    for i in range(config["n_layers"]):
        x = layer(layer_slice(blocks, i), x)
    return head(params, x, jnp.asarray(read_pos))


def final_logits(params, config, x, read_pos, mode):
    """Final norm and unembedding at the rows ``read_pos`` (B, R) of the
    hidden states ``x`` (B, L, d)."""
    xr = jnp.take_along_axis(x, read_pos[..., None], axis=1)
    h = rms_norm(xr, params["ln_f"]["scale"], config["norm_eps"])
    if config.get("tie_embeddings", False):
        w = params["embed"]["table"].T
    else:
        w = params["lm_head"]["w"]
    return mm(h, w, mode)


def normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, F32)).astype(dtype)


def uniform(key, shape, bound, dtype):
    return jax.random.uniform(key, shape, F32, -bound, bound).astype(dtype)
