"""Pallas TPU kernel: fused dequantize-matmul for int8/int4 weights.

Weight-only quantized decode is bandwidth-bound: the win is moving the
weight matrix HBM -> VMEM at 1 byte (int8) or 0.5 bytes (int4 packed)
per element instead of 2-4, and never materializing a dequantized copy
in HBM. Each grid step streams an ``(bm, K)`` activation tile and a
``(K, bn)`` quantized weight tile into VMEM; nibble unpacking, scaling
and the MXU matmul all happen on-chip, with the f32 accumulator scaled
in the epilogue (int8, per-channel) or per group before accumulation
(int4, group-wise).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _qmm_int8_kernel(x_ref, q_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                 # (bm, K)
    q = q_ref[...].astype(jnp.float32)                 # (K, bn)
    acc = jnp.dot(x, q, preferred_element_type=jnp.float32)
    s = s_ref[...].astype(jnp.float32)                 # (1, bn)
    o_ref[...] = (acc * s).astype(o_ref.dtype)


def _qmm_int4_kernel(x_ref, p_ref, s_ref, o_ref, *, group_size: int):
    x = x_ref[...].astype(jnp.float32)                 # (bm, K)
    p32 = p_ref[...].astype(jnp.int32)                 # (K//2, bn) packed
    lo = (p32 << 28) >> 28                             # sign-extended nibbles
    hi = (p32 << 24) >> 28
    K = x.shape[1]
    bn = p32.shape[1]
    q = jnp.stack([lo, hi], axis=1).reshape(K, bn).astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)                 # (ng, bn)
    w = (q.reshape(K // group_size, group_size, bn)
         * s[:, None, :]).reshape(K, bn)               # dequant in VMEM only
    o_ref[...] = jnp.dot(x, w,
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def quant_matmul_int8_pallas(x, q, scale, *, bm=128, bn=128,
                             interpret=False):
    """x: (M, K); q: (K, N) int8; scale: (N,) -> (M, N) in x.dtype."""
    M, K = x.shape
    N = q.shape[1]
    bm, bn = min(bm, M), min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, bm, N, bn)
    return pl.pallas_call(
        _qmm_int8_kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(x, q, scale.reshape(1, N))


def quant_matmul_int4_pallas(x, q4, scale, *, bm=128, bn=128,
                             interpret=False):
    """x: (M, K); q4: (K//2, N) packed int8; scale: (ng, N) -> (M, N)."""
    M, K = x.shape
    N = q4.shape[1]
    ng = scale.shape[0]
    assert K % ng == 0 and K == 2 * q4.shape[0], (K, ng, q4.shape)
    bm, bn = min(bm, M), min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, bm, N, bn)
    kernel = functools.partial(_qmm_int4_kernel, group_size=K // ng)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K // 2, bn), lambda i, j: (0, j)),
            pl.BlockSpec((ng, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(x, q4, scale)
