"""Pallas TPU decode-attention kernel (flash-decoding style).

One (or a few) query tokens attend over a long KV cache. TPU adaptation:
* The KV sequence is the sequential grid dimension; each step stages one
  (bk, hd) K/V tile into VMEM and updates the online-softmax state held in
  VMEM scratch — the cache itself never leaves HBM more than once.
* GQA is exploited: all G query heads of a KV group are processed together
  as the "matrix" side of the MXU matmuls, so the arithmetic intensity per
  KV byte is G× that of per-head decode — this kernel is the
  memory-roofline workhorse for ``decode_32k``/``long_500k``.
* Multi-query rows (speculative verify / chunked-prefill extend): the T
  query tokens of a row share the same KV region, so they fold into the
  MXU row dimension alongside the G group heads — R = T·G rows per KV
  group, each with its own absolute position for masking. Arithmetic
  intensity per KV byte grows another T×, which is what makes a prefill
  chunk nearly free next to the decode it is fused with.
* Ring-buffer validity (slot position array) and the sliding window are
  applied as masks from a position tile, so the same kernel serves full
  and windowed caches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _decode_kernel(q_ref, k_ref, v_ref, pos_ref, qpos_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, window: int,
                   bk: int, R: int):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (R, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    pos = pos_ref[0, 0]                               # (1, bk) lane-dense
    q_pos = qpos_ref[0]                               # (R, 1) per-row position

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = (pos >= 0) & (pos <= q_pos)
    if window:
        valid &= pos > (q_pos - window)
    s = jnp.where(valid, s, NEG_INF)                  # (R, bk)

    m_prev = m_scr[:, 0:1]
    l_prev = l_scr[:, 0:1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = jnp.broadcast_to(alpha * l_prev
                                  + jnp.sum(p, axis=1, keepdims=True),
                                  l_scr.shape)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_decode_kernel(bt_ref, q_ref, k_ref, v_ref, pos_ref, qpos_ref,
                         o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                         window: int, bk: int, R: int):
    # the block table is consumed by the index maps (scalar prefetch);
    # inside the body the K/V tile is already the right page
    del bt_ref
    _decode_kernel(q_ref, k_ref, v_ref, pos_ref, qpos_ref, o_ref,
                   m_scr, l_scr, acc_scr, scale=scale, window=window,
                   bk=bk, R=R)


def _row_positions(q_pos, G):
    """(B, T) query positions -> (B, T·G, 1): one position per MXU row
    (row r is query token r // G), as a column the (R, bk) mask
    broadcasts against."""
    return jnp.repeat(q_pos.astype(jnp.int32), G, axis=1)[..., None]


def paged_decode_attention_pallas(q, k_pool, v_pool, block_table, pos, q_pos,
                                  *, window=0, interpret=False):
    """Paged variant: identical online-softmax body, but the KV grid
    dimension walks *logical blocks* and the K/V tile for step j is
    fetched from pool page ``block_table[seq, j]`` via a scalar-prefetch
    index map — the gather never materialises a contiguous copy of the
    cache. The KV tile size is the page size, so one grid step stages
    exactly one page.

    q: (B, T, Hq, hd) (or (B, Hq, hd) single-query); k_pool, v_pool:
    (P + 1, ps, Hkv, hd) — last pool index is the trash page unallocated
    block-table entries point at (its junk is masked by ``pos == -1``);
    block_table: (B, NB) int32; pos: (B, S = NB * ps); q_pos: (B,) or
    (B, T)."""
    squeeze = q.ndim == 3
    if squeeze:
        q, q_pos = q[:, None], q_pos[:, None]
    B, T, Hq, hd = q.shape
    ps, Hkv = k_pool.shape[1], k_pool.shape[2]
    NB = block_table.shape[1]
    G = Hq // Hkv
    R = T * G
    qg = q.reshape(B, T, Hkv, G, hd).transpose(0, 2, 1, 3, 4) \
          .reshape(B * Hkv, R, hd)
    kg = jnp.transpose(k_pool, (2, 0, 1, 3))          # (Hkv, P+1, ps, hd)
    vg = jnp.transpose(v_pool, (2, 0, 1, 3))
    # one lane-dense row of positions per logical block; the grid's
    # (sequence, kv head) index reads its sequence's rows (b // Hkv)
    posb = pos.reshape(B, NB, 1, ps)
    qposr = _row_positions(q_pos, G)                  # (B, R, 1)

    grid = (B * Hkv, 1, NB)
    kernel = functools.partial(_paged_decode_kernel, scale=1.0 / (hd ** 0.5),
                               window=window, bk=ps, R=R)
    # grid index b covers (sequence, kv head): seq = b // Hkv, head =
    # b % Hkv — matching the dense kernel's B*Hkv regrouping. Index maps
    # receive the scalar-prefetch operands *after* the grid indices
    # (the kernel body receives them first).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, R, hd), lambda b, h, j, bt: (b, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda b, h, j, bt: (b % Hkv, bt[b // Hkv, j], 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda b, h, j, bt: (b % Hkv, bt[b // Hkv, j], 0, 0)),
            pl.BlockSpec((1, 1, 1, ps),
                         lambda b, h, j, bt: (b // Hkv, j, 0, 0)),
            pl.BlockSpec((1, R, 1), lambda b, h, j, bt: (b // Hkv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, hd), lambda b, h, j, bt: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((R, _LANES), jnp.float32),
            pltpu.VMEM((R, _LANES), jnp.float32),
            pltpu.VMEM((R, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, R, hd), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), qg, kg, vg, posb, qposr)
    out = out.reshape(B, Hkv, T, G, hd).transpose(0, 2, 1, 3, 4) \
             .reshape(B, T, Hq, hd)
    return out[:, 0] if squeeze else out


def decode_attention_pallas(q, k, v, pos, q_pos, *, window=0, bk=128,
                            interpret=False):
    """q: (B, Hq, hd) single-query or (B, T, Hq, hd) multi-query rows;
    k, v: (B, Hkv, S, hd); pos: (B, S); q_pos: (B,) or (B, T) matching q."""
    squeeze = q.ndim == 3
    if squeeze:
        q, q_pos = q[:, None], q_pos[:, None]
    B, T, Hq, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = Hq // Hkv
    R = T * G
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    # regroup q to (B*Hkv, T*G, hd) so one grid step covers a KV group:
    # row r of a group is query token r // G, group head r % G
    qg = q.reshape(B, T, Hkv, G, hd).transpose(0, 2, 1, 3, 4) \
          .reshape(B * Hkv, R, hd)
    kg = k.reshape(B * Hkv, 1, S, hd)
    vg = v.reshape(B * Hkv, 1, S, hd)
    # positions stay per sequence: grid index b reads row b // Hkv
    posr = pos[:, None, :]                            # (B, 1, S)
    qposr = _row_positions(q_pos, G)                  # (B, R, 1)

    grid = (B * Hkv, 1, S // bk)
    kernel = functools.partial(_decode_kernel, scale=1.0 / (hd ** 0.5),
                               window=window, bk=bk, R=R)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, R, hd), lambda b, h, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, j: (b // Hkv, 0, j)),
            pl.BlockSpec((1, R, 1), lambda b, h, j: (b // Hkv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, hd), lambda b, h, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, R, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((R, _LANES), jnp.float32),
            pltpu.VMEM((R, _LANES), jnp.float32),
            pltpu.VMEM((R, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kg, vg, posr, qposr)
    out = out.reshape(B, Hkv, T, G, hd).transpose(0, 2, 1, 3, 4) \
             .reshape(B, T, Hq, hd)
    return out[:, 0] if squeeze else out
