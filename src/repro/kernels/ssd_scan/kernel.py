"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation of the SSD algorithm (arXiv:2405.21060):
* The chunk axis is the sequential grid dimension; the inter-chunk
  recurrent state (p, n) lives in VMEM scratch and persists across grid
  steps — the TPU analogue of the GPU kernel's persistent-CTA carry.
* All four inner products are expressed as (chunk x n/p) matmuls so the
  quadratic *dual* form runs on the MXU; with chunk/p/n multiples of 128
  every matmul is systolic-aligned. ``B`` arrives transposed and the
  carried state is kept as (n, p), so every matmul is a plain
  (M, K) @ (K, N) product. The elementwise decay algebra runs on the VPU
  in fp32.
* One (batch, head) pair per grid row keeps the working set
  (4·chunk·max(p,n) fp32) comfortably inside VMEM.
* Layouts the TPU compiler accepts: per-position scalars (``dt``) arrive
  as lane-dense ``(1, chunk)`` rows, and the per-head scalars ``A``/``D``
  sit whole in SMEM, read at the head's grid index. Mosaic has no
  cumsum, so the in-chunk prefix sums are masked reductions, and the
  row <-> column flips the decay algebra needs are exact diagonal selects
  followed by a reduction (one nonzero term per sum). Mosaic cannot
  broadcast a (1, 1) vector along sublanes and lanes at once, so every
  broadcast runs along one axis only.

Outputs y per position and the final state (for prefill -> decode
handoff), matching ``ref.ssd_reference``.

``ssd_extend`` (the serving engine's cached extend) has two forms:
``ssd_extend_chunked_pallas`` runs the same block body from a given
state, several heads of one group a grid step side by side in lanes,
float32 at full precision; ``ssd_extend_pallas`` walks the tokens one
by one, bitwise equal to ``ref.ssd_extend_reference``. ``ops.ssd_extend``
picks the form from the number of tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _eye(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _to_col(row, eye):
    """(1, n) row -> (n, 1) column, exactly."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col, eye):
    """(n, 1) column -> (1, n) row, exactly."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _decays(dt, A, p):
    """One head's decay terms over a block, from its ``dt`` row (1,
    chunk) by masked reductions: the causal mask, ``dt`` as a column,
    the inclusive prefix sums of ``dt·A`` as a column and a row, the sums
    from each position to the block's end, and the whole block's sum as
    a row across the (n, p) state's lanes."""
    chunk = dt.shape[1]
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = li == lj
    causal = lj <= li
    dA = dt * A                                  # (1, chunk)
    # inclusive prefix sum, as a column: cum[i] = sum_{j<=i} dt_j·A
    cum_c = jnp.sum(jnp.where(causal, dA, 0.0), axis=1, keepdims=True)
    cum_r = _to_row(cum_c, eye)                  # (1, chunk)
    dt_c = _to_col(dt, eye)                      # (chunk, 1)
    # decay from each position to the chunk end: sum_{j>i} dt_j·A
    rest_c = jnp.sum(jnp.where(lj > li, dA, 0.0), axis=1, keepdims=True)
    # whole-chunk decay, as a row across the (n, p) state's lanes
    total = jnp.sum(jnp.broadcast_to(dt_c * A, (chunk, p)), axis=0,
                    keepdims=True)               # (1, p)
    return causal, dt_c, (cum_c, cum_r, rest_c, total)


def _widen(m, p: int):
    """(rows, hb) -> (rows, hb·p): column i across head i's ``p`` lanes
    (the heads of a block sit side by side in lanes)."""
    if m.shape[1] == 1:
        return m
    return jnp.concatenate(
        [jnp.broadcast_to(m[:, i:i + 1], (m.shape[0], p))
         for i in range(m.shape[1])], axis=1)


def _ssd_block(x, xdt, CB, Bt, Cm, D, state, causal, decays, *,
               precision=None):
    """One block of the SSD dual form for ``hb`` heads of one group,
    from their carried state; the heads sit side by side in lanes.

    x and ``xdt = dt·x`` (chunk, hb·p); state (n, hb·p); shared by the
    heads: ``CB = C Bᵀ`` (chunk, chunk), Bt (n, chunk), Cm (chunk, n)
    and the causal mask; D a scalar or a (1, hb·p) row; ``decays`` the
    prefix sums of ``dt·A`` as columns (chunk, hb) and rows (hb, chunk),
    the sums from each position to the block's end (chunk, hb) and the
    whole block's as a (1, hb·p) row. Returns the block's y (chunk,
    hb·p) and the state after it. A position with ``dt = 0`` decays
    nothing and adds nothing, so a block of them returns the state bit
    for bit."""
    cum_c, cum_r, rest_c, total = decays
    hb = cum_r.shape[0]
    p = x.shape[1] // hb
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=precision)
    # intra-chunk dual form, head by head: (C B^T ⊙ L) @ (dt·x), with
    # L[i, j] = exp(cum_i - cum_j) for j <= i else 0
    ys = [dot(CB * jnp.where(causal, jnp.exp(cum_c[:, k:k + 1]
                                             - cum_r[k:k + 1]), 0.0),
              xdt[:, k * p:(k + 1) * p]) for k in range(hb)]
    y = ys[0] if hb == 1 else jnp.concatenate(ys, axis=1)
    # inter-chunk: contribution of the carried state
    y = y + dot(Cm, state) * _widen(jnp.exp(cum_c), p)
    y = y + x * D
    # state update: s' = exp(cum_end)·s + Σ_j exp(cum_end - cum_j)·dt_j B_j x_j^T
    return y, state * jnp.exp(total) + dot(
        Bt, xdt * _widen(jnp.exp(rest_c), p))


def _ssd_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref,
                y_ref, state_ref, s_scr):
    hi = pl.program_id(1)
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)       # (chunk, p)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)     # (1, chunk)
    Bt = B_ref[0, 0, 0].astype(jnp.float32)      # (n, chunk)
    Cm = C_ref[0, 0, 0].astype(jnp.float32)      # (chunk, n)
    causal, dt_c, decays = _decays(dt, A_ref[hi], x.shape[1])
    CB = jnp.dot(Cm, Bt, preferred_element_type=jnp.float32)
    y, s_scr[...] = _ssd_block(x, x * dt_c, CB, Bt, Cm, D_ref[hi],
                               s_scr[...], causal, decays)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(c == nc - 1)
    def _emit_state():
        state_ref[0, 0] = s_scr[...]


def _ssd_extend_chunked_kernel(s0_ref, x_ref, dt_ref, A_ref, B_ref, C_ref,
                               D_ref, y_ref, state_ref, s_scr):
    """``hb`` heads of one group per grid step, side by side in lanes, so
    the state's two matmuls run ``hb·p`` lanes wide: ``C Bᵀ`` once for
    them all, their prefix sums as one matmul with the upper triangle of
    ones, then ``_ssd_block``. Every matmul is float32 at full precision
    (Mosaic's default is one bf16 pass)."""
    hb, chunk, p = x_ref.shape[1:]
    h0 = pl.program_id(1) * hb
    c = pl.program_id(2)
    nc = pl.num_programs(2)
    hi = jax.lax.Precision.HIGHEST
    heads = range(hb)

    @pl.when(c == 0)
    def _init():                                 # (hb, p, n) -> (n, hb·p)
        s_scr[...] = jnp.concatenate([s0_ref[0, k].T for k in heads],
                                     axis=1)

    Bt = B_ref[0, 0].T                           # (n, chunk)
    Cm = C_ref[0, 0]                             # (chunk, n)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # the heads' scalars from SMEM, A as a column and D as a row
    col = jax.lax.broadcasted_iota(jnp.int32, (hb, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, hb), 1)
    A = sum(jnp.where(col == k, A_ref[h0 + k], 0.0) for k in heads)
    D = sum(jnp.where(row == k, D_ref[h0 + k], 0.0) for k in heads)
    dt = dt_ref[0, 0]                            # (hb, chunk)
    # cum[h, i] = sum_{j<=i} dt_hj·A_h, for every head at once
    cum_r = jnp.dot(dt * A, (li <= lj).astype(jnp.float32),
                    preferred_element_type=jnp.float32, precision=hi)
    cum_c = cum_r.T                              # (chunk, hb)
    end = cum_c[chunk - 1:]                      # (1, hb)
    x = jnp.concatenate([x_ref[0, k] for k in heads], axis=1)
    y, s_scr[...] = _ssd_block(
        x, x * _widen(dt.T, p),
        jnp.dot(Cm, Bt, preferred_element_type=jnp.float32, precision=hi),
        Bt, Cm, _widen(D, p), s_scr[...], lj <= li,
        (cum_c, cum_r, end - cum_c, _widen(end, p)), precision=hi)
    for k in heads:
        y_ref[0, k] = y[:, k * p:(k + 1) * p]

    @pl.when(c == nc - 1)
    def _emit_state():
        for k in heads:
            state_ref[0, k] = s_scr[:, k * p:(k + 1) * p].T


def _ssd_extend_kernel(s0_ref, x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref,
                       y_ref, state_ref, s_scr, *, tb: int):
    hi = pl.program_id(1)
    t = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    A = A_ref[hi]
    D = D_ref[hi]
    p, n = s_scr.shape
    eye = _eye(p)

    def token(i, s):
        # one ssd_decode_step: s' = exp(dt·A)·s + (dt·x) B^T,
        # y = C s'^T (+ D·x)
        x = x_ref[0, 0, pl.ds(i, 1), :].astype(jnp.float32)    # (1, p)
        Bv = B_ref[0, 0, pl.ds(i, 1), :].astype(jnp.float32)   # (1, n)
        Cv = C_ref[0, 0, pl.ds(i, 1), :].astype(jnp.float32)
        dt_i = dt_ref[0, 0, pl.ds(i, 1), :]      # (1, 128): dt_i per lane
        xdt = x * dt_i[:, :p]
        s = s * jnp.exp(dt_i[:, :n] * A) + _to_col(xdt, eye) * Bv
        y = _to_row(jnp.sum(s * Cv, axis=1, keepdims=True), eye)
        y_ref[0, 0, pl.ds(i, 1), :] = (y + x * D).astype(y_ref.dtype)
        return s

    s_scr[...] = jax.lax.fori_loop(0, tb, token, s_scr[...])

    @pl.when(t == nt - 1)
    def _emit_state():
        state_ref[0, 0] = s_scr[...]


_DT_LANES = 128


def _token_block(T: int) -> int:
    """Tokens per grid step: a divisor of T that is a multiple of 8, else
    all of T (a block's second-minor dim must be a multiple of 8 or the
    full axis)."""
    for tb in (256, 128):
        if T % tb == 0:
            return tb
    return T


def ssd_extend_pallas(state, x, dt, A, B, C, D=None, *, interpret=False):
    """Same contract as ``ref.ssd_extend_reference``: multi-token
    sequential recurrence from an explicit initial state. Each grid step
    walks a block of tokens in order with the (p, n) state carried in
    VMEM scratch, seeded from ``state`` at the first block. Each token's
    ``dt`` arrives repeated across a row of lanes, so the loop reads it
    with the same one-row slice as its ``x``/``B``/``C`` (p, n <= 128)."""
    b, T, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    if D is None:
        D = jnp.zeros((h,), jnp.float32)
    assert max(p, n) <= _DT_LANES, (p, n)
    tb = _token_block(T)

    xk = x.transpose(0, 2, 1, 3)                 # (b, h, T, p)
    dtk = jnp.broadcast_to(dt.transpose(0, 2, 1)[..., None].astype(
        jnp.float32), (b, h, T, _DT_LANES))      # (b, h, T, lanes)
    Bk = B.transpose(0, 2, 1, 3)                 # (b, g, T, n)
    Ck = C.transpose(0, 2, 1, 3)

    grid = (b, h, T // tb)
    y, final = pl.pallas_call(
        functools.partial(_ssd_extend_kernel, tb=tb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ti: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, tb, p), lambda bi, hi, ti: (bi, hi, ti, 0)),
            pl.BlockSpec((1, 1, tb, _DT_LANES),
                         lambda bi, hi, ti: (bi, hi, ti, 0)),
            _SMEM,
            pl.BlockSpec((1, 1, tb, n),
                         lambda bi, hi, ti, rep=rep: (bi, hi // rep, ti, 0)),
            pl.BlockSpec((1, 1, tb, n),
                         lambda bi, hi, ti, rep=rep: (bi, hi // rep, ti, 0)),
            _SMEM,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tb, p), lambda bi, hi, ti: (bi, hi, ti, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ti: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, T, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_extend",
    )(state.astype(jnp.float32), xk, dtk, jnp.asarray(A, jnp.float32),
      Bk, Ck, jnp.asarray(D, jnp.float32))

    return y.transpose(0, 2, 1, 3), final


# Most heads a grid step of the chunked extend takes (timed on a TPU v5e
# at the mamba2-780m admission chunk; 24 run out of VMEM there).
EXTEND_HEADS = 16


def _heads_per_step(rep: int) -> int:
    """Heads a grid step takes: the most, up to ``EXTEND_HEADS``, that
    divide the heads of one group, so the step's heads share B and C."""
    return max(k for k in range(1, EXTEND_HEADS + 1) if rep % k == 0)


def ssd_extend_chunked_pallas(state, x, dt, A, B, C, D=None, *, chunk,
                              interpret=False):
    """Same contract as ``ref.ssd_extend_reference``, in the chunked dual
    form of ``ssd_pallas`` from the given state: the state carry in VMEM
    is seeded from ``state``, and each grid step runs one ``chunk``-token
    block of several heads of one group on the MXU (``_ssd_block``, float32 at full
    precision). Equal to the token-serial recurrence up to float
    rounding, not bit for bit; a row whose ``dt`` is all zero keeps its
    state exactly. ``T`` must be a multiple of ``chunk``.

    The call takes ``(state, x, dt, A, B, C, D)`` and returns ``(y,
    state)``, ``x``/``y`` as ``(b, h, T, p)`` and ``B``/``C`` as ``(b, g,
    T, n)``, as the serial kernel does."""
    b, T, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert T % chunk == 0, (T, chunk)
    rep = h // g
    hb = _heads_per_step(rep)
    if D is None:
        D = jnp.zeros((h,), jnp.float32)

    f32 = jnp.float32
    xk = x.astype(f32).transpose(0, 2, 1, 3)     # (b, h, T, p)
    dtk = dt.astype(f32).transpose(0, 2, 1).reshape(b, h // hb, hb, T)
    Bk = B.astype(f32).transpose(0, 2, 1, 3)     # (b, g, T, n)
    Ck = C.astype(f32).transpose(0, 2, 1, 3)

    heads_of = lambda bi, hj, ci: (bi, hj, 0, 0)
    group = lambda bi, hj, ci, k=rep // hb: (bi, hj // k, ci, 0)
    y, final = pl.pallas_call(
        _ssd_extend_chunked_kernel,
        grid=(b, h // hb, T // chunk),
        in_specs=[
            pl.BlockSpec((1, hb, p, n), heads_of),
            pl.BlockSpec((1, hb, chunk, p), lambda bi, hj, ci: (bi, hj, ci, 0)),
            pl.BlockSpec((1, 1, hb, chunk), lambda bi, hj, ci: (bi, hj, 0, ci)),
            _SMEM,
            pl.BlockSpec((1, 1, chunk, n), group),
            pl.BlockSpec((1, 1, chunk, n), group),
            _SMEM,
        ],
        out_specs=[
            pl.BlockSpec((1, hb, chunk, p), lambda bi, hj, ci: (bi, hj, ci, 0)),
            pl.BlockSpec((1, hb, p, n), heads_of),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, T, p), f32),
            jax.ShapeDtypeStruct((b, h, p, n), f32),
        ],
        scratch_shapes=[pltpu.VMEM((n, hb * p), f32)],
        interpret=interpret,
        name="ssd_extend_chunked",
    )(state.astype(f32), xk, dtk, jnp.asarray(A, f32), Bk, Ck,
      jnp.asarray(D, f32))

    return y.transpose(0, 2, 1, 3), final


def ssd_pallas(x, dt, A, B, C, D=None, *, chunk=64, initial_state=None,
               interpret=False):
    """Same contract as ``ref.ssd_reference``; initial_state must be None
    (the model's prefill path always starts from zero state)."""
    assert initial_state is None, "pallas path starts from zero state"
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0
    nc = l // chunk
    rep = h // g
    if D is None:
        D = jnp.zeros((h,), jnp.float32)

    # layout: chunk-major per (batch, head); dt one lane-dense row/chunk
    xk = x.transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, p)
    dtk = dt.transpose(0, 2, 1).reshape(b, h, nc, 1, chunk)
    Bk = B.transpose(0, 2, 3, 1).reshape(b, g, n, nc, chunk) \
          .transpose(0, 1, 3, 2, 4)              # (b, g, nc, n, chunk)
    Ck = C.transpose(0, 2, 1, 3).reshape(b, g, nc, chunk, n)

    grid = (b, h, nc)
    y, state = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            _SMEM,
            pl.BlockSpec((1, 1, 1, n, chunk),
                         lambda bi, hi, ci, rep=rep: (bi, hi // rep, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, n),
                         lambda bi, hi, ci, rep=rep: (bi, hi // rep, ci, 0, 0)),
            _SMEM,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, chunk, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(xk, dtk, jnp.asarray(A, jnp.float32), Bk, Ck,
      jnp.asarray(D, jnp.float32))

    y = y.reshape(b, h, l, p).transpose(0, 2, 1, 3)
    return y, state.transpose(0, 1, 3, 2)
