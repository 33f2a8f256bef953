"""Pure-jnp oracle for the Mamba-2 SSD (state-space dual) chunked scan.

Shapes follow the Mamba-2 paper (arXiv:2405.21060):
  x  : (b, l, h, p)   inputs split into h heads of dim p
  dt : (b, l, h)      positive step sizes (softplus already applied)
  A  : (h,)           negative per-head decay rates
  B,C: (b, l, g, n)   input/output projections, g groups (h % g == 0)
Returns y: (b, l, h, p) and the final state (b, h, p, n).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def segsum(x):
    """x: (..., T) -> (..., T, T) with out[..., i, j] = sum_{j<s<=i} x[s]
    (lower-triangular; -inf above the diagonal so exp() masks it)."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_reference(x, dt, A, B, C, D=None, *, chunk=64, initial_state=None):
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0, f"seq {l} not divisible by chunk {chunk}"
    nc = l // chunk
    rep = h // g

    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    A, B, C = A.astype(f32), B.astype(f32), C.astype(f32)

    Bh = jnp.repeat(B, rep, axis=2)                     # (b, l, h, n)
    Ch = jnp.repeat(C, rep, axis=2)

    xr = x.reshape(b, nc, chunk, h, p)
    dtr = dt.reshape(b, nc, chunk, h)
    Br = Bh.reshape(b, nc, chunk, h, n)
    Cr = Ch.reshape(b, nc, chunk, h, n)

    dA = jnp.einsum("bcsh,h->bchs", dtr, A)             # (b, nc, h, chunk)
    dA_cum = jnp.cumsum(dA, axis=-1)
    L = jnp.exp(segsum(dA))                             # (b, nc, h, c, c)
    xdt = xr * dtr[..., None]                           # (b, nc, c, h, p)

    # intra-chunk (dual / quadratic form — MXU-friendly)
    Y_diag = jnp.einsum("bclhn,bcshn,bchls,bcshp->bclhp", Cr, Br, L, xdt)

    # per-chunk end states
    decay_states = jnp.exp(dA_cum[..., -1:] - dA_cum)   # (b, nc, h, c)
    states = jnp.einsum("bcshn,bchs,bcshp->bchpn", Br, decay_states, xdt)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cum[..., -1])              # (b, nc, h)
    if initial_state is None:
        init = jnp.zeros((b, h, p, n), f32)
    else:
        init = initial_state.astype(f32)

    def step(s, inp):
        st, dec = inp
        return s * dec[..., None, None] + st, s         # emit pre-chunk state

    states_t = jnp.moveaxis(states, 1, 0)
    decay_t = jnp.moveaxis(chunk_decay, 1, 0)
    final, prev = lax.scan(step, init, (states_t, decay_t))
    prev = jnp.moveaxis(prev, 0, 1)                     # (b, nc, h, p, n)

    # inter-chunk contribution to outputs
    state_decay_out = jnp.exp(dA_cum)                   # (b, nc, h, c)
    Y_off = jnp.einsum("bclhn,bchpn,bchl->bclhp", Cr, prev, state_decay_out)

    y = (Y_diag + Y_off).reshape(b, l, h, p)
    if D is not None:
        y = y + x.reshape(b, l, h, p) * D.astype(f32)[None, None, :, None]
    return y, final


def ssd_extend_reference(state, x, dt, A, B, C, D=None):
    """Multi-token sequential recurrence from an explicit initial state.

    state: (b, h, p, n); x: (b, T, h, p); dt: (b, T, h); B, C: (b, T, g, n).
    Returns (y: (b, T, h, p), final_state: (b, h, p, n)).

    Exactly T applications of ``ssd_decode_step`` — bitwise, not just
    numerically: extending by [t1, t2] chunks equals extending by
    [t1 + t2] equals t1+t2 single decode steps. It stays token-serial as
    the oracle of the Pallas forms. The chunked dual form (``ssd_pallas``,
    and ``ssd_extend`` on the chip for chunks of whole blocks) is faster
    but agrees with it only numerically: its float reduction order
    changes with the chunking.
    """
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0))

    def step(s, inp):
        xi, dti, Bi, Ci = inp
        y, s = ssd_decode_step(s, xi, dti, A, Bi, Ci, D)
        return s, y

    final, ys = lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1), final


def ssd_decode_step(state, x, dt, A, B, C, D=None):
    """Single-token recurrence.
    state: (b, h, p, n); x: (b, h, p); dt: (b, h); B, C: (b, g, n)."""
    f32 = jnp.float32
    h = x.shape[1]
    g = B.shape[1]
    rep = h // g
    x, dt = x.astype(f32), dt.astype(f32)
    Bh = jnp.repeat(B.astype(f32), rep, axis=1)          # (b, h, n)
    Ch = jnp.repeat(C.astype(f32), rep, axis=1)
    dA = jnp.exp(dt * A.astype(f32)[None])               # (b, h)
    new_state = state.astype(f32) * dA[..., None, None] + \
        jnp.einsum("bhp,bhn->bhpn", x * dt[..., None], Bh)
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    if D is not None:
        y = y + x * D.astype(f32)[None, :, None]
    return y, new_state
