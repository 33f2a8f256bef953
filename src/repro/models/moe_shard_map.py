"""Explicit expert-parallel MoE with shard_map + jax.lax collectives.

The pjit paths in :mod:`repro.models.moe` let GSPMD *infer* the collective
schedule; this module pins it down by hand — the production-grade variant
where the communication pattern is part of the program, not a partitioner
choice:

* experts are sharded over the ``model`` axis (E_local per rank);
* tokens are data-sharded and replicated across ``model`` (the framework's
  standard activation layout), so each rank routes the same tokens,
  computes ONLY its local experts' contributions, and a single
  ``lax.psum`` over ``model`` combines — one deterministic collective per
  MoE layer, which is the information-theoretic minimum for this layout.

Numerically identical to ``moe.moe_block`` (same router, same capacity
semantics per local expert).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import mlp

def _local_expert_pass(router_w, wi, wg, wo, x, *, cfg: ModelConfig,
                       axis: str, n_shards: int, data_axes=("data",)):
    """Per-rank body. x: (B_loc, L, d) — same tokens on every model rank.
    wi/wg/wo: (E_loc, …) this rank's experts."""
    m = cfg.moe
    E, k = m.n_experts, m.top_k
    E_loc = E // n_shards
    rank = lax.axis_index(axis)
    lo = rank * E_loc

    B, L, d = x.shape
    T = B * L
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ router_w)
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    gate_vals, gate_idx = lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # keep only assignments to THIS rank's experts; foreign ones get the
    # sentinel id E_loc so they sort to the end and never claim capacity
    A = T * k
    eid = gate_idx.reshape(A) - lo                             # (A,)
    mine = (eid >= 0) & (eid < E_loc)
    eid_sort = jnp.where(mine, eid, E_loc)
    gate_of = jnp.where(mine, gate_vals.reshape(A), 0.0)
    token_of = jnp.arange(A, dtype=jnp.int32) // k

    order = jnp.argsort(eid_sort)
    eid_sorted = eid_sort[order]
    bounds = jnp.searchsorted(eid_sorted, jnp.arange(E_loc + 1))
    counts = (bounds[1:] - bounds[:-1]).astype(jnp.int32)      # (E_loc,)
    offsets = bounds[:-1].astype(jnp.int32)

    from repro.models.moe import _capacity
    C = _capacity(T, m)
    slot = jnp.arange(C, dtype=jnp.int32)
    slot_idx = jnp.clip(offsets[:, None] + slot[None, :], 0, A - 1)
    slot_valid = slot[None, :] < counts[:, None]               # (E_loc, C)
    a_idx = order[slot_idx]
    tok_idx = token_of[a_idx]
    gates = jnp.where(slot_valid, gate_of[a_idx], 0.0)

    xe = xf[tok_idx]
    xe = jnp.where(slot_valid[..., None], xe, 0).astype(cfg.act_dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) \
        * jnp.einsum("ecd,edf->ecf", xe, wi)
    ye = jnp.einsum("ecf,efd->ecd", h, wo).astype(jnp.float32)
    ye = ye * gates[..., None]

    y_partial = jnp.zeros((T, d), jnp.float32).at[
        tok_idx.reshape(-1)].add(ye.reshape(-1, d))

    # load-balance aux — exact global quantities: counts psum'd over both
    # the expert (model) and token (data) axes; router-prob mean over data
    me = lax.pmean(jnp.mean(probs, axis=0), data_axes)
    local_counts = jnp.zeros((E,), jnp.float32).at[
        jnp.where(mine, eid + lo, 0)].add(jnp.where(mine, 1.0, 0.0))
    counts_all = lax.psum(local_counts, (axis,) + tuple(data_axes))
    n_data = lax.psum(jnp.ones((), jnp.float32), data_axes)
    aux = m.aux_loss_weight * E * jnp.sum(
        counts_all / (T * n_data * k) * me)

    # ONE deterministic collective: combine expert contributions
    y = lax.psum(y_partial, axis)
    return y.reshape(B, L, d).astype(x.dtype), aux


def moe_block_shard_map(p, x, cfg: ModelConfig, mesh, *,
                        axis: str = "model", data_axes=("data",)):
    """Drop-in for ``moe.moe_block`` under an explicit mesh."""
    m = cfg.moe
    n_shards = mesh.shape[axis]
    assert m.n_experts % n_shards == 0, (m.n_experts, n_shards)
    b = tuple(data_axes)
    batch = b if len(b) > 1 else b[0]

    body = functools.partial(_local_expert_pass, cfg=cfg, axis=axis,
                             n_shards=n_shards, data_axes=b)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axis, None, None), P(axis, None, None),
                  P(axis, None, None), P(batch, None, None)),
        out_specs=(P(batch, None, None), P()),
        check_vma=False,
    )
    y, aux = fn(p["router"]["w"].astype(jnp.float32), p["wi"], p["wg"],
                p["wo"], x)
    if m.n_shared:
        y = y + mlp(p["shared"], x).astype(x.dtype)
    return y, aux
