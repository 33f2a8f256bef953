"""Production mesh definition (TPU v5e pods).

A function, not a module-level constant — importing this module must never
touch jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the partitioner propagates
    shardings through ops, as the param/cache/activation rules assume.
    (``jax.make_mesh`` defaults to Explicit axes, which reject ops whose
    operand shardings disagree instead of resharding.)"""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_serving_mesh(spec: str = "auto"):
    """Serving mesh from a spec string — the layout the ``Engine``'s
    param/cache/decode-state shardings assume, always ("data", "model").

    * ``"auto"`` (or ``""``): all local devices on the model axis,
      shape ``(1, n_devices)`` — pure tensor parallelism, the
      memory-bound serving default (weights and KV heads split n ways);
    * ``"dp,mp"`` (e.g. ``"2,4"``; ``"2x4"`` also accepted): explicit
      (data, model) axis sizes — batch slots shard over data, weights
      and KV heads over model.
    """
    if spec in ("", "auto"):
        shape = (1, len(jax.devices()))
    else:
        parts = [int(x) for x in spec.replace("x", ",").split(",")]
        if len(parts) != 2 or any(p < 1 for p in parts):
            raise ValueError(f"mesh spec {spec!r}: want 'dp,mp', "
                             f"e.g. '2,4', or 'auto'")
        shape = tuple(parts)
    return _mesh(shape, ("data", "model"))


# TPU v5e hardware constants (per chip) used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,    # FLOP/s
    "hbm_bandwidth": 819e9,       # B/s
    "ici_link_bandwidth": 50e9,   # B/s per link
    "hbm_bytes": 16 * 1024**3,    # 16 GB
}
