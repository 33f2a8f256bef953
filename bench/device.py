"""The device a run measures, and the published peaks it is measured
against (``peaks.json``, keyed by ``device_kind``)."""
from __future__ import annotations

from typing import Any, Dict

from bench.cells import BENCH, load_json


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def describe(chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    """Platform, kind and count of the devices JAX reports. Without a TPU
    (or with fewer chips than ``chips``) this raises ``NoDevice``: a
    measurement never falls back to the CPU."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {len(devs)} {d0.platform} "
                       f"device(s)")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def peaks(kind: str) -> Dict[str, float]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[kind]


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, where the backend reports
    it (0 where it does not, as on the CPU)."""
    import jax
    best = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best
