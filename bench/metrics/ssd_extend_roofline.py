"""Kernel layer: the ``ssd_extend`` Pallas kernel's share of its roofline,
in %: the least time the chip needs for the kernel's calls in the traced
ticks (for each call the larger of its FLOPs over peak FLOP/s and its
bytes over HBM bandwidth, from its shapes, ``kernels/ssd_extend.py``)
over the device time of those calls. Which bound applies is logged."""
from __future__ import annotations

import sys

from bench.kernels import ssd_extend as k


def read(ctx):
    if ctx.red is None or not ctx.peaks:
        return None
    least = spent = 0.0
    bound = {"memory": 0, "compute": 0}
    for name, seconds in ctx.red.kernels:
        shape = k.shapes(name)
        if shape is None:
            continue
        t_f = k.flops(*shape) / ctx.peaks["bf16_flops"]
        t_b = k.bytes_moved(*shape) / ctx.peaks["hbm_bytes_per_s"]
        bound["memory" if t_b >= t_f else "compute"] += 1
        least += max(t_f, t_b)
        spent += seconds
    if spent <= 0:
        return None
    print(f"[bench] ssd_extend: calls bound by {bound}", file=sys.stderr)
    return 100.0 * least / spent
