"""Client layer: how late the load generator sent requests, the 99th
percentile of (sent - due) in ms over the requests due in the traced
ticks. A starved generator shows here, not as a fast server."""
from __future__ import annotations

import numpy as np


def read(ctx):
    if not ctx.ticks:
        return None
    lo, hi = ctx.ticks[0].start, ctx.ticks[-1].end
    lags = [r.submit - r.due for r in ctx.window.records.values()
            if r.submit is not None and lo <= r.due <= hi]
    if not lags:
        return None
    return 1e3 * float(np.percentile(lags, 99))
